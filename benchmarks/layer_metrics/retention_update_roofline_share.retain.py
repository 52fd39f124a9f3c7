"""(the active rows' retention state and normaliser read once and written
once, at the exact width of the key's symmetric square / HBM bandwidth) /
device time under ``mx.retention_update`` per decode iteration, in
percent.  The count is the algorithm's, whatever implements it: a program
that reads the state twice, or keeps it padded, reads lower."""
from benchmarks.harness import decode_trace, program_trace


def read(obs, trace):
    return decode_trace.share_of_roofline(
        obs, trace, "mx.retention_update", program_trace.scope_ms(
            trace, "mx.retention_update", "serving", "/decode-"))
