"""(the active rows' recurrent state read and written / HBM bandwidth) /
device time under ``mx.ssm_update`` per decode iteration, in percent."""
from benchmarks.harness import decode_trace, program_trace


def read(obs, trace):
    return decode_trace.share_of_roofline(
        obs, trace, "mx.ssm_update", program_trace.scope_ms(
            trace, "mx.ssm_update", "serving", "/decode-"))
