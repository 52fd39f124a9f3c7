"""(bytes a decode iteration needs / the chip's HBM bandwidth) / device time
per execution of the decode programs in the traced window, in percent.
Bytes from ``ops_bytes/<config>.py`` at what the traced iterations held
(``engine.decode`` span arguments: active rows; tokens held and experts
hit are read and play no part): every weight once, the retention state of
every active row read once and written once at its exact width.  Prefills
are other programs and are left out of both sides."""
from benchmarks.harness import decode_trace


def read(obs, trace):
    return decode_trace.share_of_roofline(
        obs, trace, None, decode_trace.decode_busy_ms(trace))
