"""p99 of the gap between two consecutive tokens of one request, from the
engine's own histogram `serving.hist.itl_ms` (recorded where tokens are
appended, on the engine's clock; bucket midpoints, 3% wide). A tail of single
gaps, where `tpot_p90_ms` is a tail of per-request means: a prefill that
stalls the decoders for one tick shows here.

The histogram counts over the PROCESS'S life: the reference request, the
warm-up requests (~15 tokens), `steady_seconds`, the window and, in a traced
run, the traced stretch, all of the same loop. The window's ~45,000 gaps
are over four fifths of them."""

NAME = "serve.itl_p99_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"


def compute(ctx):
    from paddle_tpu import monitor
    hist = monitor.histogram("serving.hist.itl_ms")
    # a program that does not stamp its tokens never records: no metric
    return hist.percentile(99) if hist.count else None
