"""What a step() costs the host, hidden by the tick in flight or not:
the median over the window's rows of the engine's step record of
`wall_ms` less the two waits (`engine.decode.wait`,
`engine.prefill.wait`). When it nears `serve.decode_program_ms_p50` the
device is about to wait for the host."""
from benchmark.harness import stats, step_record

NAME = "serve.host_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    rows = step_record.for_ctx(ctx)
    if not rows:
        return None
    return stats.median([step_record.host_ms(r) for r in rows])
