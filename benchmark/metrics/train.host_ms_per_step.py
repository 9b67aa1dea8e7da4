"""Host time `TrainStep.__call__` takes a step: the summed durations of its
`trainstep.prepare` (unwrap, signature, executable lookup, key, lr),
`trainstep.dispatch` (the executable call) and `trainstep.write_back` spans,
step by step; the median over the traced steps. The device works through
all of it unless the host falls behind: `train.device_idle_share` says."""
import bisect

from benchmark.harness import program_spans, stats

PHASES = ("trainstep.prepare", "trainstep.dispatch", "trainstep.write_back")

NAME = "train.host_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "trainer step"
MOVES = "train_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    ps = program_spans.for_ctx(ctx)
    if ps is None:
        return None
    starts = ps.named(PHASES[0])
    if not starts:
        return None
    # a step's phases follow its `prepare` on the same thread, before the
    # next step's
    edges = [s.start_ns for s in starts]
    per_step = [0.0] * len(starts)
    for name in PHASES:
        for s in ps.named(name):
            i = bisect.bisect_right(edges, s.start_ns) - 1
            if i >= 0:
                per_step[i] += s.dur_ns / 1e6
    return stats.median(per_step)
