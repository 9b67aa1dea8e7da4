#!/usr/bin/env python
"""serving_replay — replay a JSONL arrival trace against the engine.

Run: python tools/serving_replay.py trace.jsonl [--max-slots 4]
         [--page-size 8] [--pool-pages 64] [--layers 2] [--hidden 64]
         [--heads 4] [--vocab 64] [--seed 0] [--step-ms 5]
         [--prefill-token-ms 0.1] [--temperature 0]
         [--cache-dtype auto] [--no-prefix-cache] [--spec-k 0]
         [--draft-layers 1] [--max-prefill-tokens N] [--json]
         [--model llama|ernie_moe] [--experts 4] [--top-k 2]
         [--moe-every 2] [--expect-moe-pallas]
         [--embedding --max-batch 8 --bucket 16]
         [--expect-zero-recompiles]
         [--expect-pallas] [--expect-prefix-hit-rate 0.5]
         [--expect-p99-ttft-ms MS] [--ttft-tag small]
         [--chaos] [--fault-seed 0] [--fault-rate 0.05]
         [--disagg --prefill-workers N --decode-workers M]
         [--kill-worker decode:1:40]
         [--replicas N --route session] [--kill-replica 1:40]
         [--trace-out spans.json] [--expect-complete-timelines]
         [--expect-hotpath-clean]

``--expect-hotpath-clean`` (exit 13) lints the DRAINED serving
surface through ``inspect_hotpath()`` (analysis/hotpath_lint.py):
every executable the trace compiled is abstract-traced for missed
donations and fetch-set bloat, and the tick scheduler is AST-walked
for host syncs / steady-tick uploads / recompile-risk cache keys.
Works under ``--disagg`` / ``--replicas`` / ``--embedding``; the
``lint.hotpath.*`` counter deltas land in the report next to
``xla.compiles``.

``--model ernie_moe`` replays against an ERNIE-MoE decoder
(text/models/ernie_moe.py, docs/SERVING.md "MoE serving") instead of
the tiny LLaMA: same trace schema, same engine/disagg/fleet drive
loops and the same chaos/prefix/TTFT gates with their exit codes
unchanged — ``--experts`` / ``--top-k`` / ``--moe-every`` size the
sparse FFNs. The report grows a ``moe`` block: the construction-time
fused-dispatch eligibility verdict plus the per-replay
``serving.moe.decode_path.*`` deltas — which MoE dispatch the compiled
serving executables actually baked in. ``--expect-moe-pallas`` turns a
silent expert-dispatch fallback into a LOUD failure (exit 10): every
compile-bearing step must have traced the fused Pallas grouped-matmul
and no ``fallback.*`` counter may move. (On the CPU backend the Pallas
path never traces, so the flag always fails there — by design, same as
``--expect-pallas``.) ``--spec-k`` under ``--model ernie_moe`` is the
dense-draft/MoE-verifier speculative schedule — the draft stays a
dense LLaMA.

``--embedding`` replays an ENCODER EMBEDDING trace against the
BatchEncoder service (inference/encoder.py, docs/SERVING.md "Embedding
service") over a tiny flash-SDPA BERT — no KV, no pages; the
scheduler under test is bucketed continuous batching. Trace lines are
one embedding request each:

    {"arrival_ms": 0, "seq_len": 17, "pooling": "mean"}

(``pooling`` optional, "mean"/"cls"; optional ``tenant`` exercises the
fairness walk, ``deadline_ms`` / ``max_queue_steps`` ride into
EmbedParams on the replay's virtual clock.) ``--max-batch`` /
``--bucket`` size the service; the report carries latency percentiles,
batch fill / pad ratio and the ``serving.embed.*`` counter deltas.
Decoder-only flags (--disagg/--replicas/--chaos/--spec-k/the
decode gates) are rejected under ``--embedding``.
``--expect-zero-recompiles`` (both modes, exit 11) fails the replay
when ``steady_state_recompiles()`` ends nonzero — the bucket-churn CI
guard.

``--replicas N`` replays against the ELASTIC FLEET
(inference/fleet.py, docs/SERVING.md "Elastic fleet"): N whole engine
replicas behind the session-aware router (``--route`` picks the
policy — ``session`` / ``least_loaded`` / ``round_robin``, the
baselines the routing win is measured against). The report grows a
per-replica utilization table (busy fraction, warm/cold routing
counts, per-replica prefix hit rate) plus fleet counters
(``serving.fleet.*``). Trace lines may carry ``"session": "name"`` —
each session gets its OWN system token block (drawn once per session
from the trace rng), so same-session requests share a prefix that
session routing can keep warm on one replica while round-robin
scatters it. ``--kill-replica INDEX:STEP`` (repeatable) is the fleet
failover chaos gate: the trace first runs clean to record reference
tokens, then with the replica death(s) — exit code 9 when any
surviving request's output diverges from the clean run, pages leak on
a live replica, or the invariant audit ends dirty.

``--disagg`` replays against the DISAGGREGATED engine
(inference/disagg.py, docs/SERVING.md "Disaggregated serving"):
``--prefill-workers`` / ``--decode-workers`` size the two fleets, the
report grows a per-worker utilization table plus migration counts
(``serving.disagg.*`` / ``serving.migrated_pages``), and trace lines
may carry ``"tenant": "name"`` for the multi-tenant fair scheduler.
``--kill-worker KIND:INDEX:STEP`` (repeatable) is the failover chaos
variant: the trace first runs clean to record reference tokens, then
with the worker death(s) — the run fails LOUDLY (exit 8) when any
surviving request's output diverges from the clean run, pages leak on
a live worker, or the invariant audit ends dirty.

Each trace line is one request:

    {"arrival_ms": 0, "prompt_len": 7, "new_tokens": 9}

``prompt_len`` tokens are drawn per-request from the trace rng; an
optional ``"system_len": N`` marks the FIRST N tokens as the shared
system prompt (one fixed token block across the whole trace) — the
prefix-cache scenario, where every request after the first maps the
shared pages and prefills only its divergent tail. Optional
``"deadline_ms"`` / ``"max_queue_steps"`` fields ride into the
request's SamplingParams; the engine runs on the replay's virtual
clock, so deadline expiries replay deterministically too. An optional
``"tag"`` labels the request's class ("whale" / "small" on the
long-context fixture): the report adds per-tag TTFT percentile rows,
and ``--expect-p99-ttft-ms MS --ttft-tag small`` turns them into a
whale-starvation gate (exit 7 when the tagged class's p99 TTFT lands
above MS, or any tagged request never reached a first token).
``--max-prefill-tokens N`` runs the engine with chunked prefill —
long prompts are written N tokens per step, interleaved with decode
ticks (docs/SERVING.md "Chunked prefill") — the knob the long-context
fixture's gate is calibrated against.

``--chaos`` is the reliability soak (docs/SERVING.md "Reliability"):
the trace is driven TWICE against the same weights — once clean to
record every request's reference tokens, once with a seeded
``FaultInjector`` (``--fault-seed`` / ``--fault-rate``) firing
injected allocator exhaustion, refcount skew, prefix-cache
collisions/stale entries, NaN rows, device errors and draft
disagreement storms. The run fails LOUDLY (exit code 6) when any
surviving request's output differs from the clean run, when pages
leak, or when the invariant audit still has findings after the drain
— the chaos contract: faults may slow or fail individual requests,
never corrupt a survivor or the pool. The injected-fault counts and
failure-reason histogram land in the report under ``"chaos"``.

The tool builds a tiny in-memory LLaMA on the CPU backend (geometry
from the flags — this measures the SCHEDULER, not the model), drives
``paddle_tpu.inference.Engine`` on a virtual clock (deterministic: the
same trace always yields the same admission schedule and the same
percentiles) that advances ``--step-ms`` per engine step PLUS
``--prefill-token-ms`` per prefill token the step executed — so a
prefix-cache hit, which prefills only the uncached tail chunk, shows
up directly as lower TTFT. It prints TTFT / TPOT / throughput
percentiles, ``prefix_hit_rate`` / ``spec_accept_rate``, the
per-replay ``kernels.decode.*`` path breakdown (pallas vs gather
fallback) and ``serving.*`` counters (docs/OBSERVABILITY.md) — the
first thing to read when a serving number regresses is whether the
compiled loop left the expected attention path or started recompiling.

The prefix cache is ON by default (``--no-prefix-cache`` disables it —
the cold-prefix baseline run); ``--spec-k N`` attaches a
``--draft-layers``-deep draft model and decodes through the
draft/verify schedule (token-identical by construction; the report's
``spec_accept_rate`` says how often the draft earned its keep).

``--expect-pallas`` turns a silent fallback into a LOUD failure (exit
code 4): the replay must have traced the Pallas paged-decode kernel
and no single-token step may have taken the XLA gather path. Use it
as the CI guard around TPU serving configs — today a fallback only
shows up as slow numbers. (On the CPU backend the Pallas path never
runs, so the flag always fails there — by design.)
``--expect-prefix-hit-rate X`` does the same for prefix reuse (exit
code 5 when the replay's hit rate lands below X): the guard for
prefix-heavy fixtures where a silent cache regression would only read
as higher TTFT.

``--trace-out PATH`` writes the STITCHED per-request span timelines
(QUEUED / each PREFILL slice / MIGRATING / PREEMPTED / DECODE /
FINISHED-or-FAILED(reason), origin worker/replica labeled per span)
as a perfetto-loadable chrome-trace — one pid per worker/replica, one
lane per slot. The timelines ride the engines' virtual clock, so two
replays of one seed write byte-identical files.
``--expect-complete-timelines`` (exit 12) gates on the stitched
export: every replayed request must reconstruct to exactly one
contiguous QUEUED..terminal timeline — the chaos-matrix completeness
guard (docs/OBSERVABILITY.md "Serving timelines & histograms").
The report also carries ``histograms`` (merged fleet-wide
``serving.hist.*`` p50/p90/p99 from the mergeable log-bucket
histograms) and ``host_device`` (the ``serving.host_ms_per_tick`` /
``serving.device_ms_per_tick`` attribution gauges, wall clock).

Fixture traces live at tests/fixtures/serving_trace.jsonl,
tests/fixtures/serving_trace_prefix.jsonl (prefix-heavy: one shared
system prompt, divergent user turns) and
tests/fixtures/serving_trace_longctx.jsonl (mixed whale/small traffic
with tags — the chunked-prefill fairness scenario).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _percentiles(vals):
    """Percentile summary over a latency stream via the mergeable
    log-bucket histogram (monitor.Histogram) — the replay never holds
    an unbounded sample list just to call np.percentile; bucket
    resolution is ~3% relative (tests/test_serving_observability.py
    pins <= 5% on the fixture distributions)."""
    from paddle_tpu import monitor
    if not vals:
        return {"p50": 0.0, "p90": 0.0, "p99": 0.0}
    h = monitor.Histogram()
    for v in vals:
        h.record(float(v))
    return {p: round(h.percentile(q), 2)
            for p, q in (("p50", 50), ("p90", 90), ("p99", 99))}


def _run_embedding(args, trace) -> int:
    """--embedding drive loop: the BatchEncoder service over a tiny
    flash-SDPA BERT on the replay's virtual clock. One trace line per
    embedding request; the virtual clock advances --step-ms per service
    tick plus --prefill-token-ms per REAL token the tick encoded, so
    batch packing quality shows up directly in the latency
    percentiles."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import monitor
    from paddle_tpu.inference.encoder import BatchEncoder, EmbedParams
    from paddle_tpu.text.models.bert import BertConfig, BertModel

    bad_pool = [(i, r["pooling"]) for i, r in enumerate(trace)
                if r.get("pooling") not in (None, "mean", "cls")]
    if bad_pool:
        print(f"serving_replay: bad pooling value(s) {bad_pool[:5]} "
              f"(want \"mean\" or \"cls\")", file=sys.stderr)
        return 2

    paddle.seed(args.seed)
    max_seq = max(int(r["seq_len"]) for r in trace)
    cfg = BertConfig.tiny(vocab=args.vocab, hidden=args.hidden,
                          layers=args.layers, heads=args.heads)
    cfg.max_position_embeddings = max(cfg.max_position_embeddings,
                                      max_seq)
    net = BertModel(cfg)
    net.eval()

    vt_box = {"vt": 0.0}
    svc = BatchEncoder(net, max_batch=args.max_batch,
                       bucket=args.bucket,
                       clock=lambda: vt_box["vt"] / 1e3)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(1, args.vocab, (int(r["seq_len"]),))
               .astype(np.int64) for r in trace]

    before = monitor.snapshot()
    tok_key = "serving.embed.tokens"
    tok_before = int(before.get(tok_key, 0))
    finished = {}
    i = 0
    steps = 0
    t0 = time.perf_counter()
    while len(finished) < len(trace):
        vt = vt_box["vt"]
        while i < len(trace) and trace[i]["arrival_ms"] <= vt:
            r = trace[i]
            # stamp arrival at the TRACE's arrival time, not the tick
            # the drive loop got around to admitting it — queue wait
            # behind a long tick must show in the latency percentiles
            vt_box["vt"] = float(r["arrival_ms"])
            svc.add_request(
                prompts[i],
                EmbedParams(pooling=r.get("pooling", "mean"),
                            deadline_ms=r.get("deadline_ms"),
                            max_queue_steps=r.get("max_queue_steps")),
                tenant=str(r.get("tenant", "default")))
            vt_box["vt"] = vt
            i += 1
        if i < len(trace) and svc.idle:
            vt_box["vt"] = max(vt, float(trace[i]["arrival_ms"]))
            continue
        for out in svc.step():
            finished[out.req_id] = out
        steps += 1
        tok_now = int(monitor.counter(tok_key).get())
        vt_box["vt"] += args.step_ms \
            + (tok_now - tok_before) * args.prefill_token_ms
        tok_before = tok_now
        if steps > 100_000:
            print("serving_replay: embedding service did not drain",
                  file=sys.stderr)
            return 3
    wall_s = time.perf_counter() - t0
    after = monitor.snapshot()
    hotpath_report = None
    if args.expect_hotpath_clean:
        # lint the DRAINED service (every bucket executable warm) so
        # the inventory covers exactly what the replay compiled; fold
        # the lint.hotpath.* counters it bumps into the delta window
        hotpath_report = svc.inspect_hotpath()
        after = dict(after)
        for k, v in monitor.snapshot().items():
            if k.startswith("lint.hotpath."):
                after[k] = v
    svc.close()

    deltas = {k: int(after.get(k, 0)) - int(before.get(k, 0))
              for k in after
              if k.startswith(("serving.embed.requests",
                               "serving.embed.finished",
                               "serving.embed.batches",
                               "serving.embed.tokens",
                               "serving.embed.pad_tokens",
                               "serving.embed.timeouts",
                               "serving.embed.cancelled",
                               "serving.embed.steps",
                               "kernels.flash.", "lint.hotpath.",
                               "xla.compiles"))
              and int(after.get(k, 0)) - int(before.get(k, 0))}
    failures = {}
    total_tokens = 0
    lats = []
    for out in finished.values():
        if out.ok:
            total_tokens += out.tokens
            lats.append(out.latency_ms)
        else:
            failures[out.finish_reason] = \
                failures.get(out.finish_reason, 0) + 1
    n_batches = deltas.get("serving.embed.batches", 0)
    real = deltas.get("serving.embed.tokens", 0)
    pad = deltas.get("serving.embed.pad_tokens", 0)
    report = {
        "mode": "embedding",
        "requests": len(trace),
        "steps": steps,
        "batches": n_batches,
        "total_tokens": total_tokens,
        "wall_s": round(wall_s, 3),
        "tokens_per_sec": round(total_tokens / max(wall_s, 1e-9), 1),
        "failed": failures,
        "latency_ms": _percentiles(lats),
        "batch_fill": round(len(lats) / max(n_batches
                                            * args.max_batch, 1), 4),
        "pad_ratio": round(pad / max(real + pad, 1), 4),
        "steady_state_recompiles": svc.steady_state_recompiles(),
        "counters": deltas,
    }
    if hotpath_report is not None:
        report["hotpath"] = {
            "findings": len(list(hotpath_report)),
            "rules": {r: len(fs)
                      for r, fs in hotpath_report.by_rule().items()},
        }
    if args.json:
        print(json.dumps(report))
    else:
        print(f"embedded {report['requests']} requests / "
              f"{report['total_tokens']} tokens in {report['steps']} "
              f"steps / {report['batches']} batches "
              f"({report['wall_s']}s wall) — "
              f"{report['tokens_per_sec']} tokens_per_sec")
        ps = report["latency_ms"]
        print(f"  latency_ms p50 {ps['p50']:8.2f}  "
              f"p90 {ps['p90']:8.2f}  p99 {ps['p99']:8.2f}   "
              f"(virtual clock)")
        print(f"  batch_fill {report['batch_fill']}  "
              f"pad_ratio {report['pad_ratio']}  "
              f"steady_state_recompiles "
              f"{report['steady_state_recompiles']}")
        if failures:
            print("  failed: " + "  ".join(
                f"{k} x{v}" for k, v in sorted(failures.items())))
        for k in sorted(report["counters"]):
            print(f"  {k} +{report['counters'][k]}")
    if args.expect_zero_recompiles \
            and report["steady_state_recompiles"]:
        print(f"serving_replay: --expect-zero-recompiles FAILED — "
              f"{report['steady_state_recompiles']} steady-state "
              f"recompile(s); the per-bucket executables churned "
              f"mid-trace (docs/SERVING.md 'Embedding service')",
              file=sys.stderr)
        return 11
    if hotpath_report is not None and hotpath_report:
        print(f"serving_replay: --expect-hotpath-clean FAILED — "
              f"{len(list(hotpath_report))} hot-path finding(s) on "
              f"the drained encoder:\n{hotpath_report.format()}\n"
              f"(docs/ANALYSIS.md 'Hot-path rules')", file=sys.stderr)
        return 13
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="serving_replay",
                                 description=__doc__)
    ap.add_argument("trace", help="JSONL arrival trace")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--pool-pages", type=int, default=64)
    ap.add_argument("--prefill-bucket", type=int, default=16)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--step-ms", type=float, default=5.0,
                    help="virtual clock advance per engine step")
    ap.add_argument("--prefill-token-ms", type=float, default=0.1,
                    help="virtual clock advance per prefill token a "
                         "step executed (cached prefixes skip these)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--cache-dtype", default="auto")
    ap.add_argument("--model", default="llama",
                    choices=("llama", "ernie_moe"),
                    help="decoder under replay: the tiny dense LLaMA "
                         "(default) or the ERNIE-MoE sparse decoder "
                         "(docs/SERVING.md 'MoE serving')")
    ap.add_argument("--experts", type=int, default=4,
                    help="expert count under --model ernie_moe")
    ap.add_argument("--top-k", type=int, default=2,
                    help="experts routed per token under --model "
                         "ernie_moe")
    ap.add_argument("--moe-every", type=int, default=2,
                    help="every Nth decoder block uses an MoE FFN "
                         "under --model ernie_moe")
    ap.add_argument("--expect-moe-pallas", action="store_true",
                    help="fail (exit 10) when the replay's MoE decode "
                         "dispatch fell off the fused Pallas "
                         "grouped-matmul — any serving.moe.decode_path"
                         ".fallback.* movement, or no pallas trace at "
                         "all (needs --model ernie_moe)")
    ap.add_argument("--embedding", action="store_true",
                    help="replay an ENCODER EMBEDDING trace against "
                         "the BatchEncoder service over a tiny BERT "
                         "(docs/SERVING.md 'Embedding service'); "
                         "lines carry seq_len (+ optional pooling/"
                         "tenant/deadline_ms/max_queue_steps)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="BatchEncoder batch width under --embedding")
    ap.add_argument("--bucket", type=int, default=16,
                    help="BatchEncoder sequence bucket under "
                         "--embedding")
    ap.add_argument("--expect-zero-recompiles", action="store_true",
                    help="fail (exit 11) when steady_state_recompiles "
                         "ends nonzero — the bucket/trace-churn CI "
                         "guard (either mode)")
    ap.add_argument("--max-prefill-tokens", type=int, default=None,
                    help="chunked prefill: at most this many prompt "
                         "tokens are prefilled per engine step, "
                         "interleaved with decode ticks (None = "
                         "monolithic prefill)")
    ap.add_argument("--disagg", action="store_true",
                    help="replay against the DISAGGREGATED engine "
                         "(inference/disagg.py): prefill/decode worker "
                         "fleets with KV-page migration; the report "
                         "adds per-worker utilization + migration "
                         "counts (docs/SERVING.md 'Disaggregated "
                         "serving')")
    ap.add_argument("--prefill-workers", type=int, default=1,
                    help="prefill fleet size under --disagg")
    ap.add_argument("--decode-workers", type=int, default=1,
                    help="decode fleet size under --disagg")
    ap.add_argument("--kill-worker", action="append", default=[],
                    metavar="KIND:INDEX:STEP",
                    help="worker-death chaos under --disagg (e.g. "
                         "decode:1:40): the trace runs once clean to "
                         "record reference tokens, then with the "
                         "kill(s) — exit 8 when any survivor's output "
                         "diverges, pages leak, or the audit ends "
                         "dirty. Repeatable.")
    ap.add_argument("--replicas", type=int, default=0,
                    help="replay against the ELASTIC FLEET "
                         "(inference/fleet.py): this many whole engine "
                         "replicas behind the session-aware router; "
                         "the report adds per-replica utilization + "
                         "routing/migration counts (docs/SERVING.md "
                         "'Elastic fleet')")
    ap.add_argument("--route", default=None,
                    choices=("session", "least_loaded", "round_robin"),
                    help="fleet routing policy under --replicas "
                         "(default session; round_robin/least_loaded "
                         "are the baselines session-aware routing is "
                         "measured against)")
    ap.add_argument("--kill-replica", action="append", default=[],
                    metavar="INDEX:STEP",
                    help="replica-death chaos under --replicas (e.g. "
                         "1:40): the trace runs once clean to record "
                         "reference tokens, then with the kill(s) — "
                         "exit 9 when any survivor's output diverges, "
                         "pages leak, or the audit ends dirty. "
                         "Repeatable.")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix KV reuse (the "
                         "cold-prefix baseline)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft k tokens per "
                         "slot per tick (0 = off)")
    ap.add_argument("--draft-layers", type=int, default=1,
                    help="layer count of the draft model (--spec-k)")
    ap.add_argument("--json", action="store_true",
                    help="emit one machine-readable JSON line instead "
                         "of the text report")
    ap.add_argument("--expect-pallas", action="store_true",
                    help="fail (exit 4) when the replay fell off the "
                         "Pallas paged-decode path — any single-token "
                         "gather step, or no pallas trace at all")
    ap.add_argument("--expect-prefix-hit-rate", type=float,
                    default=None, metavar="RATE",
                    help="fail (exit 5) when prefix_hit_rate lands "
                         "below RATE")
    ap.add_argument("--expect-p99-ttft-ms", type=float, default=None,
                    metavar="MS",
                    help="fail (exit 7) when p99 TTFT (virtual clock) "
                         "lands above MS — the whale-starvation guard "
                         "for long-context traces; scoped by "
                         "--ttft-tag when the trace tags requests")
    ap.add_argument("--ttft-tag", default=None, metavar="TAG",
                    help="restrict --expect-p99-ttft-ms to requests "
                         "whose trace line carries \"tag\": TAG "
                         "(e.g. gate only the small requests of a "
                         "mixed whale/small trace)")
    ap.add_argument("--chaos", action="store_true",
                    help="drive the trace twice — clean, then with a "
                         "seeded FaultInjector — and fail (exit 6) on "
                         "leaked pages, surviving-output divergence, "
                         "or invariant-audit findings")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="FaultInjector seed for --chaos (the whole "
                         "fault schedule replays from it)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the stitched per-request span "
                         "timelines (QUEUED/PREFILL/MIGRATING/"
                         "PREEMPTED/DECODE/terminal) as chrome-trace "
                         "JSON — perfetto-loadable, byte-identical "
                         "across same-seed replays; works under "
                         "--disagg/--replicas/--chaos; "
                         "tools/trace_summary.py tabulates it")
    ap.add_argument("--expect-hotpath-clean", action="store_true",
                    help="fail (exit 13) when inspect_hotpath() on "
                         "the drained serving surface reports any "
                         "hot-path finding (missed donation, fetch-"
                         "set bloat, host sync in the tick loop, "
                         "steady-tick upload, recompile-risk cache "
                         "key); works under --disagg/--replicas/"
                         "--embedding; hotpath counter deltas land "
                         "in the report")
    ap.add_argument("--expect-complete-timelines", action="store_true",
                    help="exit 12 unless every replayed request "
                         "yields exactly one contiguous timeline in "
                         "the stitched export (first span QUEUED, no "
                         "gaps/overlaps, one terminal span, FAILED "
                         "carrying its reason)")
    ap.add_argument("--fault-rate", type=float, default=0.05,
                    help="per-query fire probability for each fault "
                         "point under --chaos")
    args = ap.parse_args(argv)

    if not os.path.exists(args.trace):
        print(f"serving_replay: no such trace: {args.trace}",
              file=sys.stderr)
        return 2
    trace = []
    with open(args.trace) as fh:
        for ln in fh:
            ln = ln.strip()
            if ln:
                trace.append(json.loads(ln))
    trace.sort(key=lambda r: r["arrival_ms"])
    if not trace:
        print("serving_replay: empty trace", file=sys.stderr)
        return 2

    if args.embedding:
        # the embedding service has no KV/pages/draft/fleet surface —
        # a decoder-only flag here would be silently ignored, the same
        # wrong-comparison trap as --route without --replicas
        bad = [flag for flag, on in (
            ("--disagg", args.disagg),
            ("--replicas", bool(args.replicas)),
            ("--chaos", args.chaos),
            ("--kill-worker", bool(args.kill_worker)),
            ("--kill-replica", bool(args.kill_replica)),
            ("--spec-k", args.spec_k > 0),
            ("--max-prefill-tokens",
             args.max_prefill_tokens is not None),
            ("--no-prefix-cache", args.no_prefix_cache),
            ("--expect-pallas", args.expect_pallas),
            ("--expect-moe-pallas", args.expect_moe_pallas),
            ("--expect-prefix-hit-rate",
             args.expect_prefix_hit_rate is not None),
            ("--expect-p99-ttft-ms",
             args.expect_p99_ttft_ms is not None),
            ("--model ernie_moe", args.model == "ernie_moe"),
            ("--trace-out", args.trace_out is not None),
            ("--expect-complete-timelines",
             args.expect_complete_timelines),
        ) if on]
        if bad:
            print(f"serving_replay: {', '.join(bad)} make(s) no sense "
                  f"under --embedding (the BatchEncoder service has "
                  f"no KV decode surface; docs/SERVING.md 'Embedding "
                  f"service')", file=sys.stderr)
            return 2
        missing = [i for i, r in enumerate(trace) if "seq_len" not in r]
        if missing:
            print(f"serving_replay: --embedding trace line(s) "
                  f"{missing[:5]} lack \"seq_len\" — embedding traces "
                  f"are {{\"arrival_ms\", \"seq_len\"[, \"pooling\"]}} "
                  f"lines (is this a decoder trace?)", file=sys.stderr)
            return 2
        return _run_embedding(args, trace)
    if args.expect_moe_pallas and args.model != "ernie_moe":
        print("serving_replay: --expect-moe-pallas needs --model "
              "ernie_moe (a dense replay has no MoE dispatch to "
              "gate)", file=sys.stderr)
        return 2

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # runnable straight from a checkout: tools/ is sys.path[0], the
    # package root is one level up
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import monitor
    from paddle_tpu.inference.disagg import DisaggEngine
    from paddle_tpu.inference.engine import Engine, SamplingParams
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    kills = []
    for spec in args.kill_worker:
        try:
            kind, idx, step = spec.split(":")
            if kind not in ("prefill", "decode"):
                raise ValueError(kind)
            kills.append((kind, int(idx), int(step)))
        except ValueError:
            print(f"serving_replay: bad --kill-worker spec {spec!r} "
                  f"(want KIND:INDEX:STEP, e.g. decode:1:40)",
                  file=sys.stderr)
            return 2
    if kills and not args.disagg:
        print("serving_replay: --kill-worker needs --disagg",
              file=sys.stderr)
        return 2
    for spec in args.kill_replica:
        try:
            idx, step = spec.split(":")
            kills.append(("replica", int(idx), int(step)))
        except ValueError:
            print(f"serving_replay: bad --kill-replica spec {spec!r} "
                  f"(want INDEX:STEP, e.g. 1:40)", file=sys.stderr)
            return 2
    if args.kill_replica and not args.replicas:
        print("serving_replay: --kill-replica needs --replicas",
              file=sys.stderr)
        return 2
    if args.route is not None and not args.replicas:
        # same contract as --prefill-workers without --disagg: a
        # routing baseline silently measured against the single-loop
        # engine would be a wrong, non-erroring comparison
        print("serving_replay: --route needs --replicas (without it "
              "the replay drives the single-loop engine and the "
              "routing policy would be silently ignored)",
              file=sys.stderr)
        return 2
    if args.route is None:
        args.route = "session"
    if args.replicas and args.disagg:
        print("serving_replay: --replicas and --disagg are exclusive "
              "(the fleet multiplexes whole engines; disagg splits one "
              "engine into prefill/decode workers)", file=sys.stderr)
        return 2
    if args.replicas:
        idxs = {i for k, i, _ in kills if k == "replica"}
        bad = sorted(i for i in idxs if not 0 <= i < args.replicas)
        if bad:
            print(f"serving_replay: --kill-replica index(es) {bad} out "
                  f"of range (fleet size {args.replicas})",
                  file=sys.stderr)
            return 2
        if idxs and len(idxs) >= args.replicas:
            print(f"serving_replay: --kill-replica would kill every "
                  f"replica ({sorted(idxs)} of {args.replicas}) — the "
                  f"fleet must keep serving; leave at least one alive",
                  file=sys.stderr)
            return 2
    if not args.disagg and (args.prefill_workers != 1
                            or args.decode_workers != 1):
        print("serving_replay: --prefill-workers/--decode-workers "
              "need --disagg (without it the replay drives the "
              "single-loop engine and the worker counts would be "
              "silently ignored)", file=sys.stderr)
        return 2
    for kind, fleet_n in (("prefill", args.prefill_workers),
                          ("decode", args.decode_workers)):
        idxs = {i for k, i, _ in kills if k == kind}
        bad = sorted(i for i in idxs if not 0 <= i < fleet_n)
        if bad:
            print(f"serving_replay: --kill-worker {kind} index(es) "
                  f"{bad} out of range (fleet size {fleet_n})",
                  file=sys.stderr)
            return 2
        if len(idxs) >= fleet_n and idxs:
            print(f"serving_replay: --kill-worker would kill every "
                  f"{kind} worker ({sorted(idxs)} of {fleet_n}) — the "
                  f"fleet must keep serving; leave at least one alive",
                  file=sys.stderr)
            return 2

    paddle.seed(args.seed)
    max_ctx = max(r["prompt_len"] + r["new_tokens"] for r in trace)
    if args.model == "ernie_moe":
        from paddle_tpu.text.models.ernie_moe import (ErnieMoEConfig,
                                                      ErnieMoEForCausalLM)
        cfg = ErnieMoEConfig.tiny(vocab=args.vocab, hidden=args.hidden,
                                  layers=args.layers, heads=args.heads,
                                  experts=args.experts)
        cfg.top_k = args.top_k
        cfg.moe_every = args.moe_every
        model_cls = ErnieMoEForCausalLM
    else:
        cfg = LlamaConfig.tiny(vocab=args.vocab, hidden=args.hidden,
                               layers=args.layers, heads=args.heads)
        model_cls = LlamaForCausalLM
    cfg.max_position_embeddings = max(cfg.max_position_embeddings,
                                      max_ctx + max(args.spec_k, 0) + 1)
    cfg.use_flash_attention = False
    net = model_cls(cfg)
    net.eval()
    draft = None
    if args.spec_k > 0:
        paddle.seed(args.seed + 1)
        dcfg = LlamaConfig.tiny(vocab=args.vocab, hidden=args.hidden,
                                layers=args.draft_layers,
                                heads=args.heads)
        dcfg.max_position_embeddings = cfg.max_position_embeddings
        dcfg.use_flash_attention = False
        draft = LlamaForCausalLM(dcfg)
        draft.eval()

    # the engine runs on the replay's VIRTUAL clock (vt_box advanced
    # by the drive loop), so per-request deadline_ms expiries — and
    # the whole chaos schedule — replay deterministically
    vt_box = {"vt": 0.0}

    def make_engine(injector=False):
        # injector=False forces injection OFF even when the process is
        # flag-armed (FLAGS_serving_fault_seed): the plain replay and
        # the --chaos baseline pass must both be genuinely clean
        kw = dict(page_size=args.page_size,
                  prefill_bucket=args.prefill_bucket,
                  cache_dtype=args.cache_dtype, max_context=max_ctx,
                  prefix_cache=not args.no_prefix_cache,
                  draft_model=draft, spec_k=max(args.spec_k, 1),
                  clock=lambda: vt_box["vt"] / 1e3,
                  fault_injector=injector,
                  max_prefill_tokens_per_step=args.max_prefill_tokens)
        if args.disagg:
            return DisaggEngine(net,
                                prefill_workers=args.prefill_workers,
                                decode_workers=args.decode_workers,
                                max_slots=args.max_slots,
                                pool_pages=args.pool_pages, **kw)
        if args.replicas:
            from paddle_tpu.inference.fleet import ServingFleet
            return ServingFleet(net, replicas=args.replicas,
                                max_slots=args.max_slots,
                                pool_pages=args.pool_pages,
                                router=args.route, **kw)
        return Engine(net, max_slots=args.max_slots,
                      pool_pages=args.pool_pages, **kw)

    rng = np.random.default_rng(args.seed)
    # the shared system prompt is ONE token block: request prompts with
    # "system_len": N open with its first N tokens (page-aligned
    # chunks of it dedup through the prefix cache), then diverge
    max_sys = max((r.get("system_len", 0) for r in trace), default=0)
    # drawn only when the trace uses it: legacy traces (no system_len)
    # keep their exact rng stream, so replays stay comparable across
    # tool versions
    system = (rng.integers(0, args.vocab, (max_sys,)) if max_sys
              else np.zeros((0,), np.int64))
    # multi-session traces (the fleet's session-routing scenario): a
    # line with "session": "name" opens with that SESSION's OWN system
    # block instead of the single shared one — blocks drawn once per
    # session, in first-appearance order, AFTER the legacy draw so
    # session-free traces keep their exact historical rng stream
    session_blocks = {}
    for r in trace:
        name = r.get("session")
        if name is not None and name not in session_blocks:
            depth = max(int(x.get("system_len", 0)) for x in trace
                        if x.get("session") == name)
            session_blocks[name] = rng.integers(0, args.vocab, (depth,))
    prompts = []
    for r in trace:
        sl = min(int(r.get("system_len", 0)), int(r["prompt_len"]))
        head = (session_blocks[r["session"]] if r.get("session")
                is not None else system)
        tail = rng.integers(0, args.vocab, (r["prompt_len"] - sl,))
        prompts.append(np.concatenate([head[:sl], tail])
                       .astype(np.int64))
    def drive(eng, kills=()):
        """One full trace replay on the virtual clock. Returns None
        when the engine failed to drain (exit path 3). ``kills`` are
        (kind, index, step) worker deaths fired as the loop's step
        counter passes them (--disagg failover chaos)."""
        before = monitor.snapshot()
        vt_box["vt"] = 0.0
        arrival_vt = {}
        first_vt = {}
        finish = {}
        tags = {}
        pending_kills = sorted(kills, key=lambda k: k[2])
        fired_kills = []
        i = 0
        t0 = time.perf_counter()
        steps = 0
        pf_key = "serving.prefill_tokens"
        pf_before = int(before.get(pf_key, 0))
        while len(finish) < len(trace):
            vt = vt_box["vt"]
            while i < len(trace) and trace[i]["arrival_ms"] <= vt:
                r = trace[i]
                rid = eng.add_request(
                    prompts[i],
                    SamplingParams(
                        max_new_tokens=r["new_tokens"],
                        temperature=args.temperature,
                        seed=args.seed + i,
                        deadline_ms=r.get("deadline_ms"),
                        max_queue_steps=r.get("max_queue_steps")),
                    **({"tenant": str(r["tenant"])}
                       if (args.disagg or args.replicas)
                       and r.get("tenant") else {}))
                arrival_vt[rid] = r["arrival_ms"]
                if r.get("tag"):
                    tags[rid] = str(r["tag"])
                i += 1
            while pending_kills and steps >= pending_kills[0][2]:
                kind, idx, _ = pending_kills.pop(0)
                n = (eng.kill_replica(idx) if kind == "replica"
                     else eng.kill_worker(kind, idx))
                fired_kills.append((kind, idx))
                print(f"serving_replay: killed {kind}{idx} at step "
                      f"{steps} ({n} request(s) re-admitted)",
                      file=sys.stderr)
            if i < len(trace) and eng.idle:
                # idle gap: fast-forward to the next arrival (idle
                # includes mid-chunked-prefill slots — jumping the
                # clock over an in-flight prefill would inflate its
                # TTFT and spuriously expire deadlines)
                vt_box["vt"] = max(vt, float(trace[i]["arrival_ms"]))
                continue
            outs = eng.step()
            steps += 1
            # virtual cost of the tick: one decode step plus the
            # prefill tokens it executed (prefix hits prefill only
            # their tail, so reuse shows up directly in TTFT)
            pf_now = int(monitor.counter(pf_key).get())
            vt_box["vt"] += args.step_ms \
                + (pf_now - pf_before) * args.prefill_token_ms
            pf_before = pf_now
            vt = vt_box["vt"]
            for out in outs:
                finish[out.req_id] = (out, vt)
                # a request can finish the same tick it got its first
                # token (max_new_tokens=1) — the engine prunes
                # finished requests, so record its TTFT here
                if out.token_ids:
                    first_vt.setdefault(out.req_id, vt)
            # eng.requests holds only LIVE requests (waiting/active)
            for rid, req in eng.requests.items():
                if rid not in first_vt and req.generated:
                    first_vt[rid] = vt
            if steps > 100_000:
                return None
        return {
            "fired_kills": fired_kills, "unfired_kills": pending_kills,
            "finish": finish, "first_vt": first_vt,
            "arrival_vt": arrival_vt, "tags": tags, "steps": steps,
            "wall_s": time.perf_counter() - t0,
            "before": before, "after": monitor.snapshot(),
        }

    baseline = None
    # False = injection FORCED OFF (the clean contract even when the
    # process is flag-armed via FLAGS_serving_fault_*); only --chaos
    # builds a real injector — a --kill-worker run must diverge from
    # its baseline through the kill alone
    injector = False
    if args.chaos or kills:
        # worker-kill and fault chaos both need the clean run's
        # reference tokens to hold survivors exact against
        clean_eng = make_engine()
        baseline = drive(clean_eng)
        if baseline is None:
            print("serving_replay: clean engine did not drain",
                  file=sys.stderr)
            return 3
        clean_eng.close()
    if args.chaos:
        from paddle_tpu.inference.reliability import (FAULT_SITES,
                                                      FaultInjector)
        # with a SCHEDULED kill list, the injector's own worker/replica
        # death sites stay disarmed: a chaos kill landing first would
        # either make the scheduled kill hit the last live worker
        # (RuntimeError instead of the exit-8/9 contract) or turn it
        # into a no-op that reports a failover test that never ran
        sites = (tuple(s for s in FAULT_SITES
                       if not s.startswith(("worker.", "replica.")))
                 if kills else None)
        injector = FaultInjector(seed=args.fault_seed,
                                 rate=args.fault_rate, sites=sites)
    # fresh registry for the MEASURED run: the report's histograms
    # (serving.hist.*) are mergeable but not subtractable, so a chaos
    # baseline pass must not leak its samples into them (the counter
    # deltas are per-drive before/after snapshots either way)
    monitor.reset()
    eng = make_engine(injector)
    run = drive(eng, kills)
    if run is None:
        print("serving_replay: engine did not drain", file=sys.stderr)
        return 3
    if run.get("unfired_kills"):
        # a kill scheduled past the trace's drain point never fired —
        # the failover gate would pass VACUOUSLY; make the mismatch
        # loud instead of reporting a chaos run that never ran
        print(f"serving_replay: --kill-worker never fired for "
              f"{[f'{k}:{i}:{s}' for k, i, s in run['unfired_kills']]} "
              f"— the trace drained in {run['steps']} step(s); "
              f"schedule the kill earlier", file=sys.stderr)
        return 2
    finish, first_vt = run["finish"], run["first_vt"]
    arrival_vt, steps = run["arrival_vt"], run["steps"]
    wall_s, before, after = run["wall_s"], run["before"], run["after"]

    hotpath_report = None
    if args.expect_hotpath_clean:
        # lint the DRAINED surface (every executable the trace
        # compiled is warm, so the inventory is the replay's real
        # compiled set); inspect_hotpath bumps lint.hotpath.* AFTER
        # drive()'s snapshot — fold them into the delta window
        hotpath_report = eng.inspect_hotpath()
        after = dict(after)
        for k, v in monitor.snapshot().items():
            if k.startswith("lint.hotpath."):
                after[k] = v

    tags = run["tags"]
    ttft = [first_vt[r] - arrival_vt[r] for r in sorted(first_vt)]
    # per-tag TTFT columns (traces may tag request classes, e.g.
    # "whale"/"small" on the long-context fixture): the mixed-traffic
    # fairness numbers the chunked-prefill gate reads
    ttft_by_tag = {}
    for r in sorted(first_vt):
        if r in tags:
            ttft_by_tag.setdefault(tags[r], []).append(
                first_vt[r] - arrival_vt[r])
    tpot = []
    total_tokens = 0
    preempts = 0
    failures = {}
    for rid, (out, end_vt) in sorted(finish.items()):
        n = len(out.token_ids)
        total_tokens += n
        preempts += out.preemptions
        if not out.ok:
            failures[out.finish_reason] = \
                failures.get(out.finish_reason, 0) + 1
        if n > 1 and rid in first_vt:
            tpot.append((end_vt - first_vt[rid]) / (n - 1))
    deltas = {k: int(after.get(k, 0)) - int(before.get(k, 0))
              for k in after
              if k.startswith(("kernels.decode.", "kernels.flash.",
                               "kernels.moe.", "serving.moe.",
                               # fleet COUNTERS only — the serving.fleet.*
                               # namespace also holds gauges (queue_depth,
                               # replicas, per-replica hit rates) that a
                               # delta over snapshots would misreport
                               "serving.fleet.routed_",
                               "serving.fleet.migrations",
                               "serving.fleet.replica_deaths",
                               "serving.fleet.readmitted",
                               "serving.fleet.scale_events",
                               "serving.preemptions",
                               "serving.prefill_tokens",
                               "serving.prefix_", "serving.spec_",
                               "serving.timeouts", "serving.cancelled",
                               "serving.failed",
                               "serving.nan_quarantines",
                               "serving.step_errors",
                               "serving.invariant_repairs",
                               "serving.fault_injected.",
                               "lint.hotpath.", "xla.compiles"))
              and int(after.get(k, 0)) - int(before.get(k, 0))}
    # the per-replay decode-path breakdown: which attention path the
    # compiled loops actually baked in (trace-time counters,
    # docs/OBSERVABILITY.md) — "gather_step" > 0 on a TPU serving box
    # means every token is paying a full-cache copy
    path_names = {
        "pallas": "kernels.decode.paged_pallas",
        "gather_step": "kernels.decode.paged_xla_gather_step",
        "prefill_gather": "kernels.decode.paged_xla_gather",
        "dense": "kernels.decode.dense_xla",
        "rolling": "kernels.decode.rolling_xla",
    }
    decode_paths = {name: deltas.get(key, 0)
                    for name, key in path_names.items()}
    report = {
        "requests": len(trace),
        "steps": steps,
        "total_tokens": total_tokens,
        "wall_s": round(wall_s, 3),
        "tokens_per_sec": round(total_tokens / max(wall_s, 1e-9), 1),
        "preemptions": preempts,
        "failed": failures,
        "ttft_ms": _percentiles(ttft),
        "ttft_ms_by_tag": {t: _percentiles(v)
                           for t, v in sorted(ttft_by_tag.items())},
        "tpot_ms": _percentiles(tpot),
        "prefix_hit_rate": round(eng.prefix_hit_rate, 4),
        "spec_accept_rate": round(eng.spec_accept_rate, 4),
        "decode_paths": decode_paths,
        "pallas_eligible": bool(eng.pallas_eligible),
        "counters": deltas,
        "steady_state_recompiles": eng.steady_state_recompiles(),
    }
    if hotpath_report is not None:
        report["hotpath"] = {
            "findings": len(list(hotpath_report)),
            "rules": {r: len(fs)
                      for r, fs in hotpath_report.by_rule().items()},
        }
    # the observability plane's report surface: merged (fleet-wide)
    # latency histograms recorded by the engines themselves on the
    # virtual clock, plus the host/device tick attribution gauges
    detail = monitor.snapshot(detail=True)
    report["histograms"] = {
        k: v for k, v in sorted(detail.items())
        if k.startswith("serving.hist.") and isinstance(v, dict)}
    # host share over the measured run: registry was reset before the
    # run, so the tick histograms' mean*count totals are exactly the
    # measured-run sums (same arithmetic bench.py uses, via deltas)
    _hh = detail.get("serving.hist.host_ms_per_tick", {}) or {}
    _dh = detail.get("serving.hist.device_ms_per_tick", {}) or {}
    _host_sum = float(_hh.get("mean", 0.0)) * int(_hh.get("count", 0))
    _dev_sum = float(_dh.get("mean", 0.0)) * int(_dh.get("count", 0))
    host_share = (_host_sum / (_host_sum + _dev_sum)
                  if _host_sum + _dev_sum > 0 else 0.0)
    report["host_device"] = {
        "host_ms_per_tick": detail.get("serving.host_ms_per_tick",
                                       {"last": 0.0, "mean": 0.0}),
        "device_ms_per_tick": detail.get("serving.device_ms_per_tick",
                                         {"last": 0.0, "mean": 0.0}),
        "host_share": round(host_share, 4),
    }
    # stitched per-request timelines (span logs ride the Outputs)
    timelines = {rid: out.spans for rid, (out, _) in finish.items()
                 if getattr(out, "spans", None)}
    if eng.decode_fallback_reason:
        report["pallas_ineligible_reason"] = eng.decode_fallback_reason
    moe_paths = {}
    if args.model == "ernie_moe":
        # the MoE dispatch-path proof (docs/SERVING.md "MoE serving"):
        # the engine republishes trace-time kernels.moe.decode_path.*
        # deltas into serving.moe.decode_path.* — {"pallas": n} with no
        # fallback.* keys means every compiled serving executable baked
        # in the fused grouped-matmul, never a silent einsum/scatter
        pfx = "serving.moe.decode_path."
        moe_paths = {k[len(pfx):]: v for k, v in deltas.items()
                     if k.startswith(pfx)}
        report["moe"] = {
            "experts": args.experts,
            "top_k": args.top_k,
            # construction-time eligibility verdict (fleet/disagg wrap
            # per-worker engines; the counters above are the shared
            # surface there)
            "pallas_eligible": getattr(eng, "moe_pallas_eligible",
                                       None),
            "fallback_reason": getattr(eng, "moe_fallback_reason",
                                       None),
            "decode_paths": moe_paths,
        }
    if args.replicas:
        # the elastic-fleet report block: per-replica busy-step
        # utilization, warm/cold routing counts and per-replica prefix
        # hit rates — the first thing to read when fleet-wide
        # prefix_hit_rate regresses is whether the router scattered a
        # session across replicas

        def cdelta(key):
            return int(after.get(key, 0)) - int(before.get(key, 0))

        report["fleet"] = {
            "replicas": args.replicas,
            "route": args.route,
            "routed_warm": cdelta("serving.fleet.routed_warm"),
            "routed_cold": cdelta("serving.fleet.routed_cold"),
            "migrations": cdelta("serving.fleet.migrations"),
            "replica_deaths": cdelta("serving.fleet.replica_deaths"),
            "readmitted": cdelta("serving.fleet.readmitted"),
            "scale_events": cdelta("serving.fleet.scale_events"),
            "replica_kills": [f"{i}:{s}" for k, i, s in kills
                              if k == "replica"],
            "replicas_table": eng.utilization(),
            # per-replica latency straight from each replica's LABELED
            # metric scope (serving.<replica>.hist.*) — no more
            # re-deriving per-replica numbers by subtracting registry
            # snapshots around each replica's step
            "ttft_by_replica": {
                k.split(".")[1]: v for k, v in sorted(detail.items())
                if k.startswith("serving.replica")
                and k.endswith(".hist.ttft_ms")
                and isinstance(v, dict)},
        }
    if args.disagg:
        # the disaggregated report block: per-worker busy-step
        # utilization + migration counts (the first thing to read when
        # a disagg number regresses is whether one fleet is starved)
        report["disagg"] = {
            "prefill_workers": args.prefill_workers,
            "decode_workers": args.decode_workers,
            "migrations": int(after.get(
                "serving.disagg.migrations", 0)) - int(before.get(
                    "serving.disagg.migrations", 0)),
            "migrated_pages": int(after.get(
                "serving.migrated_pages", 0)) - int(before.get(
                    "serving.migrated_pages", 0)),
            "worker_kills": [f"{k}:{i}:{s}" for k, i, s in kills],
            "readmitted": int(after.get(
                "serving.disagg.readmitted", 0)) - int(before.get(
                    "serving.disagg.readmitted", 0)),
            "workers": eng.utilization(),
        }

    def survivors_vs_baseline():
        mismatched = []
        for rid, (out, _) in sorted(finish.items()):
            if not out.ok:
                continue
            ref_out, _ = baseline["finish"][rid]
            if ref_out.ok and out.token_ids != ref_out.token_ids:
                mismatched.append(rid)
        return mismatched

    def residual_pages(e):
        """Leaked pages after idle prefix-cache refs are released —
        Engine.leaked_pages / DisaggEngine.leaked_pages, the one
        shared contract (idle cache refs are not leaks)."""
        return e.leaked_pages()

    kill_failed = False
    if kills:
        # the failover contract: a worker/replica death may slow
        # requests, never change a survivor's tokens, leak pages, or
        # leave the audit dirty
        mismatched = survivors_vs_baseline()
        leaked = residual_pages(eng)
        findings = eng.check_invariants()
        kill_key = "replica_kill" if args.replicas else "worker_kill"
        report[kill_key] = {
            "kills": [f"{k}:{i}:{s}" for k, i, s in kills],
            "survivors_exact": not mismatched,
            "mismatched_request_ids": mismatched,
            "leaked_pages": leaked,
            "invariant_findings": findings,
        }
        kill_failed = bool(mismatched or leaked or findings)

    chaos_failed = False
    if args.chaos:
        # the chaos contract: faults may slow or FAIL individual
        # requests, never corrupt a survivor, leak a page, or leave
        # refcount skew behind
        mismatched = survivors_vs_baseline()
        leaked = residual_pages(eng)
        findings = eng.check_invariants()
        report["chaos"] = {
            "fault_seed": args.fault_seed,
            "fault_rate": args.fault_rate,
            "injected": dict(sorted(injector.counts.items())),
            "total_injected": injector.total_injected,
            "survivors": sum(1 for out, _ in finish.values()
                             if out.ok),
            "survivors_exact": not mismatched,
            "mismatched_request_ids": mismatched,
            "leaked_pages": leaked,
            "invariant_findings": findings,
        }
        chaos_failed = bool(mismatched or leaked or findings)
    fell_off = (decode_paths["gather_step"] > 0
                or decode_paths["pallas"] == 0)
    if not args.json:
        print(f"replayed {report['requests']} requests / "
              f"{report['total_tokens']} tokens in {report['steps']} "
              f"steps ({report['wall_s']}s wall) — "
              f"{report['tokens_per_sec']} tokens_per_sec")
        for name in ("ttft_ms", "tpot_ms"):
            ps = report[name]
            print(f"  {name:8s} p50 {ps['p50']:8.2f}  "
                  f"p90 {ps['p90']:8.2f}  p99 {ps['p99']:8.2f}   "
                  f"(virtual clock)")
        for tag, ps in report["ttft_ms_by_tag"].items():
            print(f"  ttft[{tag}] p50 {ps['p50']:8.2f}  "
                  f"p90 {ps['p90']:8.2f}  p99 {ps['p99']:8.2f}")
        hd = report["host_device"]
        print(f"  host_ms_per_tick "
              f"{hd['host_ms_per_tick'].get('mean', 0.0):.3f}  "
              f"device_ms_per_tick "
              f"{hd['device_ms_per_tick'].get('mean', 0.0):.3f}   "
              f"(wall clock, mean/tick)")
        print(f"  host_share {hd['host_share']:.4f}")
        for name, st in report["histograms"].items():
            print(f"  {name:32s} n {st['count']:5d}  "
                  f"p50 {st['p50']:8.2f}  p90 {st['p90']:8.2f}  "
                  f"p99 {st['p99']:8.2f}")
        print(f"  preemptions {report['preemptions']}  "
              f"steady_state_recompiles "
              f"{report['steady_state_recompiles']}")
        if failures:
            print("  failed: " + "  ".join(
                f"{k} x{v}" for k, v in sorted(failures.items())))
        print(f"  prefix_hit_rate {report['prefix_hit_rate']}  "
              f"spec_accept_rate {report['spec_accept_rate']}")
        if args.replicas:
            fl = report["fleet"]
            print(f"  fleet: {fl['replicas']} replicas "
                  f"(route={fl['route']}), routed warm/cold "
                  f"{fl['routed_warm']}/{fl['routed_cold']}, "
                  f"{fl['migrations']} migrations, "
                  f"{fl['replica_deaths']} deaths / "
                  f"{fl['readmitted']} re-admitted, "
                  f"{fl['scale_events']} scale events")
            for name, st in sorted(fl["replicas_table"].items()):
                dead = "" if st["alive"] else "  [DEAD]"
                hr = st["prefix_hit_rate"]
                print(f"    {name:10s} util {st['utilization']:6.2%}  "
                      f"warm {st['routed_warm']:3d}  "
                      f"cold {st['routed_cold']:3d}  "
                      f"hit_rate "
                      f"{hr if hr is not None else '-':>6}  "
                      f"finished {st['finished']:3d}{dead}")
            for name, st in sorted(fl["ttft_by_replica"].items()):
                print(f"    {name:10s} ttft n {st['count']:3d}  "
                      f"p50 {st['p50']:8.2f}  p99 {st['p99']:8.2f}")
        if args.disagg:
            dg = report["disagg"]
            print(f"  disagg: {dg['prefill_workers']}p+"
                  f"{dg['decode_workers']}d workers, "
                  f"{dg['migrations']} migrations / "
                  f"{dg['migrated_pages']} pages migrated, "
                  f"{dg['readmitted']} re-admitted")
            for name, st in sorted(dg["workers"].items()):
                dead = "" if st["alive"] else "  [DEAD]"
                print(f"    {name:10s} util {st['utilization']:6.2%}  "
                      f"migrations {st['migrations']:3d}  "
                      f"pages_migrated {st['pages_migrated']:4d}"
                      f"{dead}")
        if kills:
            wk = report["replica_kill" if args.replicas
                        else "worker_kill"]
            print(f"  kill: {', '.join(wk['kills'])} — "
                  f"exact={wk['survivors_exact']} "
                  f"leaked_pages={wk['leaked_pages']}")
        if args.chaos:
            ch = report["chaos"]
            print(f"  chaos: {ch['total_injected']} faults injected "
                  f"(seed {ch['fault_seed']}), "
                  f"{ch['survivors']}/{report['requests']} survivors, "
                  f"exact={ch['survivors_exact']}, "
                  f"leaked_pages={ch['leaked_pages']}")
            for site, n in sorted(ch["injected"].items()):
                print(f"    {site} x{n}")
        print("  decode paths: " + "  ".join(
            f"{k} +{v}" for k, v in decode_paths.items()))
        if not eng.pallas_eligible:
            print(f"  pallas ineligible: {eng.decode_fallback_reason}")
        if args.model == "ernie_moe":
            mo = report["moe"]
            shown = "  ".join(f"{k} +{v}"
                              for k, v in sorted(moe_paths.items())) \
                or "(none traced)"
            print(f"  moe dispatch paths: {shown}")
            if mo["fallback_reason"]:
                print(f"  moe pallas ineligible: "
                      f"{mo['fallback_reason']}")
        for k in sorted(report["counters"]):
            print(f"  {k} +{report['counters'][k]}")
    else:
        print(json.dumps(report))
    if args.trace_out:
        from paddle_tpu.inference import tracing
        tracing.export_serving_trace(timelines, args.trace_out)
        print(f"serving_replay: wrote {len(timelines)} timeline(s) to "
              f"{args.trace_out}", file=sys.stderr)
    if args.expect_pallas and fell_off:
        why = eng.decode_fallback_reason or \
            "backend/geometry did not trace the Pallas kernel"
        print(f"serving_replay: --expect-pallas FAILED — decode paths "
              f"{decode_paths} ({why}); every single-token step must "
              f"stay on kernels.decode.paged_pallas "
              f"(docs/DECODE.md eligibility table)", file=sys.stderr)
        return 4
    if args.expect_moe_pallas:
        fell = sum(v for k, v in moe_paths.items()
                   if k.startswith("fallback.")) > 0 \
            or moe_paths.get("pallas", 0) == 0
        if fell:
            why = getattr(eng, "moe_fallback_reason", None) or \
                "backend/geometry did not trace the fused MoE kernel"
            print(f"serving_replay: --expect-moe-pallas FAILED — moe "
                  f"dispatch paths {moe_paths} ({why}); every "
                  f"compile-bearing MoE decode step must stay on the "
                  f"fused Pallas grouped-matmul "
                  f"(docs/KERNELS.md eligibility)", file=sys.stderr)
            return 10
    if args.expect_zero_recompiles \
            and report["steady_state_recompiles"]:
        print(f"serving_replay: --expect-zero-recompiles FAILED — "
              f"{report['steady_state_recompiles']} steady-state "
              f"recompile(s); the compiled serving surfaces churned "
              f"mid-trace (docs/OBSERVABILITY.md xla.compiles)",
              file=sys.stderr)
        return 11
    if args.expect_prefix_hit_rate is not None and \
            report["prefix_hit_rate"] < args.expect_prefix_hit_rate:
        print(f"serving_replay: --expect-prefix-hit-rate FAILED — "
              f"{report['prefix_hit_rate']} < "
              f"{args.expect_prefix_hit_rate} "
              f"({'prefix cache DISABLED' if args.no_prefix_cache else 'shared prefixes are not being reused'}; "
              f"docs/SERVING.md prefix lifecycle)", file=sys.stderr)
        return 5
    if args.expect_p99_ttft_ms is not None:
        # the whale-starvation guard: the gated class's p99 TTFT (and
        # every gated request actually REACHING a first token) must
        # hold under mixed traffic — exit 7 so CI distinguishes a
        # fairness regression from the path/prefix/chaos gates
        if args.ttft_tag is not None:
            gated = report["ttft_ms_by_tag"].get(args.ttft_tag)
            n_tagged = sum(1 for t in tags.values()
                           if t == args.ttft_tag)
            n_first = len(ttft_by_tag.get(args.ttft_tag, []))
            scope = f"tag {args.ttft_tag!r}"
        else:
            gated = report["ttft_ms"]
            n_tagged = len(trace)
            n_first = len(ttft)
            scope = "all requests"
        if args.ttft_tag is not None and n_tagged == 0:
            print(f"serving_replay: --expect-p99-ttft-ms FAILED — "
                  f"no trace request carries \"tag\": "
                  f"{args.ttft_tag!r} (check the --ttft-tag spelling "
                  f"against the trace's tag fields)", file=sys.stderr)
            return 7
        p99 = gated["p99"] if gated else float("inf")
        if gated is None or n_first < n_tagged \
                or p99 > args.expect_p99_ttft_ms:
            print(f"serving_replay: --expect-p99-ttft-ms FAILED — "
                  f"{scope}: p99 {p99} > {args.expect_p99_ttft_ms} "
                  f"or first tokens missing ({n_first}/{n_tagged}) — "
                  f"long prompts are starving the queue "
                  f"(docs/SERVING.md 'Chunked prefill'; run with "
                  f"--max-prefill-tokens to bound prefill slices)",
                  file=sys.stderr)
            return 7
    if chaos_failed:
        ch = report["chaos"]
        print(f"serving_replay: --chaos FAILED — "
              f"mismatched survivors {ch['mismatched_request_ids']}, "
              f"leaked_pages {ch['leaked_pages']}, "
              f"invariant findings {ch['invariant_findings']} "
              f"(seed {args.fault_seed} replays this schedule "
              f"bit-identically; docs/SERVING.md 'Reliability')",
              file=sys.stderr)
        return 6
    if kill_failed:
        flag = "--kill-replica" if args.replicas else "--kill-worker"
        wk = report["replica_kill" if args.replicas else "worker_kill"]
        print(f"serving_replay: {flag} FAILED — "
              f"mismatched survivors {wk['mismatched_request_ids']}, "
              f"leaked_pages {wk['leaked_pages']}, "
              f"invariant findings {wk['invariant_findings']} — a "
              f"{'replica' if args.replicas else 'worker'} death may "
              f"slow requests, never change a survivor's tokens "
              f"(docs/SERVING.md "
              f"{'Elastic fleet' if args.replicas else 'Disaggregated serving'!r})",
              file=sys.stderr)
        return 9 if args.replicas else 8
    if args.expect_complete_timelines:
        # completeness is asserted VIA THE STITCHED EXPORT (the same
        # artifact --trace-out writes), not the in-memory span lists:
        # a span the export drops or reorders must fail this gate
        from paddle_tpu.inference import tracing
        rebuilt = tracing.timelines_from_trace(
            tracing.build_serving_trace(timelines))
        problems = {}
        for rid, (out, _) in sorted(finish.items()):
            spans = rebuilt.get(rid)
            if not spans:
                problems[rid] = ["no timeline in the stitched export"]
                continue
            ps = tracing.validate_timeline(spans, tol_ms=0.01)
            want = "FINISHED" if out.ok else "FAILED"
            if spans[-1].get("phase") != want:
                ps = ps + [f"request {'finished' if out.ok else 'failed'}"
                           f" but timeline ends "
                           f"{spans[-1].get('phase')!r}"]
            if ps:
                problems[rid] = ps
        if problems:
            shown = {r: problems[r] for r in sorted(problems)[:5]}
            print(f"serving_replay: --expect-complete-timelines "
                  f"FAILED — {len(problems)}/{len(finish)} request(s) "
                  f"with broken timelines, e.g. {shown} "
                  f"(every request must stitch into one contiguous "
                  f"QUEUED..FINISHED/FAILED(reason) span log across "
                  f"migration/failover; docs/OBSERVABILITY.md "
                  f"'Serving timelines')", file=sys.stderr)
            return 12
    if hotpath_report is not None and hotpath_report:
        print(f"serving_replay: --expect-hotpath-clean FAILED — "
              f"{len(list(hotpath_report))} hot-path finding(s) on "
              f"the drained serving surface:\n{hotpath_report.format()}"
              f"\n(docs/ANALYSIS.md 'Hot-path rules')", file=sys.stderr)
        return 13
    return 0


if __name__ == "__main__":
    sys.exit(main())
