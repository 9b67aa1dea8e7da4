"""Runner `serve`: the continuous-batching `Engine` on one chip, driven
from outside through `add_request()` / `step()` by one thread.

Traffic: a mix for `benchmark/harness/traffic.py` (closed loop with a
fixed number of clients, or Poisson arrivals at a fixed rate) plus
"steady_seconds" (how long the loop runs before the window opens),
"ramp_seconds" (a closed loop's clients join evenly over this long),
"traced_seconds", "reference_prompt_tokens" and "reference_new_tokens".
Configuration: the model's sizes at the top level, "serving" (weight dtype) and
"engine" (keyword arguments of `Engine`).

Set-up sends one request of every padded prompt length of the mix, then
runs the loop itself for `steady_seconds`; the window opens on the
running loop and lasts exactly `--seconds`. A request counts where it
FINISHES: inside the window it is attempted (and failed unless it ended
ok at its full length); in flight when the window closes it is neither.
A traced run profiles `traced_seconds` more of the same loop after the
window.
"""
from __future__ import annotations

import dataclasses
import heapq
import time

import numpy as np

from benchmark.harness import stats
from benchmark.harness.device import memory_peak_bytes
from benchmark.harness.job import Job, Measured, say, span, traced_window
from benchmark.harness.model import build_llama
from benchmark.harness.traffic import Generator
from benchmark.reference import decoder as ref

COUNTERS = ("kernels.decode.paged_pallas",
            "kernels.decode.paged_xla_gather_step",
            "serving.decode_fallback")


def build_model(cell, seed):
    cfg, net = build_llama(cell.config, seed, use_flash_attention=True)
    net.eval()
    if cell.config["serving"]["weight_dtype"] != "float32":
        net.astype(cell.config["serving"]["weight_dtype"])
    return cfg, net


def _params(n_new):
    from paddle_tpu.inference.engine import SamplingParams
    return SamplingParams(max_new_tokens=int(n_new), temperature=0.0,
                          eos_token_id=None)


def _drain(eng):
    outs = []
    while not eng.idle:
        outs.extend(eng.step())
    return outs


def check_against_reference(eng, net, model, rng, n_prompt, n_new):
    """One prompt through the engine (prefill, then decode through the
    paged cache), greedy; the reference scores the same tokens in one
    full forward pass. At each generated position the reference's logit
    of the engine's token must be within TOKEN_LOGIT_TOL of the logit
    range below the reference's best."""
    prompt = rng.integers(0, model["vocab_size"], n_prompt).astype(np.int64)
    eng.add_request(prompt, _params(n_new))
    out, = _drain(eng)
    if not out.ok or len(out.token_ids) != n_new:
        return f"reference request ended {out.finish_reason!r}"
    seq = np.concatenate([prompt, np.asarray(out.token_ids[:-1], np.int64)])
    rows = np.asarray(ref.logits(ref.model_weights(net), model, seq)
                      [n_prompt - 1:])
    picked = rows[np.arange(n_new), np.asarray(out.token_ids)]
    short = (rows.max(-1) - picked) / (rows.max(-1) - rows.min(-1))
    say("reference", prompt_tokens=n_prompt, new_tokens=n_new,
        shortfall=[float(s) for s in short],
        same_argmax=int((rows.argmax(-1) == np.asarray(out.token_ids)).sum()),
        tolerance=ref.TOKEN_LOGIT_TOL)
    ok = np.all(np.isfinite(short)) and float(short.max()) <= \
        ref.TOKEN_LOGIT_TOL
    return True if ok else (f"engine tokens score {short.tolist()} of the "
                            f"logit range under the reference's best")


class Loop:
    """The load generator and the engine's drive loop, one thread. Times
    are `time.perf_counter`, the engine's own clock, so its spans,
    `Output.ttft_ms` and the times here are on one axis."""

    def __init__(self, eng, gen: Generator, ramp_seconds: float):
        self.eng, self.gen = eng, gen
        self.due = []                 # heap of (due_time, index, Planned)
        self.sent = {}                # req_id -> (Planned, due, sent)
        self.done = []                # (finish_time, Output, Planned, due, sent)
        self.ticks = []               # (start, seconds, active, waiting, prefilling)
        now = time.perf_counter()
        if gen.kind == "closed":
            # the clients join one by one over `ramp_seconds`, not as one
            # burst of prefills, and out of phase, as in a system that has
            # been running: client i's first answer is cut to (i+1)/clients
            # of its planned length, so they do not all finish together
            for i in range(gen.clients):
                plan = gen.draw()
                cut = max(1, plan.max_new_tokens * (i + 1) // gen.clients)
                self._plan(now + i * ramp_seconds / gen.clients,
                           dataclasses.replace(plan, max_new_tokens=cut))
        else:
            self._plan_arrival(now)

    def _plan(self, due, plan):
        heapq.heappush(self.due, (due, plan.index, plan))

    def _plan_arrival(self, after):
        plan = self.gen.draw()
        self._plan(after + plan.gap_s, plan)

    def tick(self):
        eng = self.eng
        now = time.perf_counter()
        with span("bench.admit"):
            while self.due and self.due[0][0] <= now:
                due, _, plan = heapq.heappop(self.due)
                rid = eng.add_request(plan.prompt,
                                      _params(plan.max_new_tokens))
                self.sent[rid] = (plan, due, time.perf_counter())
                if self.gen.kind != "closed":
                    self._plan_arrival(due)
        if eng.idle:
            # open loop with nothing to do: wait for the next arrival
            if self.due:
                time.sleep(max(0.0, min(self.due[0][0] - now, 0.001)))
            return
        state = (eng.num_active, eng.num_waiting, eng.num_prefilling)
        t0 = time.perf_counter()
        with span("bench.engine_step"):
            outs = eng.step()
        t1 = time.perf_counter()
        self.ticks.append((t0, t1 - t0) + state)
        with span("bench.harvest"):
            for out in outs:
                plan, due, sent = self.sent.pop(out.req_id)
                self.done.append((t1, out, plan, due, sent))
                if self.gen.kind == "closed":
                    self._plan(t1, self.gen.draw())

    def run_until(self, t_end):
        while time.perf_counter() < t_end:
            self.tick()


def _span_ms(out, phase):
    return [s["t1_ms"] - s["t0_ms"] for s in out.spans
            if s["phase"] == phase and s.get("t1_ms") is not None]


def run(job: Job) -> Measured:
    from paddle_tpu import monitor
    from paddle_tpu.inference.engine import Engine

    cell, t = job.cell, job.cell.traffic
    model = cell.config
    rng = np.random.default_rng(job.seed)
    checks = {}

    cfg, net = build_model(cell, job.seed)
    say("model", params=net.num_params(),
        seconds=time.perf_counter() - job.process_start)
    before = monitor.snapshot()
    eng = Engine(net, **cell.config["engine"])
    try:
        checks["reference"] = check_against_reference(
            eng, net, model, rng, int(t["reference_prompt_tokens"]),
            int(t["reference_new_tokens"]))
        gen = Generator(t, cfg.vocab_size, job.seed)
        # every prefill shape the mix will use, once (compiles, or loads
        # from the persistent cache), longest first
        for n in reversed(gen.padded_prompt_lengths(eng.prefill_bucket)):
            t0 = time.perf_counter()
            eng.add_request(rng.integers(0, cfg.vocab_size, n), _params(2))
            _drain(eng)
            say("warmup_prefill", tokens=n,
                seconds=time.perf_counter() - t0)

        loop = Loop(eng, gen, float(t.get("ramp_seconds", 0.0)))
        loop.run_until(time.perf_counter() + float(t["steady_seconds"]))
        recompiles_before = eng.steady_state_recompiles()
        start = time.perf_counter()
        setup_s = start - job.process_start
        loop.run_until(start + job.seconds)
        end = start + job.seconds
        recompiles = eng.steady_state_recompiles() - recompiles_before
        hbm_peak = memory_peak_bytes(cell.chips)

        traced = {}
        if job.trace:
            with traced_window(job, traced):
                loop.run_until(time.perf_counter()
                               + float(t["traced_seconds"]))

        for rid in list(loop.sent):
            eng.cancel(rid)
        _drain(eng)
        leaked = eng.leaked_pages()
    finally:
        eng.close()
    after = monitor.snapshot()
    counters = {n: int(after.get(n, 0)) - int(before.get(n, 0))
                for n in COUNTERS}

    inside = [d for d in loop.done if start <= d[0] <= end]
    good = [d for d in inside
            if d[1].ok and len(d[1].token_ids) == d[2].max_new_tokens]
    ticks = [k for k in loop.ticks if start <= k[0] <= end]
    say("window", finished=len(inside), ok=len(good), ticks=len(ticks),
        counters=counters, leaked_pages=leaked, recompiles=recompiles,
        cache_dtype=str(eng.cache_dtype))

    on_tpu = job.device["platform"] == "tpu"
    checks["all_requests_ok"] = True if len(good) == len(inside) else \
        f"{len(inside) - len(good)} of {len(inside)} requests failed or " \
        f"came back short"
    checks["paged_pallas_decode"] = True if not on_tpu or (
        counters["kernels.decode.paged_pallas"] > 0
        and counters["kernels.decode.paged_xla_gather_step"] == 0
        and counters["serving.decode_fallback"] == 0) else \
        f"decode did not stay on the Pallas paged kernel: {counters}"
    checks["no_compile_in_window"] = True if recompiles == 0 else \
        f"{recompiles} recompile(s) inside the measured window"
    checks["no_leaked_pages"] = True if leaked == 0 else \
        f"{leaked} KV pages leaked"

    # time to first token from when the request was DUE: how late the
    # generator sent it, plus the engine's own arrival -> first token
    ttft = [(sent - due) * 1e3 + out.ttft_ms
            for _, out, _, due, sent in good]
    tpot = [out.tpot_ms for _, out, _, _, _ in good]
    e2e = {"setup_s": setup_s,
           "serve_tokens_per_s":
               sum(len(d[1].token_ids) for d in good) / job.seconds}
    # every tail the finished requests support (ten samples beyond it);
    # BENCHMARK.json says which of them the cell reports
    for name, values in (("ttft", ttft), ("tpot", tpot)):
        for pct in (50, 90, 95, 99):
            value = stats.tail_or_none(values, pct)
            if value is not None:
                e2e[f"{name}_p{pct}_ms"] = value
    return Measured(
        checks=checks, attempted=len(inside), failed=len(inside) - len(good),
        end_to_end=e2e,
        samples=dict(
            ttft_ms=ttft, tpot_ms=tpot,
            lateness_ms=[(sent - due) * 1e3 for _, _, _, due, sent in good],
            queue_ms=[x for d in good for x in _span_ms(d[1], "QUEUED")],
            prefill_ms=[x for d in good for x in _span_ms(d[1], "PREFILL")],
            preemptions=sum(d[1].preemptions for d in good),
            ticks=ticks, max_slots=eng.max_slots, window_s=job.seconds,
            requests_per_s=len(good) / job.seconds,
            hbm_peak_bytes=hbm_peak),
        trace=traced.get("trace"))
