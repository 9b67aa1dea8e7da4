"""Runner `serve_hybrid`: runner `serve`'s loop (the same `Engine`, the
same warm-up, steady loop, window and traced stretch: it IS
`runners/serve.py`'s `run`, on a copy of that module), for a decoder
whose layers keep two kinds of thing: linear-attention (KDA) layers a
constant-size state a slot, a softmax GQA layer a paged cache. The model
is named by the configuration's `architectures`, built in the
configuration's dtype from the start, and checked against
`reference/solar_open2.py`.

What differs from `serve`, and nothing else:

* `build_model`: `SolarOpen2ForCausalLM` from the published keys at the
  top level of the configuration; `n_routed_experts` there counts the
  experts HELD, so the router's width is that times `expert_share.of`.
* the reference check compares LOGITS, as `serve_latent` does: the
  reference request asks the engine for the float32 rows it sampled from
  (`SamplingParams(return_logits=True)`, engine `keep_logits`): chunked
  prefill (the state carried from chunk to chunk through the slot's
  rows), then decode through state and paged cache; the reference scores
  the same tokens in one full forward pass. Limits and reasons:
  `reference/solar_open2.py`. Two more information lines give the same
  errors against the reference with the decay and with the delta rule
  switched off, so that a reader sees whether the comparison can tell.
* the check `kda_pallas_decode` beside `paged_pallas_decode` (kept: the
  GQA layer): on a TPU every decode step of every KDA layer took the
  Pallas kernel (`kernels.decode.kda_pallas` > 0,
  `kernels.decode.kda_fallback` == 0).
* samples for the expert layers' metrics (the `serving.moe.*` counters'
  growth over the run) and for the state's (`serving.state.*`).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.harness import load
from benchmark.harness.job import Job, Measured, say
from benchmark.reference import solar_open2 as ref

COUNTERS = ("kernels.decode.kda_pallas", "kernels.decode.kda_fallback",
            "kernels.prefill.kda_chunked",
            "serving.state.resets", "serving.state.recomputes",
            "serving.moe.picks_held", "serving.moe.picks_total",
            "serving.moe.experts_touched", "serving.moe.layer_ticks")


def model_config(config: dict):
    """SolarOpen2Config from a configuration file's top-level keys."""
    from paddle_tpu.text.models import SolarOpen2Config
    share = config.get("expert_share", {"index": 0, "of": 1})
    keys = {f.name for f in dataclasses.fields(SolarOpen2Config)}
    kw = {k: v for k, v in config.items() if k in keys}
    kw["n_routed_experts"] = int(config["n_routed_experts"]) \
        * int(share["of"])
    kw["expert_share"] = (int(share["index"]), int(share["of"]))
    kw["dtype"] = config["serving"]["weight_dtype"]
    return SolarOpen2Config(**kw)


def build_model(cell, seed):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import SolarOpen2ForCausalLM
    cfg = model_config(cell.config)
    paddle.seed(seed % (2 ** 31 - 1))
    net = SolarOpen2ForCausalLM(cfg)
    net.eval()
    return cfg, net


def check_against_reference(eng, net, model, rng, n_prompt, n_new):
    """One prompt through the engine (chunked prefill, then decode
    through state and paged cache), greedy, keeping the logits rows; the
    reference scores the same tokens in one full forward pass."""
    from paddle_tpu.inference.engine import SamplingParams
    prompt = rng.integers(0, model["vocab_size"], n_prompt).astype(np.int64)
    eng.add_request(prompt, SamplingParams(
        max_new_tokens=int(n_new), temperature=0.0, eos_token_id=None,
        return_logits=True))
    outs = []
    while not eng.idle:
        outs.extend(eng.step())
    out, = outs
    if not out.ok or len(out.token_ids) != n_new:
        return f"reference request ended {out.finish_reason!r}"
    got = np.stack(out.logits)
    toks = np.asarray(out.token_ids)
    seq = np.concatenate([prompt, toks[:-1].astype(np.int64)])
    share = model.get("expert_share", {"index": 0, "of": 1})
    weights = ref.model_weights(net)

    def rows(**switches):
        return np.asarray(ref.logits(
            weights, model, seq, (int(share["index"]), int(share["of"])),
            **switches)[n_prompt - 1:])

    want = rows()
    err = ref.errors(got, want)
    short = (want.max(-1) - want[np.arange(n_new), toks]) \
        / (want.max(-1) - want.min(-1))
    say("reference", prompt_tokens=n_prompt, new_tokens=n_new, **err,
        shortfall=[float(s) for s in short],
        same_argmax=int((want.argmax(-1) == toks).sum()),
        tolerances={"median_row": ref.LOGITS_ROW_TOL,
                    "token": ref.TOKEN_LOGIT_TOL})
    for name, switch in (("reference_without_decay", dict(decay=False)),
                         ("reference_without_delta_rule",
                          dict(delta=False))):
        off = rows(**switch)
        say(name, **ref.errors(got, off),
            the_reference_itself_moves_by=ref.errors(off, want))
    ok = (np.all(np.isfinite(got))
          and err["median_row"] <= ref.LOGITS_ROW_TOL
          and float(short.max()) <= ref.TOKEN_LOGIT_TOL)
    return True if ok else (
        f"engine logits against the reference: median row error "
        f"{err['median_row']:.4f} (limit {ref.LOGITS_ROW_TOL}), token "
        f"shortfall {float(short.max()):.4f} (limit "
        f"{ref.TOKEN_LOGIT_TOL})")


def run(job: Job) -> Measured:
    from paddle_tpu import monitor

    # a copy of the module, so that giving it this model does not reach
    # a `serve` cell run in the same process
    serve = load._import_file(
        load.BENCH_DIR / "runners" / "serve.py",
        "benchmark_runner_serve_for_hybrid")
    serve.build_model = build_model
    serve.check_against_reference = check_against_reference
    before = monitor.snapshot()
    measured = serve.run(job)
    after = monitor.snapshot()
    counters = {n: int(after.get(n, 0)) - int(before.get(n, 0))
                for n in COUNTERS}
    state_bytes = int(after.get("serving.state.bytes", 0))
    say("hybrid", counters=counters, state_bytes=state_bytes)
    measured.checks["kda_pallas_decode"] = True if (
        job.device["platform"] != "tpu"
        or (counters["kernels.decode.kda_pallas"] > 0
            and counters["kernels.decode.kda_fallback"] == 0)) else \
        f"decode did not stay on the Pallas KDA kernel: {counters}"
    measured.samples["moe"] = {
        n.rpartition(".")[2]: counters[n] for n in COUNTERS
        if n.startswith("serving.moe.")}
    measured.samples["moe"]["held"] = int(job.cell.config["n_routed_experts"])
    measured.samples["state"] = {
        "bytes": state_bytes,
        "resets": counters["serving.state.resets"],
        "recomputes": counters["serving.state.recomputes"]}
    return measured
