"""Runner `serve_window`: runner `serve`'s loop (the same `Engine`, the
same warm-up, steady loop, window and traced stretch: it IS
`runners/serve.py`'s `run`, on a copy of that module), for a decoder
whose softmax GQA layers keep two kinds of thing: sliding-window layers
a ring of their last `sliding_window` keys and values a slot, full
layers a paged cache. The model is named by the configuration's
`architectures`, built in the configuration's dtype from the start, and
checked against `reference/k_exaone.py`.

What differs from `serve`, and nothing else:

* `build_model`: `KExaoneForCausalLM` from the published keys at the top
  level of the configuration; `num_experts` there counts the experts
  HELD, so the router's width is that times `expert_share.of`.
* the reference check compares LOGITS, as `serve_latent` does: the
  reference request asks the engine for the float32 rows it sampled from
  (`SamplingParams(return_logits=True)`, engine `keep_logits`): chunked
  prefill (the rings carried from chunk to chunk through the slot's
  rows), then decode through rings and paged cache; the reference scores
  the same tokens in one full forward pass. Limits and reasons:
  `reference/k_exaone.py`. Three more information lines give the same
  errors against the reference with the window, with RoPE and with the
  q/k norm switched off, and two give the reference itself with every
  matmul operand rounded to bfloat16 and to float8_e4m3 against the
  float32 pass, through the same statistics: the readings the limits
  were set between, made again in every run, so that a reader sees
  whether the comparison can still tell.
* `paged_pallas_decode` (kept) covers both kinds of layer: a ring is read
  by the same kernel as a page.
* samples for the expert layers' metrics (the `serving.moe.*` counters'
  growth over the run) and for the rings' (`serving.state.*`).
* an information line `stalls`: the window's longest `step()` calls and
  longest gaps between them, beside what the process can see of its own
  pauses (`harness/pauses.py`), so that a run that lost seconds says
  where.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.harness import load, pauses
from benchmark.harness.job import Job, Measured, say
from benchmark.reference import k_exaone as ref

COUNTERS = ("kernels.prefill.gqa_band", "kernels.prefill.gqa_whole",
            "serving.state.resets", "serving.state.recomputes",
            "serving.prefill_slices",
            "serving.moe.picks_held", "serving.moe.picks_total",
            "serving.moe.experts_touched", "serving.moe.layer_ticks",
            "serving.moe.slabs")
SWITCHES = (("reference_without_window", dict(window=False)),
            ("reference_without_rope", dict(rope=False)),
            ("reference_without_qk_norm", dict(qk_norm=False)))
# the precision the configuration states, and the nearest one below it
PRECISIONS = ("bfloat16", "float8_e4m3fn")


def _share(config: dict):
    share = config.get("expert_share", {"index": 0, "of": 1})
    return int(share["index"]), int(share["of"])


def model_config(config: dict):
    """KExaoneConfig from a configuration file's top-level keys."""
    from paddle_tpu.text.models import KExaoneConfig
    keys = {f.name for f in dataclasses.fields(KExaoneConfig)}
    kw = {k: v for k, v in config.items() if k in keys}
    kw["expert_share"] = _share(config)
    kw["num_experts"] = int(config["num_experts"]) * kw["expert_share"][1]
    kw["dtype"] = config["serving"]["weight_dtype"]
    return KExaoneConfig(**kw)


def build_model(cell, seed):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import KExaoneForCausalLM
    cfg = model_config(cell.config)
    paddle.seed(seed % (2 ** 31 - 1))
    net = KExaoneForCausalLM(cfg)
    net.eval()
    return cfg, net


def check_against_reference(eng, net, model, rng, n_prompt, n_new):
    """One prompt through the engine (chunked prefill, then decode
    through rings and paged cache), greedy, keeping the logits rows; the
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
    weights = ref.model_weights(net)

    def rows(**switches):
        return np.asarray(ref.logits(weights, model, seq, _share(model),
                                     **switches)[n_prompt - 1:])

    def shortfall(want, toks=toks):
        return (want.max(-1) - want[np.arange(n_new), toks]) \
            / (want.max(-1) - want.min(-1))

    def passes(err, short):
        return bool(err["median_row"] <= ref.LOGITS_ROW_TOL
                    and float(np.max(short)) <= ref.TOKEN_LOGIT_TOL)

    want = rows()
    err, short = ref.errors(got, want), shortfall(want)
    say("reference", prompt_tokens=n_prompt, new_tokens=n_new, **err,
        shortfall=[float(s) for s in short],
        same_argmax=int((want.argmax(-1) == toks).sum()),
        tolerances={"median_row": ref.LOGITS_ROW_TOL,
                    "token": ref.TOKEN_LOGIT_TOL})
    for name, switch in SWITCHES:
        off = rows(**switch)
        off_err, off_short = ref.errors(got, off), shortfall(off)
        say(name, **off_err, shortfall=float(off_short.max()),
            passes=passes(off_err, off_short),
            the_reference_itself_moves_by=ref.errors(off, want))
    for dtype in PRECISIONS:
        low = rows(round_to=dtype)
        low_err = ref.errors(low, want)
        low_short = shortfall(want, low.argmax(-1))
        say("reference_in_" + dtype, **low_err,
            shortfall=float(low_short.max()),
            passes=passes(low_err, low_short))
    ok = np.all(np.isfinite(got)) and passes(err, short)
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
        "benchmark_runner_serve_for_window")
    serve.build_model = build_model
    serve.check_against_reference = check_against_reference
    before = monitor.snapshot()
    with pauses.Watch() as watch:
        measured = serve.run(job)
    after = monitor.snapshot()
    say("stalls", **pauses.report(measured.samples["ticks"], watch))
    counters = {n: int(after.get(n, 0)) - int(before.get(n, 0))
                for n in COUNTERS}
    state_bytes = int(after.get("serving.state.bytes", 0))
    say("rings", counters=counters, state_bytes=state_bytes,
        swa_pages_outside_window=int(after.get(
            "serving.cache.swa_pages_outside_window", 0)))
    measured.samples["moe"] = {
        n.rpartition(".")[2]: counters[n] for n in COUNTERS
        if n.startswith("serving.moe.")}
    measured.samples["moe"]["held"] = int(job.cell.config["num_experts"])
    measured.samples["state"] = {
        "bytes": state_bytes,
        "resets": counters["serving.state.resets"],
        "recomputes": counters["serving.state.recomputes"]}
    return measured
