"""Runner `serve_latent`: runner `serve`'s loop (the same `Engine`, the
same warm-up, steady loop, window and traced stretch: it IS
`runners/serve.py`'s `run`, on a copy of that module), for a model that
`harness/model.py`'s `build_llama` cannot build and `reference/decoder.py`
cannot express: a latent-attention decoder named by the configuration's
`architectures`, built in the configuration's dtype from the start, and
checked against `reference/dots3_note.py`.

What differs from `serve`, and nothing else:

* `build_model`: `Dots3NoteForCausalLM` from the published keys at the
  top level of the configuration; `n_routed_experts` there counts the
  experts HELD, so the router's width is that times `expert_share.of`.
* the reference check compares LOGITS: the reference request asks the
  engine for the float32 rows it sampled from
  (`SamplingParams(return_logits=True)`, engine `keep_logits`), prefill
  and then decode through the paged latent cache, and the reference
  scores the same tokens in one full forward pass. Limits and reasons:
  `reference/dots3_note.py`. A second information line gives the same
  errors against the reference with the sparse selection and the window
  switched off, so that a reader sees whether the comparison can tell.
* the check `paged_mla_pallas_decode` in place of `paged_pallas_decode`:
  on a TPU every decode step of every layer took the Pallas latent
  kernel (`kernels.decode.paged_mla_pallas` > 0,
  `kernels.decode.paged_mla_fallback` == 0).
* samples for the expert layers' metrics: the `serving.moe.*` counters'
  growth over the run.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.harness import load
from benchmark.harness.job import Job, Measured, say
from benchmark.reference import dots3_note as ref

COUNTERS = ("kernels.decode.paged_mla_pallas",
            "kernels.decode.paged_mla_fallback",
            "serving.moe.picks_held", "serving.moe.picks_total",
            "serving.moe.experts_touched", "serving.moe.layer_ticks")


def model_config(config: dict):
    """Dots3NoteConfig from a configuration file's top-level keys."""
    from paddle_tpu.text.models import Dots3NoteConfig
    share = config.get("expert_share", {"index": 0, "of": 1})
    keys = {f.name for f in dataclasses.fields(Dots3NoteConfig)}
    kw = {k: v for k, v in config.items() if k in keys}
    kw["n_routed_experts"] = int(config["n_routed_experts"]) \
        * int(share["of"])
    kw["expert_share"] = (int(share["index"]), int(share["of"]))
    kw["dtype"] = config["serving"]["weight_dtype"]
    return Dots3NoteConfig(**kw)


def build_model(cell, seed):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import Dots3NoteForCausalLM
    cfg = model_config(cell.config)
    paddle.seed(seed % (2 ** 31 - 1))
    net = Dots3NoteForCausalLM(cfg)
    net.eval()
    return cfg, net


def check_against_reference(eng, net, model, rng, n_prompt, n_new):
    """One prompt through the engine (chunked prefill, then decode
    through the paged latent cache), greedy, keeping the logits rows;
    the reference scores the same tokens in one full forward pass."""
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
    dense = rows(select=False, window=False)
    say("reference_without_selection_and_window",
        **ref.errors(got, dense),
        the_reference_itself_moves_by=ref.errors(dense, want))
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
        "benchmark_runner_serve_for_latent")
    serve.build_model = build_model
    serve.check_against_reference = check_against_reference
    before = monitor.snapshot()
    measured = serve.run(job)
    after = monitor.snapshot()
    counters = {n: int(after.get(n, 0)) - int(before.get(n, 0))
                for n in COUNTERS}
    say("latent", counters=counters)
    checks = measured.checks
    del checks["paged_pallas_decode"]
    checks["paged_mla_pallas_decode"] = True if (
        job.device["platform"] != "tpu"
        or (counters["kernels.decode.paged_mla_pallas"] > 0
            and counters["kernels.decode.paged_mla_fallback"] == 0)) else \
        f"decode did not stay on the Pallas latent kernel: {counters}"
    measured.samples["moe"] = {
        n.rpartition(".")[2]: counters[n] for n in COUNTERS[2:]}
    measured.samples["moe"]["held"] = int(job.cell.config["n_routed_experts"])
    return measured
