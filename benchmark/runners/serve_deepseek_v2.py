"""Runner `serve_deepseek_v2`: runner `serve`'s loop (the same `Engine`,
the same warm-up, steady loop, window and traced stretch: it IS
`runners/serve.py`'s `run`, on a copy of that module), for DeepSeek-V2:
latent attention on every layer with no selection and no window, YaRN
positions, softmax experts routed within the best groups. The model is
built in the configuration's dtype from the start and checked against
`reference/deepseek_v2.py`.

What differs from `serve`, and nothing else:

* `build_model`: `DeepseekV2ForCausalLM` from the published keys at the
  top level of the configuration; `n_routed_experts` there counts the
  experts HELD, so the router's width is that times `expert_share.of`.
* the reference check compares LOGITS: the reference request asks the
  engine for the float32 rows it sampled from
  (`SamplingParams(return_logits=True)`, engine `keep_logits`), prefill
  in chunks and then decode through the paged latent cache, and the
  reference scores the same tokens in one full forward pass. Limits and
  reasons: `reference/deepseek_v2.py`. The request does not run alone:
  every other slot is taken first by a short filler request that is
  still decoding when the reference request ends, so the compared rows
  come from the LAST slot of a full decode program (every slot's block
  table read, the expert layers sorting every slot's picks into their
  slabs) and from prefill chunks dispatched between other lanes' ticks,
  as `serve_ssm` does. The check is held to what it must
  be able to tell: against the reference with the group limit off, and
  with YaRN off (plain RoPE, the scale 192^-0.5), the system must FAIL a
  limit, the reference with every matmul operand rounded to bfloat16
  must pass against itself in float32 and rounded to float8_e4m3 must
  fail. One information line each.
* the checks `paged_mla_pallas_decode` in place of `paged_pallas_decode`
  (on a TPU every decode step of every layer took the Pallas latent
  kernel) and `mla_flash_prefill` (every prefill chunk of every layer
  took `mla_flash_prefill`, none the XLA blocks).
* samples for the expert layers' metrics: the `serving.moe.*` counters'
  growth over the run, the groups reached among them.
* an information line with every latency tail the window supports
  (`ttft_p*_ms`, `tpot_p*_ms`), those the cell does not list too.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.harness import load
from benchmark.harness.job import Job, Measured, say
from benchmark.reference import deepseek_v2 as ref

COUNTERS = ("kernels.decode.paged_mla_pallas",
            "kernels.decode.paged_mla_fallback",
            "kernels.prefill.mla_flash", "kernels.prefill.mla_xla",
            "kernels.rope.yarn", "kernels.moe.route.group_limited",
            "serving.moe.picks_held", "serving.moe.picks_total",
            "serving.moe.experts_touched", "serving.moe.layer_ticks",
            "serving.moe.groups_reached", "serving.moe.tokens_routed")
# the filler requests beside the reference request: a prompt of one
# prefill bucket or less, and more tokens than the steps the reference
# request can take (its chunks, its ticks, the fillers' own prefills)
FILLER_PROMPT_TOKENS = 16
SWITCHES = {"without_group_limit": (dict(group_limit=False), True),
            "without_yarn": (dict(yarn=False), True)}    # must it fail?
ROUNDINGS = {"bfloat16": True, "float8_e4m3fn": False}   # must it pass?


def model_config(config: dict):
    """DeepseekV2Config from a configuration file's top-level keys."""
    from paddle_tpu.text.models import DeepseekV2Config
    share = config.get("expert_share", {"index": 0, "of": 1})
    keys = {f.name for f in dataclasses.fields(DeepseekV2Config)}
    kw = {k: v for k, v in config.items() if k in keys}
    kw["n_routed_experts"] = int(config["n_routed_experts"]) \
        * int(share["of"])
    kw["expert_share"] = (int(share["index"]), int(share["of"]))
    kw["dtype"] = config["serving"]["weight_dtype"]
    return DeepseekV2Config(**kw)


def build_model(cell, seed):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import DeepseekV2ForCausalLM
    cfg = model_config(cell.config)
    paddle.seed(seed % (2 ** 31 - 1))
    net = DeepseekV2ForCausalLM(cfg)
    net.eval()
    return cfg, net


def check_against_reference(eng, net, model, rng, n_prompt, n_new):
    """One prompt through the engine (chunked prefill, then decode
    through the paged latent cache) in the LAST slot, every other slot
    decoding a filler beside it, greedy, keeping the logits rows; the
    reference scores the same tokens in one full forward pass."""
    from paddle_tpu.inference.engine import DECODE, SamplingParams

    def params(new, **kw):
        return SamplingParams(max_new_tokens=int(new), temperature=0.0,
                              eos_token_id=None, **kw)

    fillers = eng.max_slots - 1
    chunks = -(-n_prompt // int(eng.max_prefill_tokens_per_step or n_prompt))
    for _ in range(fillers):
        eng.add_request(
            rng.integers(0, model["vocab_size"], FILLER_PROMPT_TOKENS),
            params(2 * (fillers + chunks + n_new)))
    outs, beside = [], []
    while eng.num_waiting or eng.num_prefilling:
        outs.extend(eng.step())
    prompt = rng.integers(0, model["vocab_size"], n_prompt).astype(np.int64)
    rid = eng.add_request(prompt, params(n_new, return_logits=True))
    while not eng.idle:
        outs.extend(eng.step())
        req = eng.requests.get(rid)
        if req is not None and req.slot is not None:
            beside.append((req.slot,
                           eng.num_active - int(req.state == DECODE)))
    out, = (o for o in outs if o.req_id == rid)
    if not out.ok or len(out.token_ids) != n_new:
        return f"reference request ended {out.finish_reason!r}"
    if not all(o.ok for o in outs):
        return "a filler request beside the reference request failed"
    if fillers and set(beside) != {(fillers, fillers)}:
        return (f"the reference request did not run in the last slot "
                f"beside {fillers} decoding lanes: (slot, lanes beside) "
                f"{sorted(set(beside))}")
    got = np.stack(out.logits)
    toks = np.asarray(out.token_ids)
    seq = np.concatenate([prompt, toks[:-1].astype(np.int64)])
    share = model.get("expert_share", {"index": 0, "of": 1})
    weights = ref.model_weights(net)

    def rows(**switch):
        return np.asarray(ref.logits(
            weights, model, seq, (int(share["index"]), int(share["of"])),
            rows_from=n_prompt - 1, **switch))

    def shortfall(r, picked=toks):
        """How far, in shares of a row's range, the reference's logit of
        the token picked (the engine's) lies under the row's best."""
        return (r.max(-1) - r[np.arange(n_new), picked]) \
            / (r.max(-1) - r.min(-1))

    def within(err, short):
        return bool(err["median_row"] <= ref.LOGITS_ROW_TOL
                    and ref.token_shortfall(short) <= ref.TOKEN_LOGIT_TOL)

    want = rows()
    err, short = ref.errors(got, want), shortfall(want)
    say("reference", prompt_tokens=n_prompt, new_tokens=n_new, **err,
        shortfall=[float(s) for s in short],
        same_argmax=int((want.argmax(-1) == toks).sum()),
        slot=fillers, lanes_beside=fillers,
        tolerances={"median_row": ref.LOGITS_ROW_TOL,
                    "token": ref.TOKEN_LOGIT_TOL})
    untold = []
    for name, (switch, must_fail) in SWITCHES.items():
        off = rows(**switch)
        off_err, off_short = ref.errors(got, off), shortfall(off)
        say(f"reference_{name}", **off_err,
            shortfall_top=sorted(map(float, off_short))[-3:],
            must_fail=must_fail,
            the_reference_itself_moves_by=ref.errors(off, want)["median_row"])
        if must_fail and within(off_err, off_short):
            untold.append(name)
    for dtype, must_pass in ROUNDINGS.items():
        low = rows(round_to=dtype)
        low_err, low_short = ref.errors(low, want), \
            shortfall(want, low.argmax(-1))
        say(f"reference_in_{dtype}", **low_err,
            shortfall_top=sorted(map(float, low_short))[-3:])
        if within(low_err, low_short) != must_pass:
            untold.append(f"in_{dtype}")
    if not (np.all(np.isfinite(got)) and within(err, short)):
        return (f"engine logits against the reference: median row error "
                f"{err['median_row']:.4f} (limit {ref.LOGITS_ROW_TOL}), token "
                f"shortfall {ref.token_shortfall(short):.4f} (limit "
                f"{ref.TOKEN_LOGIT_TOL})")
    return True if not untold else (
        f"the limits do not tell the reference {untold} from the system "
        f"as they must")


def run(job: Job) -> Measured:
    from paddle_tpu import monitor

    # a copy of the module, so that giving it this model does not reach
    # a `serve` cell run in the same process
    serve = load._import_file(
        load.BENCH_DIR / "runners" / "serve.py",
        "benchmark_runner_serve_for_deepseek_v2")
    serve.build_model = build_model
    serve.check_against_reference = check_against_reference
    before = monitor.snapshot()
    measured = serve.run(job)
    after = monitor.snapshot()
    counters = {n: int(after.get(n, 0)) - int(before.get(n, 0))
                for n in COUNTERS}
    say("latent", counters=counters)
    checks = measured.checks
    on_tpu = job.device["platform"] == "tpu"
    del checks["paged_pallas_decode"]
    checks["paged_mla_pallas_decode"] = True if not on_tpu or (
        counters["kernels.decode.paged_mla_pallas"] > 0
        and counters["kernels.decode.paged_mla_fallback"] == 0) else \
        f"decode did not stay on the Pallas latent kernel: {counters}"
    checks["mla_flash_prefill"] = True if not on_tpu or (
        counters["kernels.prefill.mla_flash"] > 0
        and counters["kernels.prefill.mla_xla"] == 0) else \
        f"prefill did not stay on mla_flash_prefill: {counters}"
    measured.samples["moe"] = {
        n.rpartition(".")[2]: counters[n] for n in COUNTERS
        if n.startswith("serving.moe.")}
    measured.samples["moe"]["held"] = int(job.cell.config["n_routed_experts"])
    say("tails", **{k: v for k, v in measured.end_to_end.items()
                    if k.startswith(("ttft_", "tpot_"))})
    return measured
