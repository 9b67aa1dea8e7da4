"""Runner `serve_ssm`: runner `serve`'s loop (the same `Engine`, the same
warm-up, steady loop, window and traced stretch: it IS
`runners/serve.py`'s `run`, on a copy of that module), for a decoder
whose every block keeps two things: a state-space (Mamba-2) mixer a
constant-size state a slot, a softmax GQA attention a paged cache. The
model is built in the configuration's dtype from the start and checked
against `reference/falcon_h1.py`. It is `serve_hybrid`'s logits runner
for this model class and these counters.

What differs from `serve`, and nothing else:

* `build_model`: `FalconH1ForCausalLM` from the published keys at the
  top level of the configuration.
* the reference check compares LOGITS, as `serve_hybrid` does: the
  reference request asks the engine for the float32 rows it sampled from
  (`SamplingParams(return_logits=True)`, engine `keep_logits`): prefill
  in two chunks (`S` and the convolution's tail carried through the
  slot's rows), then decode through both caches; the reference scores
  the same tokens in one full forward pass. Limits and reasons:
  `reference/falcon_h1.py`. The request does not run alone: every other
  slot is taken first by a short filler request that is still decoding
  when the reference request ends, so the compared rows come from the
  LAST slot of a full decode program and from prefill chunks dispatched
  between other lanes' ticks (a fault in how a program indexes slots,
  heads or groups beyond slot 0 shows here).
* the comparison is held to what it must be able to tell, in the same
  check: against the reference with each part of `switches` off or wrong
  the system must FAIL a limit, the reference with every matmul operand
  rounded to bfloat16 must pass both against itself in float32, and
  rounded to float8_e4m3 must fail one. One information line each.
* samples for the state's metric (`serving.state.*`).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.harness import load
from benchmark.harness.job import Job, Measured, say
from benchmark.reference import falcon_h1 as ref

COUNTERS = ("kernels.prefill.ssd_chunked",
            "serving.state.resets", "serving.state.recomputes")
# the filler requests beside the reference request: a prompt of one
# prefill bucket or less, and more tokens than the steps the reference
# request can take (its chunks, its ticks, the fillers' own prefills)
FILLER_PROMPT_TOKENS = 16
ROUNDINGS = {"bfloat16": True, "float8_e4m3fn": False}   # must it pass?


def switches(n_prompt, n_new, first_chunk):
    """name -> the reference's keyword arguments with that part off or
    wrong, for a request of `n_prompt` + `n_new` tokens whose second
    prefill chunk starts at `first_chunk`."""
    programs = (first_chunk,) + tuple(range(n_prompt, n_prompt + n_new - 1))
    return {"without_mixer": dict(mixer=False),
            "without_attention": dict(attention=False),
            "without_rope": dict(rope=False),
            "with_ssm_multipliers_ones": dict(mup=False),
            "with_state_lost_between_chunks":
                dict(lose_state_at=(first_chunk,)),
            "with_tail_lost_between_programs": dict(lose_tail_at=programs)}


def model_config(config: dict):
    """FalconH1Config from a configuration file's top-level keys."""
    from paddle_tpu.text.models import FalconH1Config
    keys = {f.name for f in dataclasses.fields(FalconH1Config)}
    kw = {k: v for k, v in config.items() if k in keys}
    kw["dtype"] = config["serving"]["weight_dtype"]
    return FalconH1Config(**kw)


def build_model(cell, seed):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import FalconH1ForCausalLM
    cfg = model_config(cell.config)
    paddle.seed(seed % (2 ** 31 - 1))
    net = FalconH1ForCausalLM(cfg)
    net.eval()
    return cfg, net


def check_against_reference(eng, net, model, rng, n_prompt, n_new):
    """One prompt through the engine (chunked prefill, then decode
    through state and paged cache) in the LAST slot, every other slot
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
    weights = ref.model_weights(net)

    def rows(**switch):
        return np.asarray(ref.logits(weights, model, seq,
                                     rows_from=n_prompt - 1, **switch))

    def shortfall(r, picked=toks):
        """How far, in shares of a row's range, the reference's logit of
        the token picked (the engine's) lies under the row's best."""
        return (r.max(-1) - r[np.arange(n_new), picked]) \
            / (r.max(-1) - r.min(-1))

    def within(err, short):
        return bool(err["median_row"] <= ref.LOGITS_ROW_TOL
                    and float(short.max()) <= ref.TOKEN_LOGIT_TOL)

    want = rows()
    err = ref.errors(got, want)
    short = shortfall(want)
    say("reference", prompt_tokens=n_prompt, new_tokens=n_new, **err,
        shortfall=[float(s) for s in short],
        same_argmax=int((want.argmax(-1) == toks).sum()),
        slot=fillers, lanes_beside=fillers,
        tolerances={"median_row": ref.LOGITS_ROW_TOL,
                    "token": ref.TOKEN_LOGIT_TOL})
    untold = []
    first_chunk = int(eng.max_prefill_tokens_per_step or n_prompt)
    for name, switch in switches(n_prompt, n_new, first_chunk).items():
        off = rows(**switch)
        off_err, off_short = ref.errors(got, off), shortfall(off)
        say(f"reference_{name}", **off_err,
            shortfall_max=float(off_short.max()),
            the_reference_itself_moves_by=ref.errors(off, want)["median_row"])
        if within(off_err, off_short):
            untold.append(name)
    for dtype, must_pass in ROUNDINGS.items():
        low = rows(round_to=dtype)
        low_err, low_short = ref.errors(low, want), \
            shortfall(want, low.argmax(-1))
        say(f"reference_in_{dtype}", **low_err,
            shortfall_max=float(low_short.max()))
        if within(low_err, low_short) != must_pass:
            untold.append(f"in_{dtype}")
    if not (np.all(np.isfinite(got)) and within(err, short)):
        return (f"engine logits against the reference: median row error "
                f"{err['median_row']:.4f} (limit {ref.LOGITS_ROW_TOL}), token "
                f"shortfall {float(short.max()):.4f} (limit "
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
        "benchmark_runner_serve_for_ssm")
    serve.build_model = build_model
    serve.check_against_reference = check_against_reference
    before = monitor.snapshot()
    measured = serve.run(job)
    after = monitor.snapshot()
    counters = {n: int(after.get(n, 0)) - int(before.get(n, 0))
                for n in COUNTERS}
    state_bytes = int(after.get("serving.state.bytes", 0))
    # the most pages in use at the end of any step of the process (the
    # gauge keeps its extremes): what of the page pool was ever filled
    free = monitor.gauge("serving.pages_free").stats()
    say("ssm", counters=counters, state_bytes=state_bytes,
        pages_in_use_peak=int(free["max"] - free["min"]))
    measured.samples["state"] = {
        "bytes": state_bytes,
        "resets": counters["serving.state.resets"],
        "recomputes": counters["serving.state.recomputes"]}
    return measured
