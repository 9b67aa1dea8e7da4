"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full WIDTH of the repo's headline model (LLaMA-2-7B layer geometry: hidden
4096, FFN 11008, 32 heads of 128, vocab 32000) cut to 4 layers (1.07 B
params), random weights from a seed:

    python chip_smoke.py            # one chip: device, kernels, train, serve
    python chip_smoke.py --chips 4  # four chips: the hybrid-parallel trainer
                                    # against the one-device step, nothing else

One process, which alone touches JAX; it starts no other. Any phase that
fails raises, the script exits nonzero and prints no result line. With no
TPU behind JAX it fails in the first phase. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
the lines before it are smoke readings (compile/step seconds, counters),
NOT benchmark results.

The phases are functions of their sizes so the rehearsals in
.claude/skills/verify/SKILL.md can run them tiny on the CPU; ``main``
fixes the real sizes and holds every reading to the chip's contract.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

WIDTH = dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
             num_hidden_layers=4, num_attention_heads=32,
             num_key_value_heads=32)
TRAIN_BATCH, TRAIN_SEQ = 12, 1024


def say(phase, **facts):
    print(json.dumps({"phase": phase, **facts}), flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def rel_err(got, want):
    """max |got - want| over max |want|, in float32."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-12))


def hbm_in_use():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_in_use", -1))


def release(after):
    """Drop what the finished phase left on the device: the phases share
    one process and one chip's HBM."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()
    say("released", after=after, hbm_bytes_in_use=hbm_in_use())


# -- device -----------------------------------------------------------------

def phase_device(want_count):
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say("device", **dev)
    check(dev["platform"] == "tpu",
          f"chip_smoke needs a TPU behind JAX; it reports {dev}")
    check(dev["count"] >= want_count,
          f"asked to run on {want_count} chip(s), JAX reports {dev}")
    return dev


# -- kernels ----------------------------------------------------------------

def _bf16_normal(key, shape, scale=1.0):
    import jax
    import jax.numpy as jnp
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
        jnp.bfloat16)


def _timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, round(time.perf_counter() - t0, 2)


def _with_grad(fn, n_args):
    """jit of (out, *cotangents) for the first n_args arguments under
    the cotangent g; the rest ride along undifferentiated."""
    import jax

    def run(g, *args):
        out, vjp = jax.vjp(lambda *a: fn(*a, *args[n_args:]),
                           *args[:n_args])
        return (out,) + vjp(g.astype(out.dtype))
    return jax.jit(run)


def _hold(what, names, got, want, tol, **facts):
    errs = {n: rel_err(a, b) for n, a, b in zip(names, got, want)}
    say(f"kernels.{what}", rel_err=errs, **facts)
    for n, e in errs.items():
        check(np.isfinite(e) and e <= tol,
              f"{what} {n} differs from its reference by {e} (> {tol})")


def check_flash(shape, tol, interpret, seed):
    """Flash attention fwd + grad against `_flash_xla` on the same
    bf16-valued inputs in float32."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import (_flash_xla,
                                                    flash_attention_arrays)

    q, k, v, g = (_bf16_normal(key, shape) for key in
                  jax.random.split(jax.random.PRNGKey(seed), 4))

    def kernel(q, k, v):
        return flash_attention_arrays(q, k, v, causal=True,
                                      force_pallas=True,
                                      interpret=interpret)

    def reference(q, k, v):
        qt, kt, vt = (jnp.swapaxes(a.astype(jnp.float32), 1, 2)
                      for a in (q, k, v))
        with jax.default_matmul_precision("highest"):
            out = _flash_xla(qt, kt, vt, True, shape[-1] ** -0.5)
        return jnp.swapaxes(out, 1, 2)

    got, secs = _timed(_with_grad(kernel, 3), g, q, k, v)
    want = _with_grad(reference, 3)(g, q, k, v)
    _hold("flash", ("out", "dq", "dk", "dv"), got, want, tol,
          shape=list(shape), dtype="bfloat16", first_call_seconds=secs)


def check_paged_decode(pool_dtype, slots, heads, head_dim, page,
                       pages_per_seq, tol, interpret, seed):
    """`paged_decode_pallas` against `paged_attention_arrays`: a
    shuffled block table, context lengths that end inside, on and short
    of a page edge, and one dead slot. An int8 pool brings its scale
    pools (the in-kernel dequant)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.paged_attention import (paged_attention_arrays,
                                                    paged_decode_pallas)
    from paddle_tpu.quantization.functional import kv_quantize_arrays

    rng = np.random.default_rng(seed)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    nb = slots * pages_per_seq + 1
    q = _bf16_normal(kq, (slots, heads, head_dim))
    kc, vc = (_bf16_normal(key, (nb, heads, page, head_dim))
              for key in (kk, kv))
    scales = {}
    if pool_dtype == "int8":
        (kc, ks), (vc, vs) = (kv_quantize_arrays(a.astype(jnp.float32))
                              for a in (kc, vc))
        scales = dict(k_scale=ks, v_scale=vs)
    bt = jnp.asarray(1 + rng.permutation(slots * pages_per_seq)
                     .reshape(slots, pages_per_seq), jnp.int32)
    cl = rng.integers(1, pages_per_seq * page + 1, (slots,))
    cl[0], cl[1], cl[2] = 0, page, pages_per_seq * page
    cl = jnp.asarray(cl, jnp.int32)
    got, secs = _timed(jax.jit(lambda *a: paged_decode_pallas(
        *a, interpret=interpret, **scales)), q, kc, vc, bt, cl)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: paged_attention_arrays(*a, **scales))(
            q, kc, vc, bt, cl)
    _hold(f"paged_decode.{pool_dtype}", ("out",), (got,), (want,), tol,
          slots=slots, heads=heads, head_dim=head_dim, page=page,
          first_call_seconds=secs)
    check(not np.asarray(got[0], np.float32).any(),
          "a dead slot (context_len 0) must emit zeros")


def check_moe(experts, cap, d_model, d_hidden, tol, interpret, seed):
    """The grouped expert FFN fwd + grad against the batched-einsum
    reference in float32: full, partly filled and empty experts."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.moe import grouped_ffn, grouped_ffn_reference

    ks = jax.random.split(jax.random.PRNGKey(seed + 2), 7)
    x = _bf16_normal(ks[0], (experts, cap, d_model))
    w1 = _bf16_normal(ks[1], (experts, d_model, d_hidden), d_model ** -0.5)
    w2 = _bf16_normal(ks[2], (experts, d_hidden, d_model), d_hidden ** -0.5)
    b1 = jax.random.normal(ks[3], (experts, 1, d_hidden), jnp.float32) * 0.1
    b2 = jax.random.normal(ks[4], (experts, 1, d_model), jnp.float32) * 0.1
    ws = jax.random.uniform(ks[5], (experts, cap, 1), jnp.float32)
    g = _bf16_normal(ks[6], (experts, cap, d_model))
    counts = np.linspace(0, cap, experts).astype(np.int32)
    counts[1] = 1
    counts = jnp.asarray(counts)

    def kernel(*a):
        return grouped_ffn(*a, counts, force_pallas=True,
                           interpret=interpret)

    def reference(*a):
        with jax.default_matmul_precision("highest"):
            return grouped_ffn_reference(
                *(t.astype(jnp.float32) for t in a), counts)

    args = (x, w1, b1, w2, b2, ws)
    got, secs = _timed(_with_grad(kernel, 6), g, *args)
    want = _with_grad(reference, 6)(g, *args)
    _hold("moe_grouped_ffn",
          ("out", "dx", "dw1", "db1", "dw2", "db2", "dws"), got, want, tol,
          experts=experts, capacity=cap, d_model=d_model,
          d_hidden=d_hidden, first_call_seconds=secs)


def phase_kernels(flash_shape, paged, moe, tol, interpret=False, seed=0):
    """Every Pallas kernel family against its XLA reference, compiled
    (the CPU rehearsal passes interpret=True): flash attention and the
    bf16 paged decode of the main path, and the two variants this
    repository had only ever run in interpret mode before the v5e
    compiler refused them — the int8-pool paged decode and the MoE
    backward."""
    check_flash(flash_shape, tol, interpret, seed)
    for pool_dtype in ("bfloat16", "int8"):
        check_paged_decode(pool_dtype, tol=tol, interpret=interpret,
                           seed=seed, **paged)
    check_moe(tol=tol, interpret=interpret, seed=seed, **moe)


# -- train ------------------------------------------------------------------

def _train_setup(width, batch, seq, seed):
    """bench.py's headline trainer configuration (fused CE, flash
    attention on, no recompute), a seeded batch, and the optimizer."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig

    cfg = LlamaConfig(**width, max_position_embeddings=seq,
                      recompute=False, fused_linear_ce=True,
                      fused_ce_chunks=4, use_flash_attention=True)
    rng = np.random.default_rng(seed)
    ids, labels = (paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
        for _ in range(2))

    def optimizer(net):
        return paddle.optimizer.AdamW(3e-4, parameters=net.parameters(),
                                      moment_dtype="bfloat16")
    return cfg, ids, labels, optimizer


def _run_steps(step, ids, labels, n_steps):
    """The first call (which compiles) and n_steps more: every loss,
    the first call's seconds, and each later step's. Every call ends in
    a value fetch, which waits for the device."""
    losses, secs = [], []
    for _ in range(1 + n_steps):
        t0 = time.perf_counter()
        losses.append(float(step((ids, labels), labels).numpy()))
        secs.append(time.perf_counter() - t0)
    return losses, secs[0], secs[1:]


def phase_train(width, batch, seq, n_steps=3, seed=0):
    """LlamaForCausalLM + AdamW(bf16 moments) + jit.TrainStep(bf16),
    fused CE, flash attention on — bench.py's headline configuration."""
    import paddle_tpu as paddle
    from paddle_tpu.kernels.flash_attention import pallas_path_eligible
    from paddle_tpu.text.models import LlamaForCausalLM

    cfg, ids, labels, optimizer = _train_setup(width, batch, seq, seed)
    paddle.seed(seed)
    net = LlamaForCausalLM(cfg)
    n_params = net.num_params()
    step = paddle.jit.TrainStep(net, lambda out, lab: out, optimizer(net),
                                amp_dtype="bfloat16")
    losses, compile_s, secs = _run_steps(step, ids, labels, n_steps)
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    facts = dict(
        n_params=n_params, batch=batch, seq=seq, losses=losses,
        smoke_compile_seconds=round(compile_s, 2),
        smoke_step_seconds=[round(s, 4) for s in secs],
        flash_pallas_eligible=pallas_path_eligible(seq, seq, head_dim),
        tpu_custom_calls=step.lower((ids, labels), labels).as_text()
        .count("tpu_custom_call"),
        hbm_bytes_in_use=hbm_in_use())
    say("train", **facts)
    check(all(np.isfinite(v) for v in facts["losses"]),
          f"train losses not finite: {facts['losses']}")
    check(len(set(facts["losses"])) > 1,
          f"train losses all equal — nothing was learned or updated: "
          f"{facts['losses']}")
    return facts


# -- serve ------------------------------------------------------------------

def phase_serve(width, max_slots, page_size, prefill_bucket, n_requests,
                prompt_lo, prompt_hi, new_tokens, seed=0):
    """The continuous-batching Engine over the paged KV stack: greedy
    requests through add_request/run(), the same trace twice."""
    import paddle_tpu as paddle
    from paddle_tpu import monitor
    from paddle_tpu.inference.engine import Engine, SamplingParams
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(**width,
                      max_position_embeddings=prompt_hi + new_tokens,
                      use_flash_attention=True)
    paddle.seed(seed)
    net = LlamaForCausalLM(cfg)
    net.eval()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            (int(rng.integers(prompt_lo, prompt_hi + 1)),))
               .astype(np.int64) for _ in range(n_requests)]
    before = monitor.snapshot()
    eng = Engine(net, max_slots=max_slots, page_size=page_size,
                 prefill_bucket=prefill_bucket, cache_dtype="auto")
    passes = []
    try:
        for _ in range(2):
            t0 = time.perf_counter()
            outs = eng.run([(p, SamplingParams(max_new_tokens=new_tokens))
                            for p in prompts])
            passes.append((outs, time.perf_counter() - t0))
        recompiles = eng.steady_state_recompiles()
        leaked = eng.leaked_pages()
    finally:
        eng.close()
    after = monitor.snapshot()

    def delta(name):
        return int(after.get(name, 0)) - int(before.get(name, 0))

    (outs, cold_s), (outs2, warm_s) = passes
    facts = dict(
        requests=n_requests, prompt_lens=[len(p) for p in prompts],
        new_tokens=new_tokens, cache_dtype=str(eng.cache_dtype),
        finish=sorted({(o.finish_reason, bool(o.ok)) for o in outs + outs2}),
        tokens=[len(o.token_ids) for o in outs],
        same_tokens_both_passes=[o.token_ids for o in outs]
        == [o.token_ids for o in outs2],
        smoke_cold_pass_seconds=round(cold_s, 2),
        smoke_warm_pass_seconds=round(warm_s, 2),
        counters={n: delta(n) for n in (
            "kernels.decode.paged_pallas",
            "kernels.decode.paged_xla_gather_step",
            "serving.decode_fallback")},
        steady_state_recompiles=recompiles, leaked_pages=leaked,
        hbm_bytes_in_use=hbm_in_use())
    say("serve", **facts)
    check(all(o.ok for o in outs + outs2),
          f"requests did not all finish ok: {facts['finish']}")
    check(all(n == new_tokens for n in facts["tokens"]),
          f"expected {new_tokens} tokens per request: {facts['tokens']}")
    check(facts["same_tokens_both_passes"],
          "greedy decoding gave different tokens on the second pass")
    check(leaked == 0, f"{leaked} KV pages leaked")
    return facts


# -- four chips -------------------------------------------------------------

def _state_spread(trees):
    """Where a step's parameters and optimizer state sit: devices that
    hold a shard, the largest per-device byte count, and the whole."""
    import jax
    per_dev, whole = {}, 0
    for leaf in jax.tree_util.tree_leaves(trees):
        whole += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    return dict(devices=sorted(per_dev), whole_bytes=int(whole),
                max_device_bytes=int(max(per_dev.values())))


def phase_multichip(width, batch, seq, n_steps=2, seed=0):
    """fleet.init(mp=2, sharding=2, stage 3) → distributed_model /
    distributed_optimizer → DistributedTrainStep, against the one-device
    TrainStep on the same seed (run and freed first)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.parallel_step import DistributedTrainStep
    from paddle_tpu.text.models import LlamaForCausalLM, force_tp_layers

    cfg, ids, labels, optimizer = _train_setup(width, batch, seq, seed)

    # the reference: the plain TrainStep on one device, over the same
    # module tree (the TP layer classes at degree 1 initialise as they
    # do at degree 2; the plain Linear classes do not)
    mesh_mod.set_mesh(mesh_mod.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))
    paddle.seed(seed)
    with force_tp_layers():
        net = LlamaForCausalLM(cfg)
    step = paddle.jit.TrainStep(net, lambda out, lab: out, optimizer(net),
                                amp_dtype="bfloat16")
    ref, ref_compile_s, ref_secs = _run_steps(step, ids, labels,
                                              n_steps - 1)
    del step, net
    release("one-device reference")

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 2,
                               "sharding_degree": 2}
    strategy.sharding_configs = dict(strategy.sharding_configs, stage=3,
                                     degree=2)
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(seed)
    net = LlamaForCausalLM(cfg)
    fleet.distributed_model(net)
    opt = fleet.distributed_optimizer(optimizer(net))
    step = DistributedTrainStep(net, lambda out, lab: out, opt,
                                amp_dtype="bfloat16", sharding_stage=3)
    dist, compile_s, secs = _run_steps(step, ids, labels, n_steps - 1)
    spread = _state_spread((step._params, step._opt_state))
    facts = dict(
        mesh={k: int(v) for k, v in mesh_mod.get_mesh().shape.items()
              if v > 1},
        losses_one_device=ref, losses_four_devices=dist,
        smoke_compile_seconds=dict(one_device=round(ref_compile_s, 2),
                                   four_devices=round(compile_s, 2)),
        smoke_step_seconds=dict(
            one_device=[round(s, 4) for s in ref_secs],
            four_devices=[round(s, 4) for s in secs]),
        state=spread,
        tpu_custom_calls=step.lower((ids, labels), labels).as_text()
        .count("tpu_custom_call"))
    say("multichip", **facts)
    check(all(np.isfinite(v) for v in dist), f"losses not finite: {dist}")
    return facts


# -- the contract -----------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the hybrid-parallel trainer on four "
                         "chips and the one-device step it is compared "
                         "with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    say("cache", dir=enable_compile_cache())
    dev = phase_device(args.chips)

    if args.chips == 4:
        f = phase_multichip(WIDTH, TRAIN_BATCH, TRAIN_SEQ, seed=args.seed)
        check(np.allclose(f["losses_four_devices"], f["losses_one_device"],
                          rtol=1e-2),
              f"four-device losses {f['losses_four_devices']} differ from "
              f"the one-device step's {f['losses_one_device']}")
        st = f["state"]
        check(len(st["devices"]) == 4,
              f"state sits on devices {st['devices']}, not on four")
        check(st["max_device_bytes"] <= 0.4 * st["whole_bytes"],
              f"one device holds {st['max_device_bytes']} of "
              f"{st['whole_bytes']} state bytes — not sharded")
        check(f["tpu_custom_calls"] > 0,
              "the four-chip step holds no Pallas kernel")
    else:
        phase_kernels(
            (TRAIN_BATCH, TRAIN_SEQ, 32, 128),
            paged=dict(slots=16, heads=32, head_dim=128, page=128,
                       pages_per_seq=4),
            moe=dict(experts=8, cap=8192, d_model=768, d_hidden=3072),
            tol=2e-2, seed=args.seed)
        release("kernels")
        f = phase_train(WIDTH, TRAIN_BATCH, TRAIN_SEQ, seed=args.seed)
        check(f["flash_pallas_eligible"],
              "pallas_path_eligible(1024, 1024, 128) is false on the chip")
        check(f["tpu_custom_calls"] >= 3 * WIDTH["num_hidden_layers"],
              f"the train step baked in {f['tpu_custom_calls']} Pallas "
              f"calls; flash fwd + dq + dkv per layer were expected")
        release("train")
        f = phase_serve(WIDTH, max_slots=16, page_size=128,
                        prefill_bucket=64, n_requests=8, prompt_lo=64,
                        prompt_hi=192, new_tokens=32, seed=args.seed)
        c = f["counters"]
        check(c["kernels.decode.paged_pallas"] > 0,
              f"decode never took the Pallas paged kernel: {c}")
        check(c["serving.decode_fallback"] == 0
              and c["kernels.decode.paged_xla_gather_step"] == 0,
              f"decode fell to the XLA gather path: {c}")
        check(f["steady_state_recompiles"] == 0,
              f"{f['steady_state_recompiles']} recompiles on the second "
              f"pass of the same trace")

    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
