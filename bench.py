"""Benchmark driver — prints ONE JSON line.

Headline: 1B-class LLaMA causal-LM training on the real chip
(BASELINE.md config-4 family): tokens/sec/chip and achieved MFU vs the
north-star 50% target; vs_baseline = achieved_MFU / 0.50. The config is
the measured-best shape for one v5e chip from the round-4 sweep
(docs/PERF.md) — LLaMA-7B layer geometry (4096 hidden / 11008 FFN) at
4 layers, 1.07B params, batch 12 / seq 1024, AdamW bf16 moments + bf16
compute, NO recompute + chunked fused lm-head+CE (the logits tensor is
never materialized), the tuned Pallas flash-attention kernel (256x512
blocks), whole-step jit with donated buffers: 0.719 MFU measured.

Extras carried in the same line: the long-sequence point (seq 2048),
the round-2 small-model number (hidden 2048 x 4L @ seq 512), the LeNet
compiled-vs-eager pair (BASELINE config 1), BERT-base and ERNIE-MoE
throughput (configs 3/5), and ResNet-50 images/sec (config 2).

MFU = tokens/sec x train FLOPs/token / peak chip FLOP/s, FLOPs/token =
6N (llama_flops_per_token). Peak per device kind below (bf16); an
unknown kind is an error.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e bf16
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # Trillium reports 'TPU v6 lite'
    "TPU v6e": 918e12,
}


def _enable_compile_cache():
    """Persistent XLA compilation cache (JAX_COMPILATION_CACHE_DIR if
    set, else the checkout's own directory): the bench models cost
    minutes of compiles cold."""
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()


def _peak():
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_FLOPS:
        raise RuntimeError(
            f"no published peak for device_kind {kind!r}: add it to "
            f"PEAK_FLOPS with its source before reporting an MFU on it")
    return PEAK_FLOPS[kind], kind


# MFU is FLOPs-done / peak-FLOPs: > 1.0 against a correct denominator
# is physically impossible. A reported MFU above this marks either a
# wrong PEAK_FLOPS row for the chip or an analytic FLOP overcount —
# the result line carries an explicit *_mfu_suspect flag instead of
# shipping an impossible number silently (docs/PERF.md "Device-peak
# note": the old 367 TF/s "measured peak" predates this protocol).
MFU_PLAUSIBLE_BOUND = 1.0


def bench_peak_microbench(n=4096, layers=8, reps=3):
    """Measured bf16 peak, DCE-proof (the MFU-denominator check):

    a chain of ``layers`` [n, n] bf16 matmuls whose summed output is
    DIFFERENTIATED — ``value_and_grad`` returns every layer's weight
    gradient, so XLA cannot dead-code-eliminate any matmul the FLOP
    count claims — and CONSUMED: ``block_until_ready`` on the returned
    loss+grads sits INSIDE the timed window, so dispatch-and-walk-away
    cannot inflate the rate. FLOPs counted conservatively at
    ``6 * n^3`` per layer (fwd 2n^3, dW 2n^3, dx 2n^3) minus the first
    layer's unused dx. Returns (measured TF/s, measured / table-peak
    ratio) — a ratio above ~1.0 means the PEAK_FLOPS row for this chip
    is WRONG (underquoted), not that the chip beat physics."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    keys = jax.random.split(key, layers + 1)
    ws = [jax.random.normal(k, (n, n), jnp.bfloat16) * 0.01
          for k in keys[:layers]]
    x = jax.random.normal(keys[-1], (n, n), jnp.bfloat16)

    def loss(ws, x):
        h = x
        for w in ws:
            h = h @ w
        # fp32 sum anchors every layer's output into the loss
        return jnp.sum(h.astype(jnp.float32))

    step = jax.jit(jax.value_and_grad(loss))
    out = step(ws, x)
    jax.block_until_ready(out)            # compile + warm outside the window
    t0 = time.perf_counter()
    for _ in range(reps):
        out = step(ws, x)
    jax.block_until_ready(out)            # consumption is part of the time
    dt = time.perf_counter() - t0
    flops = reps * (6 * layers - 2) * (n ** 3)
    measured = flops / dt
    table, _ = _peak()
    return measured / 1e12, measured / table


# decode-bench name -> attention path it traced ("pallas" /
# "xla-gather" / "xla-dense" / ...), read off the kernels.decode.*
# counter deltas around each decode bench (the counters bump at TRACE
# time, so they name the path the compiled loop actually baked in)
_decode_paths = {}


# bench name -> nonzero kernels.moe.dispatch_path.* deltas around the
# run (pallas / einsum / scatter / fallback.<reason> — trace-time, so
# they name the dispatch the compiled step actually baked in); empty =
# warm executables, path decided in an earlier run
_moe_paths = {}
# bench name -> nonzero kernels.flash.sdpa.* deltas (pallas[_mask] /
# xla[_mask] / xla_dense_mask / xla_core) — which attention path the
# encoder models traced
_sdpa_paths = {}


def _counter_deltas(prefix, fn):
    """Run fn and return (its result, the nonzero trace-time counter
    deltas under `prefix` keyed by suffix)."""
    from paddle_tpu import monitor
    before = monitor.snapshot()
    out = fn()
    after = monitor.snapshot()
    deltas = {}
    for key, val in after.items():
        if key.startswith(prefix + "."):
            d = int(val) - int(before.get(key, 0))
            if d > 0:
                deltas[key[len(prefix) + 1:]] = d
    return out, deltas


def _record_counter_paths(store, prefix, name, fn):
    """Run a bench and attribute which kernel path its compiled program
    baked in, from the trace-time counter deltas under `prefix`."""
    out, deltas = _counter_deltas(prefix, fn)
    store[name] = deltas if deltas else "cached-executable"
    return out


def _record_decode_path(name, fn):
    """Run a decode bench and attribute which attention path its
    compiled loop took from the kernels.decode.* counter deltas."""
    tok, deltas = _counter_deltas("kernels.decode", fn)
    for suffix, path in (("paged_pallas", "pallas"),
                         ("paged_xla_gather_step", "xla-gather"),
                         ("rolling_xla", "xla-rolling"),
                         ("dense_xla", "xla-dense")):
        if deltas.get(suffix, 0) > 0:
            _decode_paths[name] = path
            break
    else:
        # no retrace: path decided by an earlier run's executables
        _decode_paths[name] = "cached-executable"
    return tok


def _telemetry_extras(result):
    """PADDLE_TPU_MONITOR=1: fold the runtime counters (XLA compile
    count/seconds fed by the always-on listener in profiler/stats.py,
    eager dispatch count, device-memory watermark) into extras — a
    compile count that grows across re-printed lines means some extra
    is recompiling per step (shape churn), exactly the thing the
    headline MFU number can't show. The decode-path attribution rides
    along unconditionally (the counter registry is always live)."""
    from paddle_tpu import monitor
    tel = result["extras"].setdefault("telemetry", {})
    if _decode_paths:
        tel["decode_attention_path"] = dict(_decode_paths)
    if _moe_paths:
        # the dispatch-path breakdown: a silent degrade from pallas to
        # einsum shows up here as fallback.<reason> in every bench run
        tel["moe_dispatch_path"] = dict(_moe_paths)
    if _sdpa_paths:
        tel["sdpa_attention_path"] = dict(_sdpa_paths)
    if not monitor.enabled():
        if not tel:
            result["extras"].pop("telemetry", None)
        return
    from paddle_tpu.profiler.stats import read_memory
    snap = monitor.snapshot()
    tel.update({
        "xla_compiles": int(snap.get("xla.compiles", 0)),
        "xla_compile_secs": round(float(snap.get("xla.compile_secs",
                                                 0.0)), 2),
        "eager_op_dispatches": int(snap.get("dispatch.ops", 0)),
    })
    # host/device tick attribution from the serving loop, when any
    # serving bench ran: last-tick gauge values (the per-tick
    # distribution lives in serving.hist.* — see the
    # llama_1b_serving_host_share_per_tick extra for the trace-wide
    # share)
    if "serving.host_ms_per_tick" in snap:
        tel["serving.host_ms_per_tick"] = round(
            float(snap["serving.host_ms_per_tick"]), 3)
        tel["serving.device_ms_per_tick"] = round(
            float(snap.get("serving.device_ms_per_tick", 0.0)), 3)
    mem = read_memory()
    if mem["peak_bytes_in_use"]:
        tel[f"peak_bytes_{mem['source']}"] = mem["peak_bytes_in_use"]


def _time_steps(step_fn, n, groups=2):
    """Best-of-groups steps/sec; each group ends in a value fetch, which
    waits for the device."""
    best_dt = float("inf")
    for _ in range(groups):
        t0 = time.perf_counter()
        for _ in range(n):
            loss = step_fn()
        float(loss.numpy())
        best_dt = min(best_dt, (time.perf_counter() - t0) / n)
    return best_dt


def llama_step_io(cfg, ids, labels):
    """(loss_fn, step-inputs) for a LlamaConfig — shared by the bench
    and tools/mfu_sweep.py so both measure the identical path. With
    fused_linear_ce the model computes its own chunked head-matmul+CE
    loss (labels ride along as a forward input) and loss_fn passes the
    scalar through."""
    import paddle_tpu.nn as nn
    if cfg.fused_linear_ce:
        return (lambda out, lab: out), (ids, labels)
    return nn.CrossEntropyLoss(), ids


def _llama_run(cfg, batch, seq, n_steps=6, moment_dtype="bfloat16",
               startend_row_indices=None):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import (LlamaForCausalLM,
                                        llama_flops_per_token)

    paddle.seed(0)
    net = LlamaForCausalLM(cfg)
    # bf16 AdamW moments (fp32 master weights + update math): frees
    # ~4.3 GB of HBM on the 1B config (docs/PERF.md has the full
    # round-4 sweep this config family came from)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    loss_fn, inputs = llama_step_io(cfg, ids, labels)
    if startend_row_indices is not None:
        # flashmask document mask riding as the model's third forward
        # input (attn_mask_startend_row_indices) — only the fused-CE
        # path takes labels in-forward, so the tuple layout lines up
        if not cfg.fused_linear_ce:
            raise ValueError(
                "startend_row_indices benching requires "
                "fused_linear_ce=True (mask is the third forward input)")
        inputs = (*inputs, startend_row_indices)
    opt = paddle.optimizer.AdamW(3e-4, parameters=net.parameters(),
                                 moment_dtype=moment_dtype)
    step = paddle.jit.TrainStep(net, loss_fn, opt, amp_dtype="bfloat16")

    step(inputs, labels)                    # compile
    float(step(inputs, labels).numpy())     # warm
    dt = _time_steps(lambda: step(inputs, labels), n_steps)
    tokens_per_sec = batch * seq / dt
    peak, kind = _peak()
    mfu = tokens_per_sec * llama_flops_per_token(cfg) / peak
    n_params = net.num_params()
    return tokens_per_sec, mfu, kind, n_params


def bench_llama_1b():
    """Headline: 1.07B params (LLaMA-7B layer shapes), seq 1024.

    Round-4 measured-best single-chip config (tools/mfu_sweep.py, real
    v5e): batch 12, NO recompute, chunked fused lm-head+CE
    (fused_linear_ce — never materializes the [12288, 32000] logits),
    bf16 optimizer moments. The fused CE frees enough HBM that backward
    reuses every saved activation instead of recomputing: 0.650 (b8,
    selective_qkv) -> 0.719 MFU measured (4 CE chunks beat the default
    8: 0.7193 vs 0.7130; 2 and 16 both lower).
    """
    from paddle_tpu.text.models import LlamaConfig
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=4, num_attention_heads=32,
        num_key_value_heads=32, max_position_embeddings=1024,
        recompute=False, fused_linear_ce=True, fused_ce_chunks=4,
        use_flash_attention=True)
    return _llama_run(cfg, batch=12, seq=1024)


def bench_llama_long_seq():
    """Same 1.07B model at seq 2048 (long-context point, VERDICT r2 #2).
    Measured-best: batch 6, no recompute, fused CE x4 chunks — 0.693."""
    from paddle_tpu.text.models import LlamaConfig
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=4, num_attention_heads=32,
        num_key_value_heads=32, max_position_embeddings=2048,
        recompute=False, fused_linear_ce=True, fused_ce_chunks=4,
        use_flash_attention=True)
    return _llama_run(cfg, batch=6, seq=2048)


def bench_llama_small():
    """Round-2 shape kept for continuity: 0.3B-class, seq 512. XLA
    attention: at seq 512 the fused softmax path still edges out the
    Pallas kernel (0.727 vs 0.689 MFU measured); flash wins from ~1024."""
    from paddle_tpu.text.models import LlamaConfig
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=4, num_attention_heads=16,
        num_key_value_heads=16, max_position_embeddings=1024,
        use_flash_attention=False)
    return _llama_run(cfg, batch=32, seq=512, n_steps=20)


def bench_bert(cfg=None, batch=256, seq=128, n_steps=10):
    """BERT-base MLM train step (BASELINE config 3 family, single chip):
    tokens/sec + approximate MFU via the 6N FLOPs/token rule.

    batch 256 / seq 128 is the measured-best of the round-5 sweep
    (118.9K tok/s, docs/PERF.md table): seq 128 is the classic BERT
    phase-1 pretraining length and cuts the attention-core share (the
    head_dim-64 matmuls run at half MXU efficiency) 4x vs seq 512;
    int32 ids avoid emulated i64 index math; dense softmax-CE beats the
    chunked fused-CE scan at this size (the [b, s, vocab] bf16 logits
    are only 2 GB). The encoder attention now routes through the Pallas
    flash kernel via scaled_dot_product_attention (head-dim-64
    probe-gated, docs/KERNELS.md); extras.telemetry.sdpa_attention_path
    shows which path this run traced. To benchmark the fused-CE path
    instead, pass
    cfg.fused_mlm_ce=True AND labels as the third forward input with an
    identity loss_fn — forward(ids, tt, labels) then returns the loss
    directly (see tests/test_text_models.py fused test)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.text.models import BertConfig, BertForPretraining

    paddle.seed(0)
    if cfg is None:
        # bert-base, with the position table stretched to cover the
        # requested seq — JAX's clamped gather would otherwise silently
        # reuse the last position row past max_position_embeddings
        cfg = BertConfig(max_position_embeddings=max(512, seq))
    net = BertForPretraining(cfg)
    ce = nn.CrossEntropyLoss()

    def loss_fn(outs, labels):
        return ce(outs[0], labels)

    opt = paddle.optimizer.AdamW(1e-4, parameters=net.parameters(),
                                 moment_dtype="bfloat16")
    step = paddle.jit.TrainStep(net, loss_fn, opt, amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    tt = paddle.to_tensor(np.zeros((batch, seq), np.int32))
    labels = paddle.to_tensor(rng.integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    step((ids, tt), labels)
    float(step((ids, tt), labels).numpy())
    dt = _time_steps(lambda: step((ids, tt), labels), n_steps)
    tokens_per_sec = batch * seq / dt
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    peak, _ = _peak()
    mfu = tokens_per_sec * 6 * n_params / peak
    return tokens_per_sec, mfu


def bench_ernie_moe(cfg=None, batch=32, seq=512, n_steps=6,
                    dispatch_mode=None):
    """ERNIE-MoE causal LM step (BASELINE config 5 family, single chip):
    (tokens/sec, routed MFU). The MFU numerator is ACTIVE-params FLOPs
    (top_k experts/token + router, ernie_moe_flops_per_token) — the
    honest MoE utilization number; dense-equivalent params would
    overstate it by num_experts/top_k on the expert FFNs. batch 32 is
    the measured peak with GShard group-wise dispatch (71.7K tok/s —
    1.9x the ungrouped dispatch at the same shape, whose einsum cost is
    quadratic in tokens; 64 regresses). The einsum/scatter/pallas
    dispatch studies live in docs/PERF.md; the default config now runs
    dispatch_mode="pallas" (the fused grouped-matmul kernel), and the
    extras.telemetry.moe_dispatch_path breakdown shows whether the run
    stayed on it. `dispatch_mode` overrides the config's mode."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.text.models import ErnieMoEConfig, ErnieMoEForCausalLM

    paddle.seed(0)
    cfg = cfg or ErnieMoEConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_hidden_layers=8, num_attention_heads=16,
        num_key_value_heads=16, num_experts=8, moe_every=2,
        max_position_embeddings=max(seq, 512))
    if dispatch_mode is not None:
        import dataclasses
        cfg = dataclasses.replace(cfg, moe_dispatch_mode=dispatch_mode)
    net = ErnieMoEForCausalLM(cfg)
    ce = nn.CrossEntropyLoss()

    def loss_fn(out, labels):
        return ce(out, labels) + net.aux_loss()

    opt = paddle.optimizer.AdamW(1e-4, parameters=net.parameters(),
                                 moment_dtype="bfloat16")
    step = paddle.jit.TrainStep(net, loss_fn, opt, amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    labels = paddle.to_tensor(rng.integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    step(ids, labels)
    float(step(ids, labels).numpy())
    dt = _time_steps(lambda: step(ids, labels), n_steps)
    tokens_per_sec = batch * seq / dt
    from paddle_tpu.text.models.ernie_moe import ernie_moe_flops_per_token
    peak, _ = _peak()
    # ROUTED FLOPs (active params: top_k experts/token), not the
    # dense-equivalent count — the honest MoE utilization number
    mfu = tokens_per_sec * ernie_moe_flops_per_token(cfg) / peak
    return tokens_per_sec, mfu


def bench_llama_decode(batch=32, prompt=128, new_tokens=256,
                       quantize=False, cache_impl="auto", window=None,
                       cache_dtype="auto"):
    """Compiled KV-cache decode throughput on the 1B model (inference
    axis of BASELINE config 4): greedy text.generate — prefill + one
    lax.scan of single-token cached steps — new tokens/sec across the
    batch. Decode is weight-bandwidth bound, so throughput scales with
    batch (measured: 1.6K @ b8, 5.9K @ b32, 7.9K @ b64); b32 is the
    reported point.

    quantize=True converts the model to int8 weight-only execution
    (quantization.quantize_for_inference) — half the weight bytes, the
    lever that matters on a bandwidth-bound decode. cache_impl/window
    select the serving-cache layout points (paged block-table, rolling
    sliding-window buffer); cache_dtype the KV-cache precision ladder
    ("auto" = model compute dtype → bf16 on TPU; "int8" = quantized
    KV, a quarter of the f32 cache bytes — docs/DECODE.md)."""
    import paddle_tpu as paddle
    from paddle_tpu.text import generate
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=4, num_attention_heads=32,
        num_key_value_heads=32,
        max_position_embeddings=prompt + new_tokens,
        sliding_window=window,
        use_flash_attention=True)
    net = LlamaForCausalLM(cfg)
    net.eval()
    if quantize:
        from paddle_tpu.quantization import quantize_for_inference
        quantize_for_inference(net)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, prompt)).astype(np.int64))

    def run():
        return generate(net, ids, max_new_tokens=new_tokens,
                        cache_impl=cache_impl, cache_dtype=cache_dtype)

    np.asarray(run().numpy())                             # compile
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        out = run()
        np.asarray(out.numpy())
        best = min(best, time.perf_counter() - t0)
    return batch * new_tokens / best


def _drive_serving_trace(eng, arrivals, prompts, n_requests,
                         new_tokens):
    """One timed pass of the fixed-seed arrival trace against any
    serving engine (single-loop, disaggregated, or TP-sharded — the
    add_request/step/idle surface is shared). Returns generated
    tokens/sec across the whole trace."""
    from paddle_tpu.inference.engine import SamplingParams
    t0 = time.perf_counter()
    done = toks = 0
    i = 0
    while done < n_requests:
        now = time.perf_counter() - t0
        while i < n_requests and arrivals[i] <= now:
            eng.add_request(prompts[i], SamplingParams(
                max_new_tokens=new_tokens))
            i += 1
        if i < n_requests and eng.idle:
            # idle gap before the next arrival: sleep instead of
            # busy-spinning no-op steps (which would burn host CPU
            # and inflate serving.steps inside the timed region).
            # eng.idle counts mid-chunked-prefill slots as busy —
            # sleeping through a whale's remaining slices would
            # stall it until the next arrival.
            time.sleep(max(0.0, arrivals[i]
                           - (time.perf_counter() - t0)))
            continue
        outs = eng.step()
        done += len(outs)
        toks += sum(len(o.token_ids) for o in outs if o.ok)
    return toks / (time.perf_counter() - t0)


# steady-state host share of the LAST bench_llama_serving measured
# pass (compile pass excluded) — read by the serving extras right
# after the tokens/sec number they ran for
_LAST_SERVING_HOST_SHARE = 0.0


def bench_llama_serving(n_requests=24, max_slots=16, prompt_lo=64,
                        prompt_hi=192, new_tokens=128,
                        arrival_rate_hz=40.0, cache_dtype="auto",
                        shared_prefix=0, prefix_cache=False,
                        draft_layers=0, spec_k=4,
                        fault_rate=0.0, fault_seed=0,
                        whale_every=0, whale_prompt=0,
                        max_prefill_tokens=None,
                        prefill_workers=0, decode_workers=0):
    """Continuous-batching serving throughput on the 1B model
    (paddle_tpu.inference.Engine over the paged KV stack,
    docs/SERVING.md): a fixed-seed Poisson-ish arrival trace
    (exponential inter-arrival gaps at `arrival_rate_hz`, prompt
    lengths uniform in [prompt_lo, prompt_hi)) is replayed against the
    engine — requests join running decode batches mid-flight, pages
    come from the shared pool, and single-token steps take the Pallas
    paged-decode path on TPU. Reported: generated tokens/sec across
    the whole trace (admission + prefill + decode), the serving analog
    of the static-batch llama_1b_decode number. The trace runs once
    cold (compiles the prefill buckets + the decode shape) and the
    timed pass reuses the warm executables.

    shared_prefix=N opens every prompt with the same N-token system
    block and prefix_cache=True dedups it through the content-
    addressed page store (docs/SERVING.md): every request after the
    first prefills only its divergent tail. draft_layers=K attaches a
    K-layer draft model (same vocab/geometry) and decodes through the
    draft/verify schedule with spec_k drafted tokens per tick —
    token-identical by construction, faster whenever the draft earns
    its accept rate.

    fault_rate>0 arms the seeded FaultInjector (docs/SERVING.md
    "Reliability") for both passes: the reported number is
    surviving-request throughput under injected chaos — the price of
    the per-step invariant audit plus the faults themselves — and the
    run raises if the pool leaks pages or the audit ends dirty.

    whale_every=N makes every Nth request a ``whale_prompt``-token
    long-context request (mixed whale/small traffic), and
    max_prefill_tokens bounds the prefill work per engine step
    (chunked prefill, docs/SERVING.md) — the long-context serving
    point measures whale throughput WITHOUT letting whale prefills
    monopolize the decode loop.

    prefill_workers/decode_workers > 0 runs the trace against the
    DISAGGREGATED engine (inference/disagg.py, docs/SERVING.md
    "Disaggregated serving"): that many prefill/decode workers as
    independent compiled surfaces, KV pages migrating between their
    pools — the serving point for the MPMD split."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import Engine, SamplingParams
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    max_prompt = max(prompt_hi, whale_prompt + 1)
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=4, num_attention_heads=32,
        num_key_value_heads=32,
        max_position_embeddings=max_prompt + new_tokens,
        use_flash_attention=True)
    net = LlamaForCausalLM(cfg)
    net.eval()
    draft = None
    if draft_layers:
        import dataclasses
        paddle.seed(1)
        # same geometry/vocab as the target, shallower — the
        # draft/verify schedule requires it (docs/SERVING.md)
        dcfg = dataclasses.replace(
            cfg, num_hidden_layers=int(draft_layers))
        draft = LlamaForCausalLM(dcfg)
        draft.eval()
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate_hz,
                                         n_requests))
    # drawn ONLY when a shared prefix is asked for: the legacy traces
    # (shared_prefix=0) must keep their exact seed-0 rng stream so the
    # recorded serving numbers stay comparable across runs
    system = (rng.integers(0, cfg.vocab_size, (shared_prefix,))
              if shared_prefix else np.zeros((0,), np.int64))
    prompts = [np.concatenate([
        system,
        rng.integers(0, cfg.vocab_size,
                     (int(rng.integers(prompt_lo, prompt_hi))
                      - shared_prefix,))]).astype(np.int64)
        for _ in range(n_requests)]
    if whale_every:
        # every Nth request becomes a long-context whale (drawn AFTER
        # the legacy stream above so shared_prefix=0/whale_every=0
        # benches keep their exact historical rng sequence)
        for i in range(0, n_requests, int(whale_every)):
            prompts[i] = rng.integers(
                0, cfg.vocab_size, (int(whale_prompt),)).astype(np.int64)

    # ONE engine for both passes: the executables are per-instance jit
    # closures, so a fresh engine per pass would put every compile
    # back inside the timed region. A drained engine is reusable —
    # all pages free, all slots empty.
    # page_size 128 keeps the [page, head_dim] tiles Pallas-eligible
    # for every cache_dtype (docs/DECODE.md); cache_dtype="int8"
    # serves quantized KV pools dequantized inside the decode kernel
    injector = None
    if fault_rate > 0.0:
        from paddle_tpu.inference.reliability import FaultInjector
        injector = FaultInjector(seed=fault_seed, rate=fault_rate)
    common = dict(page_size=128, prefill_bucket=64,
                  max_context=max_prompt + new_tokens,
                  cache_dtype=cache_dtype, prefix_cache=prefix_cache,
                  draft_model=draft, spec_k=spec_k,
                  fault_injector=injector,
                  max_prefill_tokens_per_step=max_prefill_tokens)
    if prefill_workers > 0 or decode_workers > 0:
        from paddle_tpu.inference.disagg import DisaggEngine
        eng = DisaggEngine(net, prefill_workers=max(prefill_workers, 1),
                           decode_workers=max(decode_workers, 1),
                           max_slots=max_slots, **common)
    else:
        eng = Engine(net, max_slots=max_slots, **common)

    def run_trace():
        return _drive_serving_trace(eng, arrivals, prompts, n_requests,
                                    new_tokens)

    run_trace()                 # compile pass (warms eng's executables)
    # host/device attribution over the MEASURED pass only: the cold
    # pass above puts every compile on the host side of the split, so
    # sampling the subtractable histogram sums here (not around the
    # whole bench) is what makes the share a steady-state number
    from paddle_tpu import monitor
    host_h = monitor.histogram("serving.hist.host_ms_per_tick")
    dev_h = monitor.histogram("serving.hist.device_ms_per_tick")
    h0, d0 = host_h.sum, dev_h.sum
    tok_s = run_trace()
    host_ms = host_h.sum - h0
    dev_ms = dev_h.sum - d0
    global _LAST_SERVING_HOST_SHARE
    _LAST_SERVING_HOST_SHARE = (host_ms / (host_ms + dev_ms)
                                if host_ms + dev_ms > 0.0 else 0.0)
    if injector is not None:
        # the chaos contract, enforced on the measured pass too: no
        # leaked pages, no lingering refcount skew
        findings = eng.check_invariants()
        leaked = eng.leaked_pages()
        if findings or leaked:
            raise RuntimeError(
                f"serving chaos bench corrupted the pool: "
                f"{leaked} leaked page(s), findings {findings}")
    return tok_s


def bench_llama_serving_tp2(n_requests=12, max_slots=8, prompt_lo=64,
                            prompt_hi=192, new_tokens=128,
                            arrival_rate_hz=40.0, cache_dtype="auto"):
    """TP-sharded decode serving (docs/SERVING.md "TP-sharded
    decode"): the SAME 1B engine trace as ``llama_1b_serving`` but
    with the model and KV pools sharded mp=2 — weights column/row
    split by the TP layer classes, pools over the kv-head axis, the
    tiny decode state replicated and committed so the fused decode
    step stays ONE executable. Needs >= 2 devices (two chips, or the
    CPU backend's virtual devices); raises otherwise so the ledger
    records the gap instead of a fake single-device number."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.inference.engine import Engine, SamplingParams
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    if len(jax.devices()) < 2:
        raise RuntimeError(
            f"mp=2 serving needs >= 2 devices, have "
            f"{len(jax.devices())} ({jax.default_backend()})")
    prev = mesh_mod.get_mesh()
    mesh = mesh_mod.build_mesh({"dp": 1, "mp": 2},
                               devices=jax.devices()[:2])
    # BOTH installs, explicitly: the TP layer classes read paddle's
    # global mesh (llama._use_tp), jit sharding reads jax's ambient
    # context — on a jax with NATIVE set_mesh only the latter would
    # be set, and the "TP" bench would silently measure a dense model
    mesh_mod.set_mesh(mesh)
    try:
        with jax.set_mesh(mesh):
            paddle.seed(0)
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=4096,
                intermediate_size=11008, num_hidden_layers=4,
                num_attention_heads=32, num_key_value_heads=32,
                max_position_embeddings=prompt_hi + new_tokens,
                use_flash_attention=True)
            net = LlamaForCausalLM(cfg)
            net.eval()
            rng = np.random.default_rng(0)
            arrivals = np.cumsum(rng.exponential(
                1.0 / arrival_rate_hz, n_requests))
            prompts = [rng.integers(
                0, cfg.vocab_size,
                (int(rng.integers(prompt_lo, prompt_hi)),)).astype(
                np.int64) for _ in range(n_requests)]
            eng = Engine(net, max_slots=max_slots, page_size=128,
                         prefill_bucket=64,
                         max_context=prompt_hi + new_tokens,
                         cache_dtype=cache_dtype)

            def run_trace():
                return _drive_serving_trace(eng, arrivals, prompts,
                                            n_requests, new_tokens)

            run_trace()          # compile pass
            tok_s = run_trace()
            if eng.steady_state_recompiles() != 0:
                raise RuntimeError(
                    f"TP serving bench recompiled in steady state "
                    f"({eng.steady_state_recompiles()}) — the sharded "
                    f"decode surface is not unique")
            return tok_s
    finally:
        mesh_mod._global_mesh = prev


def bench_llama_serving_fleet(replicas=2, n_requests=24, max_slots=8,
                              prompt_lo=192, prompt_hi=320,
                              new_tokens=96, arrival_rate_hz=40.0,
                              n_sessions=4, session_prefix=128):
    """Elastic-fleet serving throughput (inference/fleet.py,
    docs/SERVING.md "Elastic fleet"): the 1B engine replicated
    ``replicas`` times behind the session-aware router, driven by a
    fixed-seed session-heavy arrival trace — ``n_sessions`` distinct
    ``session_prefix``-token system blocks, each request opening with
    its session's block so the router steers it to the replica whose
    prefix cache is warm. Returns (tokens/sec at 1 replica, tokens/sec
    at ``replicas`` replicas, the scaling ratio): the 1→N scaling is
    THE fleet number — on hardware with one chip per replica the
    expectation is >= 1.8x for 1→2 (never measured); in-process
    replicas sharing one device measure the router/scheduler overhead
    instead, which is why both points are recorded."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.fleet import ServingFleet
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=4, num_attention_heads=32,
        num_key_value_heads=32,
        max_position_embeddings=prompt_hi + new_tokens,
        use_flash_attention=True)
    net = LlamaForCausalLM(cfg)
    net.eval()
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate_hz,
                                         n_requests))
    blocks = [rng.integers(0, cfg.vocab_size, (session_prefix,))
              for _ in range(n_sessions)]
    prompts = []
    for i in range(n_requests):
        s = int(rng.integers(0, n_sessions))
        tail = rng.integers(
            0, cfg.vocab_size,
            (int(rng.integers(prompt_lo, prompt_hi)) - session_prefix,))
        prompts.append(np.concatenate([blocks[s], tail])
                       .astype(np.int64))

    def measure(n):
        fleet = ServingFleet(net, replicas=n, max_slots=max_slots,
                             page_size=128, prefill_bucket=64,
                             max_context=prompt_hi + new_tokens,
                             prefix_cache=True, router="session")
        _drive_serving_trace(fleet, arrivals, prompts, n_requests,
                             new_tokens)              # compile pass
        tok_s = _drive_serving_trace(fleet, arrivals, prompts,
                                     n_requests, new_tokens)
        if fleet.steady_state_recompiles() != 0:
            raise RuntimeError(
                f"fleet bench recompiled in steady state "
                f"({fleet.steady_state_recompiles()})")
        leaked = fleet.leaked_pages()
        if leaked:
            raise RuntimeError(
                f"fleet bench leaked {leaked} page(s)")
        fleet.close()
        return tok_s

    r1 = measure(1)
    rn = measure(int(replicas))
    return r1, rn, rn / r1


def bench_ernie_moe_serving(n_requests=16, max_slots=8, prompt_lo=64,
                            prompt_hi=192, new_tokens=96,
                            arrival_rate_hz=40.0, draft_layers=0,
                            spec_k=4):
    """ERNIE-MoE continuous-batching serving throughput
    (docs/SERVING.md "MoE serving"): the SAME fixed-seed arrival-trace
    drive as ``llama_1b_serving`` but the model is a sparse ERNIE-MoE
    decoder — 8 experts / top-2 routing every second block, geometry
    chosen Pallas-eligible (hidden 1024 / expert FFN 2816, both
    lane-aligned) so decode ticks dispatch through the fused
    grouped-matmul with no-drop serving capacity and dead-lane
    masking. The run FAILS if any ``serving.moe.decode_path.
    fallback.*`` counter moved on a TPU backend — the bench must
    measure the fused path, never a silently slower scatter.

    draft_layers=K attaches a K-layer DENSE LLaMA draft (same
    hidden/heads/vocab) and decodes through the draft/verify schedule
    with ``spec_k`` drafted tokens per tick — the dense-draft/MoE-
    verifier speculative point (token-identical by construction)."""
    import paddle_tpu as paddle
    from paddle_tpu import monitor
    from paddle_tpu.core import place
    from paddle_tpu.inference.engine import Engine
    from paddle_tpu.text.models import (ErnieMoEConfig,
                                        ErnieMoEForCausalLM,
                                        LlamaConfig, LlamaForCausalLM)

    paddle.seed(0)
    max_ctx = prompt_hi + new_tokens + (spec_k + 1 if draft_layers
                                        else 0)
    cfg = ErnieMoEConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_hidden_layers=4, num_attention_heads=16,
        num_key_value_heads=16, num_experts=8, moe_every=2,
        max_position_embeddings=max_ctx,
        use_flash_attention=True)
    net = ErnieMoEForCausalLM(cfg)
    net.eval()
    draft = None
    if draft_layers:
        paddle.seed(1)
        dcfg = LlamaConfig(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
            intermediate_size=cfg.intermediate_size,
            num_hidden_layers=int(draft_layers),
            num_attention_heads=cfg.num_attention_heads,
            num_key_value_heads=cfg.num_key_value_heads,
            max_position_embeddings=cfg.max_position_embeddings,
            use_flash_attention=True)
        draft = LlamaForCausalLM(dcfg)
        draft.eval()
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate_hz,
                                         n_requests))
    prompts = [rng.integers(
        0, cfg.vocab_size,
        (int(rng.integers(prompt_lo, prompt_hi)),)).astype(np.int64)
        for _ in range(n_requests)]
    before = {k: int(v) for k, v in monitor.snapshot().items()
              if k.startswith("serving.moe.decode_path.fallback.")}
    eng = Engine(net, max_slots=max_slots, page_size=128,
                 prefill_bucket=64, max_context=max_ctx,
                 draft_model=draft, spec_k=spec_k)
    _drive_serving_trace(eng, arrivals, prompts, n_requests,
                         new_tokens)                  # compile pass
    tok_s = _drive_serving_trace(eng, arrivals, prompts, n_requests,
                                 new_tokens)
    if eng.steady_state_recompiles() != 0:
        raise RuntimeError(
            f"MoE serving bench recompiled in steady state "
            f"({eng.steady_state_recompiles()})")
    # delta around THIS run only — a stale fallback counter from an
    # earlier bench in the same process must not fail a clean run
    fallbacks = {k: int(v) - before.get(k, 0)
                 for k, v in monitor.snapshot().items()
                 if k.startswith("serving.moe.decode_path.fallback.")
                 and int(v) - before.get(k, 0)}
    if fallbacks and place.accelerator_available():
        # a TPU bench that silently measured the scatter path would
        # record a number that says nothing about the fused kernel
        raise RuntimeError(
            f"MoE serving bench fell off the fused Pallas dispatch: "
            f"{fallbacks} (docs/KERNELS.md eligibility)")
    return tok_s


def bench_bert_embedding(n_requests=64, max_batch=16, bucket=32,
                         seq_lo=16, seq_hi=128,
                         arrival_rate_hz=400.0):
    """Encoder embedding-service throughput (inference/encoder.py,
    docs/SERVING.md "Embedding service"): a fixed-seed arrival trace
    of mixed-length mean/CLS requests against the BatchEncoder over
    bert-base with flash SDPA — bucketed continuous batching, no KV,
    no pages; the number is REAL (unpadded) tokens/sec across the
    whole trace, so both batch packing and pad waste show up in it.
    The run fails on any steady-state recompile: every arrival mix
    must bounce between the warmed per-bucket executables."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.encoder import BatchEncoder, EmbedParams
    from paddle_tpu.text.models import BertConfig, BertModel

    paddle.seed(0)
    cfg = BertConfig(max_position_embeddings=max(512, seq_hi))
    net = BertModel(cfg)
    net.eval()
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate_hz,
                                         n_requests))
    seqs = [rng.integers(
        0, cfg.vocab_size,
        (int(rng.integers(seq_lo, seq_hi)),)).astype(np.int64)
        for _ in range(n_requests)]
    pools = [("mean" if i % 2 else "cls") for i in range(n_requests)]
    svc = BatchEncoder(net, max_batch=max_batch, bucket=bucket)

    def run_trace():
        t0 = time.perf_counter()
        done = toks = 0
        i = 0
        while done < n_requests:
            now = time.perf_counter() - t0
            while i < n_requests and arrivals[i] <= now:
                svc.add_request(seqs[i],
                                EmbedParams(pooling=pools[i]))
                i += 1
            if i < n_requests and svc.idle:
                time.sleep(max(0.0, arrivals[i]
                               - (time.perf_counter() - t0)))
                continue
            outs = svc.step()
            done += len(outs)
            toks += sum(o.tokens for o in outs if o.ok)
        return toks / (time.perf_counter() - t0)

    run_trace()                 # compile pass (warms every bucket)
    tok_s = run_trace()
    if svc.steady_state_recompiles() != 0:
        raise RuntimeError(
            f"embedding bench recompiled in steady state "
            f"({svc.steady_state_recompiles()})")
    svc.close()
    return tok_s


def bench_llama_seq8k_flashmask(batch=1, seq=8192, docs=4, n_steps=4):
    """Long-context training headline: the 1.07B LLaMA at seq 8192 with
    a packed DOCUMENT mask — the Pallas flashmask kernel end-to-end
    (fwd + bwd + AdamW step, fused lm-head+CE, bf16 moments). The mask
    rides as ``attn_mask_startend_row_indices`` (O(S) column bands; a
    dense [b,h,S,S] additive mask would be 2 GB/head-batch at this
    length) and cross-document key tiles are SKIPPED by the kernel, so
    this measures the real packed-pretraining step, not a synthetic
    kernel loop. Reported as tokens/sec + MFU (6N rule — the same
    accounting as every other llama point, so the seq-1024/2048/8192
    ladder is comparable)."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.text.models import LlamaConfig

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=4, num_attention_heads=32,
        num_key_value_heads=32, max_position_embeddings=seq,
        recompute=False, fused_linear_ce=True, fused_ce_chunks=4,
        use_flash_attention=True)
    se = F.document_startend_row_indices([seq // docs] * docs)
    # same protocol as every other llama point (_llama_run), with the
    # mask riding as an extra traced step input — the seq ladder stays
    # like-for-like
    return _llama_run(cfg, batch=batch, seq=seq, n_steps=n_steps,
                      startend_row_indices=se)


def bench_flashmask_8k(b=4, h=8, s=8192, d=128, n=20):
    """Pallas flashmask fwd at seq 8K with a 4-document causal mask —
    the memory-linear mask path (the dense [b,h,S,S] additive mask this
    replaced is 2.1 GB at b1 h8 and measured 21 ms/batch-row;
    docs/PERF.md flashmask table). Timed with the kernel looped
    in-graph so per-call dispatch latency doesn't dominate.
    Returns ms per forward."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu  # noqa: F401 — platform/flags init
    from paddle_tpu.kernels import flash_attention as fa

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)).astype(np.float32)
                    * 0.3, jnp.bfloat16)
    idx = np.zeros((1, 1, s, 1), np.int32)
    for lo in range(0, s, 2048):
        idx[:, :, lo:lo + 2048, 0] = lo + 2048
    se = fa._normalize_startend(jnp.asarray(idx), s, s, True)
    scale = d ** -0.5

    @jax.jit
    def fn(q):
        def body(i, acc):
            # body closes over the TRACED q (defined in-jit), so the
            # 64 MB input is a real argument, not a baked-in constant
            qi = q.at[0, 0, 0, 0].add(acc.astype(jnp.bfloat16))
            out = fa._flash_pallas(qi, qi, qi, se, True, scale, False)
            return acc + jnp.sum(out.astype(jnp.float32)) * 1e-9

        return jax.lax.fori_loop(0, n, body, jnp.float32(0))
    float(fn(q))
    t0 = time.perf_counter()
    float(fn(q))
    return (time.perf_counter() - t0) / n * 1e3


def bench_plan_search(n_devices=8):
    """Auto-parallel planner wall time + calibration: search the full
    DP/TP/PP/sharding/SEP plan space for the 1B headline model at
    `n_devices` chips (enumerate -> shard_lint prune -> abstract-traced
    roofline ranking, all device-free), and score the planner's
    rank-correlation against the frozen 13-dryrun-config ledger.
    Returns (search_ms, rank_corr, best_plan_str). Hardware-independent
    by construction — the planner never touches a device."""
    from paddle_tpu.analysis import planner

    spec = planner.ModelSpec.llama_1b(global_batch=12 * n_devices)
    t0 = time.perf_counter()
    ranked = planner.search_plans(spec, n_devices)
    search_ms = (time.perf_counter() - t0) * 1e3
    if not ranked or not ranked[0].ok:
        raise RuntimeError("planner found no legal 1B plan")
    rep = planner.calibration_report()
    if not rep["passed"]:
        raise RuntimeError(
            f"planner calibration failed: corr={rep['spearman']:.3f} "
            f"families={rep['families_ok']}")
    return search_ms, rep["spearman"], ranked[0].plan.describe()


def bench_llama_mpmd_pp4(n_steps=6, batch=8, seq=512, n_micro=8,
                         cfg=None):
    """MPMD pipeline-parallel training throughput (docs/MPMD.md): the
    1B-layer-shape llama split over pp=4 stages and trained under
    ``schedule_mode="MPMD"`` — per-stage fixed compiled programs, the
    host driver executing the mpmd_lint-verified FThenB event graph,
    cross-stage activations as explicit ``device_put`` edges (no
    single-SPMD scan, no ppermute). Returns (tokens/sec, measured
    bubble fraction, predicted bubble fraction): measured is the
    driver's structural occupancy over the executed span
    (``stats()["bubble_fraction"]``), predicted the schedule's
    analytic (S-1)/(M+S-1) stamped on the graph — the pair is the
    schedule-quality gate a chip run reads next to raw speed. Needs
    >= 4 devices; raises otherwise so the ledger records the gap."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel import \
        PipelineParallel
    from paddle_tpu.text.models import LlamaConfig, build_llama_pipe

    if len(jax.devices()) < 4:
        raise RuntimeError(
            f"pp=4 MPMD bench needs >= 4 devices, have "
            f"{len(jax.devices())} ({jax.default_backend()})")
    prev = mesh_mod.get_mesh()
    mesh = mesh_mod.build_mesh({"pp": 4, "dp": 1},
                               devices=jax.devices()[:4])
    mesh_mod.set_mesh(mesh)
    try:
        paddle.seed(0)
        if cfg is None:
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=2048,
                intermediate_size=5632, num_hidden_layers=8,
                num_attention_heads=16, num_key_value_heads=16,
                max_position_embeddings=seq,
                use_flash_attention=False)
        pl = build_llama_pipe(cfg, num_stages=4)
        strat = fleet.DistributedStrategy()
        strat.pipeline_configs["accumulate_steps"] = n_micro
        strat.pipeline_configs["schedule_mode"] = "MPMD"
        model = PipelineParallel(pl, strategy=strat)
        opt = paddle.optimizer.AdamW(1e-4, parameters=pl.parameters())
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size,
                           (batch, seq + 1)).astype(np.int64)
        data = (paddle.to_tensor(ids[:, :-1]),
                paddle.to_tensor(ids[:, 1:]))
        with jax.set_mesh(mesh):
            model.train_batch(data, opt)          # compile pass
            t0 = time.perf_counter()
            for _ in range(n_steps):
                loss = model.train_batch(data, opt)
            float(loss.numpy())                   # sync
            dt = time.perf_counter() - t0
        if model.mpmd_driver.steady_state_recompiles() != 0:
            raise RuntimeError(
                f"MPMD bench recompiled in steady state "
                f"({model.mpmd_driver.steady_state_recompiles()}) — "
                f"the per-stage executable set is not fixed")
        stats = model.mpmd_driver.stats()
        tok_s = n_steps * batch * seq / dt
        return (tok_s, float(stats["bubble_fraction"]),
                float(stats.get("predicted_bubble_fraction",
                                stats["bubble_fraction"])))
    finally:
        mesh_mod._global_mesh = prev


def bench_resnet50(batch=256, n_steps=10):
    """ResNet-50 ImageNet-shape train step (BASELINE config 2 metric:
    images/sec, single chip — the 8->64-chip scaling axis is covered by
    the dryrun's dp config). bf16 AMP, momentum-SGD, NCHW 224x224
    synthetic batch (XLA picks its own device layout)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    net = resnet50(num_classes=1000)
    loss_fn = nn.CrossEntropyLoss()
    opt = paddle.optimizer.Momentum(0.1, momentum=0.9,
                                    parameters=net.parameters())
    step = paddle.jit.TrainStep(net, loss_fn, opt, amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal(
        (batch, 3, 224, 224)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 1000, batch).astype(np.int64))
    step(x, y)
    float(step(x, y).numpy())
    dt = _time_steps(lambda: step(x, y), n_steps)
    return batch / dt


def bench_lenet():
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    batch = 256
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (batch, 1, 28, 28)).astype(np.float32))
    y = paddle.to_tensor(np.random.default_rng(1).integers(0, 10, batch))

    net = LeNet()
    loss_fn = nn.CrossEntropyLoss()
    opt = paddle.optimizer.Adam(1e-3, parameters=net.parameters())
    step = paddle.jit.TrainStep(net, loss_fn, opt)
    step(x, y)
    float(step(x, y).numpy())
    # tiny steps (~10 ms) are dominated by host dispatch jitter — take
    # the best of 3 timing groups
    n = 100
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            loss = step(x, y)
        float(loss.numpy())
        best = max(best, n / (time.perf_counter() - t0))
    compiled_sps = best

    # eager dygraph path (the reference-dygraph analog)
    net2 = LeNet()
    opt2 = paddle.optimizer.Adam(1e-3, parameters=net2.parameters())

    def eager_step():
        loss = loss_fn(net2(x), y)
        loss.backward()
        opt2.step()
        opt2.clear_grad()
        return loss

    eager_step()
    n2 = 10
    best_dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n2):
            loss = eager_step()
        float(loss.numpy())
        best_dt = min(best_dt, time.perf_counter() - t0)
    eager_sps = n2 / best_dt
    return compiled_sps, compiled_sps / eager_sps


def main():
    """Timeout-proof protocol (round-4 fix for the r3 rc=124 loss):

    1. Measure the 1B HEADLINE first and print the complete JSON line
       the moment it exists — a driver kill after this point can only
       truncate extras, never erase the round's number.
    2. Run each extra under an explicit wall-clock budget
       (``BENCH_TIME_BUDGET`` seconds, default 19 min); an extra is
       skipped — and recorded as skipped — when its cost estimate
       would overrun the budget. After every extra the FULL line is
       re-printed, so the last JSON line on stdout is always the most
       complete result.
    """
    _enable_compile_cache()
    t_start = time.time()
    budget = float(os.environ.get("BENCH_TIME_BUDGET", str(19 * 60)))
    deadline = t_start + budget

    tok_1b, mfu_1b, kind, n_params = bench_llama_1b()
    result = {
        "metric": "llama_1b_train_tokens_per_sec_per_chip",
        "value": round(tok_1b, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu_1b / 0.50, 3),
        "extras": {
            "llama_1b_mfu": round(mfu_1b, 4),
            "llama_1b_params": int(n_params),
            "device_kind": kind,
        },
    }
    if mfu_1b > MFU_PLAUSIBLE_BOUND:
        # an impossible MFU ships FLAGGED, never silently: either the
        # PEAK_FLOPS row is wrong for this chip or the analytic FLOP
        # count overshot (docs/PERF.md "Device-peak note")
        result["extras"]["llama_1b_mfu_suspect"] = True
    _telemetry_extras(result)
    print(json.dumps(result), flush=True)

    def add_llama(prefix, fn):
        tok, mfu, _, _ = fn()
        result["extras"][f"{prefix}_mfu"] = round(mfu, 4)
        result["extras"][f"{prefix}_tokens_per_sec"] = round(tok, 1)

    def add_lenet():
        sps, speedup = bench_lenet()
        result["extras"]["lenet_train_steps_per_sec_b256"] = round(sps, 2)
        result["extras"]["lenet_compiled_vs_eager_speedup"] = round(speedup, 1)

    def add_bert():
        tok, mfu = _record_counter_paths(
            _sdpa_paths, "kernels.flash.sdpa", "bert_base", bench_bert)
        result["extras"]["bert_base_tokens_per_sec"] = round(tok, 1)
        result["extras"]["bert_base_mfu_approx"] = round(mfu, 4)

    def add_moe():
        # default config: dispatch_mode="pallas" with counter-visible
        # fallback; the moe_dispatch_path telemetry names what it took
        tok, mfu = _record_counter_paths(
            _moe_paths, "kernels.moe.dispatch_path", "ernie_moe",
            bench_ernie_moe)
        result["extras"]["ernie_moe_tokens_per_sec"] = round(tok, 1)
        result["extras"]["ernie_moe_mfu_routed"] = round(mfu, 4)

    def add_moe_pallas():
        # the explicitly-gated fused-dispatch point: stays meaningful
        # even if the config default ever changes
        tok, _mfu = _record_counter_paths(
            _moe_paths, "kernels.moe.dispatch_path", "ernie_moe_pallas",
            lambda: bench_ernie_moe(dispatch_mode="pallas"))
        result["extras"]["ernie_moe_dispatch_pallas_tokens_per_sec"] = \
            round(tok, 1)

    def add_resnet():
        ips = bench_resnet50()
        result["extras"]["resnet50_images_per_sec"] = round(ips, 1)

    def add_decode():
        # default cache_dtype="auto" → bf16 KV caches on TPU
        tok = _record_decode_path("decode", bench_llama_decode)
        result["extras"]["llama_1b_decode_tokens_per_sec"] = round(tok, 1)

    def add_decode_int8():
        tok = _record_decode_path(
            "decode_int8w", lambda: bench_llama_decode(quantize=True))
        result["extras"]["llama_1b_decode_int8_tokens_per_sec"] = \
            round(tok, 1)

    def add_decode_bf16kv():
        tok = _record_decode_path(
            "decode_bf16kv",
            lambda: bench_llama_decode(cache_dtype="bfloat16"))
        result["extras"]["llama_1b_decode_bf16kv_tokens_per_sec"] = \
            round(tok, 1)

    def add_decode_int8kv():
        tok = _record_decode_path(
            "decode_int8kv",
            lambda: bench_llama_decode(cache_dtype="int8"))
        result["extras"]["llama_1b_decode_int8kv_tokens_per_sec"] = \
            round(tok, 1)

    def add_decode_paged():
        tok = _record_decode_path(
            "decode_paged",
            lambda: bench_llama_decode(cache_impl="paged"))
        result["extras"]["llama_1b_decode_paged_tokens_per_sec"] = \
            round(tok, 1)
        dense = result["extras"].get("llama_1b_decode_tokens_per_sec")
        if dense:
            # the r05 measurement-debt number: paged decode as a
            # fraction of dense decode (was 0.52 pre-PR 6; the
            # multi-sequence DMA kernel is supposed to close it) —
            # recorded explicitly so the gap can never hide in two
            # far-apart extras again
            result["extras"]["llama_1b_decode_paged_vs_dense_ratio"] = \
                round(tok / dense, 3)

    def add_decode_paged_int8():
        # int8 KV pools through the paged layout: pages stream at a
        # quarter of the f32 bytes, dequantized in-VMEM by the
        # multi-sequence decode kernel
        tok = _record_decode_path(
            "decode_paged_int8",
            lambda: bench_llama_decode(cache_impl="paged",
                                       cache_dtype="int8"))
        result["extras"]["llama_1b_decode_paged_int8_tokens_per_sec"] = \
            round(tok, 1)

    def add_decode_window():
        # sliding_window 128 < total 384: the rolling O(window) buffer
        tok = _record_decode_path(
            "decode_rolling", lambda: bench_llama_decode(window=128))
        result["extras"]["llama_1b_decode_rolling_tokens_per_sec"] = \
            round(tok, 1)

    def add_serving():
        # host/device tick attribution rides the same measured trace:
        # every Engine.step() splits its wall time into host-schedule
        # vs device-dispatch histograms (docs/OBSERVABILITY.md), and
        # the bench samples the subtractable sums around its MEASURED
        # pass (compiles excluded), so the share over exactly those
        # ticks costs no extra run. A high share at max_slots means
        # the serving loop is host-bound, the thing the tokens/sec
        # headline can't distinguish from a slow chip.
        tok = _record_decode_path("serving", bench_llama_serving)
        result["extras"]["llama_1b_serving_tokens_per_sec"] = \
            round(tok, 1)
        result["extras"]["llama_1b_serving_host_share_per_tick"] = \
            round(_LAST_SERVING_HOST_SHARE, 4)

    def add_serving_int8kv():
        # the engine bench finally exercises int8-KV: same arrival
        # trace, quantized page pools end to end (per-slot scale pools
        # consumed inside the decode executable)
        tok = _record_decode_path(
            "serving_int8kv",
            lambda: bench_llama_serving(cache_dtype="int8"))
        result["extras"]["llama_1b_serving_int8kv_tokens_per_sec"] = \
            round(tok, 1)

    def add_serving_prefix():
        # shared-system-prompt trace through the prefix cache: every
        # request after the first maps the hot 256-token prefix's
        # pages and prefills only its divergent tail
        tok = _record_decode_path(
            "serving_prefix",
            lambda: bench_llama_serving(shared_prefix=256,
                                        prompt_lo=320, prompt_hi=448,
                                        prefix_cache=True))
        result["extras"]["llama_1b_serving_prefix_tokens_per_sec"] = \
            round(tok, 1)

    def add_serving_spec():
        # draft/verify speculative decoding: a 1-layer draft proposes
        # 4 tokens per tick, the 4-layer target verifies all 5
        # positions in one forward — output tokens identical, serving
        # throughput scales with the accept rate
        tok = _record_decode_path(
            "serving_spec",
            lambda: bench_llama_serving(draft_layers=1, spec_k=4))
        result["extras"]["llama_1b_serving_spec_tokens_per_sec"] = \
            round(tok, 1)

    def add_seq8k_flashmask():
        # the seq-8K packed-document training point: flashmask bands
        # end-to-end through fwd+bwd+optimizer with fused CE
        tok, mfu, _, _ = bench_llama_seq8k_flashmask()
        result["extras"]["llama_seq8k_flashmask_mfu"] = round(mfu, 4)
        result["extras"]["llama_seq8k_flashmask_tokens_per_sec"] = \
            round(tok, 1)

    def add_serving_longctx():
        # mixed whale/small serving under chunked prefill: every 4th
        # request is a 1536-token whale, prefill bounded to 256
        # tokens/step so decode ticks interleave (docs/SERVING.md
        # "Chunked prefill"); throughput across the whole trace
        tok = _record_decode_path(
            "serving_longctx",
            lambda: bench_llama_serving(
                n_requests=16, whale_every=4, whale_prompt=1536,
                max_prefill_tokens=256, new_tokens=96,
                arrival_rate_hz=20.0))
        result["extras"]["llama_1b_serving_longctx_tokens_per_sec"] = \
            round(tok, 1)

    def add_serving_chaos():
        # the reliability tax: the same arrival trace under a seeded
        # FaultInjector (2% per fault point per query) with the
        # per-step invariant audit on — surviving-request throughput,
        # and a hard failure on any leaked page or audit finding
        tok = _record_decode_path(
            "serving_chaos",
            lambda: bench_llama_serving(fault_rate=0.02))
        result["extras"]["llama_1b_serving_chaos_tokens_per_sec"] = \
            round(tok, 1)

    def add_serving_disagg():
        # disaggregated prefill/decode: 2 prefill + 2 decode workers
        # as independent compiled surfaces, KV pages migrating between
        # their pools (docs/SERVING.md "Disaggregated serving")
        tok = _record_decode_path(
            "serving_disagg",
            lambda: bench_llama_serving(prefill_workers=2,
                                        decode_workers=2))
        result["extras"]["llama_1b_serving_disagg_tokens_per_sec"] = \
            round(tok, 1)

    def add_serving_fleet():
        # the elastic fleet: session-heavy trace over N=2 engine
        # replicas behind the session-aware router; records the
        # 2-replica throughput AND the 1->2 scaling ratio (>= 1.8x
        # expected with one chip per replica — never measured)
        r1, r2, scaling = bench_llama_serving_fleet()
        result["extras"]["llama_1b_serving_fleet_tokens_per_sec"] = \
            round(r2, 1)
        result["extras"]["llama_1b_serving_fleet_scaling_1to2"] = \
            round(scaling, 3)

    def add_moe_serving():
        # ERNIE-MoE through the continuous-batching engine: decode
        # ticks on the fused Pallas grouped-matmul dispatch (no-drop
        # capacity, dead-lane masking); the moe_dispatch_path
        # telemetry names what the serving executables baked in
        tok = _record_counter_paths(
            _moe_paths, "kernels.moe.decode_path", "moe_serving",
            bench_ernie_moe_serving)
        result["extras"]["ernie_moe_serving_tokens_per_sec"] = \
            round(tok, 1)

    def add_moe_serving_spec():
        # dense-draft speculative decoding against the MoE verifier:
        # a 1-layer dense LLaMA drafts 4 tokens/tick, the sparse
        # target verifies all 5 positions in one forward — token-
        # identical, faster whenever the draft earns its accept rate
        tok = _record_counter_paths(
            _moe_paths, "kernels.moe.decode_path", "moe_serving_spec",
            lambda: bench_ernie_moe_serving(draft_layers=1, spec_k=4))
        result["extras"]["ernie_moe_serving_spec_tokens_per_sec"] = \
            round(tok, 1)

    def add_bert_embedding():
        # the encoder embedding service: bucketed continuous batching
        # over flash-SDPA bert-base, REAL tokens/sec (pad waste counts
        # against it); sdpa_attention_path telemetry rides along
        tok = _record_counter_paths(
            _sdpa_paths, "kernels.flash.sdpa", "bert_embedding",
            bench_bert_embedding)
        result["extras"]["bert_embedding_tokens_per_sec"] = \
            round(tok, 1)

    def add_serving_tp2():
        # mp=2 TP-sharded decode: weights + KV pools sharded over two
        # devices, one fused decode executable (needs >= 2 devices;
        # recorded as an error string on a 1-chip runner)
        tok = _record_decode_path("serving_tp2",
                                  bench_llama_serving_tp2)
        result["extras"]["llama_1b_serving_tp2_tokens_per_sec"] = \
            round(tok, 1)

    def add_flashmask():
        ms = bench_flashmask_8k()
        result["extras"]["flashmask_seq8k_docmask_ms"] = round(ms, 2)

    def add_peak_microbench():
        # the MFU-denominator check: synchronized, DCE-proof measured
        # bf16 peak vs the PEAK_FLOPS table row; ratio > ~1.0 means
        # the table (the MFU denominator) underquotes this chip
        tf, ratio = bench_peak_microbench()
        result["extras"]["peak_bf16_measured_tflops"] = round(tf, 1)
        result["extras"]["peak_bf16_measured_vs_table"] = \
            round(ratio, 3)

    def add_plan_search():
        ms, corr, best = bench_plan_search()
        result["extras"]["llama_1b_plan_search_ms"] = round(ms, 1)
        result["extras"]["llama_1b_plan_predicted_vs_dryrun_rank_corr"] \
            = round(corr, 3)
        result["extras"]["llama_1b_plan_best"] = best

    def add_mpmd_pp():
        # MPMD pipeline training (docs/MPMD.md): pp=4 llama under the
        # host schedule driver — raw speed next to the schedule-
        # quality pair (measured occupancy vs the analytic FThenB
        # bubble), zero steady-state recompiles enforced in-bench
        tok, bub, pred = bench_llama_mpmd_pp4()
        result["extras"]["llama_1b_mpmd_pp4_tokens_per_sec"] = \
            round(tok, 1)
        result["extras"]["llama_1b_mpmd_pp4_bubble_fraction"] = \
            round(bub, 4)
        result["extras"]["llama_1b_mpmd_pp4_bubble_predicted"] = \
            round(pred, 4)

    # (name, runner, wall-clock cost estimate in seconds: compile+measure,
    # cold cache — estimates from the round-4 dress-rehearsal runs, not
    # re-measured on this chip). Ordered so every BASELINE config (4-long-ctx,
    # 3, 2, 5, 1) gets a point before the round-2 continuity shape.
    extras = [
        ("llama_seq2048", lambda: add_llama("llama_seq2048",
                                            bench_llama_long_seq), 300),
        ("llama_seq8k_flashmask", add_seq8k_flashmask, 360),
        ("bert_base", add_bert, 180),
        ("resnet50", add_resnet, 240),
        ("ernie_moe", add_moe, 240),
        ("ernie_moe_dispatch_pallas", add_moe_pallas, 240),
        ("lenet", add_lenet, 100),
        ("llama_small_seq512", lambda: add_llama("llama_small_seq512",
                                                 bench_llama_small), 180),
        ("llama_decode", add_decode, 240),
        ("llama_decode_bf16kv", add_decode_bf16kv, 240),
        ("llama_decode_int8kv", add_decode_int8kv, 240),
        ("llama_decode_int8", add_decode_int8, 240),
        ("llama_decode_paged", add_decode_paged, 240),
        ("llama_decode_paged_int8", add_decode_paged_int8, 240),
        ("llama_decode_rolling", add_decode_window, 240),
        ("llama_serving", add_serving, 300),
        ("llama_serving_int8kv", add_serving_int8kv, 300),
        ("llama_serving_prefix", add_serving_prefix, 300),
        ("llama_serving_spec", add_serving_spec, 300),
        ("llama_serving_longctx", add_serving_longctx, 300),
        ("llama_serving_chaos", add_serving_chaos, 300),
        ("llama_serving_disagg", add_serving_disagg, 300),
        ("llama_serving_fleet", add_serving_fleet, 420),
        ("llama_serving_tp2", add_serving_tp2, 300),
        ("ernie_moe_serving", add_moe_serving, 300),
        ("ernie_moe_serving_spec", add_moe_serving_spec, 300),
        ("bert_embedding", add_bert_embedding, 240),
        ("flashmask_8k", add_flashmask, 90),
        ("peak_bf16", add_peak_microbench, 120),
        ("plan_search", add_plan_search, 60),
        ("llama_mpmd_pp4", add_mpmd_pp, 420),
    ]
    skipped = []
    errored = []
    for name, run, est in extras:
        if time.time() + est > deadline:
            skipped.append(name)
            continue
        try:
            run()
        except Exception as exc:  # noqa: BLE001 — the line still prints; the run exits nonzero
            errored.append(name)
            result["extras"][f"{name}_error"] = f"{type(exc).__name__}: {exc}"[:200]
        if skipped:
            result["extras"]["skipped"] = skipped
        _telemetry_extras(result)
        print(json.dumps(result), flush=True)
    if skipped:
        result["extras"]["skipped"] = skipped
        print(json.dumps(result), flush=True)
    if errored:
        raise SystemExit(f"bench extras errored: {', '.join(errored)}")


if __name__ == "__main__":
    main()
