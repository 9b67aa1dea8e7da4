"""LLaMA model family — the flagship hybrid-parallel model.

Reference: test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py
(the reference repo's in-tree LLaMA used for dp/mp/pp accuracy-alignment
tests; BASELINE.md config 4 targets LLaMA-7B TP+PP+ZeRO-3).

TPU-first design choices:
- bfloat16-friendly: RMSNorm computed in fp32, cast back.
- attention through kernels.flash_attention (Pallas on chip, XLA
  fallback) or kernels.ring_attention when a 'sep' (context-parallel)
  axis is active.
- tensor parallelism via the mpu layer library (Column/Row parallel,
  VocabParallelEmbedding) — GSPMD inserts the collectives.
- homogeneous LlamaDecoderLayer blocks so PipelineLayer/PipelineParallel
  can stack-and-pipeline them (pipelinable_run).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

from ... import ops
from ...core.dispatch import run_op, unwrap
from ...distributed import mesh as mesh_mod
from ...distributed.fleet.layers.mpu import (ColumnParallelLinear,
                                             RowParallelLinear,
                                             VocabParallelEmbedding)
from ...incubate.nn.functional import fused_rotary_position_embedding
from ...nn import functional as F
from ...nn.layer.common import Dropout, Embedding, Linear
from ...nn.layer.layers import Layer

import jax
import jax.numpy as jnp

from ...core.dispatch import wrap

NEG_INF_ATTN = -1e30


def _attend_cache(qa, kk, vv, mask, rep):
    """Shared decode-attention core: masked softmax of qa against the
    (kv-shaped) cache keys/values, GQA heads repeated. qa [b, s, h, d];
    kk/vv [b, L, h_kv, d]; mask [s, L] shared across the batch, or
    [b, s, L] when sequences sit at different positions (the serving
    engine's continuous batches).

    Decode attention is HBM-bandwidth bound, so a half-precision cache
    stays half-precision INTO the dots (MXU-native bf16 operands) with
    f32 accumulation via preferred_element_type — casting the cache to
    f32 first would make XLA materialize a full-width copy of the
    hottest tensor in the loop. Softmax stays f32 like the flash
    kernels."""
    if rep != 1:
        kk = jnp.repeat(kk, rep, axis=2)
        vv = jnp.repeat(vv, rep, axis=2)
    cdt = kk.dtype if kk.dtype in (jnp.bfloat16, jnp.float16) \
        else jnp.float32
    scale = 1.0 / jnp.sqrt(jnp.float32(qa.shape[-1]))
    logits = jnp.einsum("bshd,bLhd->bhsL", qa.astype(cdt),
                        kk.astype(cdt),
                        preferred_element_type=jnp.float32) * scale
    mexp = mask[None, None] if mask.ndim == 2 else mask[:, None]
    logits = jnp.where(mexp, logits, NEG_INF_ATTN)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhsL,bLhd->bshd", p.astype(cdt), vv.astype(cdt),
                      preferred_element_type=jnp.float32).astype(qa.dtype)


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    # Mistral-style sliding-window (local) attention: each query sees at
    # most this many most-recent keys (None = full causal). Served by
    # the Pallas flash kernel's banded k-loop, so attention compute
    # scales with window * seq instead of seq^2
    sliding_window: int | None = None
    sequence_parallel: bool = False
    # activation checkpointing per decoder layer (reference
    # recompute_interval semantics): required to fit 1B+ params at
    # seq>=2048 in one chip's HBM
    recompute: bool = False
    # "full" reruns the whole layer in backward (~2N extra FLOPs/token);
    # "selective" saves the attention-core output and the SwiGLU mid
    # activation (checkpoint_name tags) so backward only recomputes the
    # cheap projections/norms — the reference's recompute_granularity
    # knob, TPU-style via jax.checkpoint policies
    recompute_granularity: str = "full"
    # compute the LM loss as a chunked fused head-matmul + softmax-CE
    # (incubate fused_linear_cross_entropy) instead of materializing the
    # [tokens, vocab] logits; forward(ids, labels) then returns the loss
    fused_linear_ce: bool = False
    # row chunks for the fused CE scan: peak loss memory is one
    # [tokens/chunks, vocab] f32 tile
    fused_ce_chunks: int = 8
    dtype: str = "float32"

    @staticmethod
    def llama_7b():
        return LlamaConfig()

    @staticmethod
    def tiny(vocab=128, hidden=64, layers=2, heads=4):
        return LlamaConfig(
            vocab_size=vocab, hidden_size=hidden,
            intermediate_size=hidden * 4 // 2 * 2,
            num_hidden_layers=layers, num_attention_heads=heads,
            num_key_value_heads=heads, max_position_embeddings=256)


class LlamaRMSNorm(Layer):
    def __init__(self, hidden_size, eps=1e-6):
        super().__init__()
        from ...nn.initializer import Constant
        self.weight = self.create_parameter(
            [hidden_size], default_initializer=Constant(1.0))
        self.eps = eps

    def forward(self, x):
        return F.rms_norm(x, self.weight, epsilon=self.eps)


# When True, the parallel layer classes (VocabParallelEmbedding,
# Column/RowParallelLinear) are used even on an mp=1 mesh. They hold
# GLOBAL weights whose sharding degrades to Replicate at degree 1, so
# numerics and RNG draw order are identical to the plain classes — the
# knob exists so a single-device alignment run can build the exact same
# module tree as a TP run (reference counterpart: the dist/single
# acc-align tests in test/auto_parallel/hybrid_strategy).
_FORCE_TP = False


class force_tp_layers:
    """Context manager: build LLaMA modules with the parallel layer
    classes regardless of the current mesh's 'mp' degree."""

    def __enter__(self):
        global _FORCE_TP
        self._prev = _FORCE_TP
        _FORCE_TP = True
        return self

    def __exit__(self, *exc):
        global _FORCE_TP
        _FORCE_TP = self._prev
        return False


def _use_tp():
    return _FORCE_TP or mesh_mod.axis_degree("mp") > 1


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.hidden_size // c.num_attention_heads
        self.use_flash = c.use_flash_attention
        if c.sliding_window is not None and int(c.sliding_window) < 1:
            # validate ONCE at construction: every attention path (flash
            # band, ring band, cached-decode band) assumes window >= 1 —
            # a 0/negative window would silently mask every key
            raise ValueError(
                f"sliding_window must be >= 1, got {c.sliding_window}")
        self.window = None if c.sliding_window is None \
            else int(c.sliding_window)
        # checkpoint_name tags only matter inside a policy-bearing
        # jax.checkpoint; skip the per-op tape cost otherwise
        self._tag = (c.recompute
                     and c.recompute_granularity.startswith("selective"))
        hs = c.hidden_size
        kv = self.num_kv_heads * self.head_dim
        Lin = ColumnParallelLinear if _use_tp() else None
        if Lin is not None:
            self.q_proj = ColumnParallelLinear(hs, hs, has_bias=False,
                                               gather_output=False)
            self.k_proj = ColumnParallelLinear(hs, kv, has_bias=False,
                                               gather_output=False)
            self.v_proj = ColumnParallelLinear(hs, kv, has_bias=False,
                                               gather_output=False)
            self.o_proj = RowParallelLinear(hs, hs, has_bias=False,
                                            input_is_parallel=True)
        else:
            self.q_proj = Linear(hs, hs, bias_attr=False)
            self.k_proj = Linear(hs, kv, bias_attr=False)
            self.v_proj = Linear(hs, kv, bias_attr=False)
            self.o_proj = Linear(hs, hs, bias_attr=False)

    def forward(self, x, position_ids=None, kv_cache=None,
                cache_index=None, attn_mask_startend_row_indices=None):
        b, s, _ = x.shape
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads,
                                    self.head_dim])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads,
                                    self.head_dim])
        if kv_cache is not None and position_ids is None:
            # decode: rope positions continue from the cache write offset
            # (a scalar for one-shot generate; [b] per-slot offsets for
            # the serving engine's continuous batches)
            idx = jnp.asarray(cache_index, jnp.int32)
            position_ids = wrap(jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, :]
                + jnp.reshape(idx, (-1, 1)), (b, s)))
        q, k, _ = fused_rotary_position_embedding(
            q, k, None, position_ids=position_ids,
            use_neox_rotary_style=True)
        if kv_cache is not None:
            return self._cached_attention(q, k, v, kv_cache, cache_index)
        se = attn_mask_startend_row_indices
        if se is not None:
            # flashmask (reference flashmask_attention capability): a
            # column-sparse [b|1, 1|h_kv, s, C] int32 mask — the
            # document mask for packed long-context training — with
            # O(S) memory instead of a dense [b, h, S, S] bias. Only
            # the flash path understands the bands (Pallas kernel on
            # chip, the exact masked-XLA fallback elsewhere).
            if mesh_mod.axis_degree("sep") > 1:
                raise ValueError(
                    "attn_mask_startend_row_indices is not supported "
                    "under sequence/context parallelism (sep > 1): "
                    "ring attention rotates K/V blocks and cannot "
                    "apply per-column band masks yet")
            if self.window is not None:
                raise ValueError(
                    "attn_mask_startend_row_indices cannot be combined "
                    "with sliding_window — express the window as extra "
                    "mask bands instead")
            if not self.use_flash:
                raise ValueError(
                    "attn_mask_startend_row_indices requires "
                    "use_flash_attention=True (the flashmask bands "
                    "only exist on the flash path; its XLA fallback "
                    "is exact on non-TPU backends)")
            from ...kernels.flash_attention import flash_attention
            out = flash_attention(q, k, v, causal=True,
                                  startend_row_indices=se)
            out = out.reshape([b, s, self.num_heads * self.head_dim])
            if self._tag:
                from ...distributed.fleet.recompute import checkpoint_name
                out = checkpoint_name(out, "attn_core")
            return self.o_proj(out)
        if self._tag:
            from ...distributed.fleet.recompute import checkpoint_name
            q = checkpoint_name(q, "attn_q")
            k = checkpoint_name(k, "attn_k")
            v = checkpoint_name(v, "attn_v")
        # decide the attention path ONCE: flash serves GQA in-kernel
        # (kv head = q head // rep) and ring rotates only the grouped
        # k/v heads (rep-times less ICI traffic); only the XLA sdpa
        # path needs the kv heads materialized via repeat
        if mesh_mod.axis_degree("sep") > 1:
            path = "ring"
        elif self.use_flash or self.window is not None:
            path = "flash"
        else:
            path = "sdpa"
        if self.num_kv_heads != self.num_heads and path == "sdpa":
            rep = self.num_heads // self.num_kv_heads
            k = ops.manipulation.repeat_interleave(k, rep, axis=2)
            v = ops.manipulation.repeat_interleave(v, rep, axis=2)
        if path == "ring":
            from ...kernels.ring_attention import ring_flash_attention
            out = ring_flash_attention(q, k, v, causal=True,
                                       window=self.window)
        elif path == "flash":
            from ...kernels.flash_attention import flash_attention
            out = flash_attention(q, k, v, causal=True,
                                  window=self.window)
        else:
            # use_flash_attention=False is an explicit opt-out (exact
            # XLA numerics / Mosaic-miscompile escape hatch): pin sdpa
            # to its XLA core so the routing layer can't re-route it
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                                 use_flash=False)
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        if self._tag:
            from ...distributed.fleet.recompute import checkpoint_name
            out = checkpoint_name(out, "attn_core")
        return self.o_proj(out)

    def _cached_attention(self, q, k, v, kv_cache, cache_index):
        """KV-cache decode: write this call's k/v at ``cache_index``,
        attend q against the cache prefix. sliding_window adds its band
        to the cache mask. Cache tuple shapes (see docs/DECODE.md):

        - (k, v): DENSE full-length cache, any float dtype (the decode
          stack allocates the model's compute dtype by default);
        - (k, v, k_scale, v_scale): dense INT8 cache with per
          (token, kv_head) scales (quantization.kv_quantize_arrays);
        - (k, v, pos) with 1-D pos: Mistral-style ROLLING buffer of
          C = min(window, total) slots — writes land at pos % C,
          evicting the oldest, and pos[] tracks each slot's absolute
          position for the mask, so long-generation KV memory is
          O(window) not O(L); (k, v, pos, k_scale, v_scale) is its
          int8 form;
        - (k_pool, v_pool, block_tables) with 2-D block_tables: PAGED
          cache (serving block-table layout, kernels/
          paged_attention.py); (k_pool, v_pool, block_tables, k_scale,
          v_scale) is its int8 form (per-slot scale pools).

        One run_op so the cache update and masked attention stay a
        single traced unit."""
        if len(kv_cache) in (3, 5) and kv_cache[2].ndim == 2:
            return self._paged_cached_attention(q, k, v, kv_cache,
                                                cache_index)
        if len(kv_cache) in (3, 5):
            return self._rolling_cached_attention(q, k, v, kv_cache,
                                                  cache_index)
        window = self.window
        rep = self.num_heads // self.num_kv_heads
        quant = len(kv_cache) == 4
        from ... import monitor
        monitor.counter("kernels.decode.dense_xla").increase()

        def fn(qa, ka, va, ck, cv, *rest):
            if quant:
                ks, vs, idx = rest
            else:
                (idx,) = rest
                ks = vs = None
            s = qa.shape[1]
            L = ck.shape[1]
            idx = idx.astype(jnp.int32)
            zero = jnp.int32(0)
            if quant:
                from ...quantization.functional import kv_quantize_arrays
                qk, sk = kv_quantize_arrays(ka)
                qv, sv = kv_quantize_arrays(va)
                ck = jax.lax.dynamic_update_slice(
                    ck, qk, (zero, idx, zero, zero))
                cv = jax.lax.dynamic_update_slice(
                    cv, qv, (zero, idx, zero, zero))
                ks = jax.lax.dynamic_update_slice(ks, sk,
                                                  (zero, idx, zero))
                vs = jax.lax.dynamic_update_slice(vs, sv,
                                                  (zero, idx, zero))
                kk = ck.astype(jnp.float32) * ks[..., None]
                vv = cv.astype(jnp.float32) * vs[..., None]
            else:
                ck = jax.lax.dynamic_update_slice(
                    ck, ka.astype(ck.dtype), (zero, idx, zero, zero))
                cv = jax.lax.dynamic_update_slice(
                    cv, va.astype(cv.dtype), (zero, idx, zero, zero))
                kk, vv = ck, cv
            # query local position i sits at absolute idx + i; it sees
            # cache slots <= that position (within the window band)
            q_pos = idx + jnp.arange(s, dtype=jnp.int32)
            k_pos = jnp.arange(L, dtype=jnp.int32)
            mask = k_pos[None, :] <= q_pos[:, None]        # [s, L]
            if window is not None:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            out = _attend_cache(qa, kk, vv, mask, rep)
            if quant:
                return out, ck, cv, ks, vs
            return out, ck, cv

        idx_t = wrap(jnp.asarray(cache_index, jnp.int32))
        args = [q, k, v] + list(kv_cache) + [idx_t]
        res = run_op("cached_attention", fn, args)
        out, new_cache = res[0], tuple(res[1:])
        b, s = out.shape[0], out.shape[1]
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        return self.o_proj(out), new_cache

    def _paged_cached_attention(self, q, k, v, kv_cache, cache_index):
        """Paged-KV decode (reference block_multihead_attention,
        paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu):
        the cache is a global page pool addressed per sequence through a
        block table. Writes land in page pos // block_size, slot
        pos % block_size; attention gathers the sequence's pages with
        ONE XLA gather and applies the same causal(+window) band as the
        dense cache — numerics identical, memory allocated page-wise.
        ``cache_index`` may be per-sequence ([b]) — the layout the
        serving engine (inference/engine.py) drives, where every slot
        sits at a different position in its own block-table row.
        A 5-tuple cache carries int8 pools + per-slot scale pools; the
        Pallas kernel dequantizes in VMEM so int8 pages stream at a
        quarter of the f32 bytes."""
        from ... import monitor
        from ...core import place
        from ...kernels.paged_attention import (gather_pages,
                                                gather_page_scales,
                                                log_paged_ineligible,
                                                paged_decode_pallas,
                                                paged_pallas_eligible,
                                                paged_write_arrays,
                                                paged_write_quant_arrays)
        window = self.window
        rep = self.num_heads // self.num_kv_heads
        quant = len(kv_cache) == 5

        def fn(qa, ka, va, kc, vc, bt, *rest):
            if quant:
                ks, vs, idx = rest
            else:
                (idx,) = rest
                ks = vs = None
            b, s = qa.shape[0], qa.shape[1]
            _, hkv, bs_, d = kc.shape       # head-major page pool
            # cache_index may be a scalar (one-shot generate: every row
            # at the same offset) or [b] (serving engine: each slot at
            # its own position) — everything below is per-sequence
            idx = idx.astype(jnp.int32)
            pos0 = jnp.broadcast_to(jnp.atleast_1d(idx), (b,))
            if quant:
                kc, vc, ks, vs = paged_write_quant_arrays(
                    ka, va, kc, vc, ks, vs, bt, pos0)
            else:
                kc, vc = paged_write_arrays(ka, va, kc, vc, bt, pos0)

            def done(out):
                if quant:
                    return out, kc, vc, ks, vs
                return out, kc, vc

            # single-token decode steps take the Pallas kernel: pages
            # stream from the pool via scalar-prefetched block tables —
            # the XLA path below gathers (copies) every page the block
            # table names every step, which measured 2.8x slower at
            # b32. Neither path copies the pool: the write above
            # updates it in place (docs/DECODE.md "The KV write"). The
            # counters record, at trace time, which path the compiled
            # loop actually baked in (bench extras.telemetry reads the
            # deltas — docs/OBSERVABILITY.md).
            if s == 1 and place.accelerator_available():
                if paged_pallas_eligible(d, bs_, kc.dtype):
                    out = paged_decode_pallas(
                        qa[:, 0], kc, vc, bt, pos0 + 1,
                        window=window, k_scale=ks, v_scale=vs)
                    monitor.counter(
                        "kernels.decode.paged_pallas").increase()
                    return done(out[:, None])
                # name the violated constraint ONCE at trace time —
                # otherwise an ineligible pool geometry only ever
                # shows up as slow serving numbers
                log_paged_ineligible(d, bs_, kc.dtype)
            monitor.counter(
                "kernels.decode.paged_xla_gather_step" if s == 1
                else "kernels.decode.paged_xla_gather").increase()
            L = bt.shape[1] * bs_
            kk = gather_pages(kc, bt)
            vv = gather_pages(vc, bt)
            if quant:
                kk = kk.astype(jnp.float32) \
                    * gather_page_scales(ks, bt)[..., None]
                vv = vv.astype(jnp.float32) \
                    * gather_page_scales(vs, bt)[..., None]
            q_pos = pos0[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
            k_pos = jnp.arange(L, dtype=jnp.int32)
            mask = k_pos[None, None, :] <= q_pos[:, :, None]  # [b, s, L]
            if window is not None:
                mask &= (q_pos[:, :, None] - k_pos[None, None, :]) < window
            out = _attend_cache(qa, kk, vv, mask, rep)
            return done(out)

        idx_t = wrap(jnp.asarray(cache_index, jnp.int32))
        if quant:
            args = [q, k, v, kv_cache[0], kv_cache[1], kv_cache[2],
                    kv_cache[3], kv_cache[4], idx_t]
        else:
            args = [q, k, v, kv_cache[0], kv_cache[1], kv_cache[2],
                    idx_t]
        res = run_op("paged_cached_attention", fn, args)
        out = res[0]
        if quant:
            new_cache = (res[1], res[2], kv_cache[2], res[3], res[4])
        else:
            new_cache = (res[1], res[2], kv_cache[2])
        b, s = out.shape[0], out.shape[1]
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        return self.o_proj(out), new_cache

    def _rolling_cached_attention(self, q, k, v, kv_cache, cache_index):
        """Rolling-buffer decode (see _cached_attention): the C-slot
        cache holds the window's K/V; slot j's absolute position lives
        in pos[j] (-1 = never written), making the band mask a direct
        position compare with no modular arithmetic. A 5-tuple cache
        adds int8 slots + per (slot, kv_head) scales; the current chunk
        attends through its own quantize→dequantize round trip so
        rolling stays bit-consistent with the dense int8 layout."""
        from ... import monitor
        window = self.window
        rep = self.num_heads // self.num_kv_heads
        if window is None:
            raise ValueError(
                "rolling (k, v, pos) caches require sliding_window")
        quant = len(kv_cache) == 5
        monitor.counter("kernels.decode.rolling_xla").increase()

        def fn(qa, ka, va, ck, cv, pos, *rest):
            if quant:
                ks, vs, idx = rest
            else:
                (idx,) = rest
                ks = vs = None
            b, s, hq, d = qa.shape
            C = ck.shape[1]
            idx = idx.astype(jnp.int32)
            cur_pos = idx + jnp.arange(s, dtype=jnp.int32)
            if quant:
                from ...quantization.functional import (
                    kv_dequantize_arrays, kv_quantize_arrays)
                qk, sk = kv_quantize_arrays(ka)
                qv, sv = kv_quantize_arrays(va)
                ka_c = kv_dequantize_arrays(qk, sk)
                va_c = kv_dequantize_arrays(qv, sv)
                ckf = ck.astype(jnp.float32) * ks[..., None]
                cvf = cv.astype(jnp.float32) * vs[..., None]
            else:
                ka_c, va_c = ka.astype(ck.dtype), va.astype(cv.dtype)
                ckf, cvf = ck, cv
            # Attend against PRE-update cache + the current chunk, so a
            # long prefill's intermediate rows still see the (not yet
            # evicted) keys just left of the kept window. Stale cache
            # slots that this chunk will overwrite hold positions
            # <= idx - C <= q_pos - window, so the band mask hides them
            # without any explicit eviction logic; cache and chunk
            # positions never collide (old < idx <= new).
            kk = jnp.concatenate([ckf, ka_c.astype(ckf.dtype)], axis=1)
            vv = jnp.concatenate([cvf, va_c.astype(cvf.dtype)], axis=1)
            pos_cat = jnp.concatenate([pos, cur_pos])     # [C + s]
            mask = (pos_cat[None, :] >= 0) \
                & (pos_cat[None, :] <= cur_pos[:, None]) \
                & ((cur_pos[:, None] - pos_cat[None, :]) < window)
            out = _attend_cache(qa, kk, vv, mask, rep)
            # roll the chunk in: only its last min(s, C) tokens survive
            lo = s - C if s > C else 0
            if quant:
                ka_w, va_w = qk[:, lo:], qv[:, lo:]
            else:
                ka_w, va_w = ka[:, lo:], va[:, lo:]
            new_pos = idx + jnp.arange(lo, s, dtype=jnp.int32)
            slots = new_pos % C
            ck = ck.at[:, slots].set(ka_w.astype(ck.dtype))
            cv = cv.at[:, slots].set(va_w.astype(cv.dtype))
            pos = pos.at[slots].set(new_pos)
            if quant:
                ks = ks.at[:, slots].set(sk[:, lo:])
                vs = vs.at[:, slots].set(sv[:, lo:])
                return out, ck, cv, pos, ks, vs
            return out, ck, cv, pos

        idx_t = wrap(jnp.asarray(cache_index, jnp.int32))
        args = [q, k, v, kv_cache[0], kv_cache[1], kv_cache[2]]
        if quant:
            args += [kv_cache[3], kv_cache[4]]
        res = run_op("rolling_cached_attention", fn, args + [idx_t])
        out, new_cache = res[0], tuple(res[1:])
        b, s = out.shape[0], out.shape[1]
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        return self.o_proj(out), new_cache


class LlamaMLP(Layer):
    """SwiGLU MLP (gate/up column-parallel, down row-parallel)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        hs, im = config.hidden_size, config.intermediate_size
        self._tag = (config.recompute
                     and config.recompute_granularity.startswith(
                         "selective"))
        if _use_tp():
            self.gate_proj = ColumnParallelLinear(hs, im, has_bias=False,
                                                  gather_output=False)
            self.up_proj = ColumnParallelLinear(hs, im, has_bias=False,
                                                gather_output=False)
            self.down_proj = RowParallelLinear(im, hs, has_bias=False,
                                               input_is_parallel=True)
        else:
            self.gate_proj = Linear(hs, im, bias_attr=False)
            self.up_proj = Linear(hs, im, bias_attr=False)
            self.down_proj = Linear(im, hs, bias_attr=False)

    def forward(self, x):
        mid = F.silu(self.gate_proj(x)) * self.up_proj(x)
        if self._tag:
            from ...distributed.fleet.recompute import checkpoint_name
            mid = checkpoint_name(mid, "ffn_mid")
        return self.down_proj(mid)


class LlamaDecoderLayer(Layer):
    """One homogeneous block — the unit PipelineParallel stacks."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(config.hidden_size,
                                            config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = LlamaRMSNorm(config.hidden_size,
                                                     config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, kv_cache=None, cache_index=None,
                attn_mask_startend_row_indices=None):
        if kv_cache is not None:
            attn, new_cache = self.self_attn(
                self.input_layernorm(x), kv_cache=kv_cache,
                cache_index=cache_index)
            x = x + attn
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        x = x + self.self_attn(
            self.input_layernorm(x),
            attn_mask_startend_row_indices=attn_mask_startend_row_indices)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        if _use_tp():
            self.embed_tokens = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size)
        else:
            self.embed_tokens = Embedding(config.vocab_size,
                                          config.hidden_size)
        from ...nn.layer.container import LayerList
        self.layers = LayerList(
            [LlamaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, kv_caches=None, cache_index=None,
                attn_mask_startend_row_indices=None):
        se = attn_mask_startend_row_indices
        if se is not None and self.config.sequence_parallel and \
                mesh_mod.axis_degree("mp") > 1:
            raise ValueError(
                "attn_mask_startend_row_indices is not supported with "
                "sequence_parallel (the scattered activations would "
                "desync from the full-sequence mask bands)")
        if se is not None and kv_caches is not None:
            raise ValueError(
                "attn_mask_startend_row_indices is not supported with "
                "kv_caches (cached decode applies causal(+window) "
                "masks only)")
        x = self.embed_tokens(input_ids)
        if kv_caches is not None:
            new_caches = []
            for lyr, cache in zip(self.layers, kv_caches):
                x, nc = lyr(x, kv_cache=cache, cache_index=cache_index)
                new_caches.append(nc)
            return self.norm(x), new_caches
        if self.config.sequence_parallel and \
                mesh_mod.axis_degree("mp") > 1:
            from ...distributed.fleet.utils.sequence_parallel_utils import \
                scatter
            x = scatter(x)
        if self.config.recompute:
            from ...distributed.fleet.recompute import (recompute,
                                                        save_only_names)
            gran = self.config.recompute_granularity
            if gran not in ("full", "selective", "selective_qkv"):
                raise ValueError(
                    f"recompute_granularity={gran!r}: expected 'full', "
                    "'selective' or 'selective_qkv'")
            policy = None
            if gran == "selective":
                policy = save_only_names("attn_core", "ffn_mid")
            elif gran == "selective_qkv":
                # also keep q/k/v: backward then recomputes no matmuls,
                # only norms/rope/elementwise (+ the flash fwd kernel)
                policy = save_only_names("attn_core", "ffn_mid",
                                         "attn_q", "attn_k", "attn_v")
            for lyr in self.layers:
                if se is None:
                    x = recompute(lyr, x, policy=policy)
                else:
                    # positional bridge: recompute only accepts tensor
                    # args positionally, and the mask must be a
                    # checkpointed INPUT (its bands re-drive the flash
                    # kernel in the rematerialized forward); the layer
                    # rides in the closure, where _owning_layers finds
                    # its params
                    def _blk(a, m):
                        # true closure over lyr — _owning_layers reads
                        # __closure__ to bind the block's params
                        return lyr(a,
                                   attn_mask_startend_row_indices=m)
                    x = recompute(_blk, x, se, policy=policy)
        else:
            for lyr in self.layers:
                x = lyr(x, attn_mask_startend_row_indices=se)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        elif _use_tp():
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=True)
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    def _head(self, h):
        if self.lm_head is not None:
            return self.lm_head(h)
        w = self.llama.embed_tokens.weight

        def tied(hh, ww):
            return jnp.einsum("bsh,vh->bsv", hh, ww)
        return run_op("tied_lm_head", tied, [h, w])

    def forward(self, input_ids, labels=None,
                attn_mask_startend_row_indices=None, kv_caches=None,
                cache_index=None):
        if kv_caches is not None:
            if attn_mask_startend_row_indices is not None:
                raise ValueError(
                    "attn_mask_startend_row_indices is not supported "
                    "with kv_caches (cached decode applies causal(+"
                    "window) masks only — packed multi-document "
                    "contexts must be decoded as separate requests)")
            h, new_caches = self.llama(input_ids, kv_caches=kv_caches,
                                       cache_index=cache_index)
            return self._head(h), new_caches
        h = self.llama(input_ids, attn_mask_startend_row_indices=(
            attn_mask_startend_row_indices))
        if labels is not None and self.config.fused_linear_ce:
            from ...incubate.nn.functional import fused_linear_cross_entropy
            if self.lm_head is not None:
                w = self.lm_head.weight
            else:
                # tied head: Linear layout is [H, V]; embedding is [V, H]
                w = self.llama.embed_tokens.weight.t()
            return fused_linear_cross_entropy(
                h, w, labels, n_chunks=self.config.fused_ce_chunks)
        return self._head(h)

    def num_params(self):
        return sum(math.prod(p.shape) for _, p in self.named_parameters())

    def serving_spec(self):
        """Engine geometry probe (inference/engine.py
        ``serving_model_spec``): the decoder's KV-cache geometry as a
        plain dict, so the engine never reaches into model-specific
        config attribute names."""
        c = self.config
        return {
            "kind": "decoder",
            "num_layers": c.num_hidden_layers,
            "kv_heads": c.num_key_value_heads,
            "head_dim": c.hidden_size // c.num_attention_heads,
            "max_context": c.max_position_embeddings,
            "vocab_size": c.vocab_size,
        }


def _tied_head(embed_layer, x):
    """Tied lm head for the pipeline build: logits = h @ E^T, reading
    the (possibly vocab-sharded) embedding weight; the feature dim is
    gathered like ColumnParallelLinear(gather_output=True)."""
    out = x.matmul(embed_layer.weight.t())
    from ...distributed.fleet.layers.mpu.mp_ops import UNSET, mark_sharding
    entries = [UNSET] * (len(out.shape) - 1) + [None]
    return mark_sharding(out, *entries)


def build_llama_pipe(config: LlamaConfig, num_stages=None, loss_fn=None):
    """PipelineLayer view of LlamaForCausalLM for pipeline-parallel
    training: [embedding] + num_hidden_layers homogeneous
    LlamaDecoderLayer blocks + [final RMSNorm, lm head].

    The decoder blocks form the homogeneous run PipelineParallel
    stacks-and-pipelines; embedding and norm+head are the prefix/suffix
    (pp-sharded by _pp_shard_tree). Construction order matches
    LlamaForCausalLM so paddle.seed(k) yields identical initial weights
    — the basis for the dist/single acc-align dryrun.

    config.tie_word_embeddings maps to a SharedLayerDesc pair (the
    embedding weight is ONE Parameter used at both ends — its gradient
    is the summed cotangent, the compiled analog of the reference's
    shared-weight allreduce); config.recompute maps to the schedule's
    per-stage remat (PipelineLayer recompute_interval).

    Reference: the PipelineLayer LLaMA used by the reference's hybrid
    acc-align suite (test/auto_parallel/hybrid_strategy/
    semi_auto_parallel_llama_model.py with pp>1 via
    fleet/meta_parallel/parallel_layers/pp_layers.py segmentation).
    """
    from ...distributed.fleet.meta_parallel import (PipelineLayer,
                                                    SharedLayerDesc)
    from ...nn import CrossEntropyLoss
    c = config
    embed_cls = VocabParallelEmbedding if _use_tp() else Embedding
    # build the embedding FIRST either way: SharedLayerDesc is lazy, and
    # a deferred build would consume RNG draws after the blocks, breaking
    # same-seed parity with LlamaForCausalLM
    embed = embed_cls(c.vocab_size, c.hidden_size)
    if c.tie_word_embeddings:
        first = SharedLayerDesc("tok_embed", lambda: embed)
    else:
        first = embed
    blocks = [LlamaDecoderLayer(c) for _ in range(c.num_hidden_layers)]
    norm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
    if c.tie_word_embeddings:
        head = SharedLayerDesc("tok_embed", lambda: embed,
                               forward_func=_tied_head)
    elif _use_tp():
        head = ColumnParallelLinear(c.hidden_size, c.vocab_size,
                                    has_bias=False, gather_output=True)
    else:
        head = Linear(c.hidden_size, c.vocab_size, bias_attr=False)
    return PipelineLayer([first] + blocks + [norm, head],
                         num_stages=num_stages,
                         recompute_interval=1 if c.recompute else 0,
                         loss_fn=loss_fn or CrossEntropyLoss())


def llama_flops_per_token(config: LlamaConfig) -> float:
    """Approximate training FLOPs/token (6N rule + attention term)."""
    n = (config.vocab_size * config.hidden_size * 2
         + config.num_hidden_layers * (
             4 * config.hidden_size * config.hidden_size
             + 3 * config.hidden_size * config.intermediate_size))
    return 6.0 * n
