"""Autoregressive generation for the text model family.

The reference core framework leaves generation to its NLP suite — it
ships only the fused CUDA decode primitives (python/paddle/incubate/nn/
functional/masked_multihead_attention.py:27, the KV-cache decode-step
attention; ops.yaml N/A set here). TPU-native, generation ships with
the models and the decode-step attention is the kv-cache branch of
LlamaAttention: the WHOLE decode loop is one compiled program — ``lax.scan``
over decode steps inside a single ``jax.jit``, operating on a
statically padded token buffer. Each step runs the causal forward over
the padded buffer and reads the logits at the current position; causal
masking makes the not-yet-written tail positions unreachable, so no
attention mask bookkeeping is needed and shapes never change (no
retraces). This trades per-step FLOPs (full-prefix recompute, O(L²))
for compiler simplicity — the KV-cache decode path is the natural
follow-up optimization.

    out = generate(model, input_ids, max_new_tokens=32)          # greedy
    out = generate(model, input_ids, 32, temperature=0.8, top_k=40,
                   seed=0)                                        # sample
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import unwrap, wrap
from ..core import place, tape as tape_mod
from ..jit.functional import functional_call, get_buffers, get_frozen, \
    get_params


# decode-length bucket: max_new_tokens rounds up to a multiple of this
# before shaping the compiled loop, so nearby lengths share ONE
# executable (the tail past the requested length is generated and
# sliced off; the bucketed cache tail is causally unreachable)
CACHE_BUCKET = 64


def _bucketed(n: int) -> int:
    return -(-int(n) // CACHE_BUCKET) * CACHE_BUCKET


def _model_forward(model, st, tokens, caches=None, index=None):
    """One functional forward over the (possibly traced) state triple
    ``st = (params, buffers, frozen)`` — the step primitive that
    ``generate``, ``beam_search`` and the serving engine
    (inference/engine.py) all build their compiled loops on. ``caches``
    /``index`` ride through as the model's ``kv_caches``/``cache_index``
    kwargs; ``index`` may be a scalar or a per-sequence [b] array (the
    engine's continuous batches)."""
    p, buf, frz = st
    kwargs = {}
    if caches is not None:
        kwargs = {"kv_caches": caches, "cache_index": index}
    out, _ = functional_call(model, p, buf, (tokens,), kwargs,
                             frozen=frz, training=False)
    return out


def sample_token_arrays(logits, keys, temperature, top_k, top_p,
                        use_filters: bool = True):
    """Per-row token sampling with PER-ROW (traced) parameters — the
    serving engine's sampler, where every slot carries its own request's
    settings inside ONE fixed-shape executable.

    logits [b, V] float; keys [b, 2] uint32 (raw jax.random key data);
    temperature/top_p [b] float, top_k [b] int (0 = filter off).
    Returns (tokens [b] int32, new_keys [b, 2]).

    Row semantics mirror ``generate``'s pick_next exactly, so a request
    decoded in any engine slot is token-identical to a b=1 ``generate``
    with the same seed: temperature 0 = greedy and consumes NO rng (the
    key passes through unchanged, like pick_next's untouched key);
    top-k-only keeps threshold ties; a composed top-k+top-p uses the
    rank rule and renormalizes within the top-k survivors before the
    nucleus cut — the same two filter variants pick_next traces.

    ``use_filters=False`` is the STATIC no-filter fast path (the
    engine's temperature-only decode variant): the full-vocab argsort
    the traced filters force — work XLA cannot dead-code out when
    top_k/top_p ride as arrays — is skipped entirely. Tokens are
    bit-identical to the filtered path when every row's filters are
    off, because the filters reduce to identity and the same rng
    stream is consumed."""
    V = logits.shape[-1]

    def row(logit, key, temp, k, p):
        logit = logit.astype(jnp.float32)
        greedy = jnp.argmax(logit).astype(jnp.int32)
        key2, sub = jax.random.split(key)
        scaled = logit / jnp.maximum(temp, jnp.float32(1e-6))
        if use_filters:
            k_on = k > 0
            p_on = (p > 0.0) & (p < 1.0)
            order = jnp.argsort(-scaled)
            svals = scaled[order]
            # pick_next's top-k-only rule: threshold at the k-th value
            # (exact ties keep every tied token)
            kth = svals[jnp.clip(k - 1, 0, V - 1)]
            keep_thresh = jnp.where(k_on, scaled >= kth, True)
            # pick_next's composed rule: rank < k, nucleus over the
            # renormalized survivors (first survivor always kept)
            keep_sorted = jnp.where(
                k_on, jnp.arange(V, dtype=jnp.int32) < k, True)
            probs = jax.nn.softmax(jnp.where(keep_sorted, svals,
                                             -jnp.inf))
            csum = jnp.cumsum(probs)
            keep_sorted &= jnp.where(p_on, (csum - probs) < p, True)
            keep_rank = jnp.zeros((V,), bool).at[order].set(keep_sorted)
            keep = jnp.where(p_on, keep_rank, keep_thresh)
            filt = jnp.where(keep, scaled, -jnp.inf)
        else:
            filt = scaled
        sampled = jax.random.categorical(
            sub, filt[None, :], axis=-1)[0].astype(jnp.int32)
        do_sample = temp > 0
        tok = jnp.where(do_sample, sampled, greedy)
        new_key = jnp.where(do_sample, key2, key)
        return tok, new_key

    return jax.vmap(row)(logits, keys,
                         jnp.asarray(temperature, jnp.float32),
                         jnp.asarray(top_k, jnp.int32),
                         jnp.asarray(top_p, jnp.float32))


def verify_token_arrays(logits, drafts, keys, temperature, top_k, top_p,
                        use_filters: bool = True, greedy: bool = False):
    """Multi-position verify scoring — the speculative-decoding
    acceptance core (inference/speculative.py). The target model scored
    ``n = k + 1`` positions in ONE forward: position 0 continues the
    real context, position j continues the context extended by draft
    tokens ``drafts[:, :j]``. This walks the positions with the SAME
    per-row sampler the plain engine uses (``sample_token_arrays`` —
    pick_next-exact semantics, per-request rng chains) and accepts
    draft tokens only while they MATCH the token the target chain
    emits, so the emitted stream is bit-identical to the engine
    without a draft model: token exactness is the acceptance rule, and
    the output distribution is trivially the target's because every
    emitted token is drawn from the target chain.

    logits [b, n, V] float; drafts [b, n-1] int32 (the proposed
    tokens); keys [b, 2] uint32; temperature/top_p [b] f32, top_k [b]
    int. ``greedy=True`` is the all-greedy static variant (argmax, no
    rng machinery traced); otherwise ``use_filters`` picks the
    filtered/no-filter sampler exactly like the decode step variants.

    Returns (tokens [b, n] int32, accepted [b] int32, new_keys
    [b, 2]): row r's emission for the tick is tokens[r, :accepted[r]+1]
    (accepted counts MATCHED drafts, so one extra "free" target token
    always rides along); rows stop consuming rng at their first
    mismatch, which leaves new_keys exactly where a plain per-token
    decode of the same emission would leave them."""
    n = logits.shape[1]
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    # position j matches against drafts[:, j]; the last position has no
    # draft to match — a -1 sentinel (never a vocab id) ends the chain
    b = logits.shape[0]
    dr = jnp.concatenate(
        [jnp.asarray(drafts, jnp.int32),
         jnp.full((b, 1), -1, jnp.int32)], axis=1)       # [b, n]

    def step(carry, x):
        active, keys = carry
        lg, d = x                                         # [b, V], [b]
        if greedy:
            tok = jnp.argmax(lg.astype(jnp.float32),
                             axis=-1).astype(jnp.int32)
            keys2 = keys
        else:
            tok, keys2 = sample_token_arrays(lg, keys, temperature,
                                             top_k, top_p,
                                             use_filters=use_filters)
        # frozen rows (already mismatched) must not consume rng: their
        # keys stay put so the NEXT tick resumes the chain exactly
        keys = jnp.where(active[:, None], keys2, keys)
        matched = jnp.logical_and(active, tok == d)
        return (matched, keys), (tok, matched)

    (_, new_keys), (toks, matches) = jax.lax.scan(
        step, (jnp.ones((b,), bool), keys),
        (jnp.swapaxes(logits, 0, 1), jnp.swapaxes(dr, 0, 1)))
    tokens = jnp.swapaxes(toks, 0, 1)                     # [b, n]
    accepted = jnp.sum(jnp.swapaxes(matches, 0, 1),
                       axis=1).astype(jnp.int32)          # [b]
    return tokens, accepted, new_keys


def _resolve_cache_dtype(cache_dtype, params):
    """Resolve the cache_dtype knob to a concrete dtype. "auto" = the
    model's compute dtype: the params' floating dtype when it is
    half-precision, else bf16 on TPU backends (decode attention
    accumulates in f32 regardless, and the flash/paged kernels read
    bf16 natively) and f32 elsewhere (keeps CPU CI token-exact against
    the f32 reference paths)."""
    if cache_dtype in (None, "auto"):
        leaves = [l for l in jax.tree_util.tree_leaves(params)
                  if hasattr(l, "dtype")
                  and jnp.issubdtype(l.dtype, jnp.floating)]
        if leaves and leaves[0].dtype in (jnp.bfloat16, jnp.float16):
            return jnp.dtype(leaves[0].dtype)
        if place.accelerator_available():
            return jnp.dtype(jnp.bfloat16)
        return jnp.dtype(jnp.float32)
    dt = jnp.dtype(cache_dtype)
    allowed = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
               jnp.dtype(jnp.float16), jnp.dtype(jnp.int8))
    if dt not in allowed:
        raise ValueError(
            f"cache_dtype must be one of 'auto', 'float32', 'bfloat16',"
            f" 'float16', 'int8'; got {cache_dtype!r}")
    return dt


def generate(model, input_ids, max_new_tokens,
             temperature: float = 0.0, top_k: int = 0,
             top_p: float = 0.0,
             eos_token_id=None, seed: int = 0,
             use_cache: bool = True, cache_impl: str = "auto",
             page_size: int = 32, cache_dtype: str = "auto"):
    """Generate ``max_new_tokens`` continuations for ``input_ids``
    [B, S] with the causal-LM ``model``. temperature == 0 → greedy;
    otherwise softmax sampling at that temperature, optionally top-k
    truncated and/or nucleus-filtered (``0 < top_p < 1`` keeps the
    smallest set of tokens whose probability mass reaches top_p —
    top_p=1.0 applies no filtering; both filters compose, top-k first).
    Rows that emit ``eos_token_id`` keep their eos and stop changing.
    Returns a Tensor [B, S + max_new_tokens].

    use_cache=True runs the KV-cache decode: prefill writes the prompt
    into per-layer caches, then each scan step feeds ONE token and
    attends against the cache — O(L) per step instead of the padded
    full-recompute path's O(L²). Requires the model to support
    ``kv_caches``/``cache_index`` forward kwargs (the in-tree
    LlamaForCausalLM does, including sliding-window configs — the
    cached attention applies the window band to its mask);
    use_cache=False is the model-agnostic padded fallback.

    cache_impl selects the cache layout: "auto" = dense [B, total]
    buffers, or a rolling O(window) buffer when the model's
    sliding_window is shorter than the output; "dense"/"rolling" force
    those; "paged" uses the serving block-table layout
    (kernels/paged_attention.py) with ``page_size``-token pages —
    numerics identical, memory allocated page-wise like the reference's
    block_multihead_attention serving cache.

    cache_dtype selects KV-cache precision (docs/DECODE.md): "auto" =
    the model's compute dtype (bf16 on TPU — decode attention is
    HBM-bandwidth bound, and attention accumulates in f32 either way);
    "float32"/"bfloat16"/"float16" force a dtype; "int8" stores
    quantized K/V with per (token, kv_head) scales — a quarter of the
    f32 cache bytes, dequantized inside the attention step (in-VMEM for
    the Pallas paged-decode kernel).

    max_new_tokens is bucketed (multiples of 64) when shaping the
    compiled loop, so nearby lengths reuse one executable instead of
    retracing; the returned tensor is exactly
    [B, S + max_new_tokens].

    max_new_tokens and eos_token_id also accept PER-ROW arrays of
    length B (per-request generation config — the serving engine's
    contract, available on the one-shot path too): row r generates at
    most max_new_tokens[r] tokens and freezes on eos_token_id[r]; past
    its own budget a row emits its eos (or 0 when no eos is set). The
    returned tensor is [B, S + max(max_new_tokens)]; the budgets ride
    as traced arguments, so varying them reuses the same executable."""
    ids = np.asarray(unwrap(input_ids))
    b, s = ids.shape
    mx = np.asarray(unwrap(max_new_tokens))
    if mx.ndim > 1 or (mx.ndim == 1 and mx.shape[0] != b):
        raise ValueError(
            f"max_new_tokens must be a scalar or a [batch] vector; got "
            f"shape {mx.shape} for batch {b}")
    eos_np = None if eos_token_id is None \
        else np.asarray(unwrap(eos_token_id))
    if eos_np is not None:
        if eos_np.ndim == 0:
            # normalize 0-dim arrays to a python int: the scalar path
            # bakes eos into the hashed jit-cache sig
            eos_token_id = int(eos_np)
            eos_np = np.asarray(eos_token_id)
        elif eos_np.ndim > 1 or eos_np.shape[0] != b:
            raise ValueError(
                f"eos_token_id must be a scalar or a [batch] vector; "
                f"got shape {eos_np.shape} for batch {b}")
    # per-row mode: budgets/eos ride as TRACED [b] vectors so the same
    # executable serves any per-request config mix
    per_row = mx.ndim == 1 or (eos_np is not None and eos_np.ndim == 1)
    max_req = int(np.max(mx)) if mx.size else 0
    total = s + _bucketed(max_req)
    if max_req <= 0:
        return wrap(jnp.asarray(ids))
    if use_cache:
        import inspect
        try:
            sig = inspect.signature(model.forward)
            if "kv_caches" not in sig.parameters:
                use_cache = False  # model-agnostic padded fallback
        except (TypeError, ValueError):
            use_cache = False
    params = get_params(model)
    buffers = get_buffers(model)
    frozen = get_frozen(model)
    has_eos = eos_np is not None

    def fwd(st, tokens, caches=None, index=None):
        return _model_forward(model, st, tokens, caches, index)

    def pick_next(cur, done, key, dtype):
        cur = cur.astype(jnp.float32)
        if temperature and temperature > 0:
            key, sub = jax.random.split(key)
            scaled = cur / jnp.float32(temperature)
            k_eff = min(int(top_k), cur.shape[-1]) if top_k else 0
            p_on = bool(top_p) and 0.0 < float(top_p) < 1.0
            if k_eff > 0 and not p_on:
                # top-k only: lax.top_k + threshold is O(V·k) per row —
                # no reason to pay the full-vocab O(V log V) argsort
                # the composed top-k+top-p filter below needs. (Exact
                # threshold ties keep every tied token; the argsort
                # path would keep the first k by index — a measure-zero
                # difference for float logits.)
                kth = jax.lax.top_k(scaled, k_eff)[0][:, -1:]
                scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
            elif k_eff > 0 or p_on:
                # ONE descending argsort serves both filters (a second
                # full-vocab sort per decode step would double the
                # compiled loop's sort work)
                order = jnp.argsort(-scaled, axis=-1)
                svals = jnp.take_along_axis(scaled, order, axis=-1)
                keep_sorted = jnp.ones(svals.shape, bool)
                if k_eff > 0:
                    keep_sorted &= jnp.arange(
                        svals.shape[-1])[None, :] < k_eff
                if p_on:
                    # nucleus: the smallest descending-prob prefix whose
                    # mass reaches top_p (the first token always
                    # survives, so the filter never empties a row);
                    # renormalize within the top-k survivors
                    probs = jax.nn.softmax(
                        jnp.where(keep_sorted, svals, -jnp.inf), -1)
                    csum = jnp.cumsum(probs, axis=-1)
                    keep_sorted &= (csum - probs) < jnp.float32(top_p)
                keep = jnp.zeros_like(keep_sorted).at[
                    jnp.arange(order.shape[0])[:, None], order
                ].set(keep_sorted)
                scaled = jnp.where(keep, scaled, -jnp.inf)
            nxt = jax.random.categorical(sub, scaled, axis=-1)
        else:
            nxt = jnp.argmax(cur, axis=-1)
        nxt = nxt.astype(dtype)
        if has_eos and not per_row:
            pad = jnp.asarray(eos_token_id, dtype)
            nxt = jnp.where(done, pad, nxt)
            done = jnp.logical_or(done, nxt == pad)
        return nxt, done, key

    def pick_next_rows(cur, done, key, dtype, g, mxv, padv):
        """Per-row variant: sampling is pick_next's, then row r freezes
        past its own budget (g > mxv[r], g = 1-based index of the token
        being generated) or after its own eos; frozen rows emit padv[r]
        (the row's eos, or 0 with no eos set)."""
        nxt, _, key = pick_next(cur, done, key, dtype)
        done = jnp.logical_or(done, g > mxv)
        pad = padv.astype(dtype)
        nxt = jnp.where(done, pad, nxt)
        if has_eos:
            done = jnp.logical_or(done, nxt == pad)
        return nxt, done, key

    def decode_padded(st, tokens, key, *extra):
        def step(carry, i):
            tokens, done, key = carry
            logits = fwd(st, tokens)                     # [B, L, V]
            cur = jax.lax.dynamic_index_in_dim(
                jnp.swapaxes(logits, 0, 1), i - 1, 0, keepdims=False)
            if per_row:
                nxt, done, key = pick_next_rows(
                    cur, done, key, tokens.dtype, i - s + 1, *extra)
            else:
                nxt, done, key = pick_next(cur, done, key, tokens.dtype)
            tokens = jax.lax.dynamic_update_slice(
                tokens, nxt[:, None], (jnp.int32(0), i))
            return (tokens, done, key), None

        done0 = jnp.zeros((b,), bool)
        (tokens, _, _), _ = jax.lax.scan(
            step, (tokens, done0, key),
            jnp.arange(s, total, dtype=jnp.int32))
        return tokens

    def decode_cached(st, tokens, key, *extra):
        cfg = model.config
        hkv = cfg.num_key_value_heads
        hd = cfg.hidden_size // cfg.num_attention_heads
        win = getattr(cfg, "sliding_window", None)
        vdt = _resolve_cache_dtype(cache_dtype, st[0])
        quant = vdt == jnp.dtype(jnp.int8)
        impl = cache_impl
        if impl == "auto":
            impl = ("rolling" if win is not None and int(win) < total
                    else "dense")
        elif impl == "rolling" and win is None:
            raise ValueError(
                "cache_impl='rolling' needs the model's sliding_window "
                "set (the rolling buffer holds exactly `window` slots)")
        if impl == "rolling" and int(win) >= total:
            impl = "dense"   # window covers everything: dense == rolling
        if impl == "paged":
            # serving block-table layout: per-seq pages of `page_size`
            # tokens from a global pool. This one-shot pool is sized
            # EXACTLY for the bucketed total, so the tables are a
            # plain arange and exhaustion is impossible by
            # construction; dynamic page accounting (free lists,
            # watermarks, loud pool-exhaustion errors) lives in
            # inference/allocator.PageAllocator under the serving
            # engine, and an over-capacity write here fails loudly in
            # _page_slots's capacity check
            bs_ = int(page_size)
            nblocks = -(-total // bs_)
            bt = jnp.arange(b * nblocks, dtype=jnp.int32).reshape(
                b, nblocks)
            caches = [
                (jnp.zeros((b * nblocks, hkv, bs_, hd), vdt),
                 jnp.zeros((b * nblocks, hkv, bs_, hd), vdt),
                 bt)
                + ((jnp.zeros((b * nblocks, hkv, bs_), jnp.float32),
                    jnp.zeros((b * nblocks, hkv, bs_), jnp.float32))
                   if quant else ())
                for _ in range(cfg.num_hidden_layers)]
        elif impl == "rolling":
            # Mistral-style rolling buffer: C = window slots per layer
            # (plus a slot-position track), KV memory O(window) not
            # O(prompt + new_tokens)
            C = int(win)
            caches = [
                (jnp.zeros((b, C, hkv, hd), vdt),
                 jnp.zeros((b, C, hkv, hd), vdt),
                 jnp.full((C,), -1, jnp.int32))
                + ((jnp.zeros((b, C, hkv), jnp.float32),
                    jnp.zeros((b, C, hkv), jnp.float32))
                   if quant else ())
                for _ in range(cfg.num_hidden_layers)]
        else:
            caches = [
                (jnp.zeros((b, total, hkv, hd), vdt),
                 jnp.zeros((b, total, hkv, hd), vdt))
                + ((jnp.zeros((b, total, hkv), jnp.float32),
                    jnp.zeros((b, total, hkv), jnp.float32))
                   if quant else ())
                for _ in range(cfg.num_hidden_layers)]
        # prefill the prompt (writes cache slots [0, s))
        logits, caches = fwd(st, tokens[:, :s], caches, jnp.int32(0))
        done0 = jnp.zeros((b,), bool)
        if per_row:
            nxt, done, key = pick_next_rows(logits[:, -1], done0, key,
                                            tokens.dtype, 1, *extra)
        else:
            nxt, done, key = pick_next(logits[:, -1], done0, key,
                                       tokens.dtype)
        tokens = jax.lax.dynamic_update_slice(
            tokens, nxt[:, None], (jnp.int32(0), jnp.int32(s)))

        def step(carry, i):
            tokens, caches, done, key = carry
            cur_tok = jax.lax.dynamic_slice(tokens, (jnp.int32(0), i),
                                            (b, 1))
            logits, caches = fwd(st, cur_tok, caches, i)
            if per_row:
                nxt, done, key = pick_next_rows(
                    logits[:, -1], done, key, tokens.dtype,
                    i + 2 - s, *extra)
            else:
                nxt, done, key = pick_next(logits[:, -1], done, key,
                                           tokens.dtype)
            tokens = jax.lax.dynamic_update_slice(
                tokens, nxt[:, None], (jnp.int32(0), i + 1))
            return (tokens, caches, done, key), None

        (tokens, _, _, _), _ = jax.lax.scan(
            step, (tokens, caches, done, key),
            jnp.arange(s, total - 1, dtype=jnp.int32))
        return tokens

    padded = jnp.concatenate(
        [jnp.asarray(ids),
         jnp.zeros((b, total - s), ids.dtype)], axis=1)
    key = jax.random.PRNGKey(int(seed))
    decode = decode_cached if use_cache else decode_padded
    # jit cache keyed on the model + every trace-baked static: a fresh
    # jax.jit(closure) per call would retrace the whole decode loop
    # every generate() invocation. Config fields that shape the decode
    # trace (cache layout, head geometry) are part of the key — mutating
    # model.config between calls must NOT silently reuse a stale
    # executable (e.g. toggling sliding_window flips rolling vs dense).
    cfg = getattr(model, "config", None)
    cfg_key = tuple(
        (f, repr(getattr(cfg, f, None)))
        for f in ("sliding_window", "num_hidden_layers",
                  "num_key_value_heads", "num_attention_heads",
                  "hidden_size", "use_flash_attention")) \
        if cfg is not None else ()
    # `total` is the BUCKETED length: every max_new_tokens in the same
    # 64-bucket maps to the same sig and reuses one compiled loop
    # (tests assert steady_state_recompiles() == 0 across such calls).
    # In per-row mode the budgets/eos ride as TRACED vectors, so the
    # sig carries only the flags — any per-request mix shares one
    # executable too.
    eos_sig = ("per_row", has_eos) if per_row else eos_token_id
    sig = (use_cache, cache_impl, int(page_size), b, s, total,
           float(temperature), int(top_k),
           float(top_p), eos_sig, str(ids.dtype),
           str(_resolve_cache_dtype(cache_dtype, params)), cfg_key)
    per_model = _jit_cache.setdefault(model, {})
    fn = per_model.get(sig)
    if fn is None:
        fn = jax.jit(decode)
        per_model[sig] = fn
    extra_dev = ()
    if per_row:
        padv = np.broadcast_to(
            eos_np if has_eos else np.zeros((), ids.dtype), (b,))
        extra_dev = (jnp.asarray(np.broadcast_to(mx, (b,)),
                                 jnp.int32),
                     jnp.asarray(padv.astype(ids.dtype)))
    # params AND buffers AND frozen params ride as jit arguments —
    # closure-captured state would bake the FIRST call's weights into
    # the cached executable (stale after set_state_dict on a frozen
    # model)
    with tape_mod.no_grad_guard():
        out = fn((params, buffers, frozen), padded, key, *extra_dev)
    # slice the bucket tail off HOST-side: a device-side slice would
    # compile one (tiny) executable per distinct max_new_tokens, which
    # is exactly the per-length churn the bucketing removes — and every
    # generate caller fetches the tokens next anyway
    return wrap(jnp.asarray(np.asarray(out)[:, :s + max_req]))


def beam_search(model, input_ids, max_new_tokens: int, num_beams: int = 4,
                length_penalty: float = 1.0,
                eos_token_id: Optional[int] = None,
                cache_dtype: str = "auto"):
    """Compiled beam-search decode: the k beams fold into the batch dim
    inside ONE ``lax.scan`` (B = batch * num_beams rows), per-beam KV
    caches are reordered by a batched gather at every step, and the
    final beam is picked by length-normalized score
    ``score / len ** length_penalty`` (eos ends a beam; finished beams
    carry their score unchanged). Returns [batch, S + max_new_tokens]
    (the best beam per sequence).

    The reference core framework ships no beam search (its serving
    stack's domain); this is the text-family counterpart of
    ``generate`` for search decoding — deterministic, so token-exact
    against an eager reference loop (tests/test_utils_text.py).
    """
    ids = np.asarray(unwrap(input_ids))
    b, s = ids.shape
    k = int(num_beams)
    total = s + int(max_new_tokens)
    if max_new_tokens <= 0:
        return wrap(jnp.asarray(ids))
    if k == 1:
        return generate(model, input_ids, max_new_tokens,
                        eos_token_id=eos_token_id,
                        cache_dtype=cache_dtype)
    params = get_params(model)
    buffers = get_buffers(model)
    frozen = get_frozen(model)
    cfg = model.config
    V = cfg.vocab_size
    NEG = jnp.float32(-1e30)

    def fwd(st, tokens, caches, index):
        return _model_forward(model, st, tokens, caches, index)

    def decode(st, prompt):
        hkv = cfg.num_key_value_heads
        hd = cfg.hidden_size // cfg.num_attention_heads
        # beam caches follow the same cache_dtype ladder as generate
        # (dense layout only — beams reorder by gather, and tree_map
        # moves int8 values and their scales together)
        vdt = _resolve_cache_dtype(cache_dtype, st[0])
        quant = vdt == jnp.dtype(jnp.int8)
        caches = [
            (jnp.zeros((b, total, hkv, hd), vdt),
             jnp.zeros((b, total, hkv, hd), vdt))
            + ((jnp.zeros((b, total, hkv), jnp.float32),
                jnp.zeros((b, total, hkv), jnp.float32))
               if quant else ())
            for _ in range(cfg.num_hidden_layers)]
        logits, caches = fwd(st, prompt, caches, jnp.int32(0))
        lp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), -1)
        scores, tok0 = jax.lax.top_k(lp, k)          # [b, k]
        # fold beams into batch: row r = b_i * k + beam
        tokens = jnp.repeat(prompt, k, axis=0)       # [B, s]
        tokens = jnp.concatenate(
            [tokens, jnp.zeros((b * k, total - s), prompt.dtype)], 1)
        tokens = tokens.at[:, s].set(tok0.reshape(-1))
        caches = jax.tree_util.tree_map(
            lambda a: jnp.repeat(a, k, axis=0), caches)
        done0 = (tok0.reshape(-1) == eos_token_id) if eos_token_id \
            is not None else jnp.zeros((b * k,), bool)
        # length of generated part per beam (stops growing at eos)
        len0 = jnp.ones((b * k,), jnp.int32)

        def step(carry, i):
            tokens, caches, scores, done, lens = carry
            cur = jax.lax.dynamic_slice(tokens, (jnp.int32(0), i),
                                        (b * k, 1))
            logits, caches = fwd(st, cur, caches, i)
            lp = jax.nn.log_softmax(
                logits[:, -1].astype(jnp.float32), -1)   # [B, V]
            if eos_token_id is not None:
                # finished beams may only extend with eos at zero cost
                eos_only = jnp.full((V,), NEG).at[eos_token_id].set(0.0)
                lp = jnp.where(done[:, None], eos_only[None], lp)
            cand = scores.reshape(b, k, 1) + lp.reshape(b, k, V)
            scores, flat = jax.lax.top_k(cand.reshape(b, k * V), k)
            beam = flat // V                              # [b, k]
            tok = (flat % V).astype(tokens.dtype)
            rows = (jnp.arange(b, dtype=jnp.int32)[:, None] * k
                    + beam).reshape(-1)
            tokens = tokens[rows]
            caches = jax.tree_util.tree_map(lambda a: a[rows], caches)
            done = done[rows]
            lens = lens[rows]
            tokens = jax.lax.dynamic_update_slice(
                tokens, tok.reshape(-1, 1), (jnp.int32(0), i + 1))
            lens = jnp.where(done, lens, lens + 1)
            if eos_token_id is not None:
                done = jnp.logical_or(done,
                                      tok.reshape(-1) == eos_token_id)
            return (tokens, caches, scores.reshape(-1), done, lens), None

        (tokens, _, scores, done, lens), _ = jax.lax.scan(
            step, (tokens, caches, scores.reshape(-1), done0, len0),
            jnp.arange(s, total - 1, dtype=jnp.int32))
        norm = scores / jnp.power(lens.astype(jnp.float32),
                                  jnp.float32(length_penalty))
        best = jnp.argmax(norm.reshape(b, k), axis=-1)   # [b]
        rows = jnp.arange(b) * k + best
        return tokens[rows]

    sig = ("beam", b, s, total, k, float(length_penalty), eos_token_id,
           str(ids.dtype), str(_resolve_cache_dtype(cache_dtype, params)))
    per_model = _jit_cache.setdefault(model, {})
    fn = per_model.get(sig)
    if fn is None:
        fn = jax.jit(decode)
        per_model[sig] = fn
    with tape_mod.no_grad_guard():
        out = fn((params, buffers, frozen), jnp.asarray(ids))
    return wrap(out)


# model -> {static signature -> jitted decode}; weak keys so a dropped
# model releases its compiled executables
import weakref  # noqa: E402

_jit_cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
