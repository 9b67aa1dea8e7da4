"""The plain reference for `DeepSeek-V2`: its forward pass in
straightforward `jax.numpy`, float32, matmuls at "highest" precision. No
kernel, no cache, no batching: one sequence, every query against every
key it may attend. It shares no code with `paddle_tpu`; it only reads
the built model's weights by parameter name. It is computed in blocks
(`HEAD_BLOCK` heads at a time, their queries `QUERY_BLOCK` at a time;
experts one at a time and the dense FFN `WIDTH_BLOCK` of its width at a
time; each weight widened to float32 only while it runs, the head only
on the rows asked for) so that what it holds grows with the sequence
by a few hundred KB a token, and a 12,320-token pass fits the chip
beside the bfloat16 model and its cache.

The layer equations, as DeepSeek-V2's published `modeling_deepseek.py`
has them (Linear weights `[in, out]`, no biases; `u` the RMS-normed
layer input, eps `rms_norm_eps`; H heads; `t` a query, `s` a key):

    h = x + Attn(norm1(x));  y = h + FFN(norm2(h));  final RMSNorm, head

Attention (MLA on every layer):
    c_q = RMSNorm(u W_qa);  q = c_q W_qb           -> H x (nope + rope)
    [c_kv ; k_r] = u W_kva;  c_kv = RMSNorm(c_kv)
    [k_nope ; v] = c_kv W_kvb                      -> H x (nope + dv)
    q_r, k_r: each rope vector's entries (2i, 2i + 1) are moved to (i,
      i + rope/2) (the published `view(d/2, 2).transpose`), then
      x cos + rotate_half(x) sin with cos, sin of pos x inv_freq
      (k_r one for all heads)
    score[h,t,s] = (q_nope.k_nope + q_r.k_r) * softmax_scale, s <= t
    out = softmax(score) v, heads side by side, W_o
  YaRN (`rope_scaling` type yarn): inv_freq = freq / factor * (1 - m) +
    freq * m, freq = base^(-2i/rope), m = 1 - clamp((i - low) / (high -
    low), 0, 1), low / high the floor / ceil of rope ln(L / (beta 2 pi))
    / (2 ln base) for beta_fast / beta_slow, L the original context;
    cos and sin times mscale(factor, mscale) / mscale(factor,
    mscale_all_dim); softmax_scale = (nope + rope)^-0.5 mscale(factor,
    mscale_all_dim)^2, mscale(s, m) = 0.1 m ln(s) + 1.
Expert layer (layers >= first_k_dense_replace):
    p = softmax(h' W_r) over the E experts, float32
    group_limited_greedy: a group of E / n_group consecutive experts
      scores its max p; the topk_group best groups stay (the others' p
      are zeroed); the top num_experts_per_tok of p are chosen
    weights: the chosen p, divided by their sum if norm_topk_prob, else
      times routed_scaling_factor
    sum_e w_e E_e(h') + E_shared(h'), E(z) = (silu(z W_g) * (z W_u)) W_d;
      the shared expert one SwiGLU of n_shared_experts x its width
  `expert_share = (index, of)`: only the experts [index * E/of, (index +
    1) * E/of) exist here; picks that fall on the others add nothing,
    and that partial sum goes on.
Leading dense layers: the same E(z) at `intermediate_size`.

Departures from the published description: `seq_aux`, the sequence-wise
auxiliary balance loss, is a training loss and is left out (serving
computes no loss).

Switches: not the model, but what the comparison is run against a second
time, to show that it can tell. `group_limit=False` routes by the plain
top-k of the softmax over all E experts; `yarn=False` uses plain RoPE at
`rope_theta` and the scale `(nope + rope)^-0.5`. `round_to` rounds every
matmul operand to that dtype first: the reading "one precision lower
than the configuration states" of PERF.md.

Tolerances, used by `runners/serve_deepseek_v2.py` on the chip (bfloat16
weights and cache against this float32 pass) and by the CPU tests (both
sides float32, held to 1e-4):

* LOGITS_ROW_TOL — the MEDIAN over the compared logit rows of
  ||system row - reference row|| / ||reference row||. bf16 rounds each
  operand to 2**-9, but one DISCRETE choice sits on the path, made on
  bf16 inputs: the router's group and expert picks (3 of 8 groups, then
  6 of 160 experts, the weights times 16), and a pick that flips where
  two scores nearly tie moves that token's row by a third of its size:
  the error has a heavy tail, hence a median over rows. Read on the chip
  at the cell's sizes (PERF.md section 6; the mix's longest prompt,
  12,288 tokens, and 32 new ones, in the last of 64 slots, six seeds):
  the system 1.58-1.64%, the reference with every matmul operand
  rounded to bfloat16 0.97-1.03% against itself; float8_e4m3 118-120%;
  against the reference with the group limit off 13.8-21.1%, with YaRN
  off 96.8-99.0%. The limit, 5%, lies 3x over the system's reading and
  2.8x under the smallest must-fail one. A row whose token routes the
  same with and without the group limit in every layer (no pick in the
  held group either way) reads as the system does, about one row in
  five: with 8 rows the median would fall under 5% about one seed in
  twenty, so the runner compares 32.
* TOKEN_LOGIT_TOL — the reference's logit of the token the engine
  emitted lies below its best by at most this share of the row's range,
  in every compared row but one (`token_shortfall`: the SECOND largest
  over the rows): a sanity check, not a separator. Greedy decoding under
  bf16 picks another token where two logits nearly tie, or in a row
  whose token a routing flip moved (above): such a flip took the
  bfloat16-rounded reference's own pick 11.6% of a row's range under
  the best in one row of 32 in one run of six (its other rows and runs
  read 0-5.5%, the system 0-2.2%), so a limit on the largest row would
  fail the configuration's own precision about one run in twenty; one
  row is left to a flip, as the row check's median leaves a few. float8
  and YaRN off read 46-64% in their largest row. The limit, 8%, as the
  other latent cell's.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .dots3_note import (_F32, _f, _mm, _norm, _rms_norm,  # noqa: F401
                         errors, model_weights)

LOGITS_ROW_TOL = 0.05
TOKEN_LOGIT_TOL = 0.08
QUERY_BLOCK = 128
HEAD_BLOCK = 16
WIDTH_BLOCK = 1536


def token_shortfall(short) -> float:
    """What TOKEN_LOGIT_TOL limits: the second largest of the rows'
    shortfalls (at least two rows)."""
    return float(np.sort(np.asarray(short))[-2])


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def rotary(model: dict, yarn=True):
    """(inv_freq [rope / 2] float32, the factor on cos and sin, the
    softmax scale) as the published code computes them."""
    rope = int(model["qk_rope_head_dim"])
    base = float(model["rope_theta"])
    scale = (int(model["qk_nope_head_dim"]) + rope) ** -0.5
    freq = 1.0 / base ** (np.arange(0, rope, 2, dtype=np.float64) / rope)
    rs = model.get("rope_scaling") if yarn else None
    if not rs:
        return freq.astype(np.float32), 1.0, scale
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def dim_of(rotations):
        return rope * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(rs["beta_slow"]))), rope - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rope // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = freq / factor * (1 - mask) + freq * mask
    m, m_all = float(rs.get("mscale", 1)), float(rs.get("mscale_all_dim", 0))
    cs = _mscale(factor, m) / _mscale(factor, m_all)
    if m_all:
        scale = scale * _mscale(factor, m_all) ** 2
    return inv.astype(np.float32), cs, scale


def apply_rope(t, pos, inv, cs):
    """The published rotary on the last axis of t [s, ..., d], pos [s]:
    interleaved pairs moved to halves, then rotate-half."""
    d = t.shape[-1]
    t = jnp.swapaxes(t.reshape(t.shape[:-1] + (d // 2, 2)), -1, -2) \
        .reshape(t.shape)
    freqs = pos.astype(_F32)[:, None] * jnp.asarray(inv)[None]
    emb = jnp.concatenate([freqs, freqs], -1)
    emb = emb.reshape((emb.shape[0],) + (1,) * (t.ndim - 2) + (d,))
    cos, sin = jnp.cos(emb) * cs, jnp.sin(emb) * cs
    rot = jnp.concatenate([-t[..., d // 2:], t[..., :d // 2]], -1)
    return t * cos + rot * sin


@functools.partial(jax.jit, static_argnames=(
    "heads", "q_rank", "kv_rank", "nope", "rope", "dv", "eps", "inv", "cs",
    "scale", "round_to"))
def _attention(x, w, *, heads, q_rank, kv_rank, nope, rope, dv, eps, inv,
               cs, scale, round_to):
    """x [s, hidden] -> x + Attn(norm1(x)), `HEAD_BLOCK` heads at a time
    (their queries, keys, values and share of W_o), their queries
    `QUERY_BLOCK` at a time."""
    mm = functools.partial(_mm, round_to=round_to)
    rq = lambda a: _f(a, round_to)
    s = x.shape[0]
    pos = jnp.arange(s)
    inv = np.asarray(inv, np.float32)
    hb = math.gcd(heads, HEAD_BLOCK)
    u = _rms_norm(x, w["input_layernorm.weight"], eps)
    c_q = _rms_norm(mm(u, w["self_attn.q_a_proj.weight"]),
                    w["self_attn.q_a_layernorm.weight"], eps)
    kv = mm(u, w["self_attn.kv_a_proj_with_mqa.weight"])
    c_kv = _rms_norm(kv[:, :kv_rank], w["self_attn.kv_a_layernorm.weight"],
                     eps)
    k_rope = apply_rope(kv[:, kv_rank:], pos, inv, cs)          # [s, rope]

    def by_heads(name, width):
        """A [in, heads x width] weight as [heads / hb, in, hb x width]."""
        a = w[name]
        return a.reshape(a.shape[0], heads // hb, hb * width).swapaxes(0, 1)

    def heads_of(acc, ws):
        w_qb, w_kvb, w_o = ws
        q = mm(c_q, w_qb).reshape(s, hb, nope + rope)
        q_nope = q[..., :nope]
        q_rope = apply_rope(q[..., nope:], pos, inv, cs)
        kvb = mm(c_kv, w_kvb).reshape(s, hb, nope + dv)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]

        def block(q0):
            t = jnp.minimum(q0 + jnp.arange(QUERY_BLOCK), s - 1)
            sc = (jnp.einsum("thd,shd->hts", rq(q_nope[t]), rq(k_nope))
                  + jnp.einsum("thd,sd->hts", rq(q_rope[t]), rq(k_rope))) \
                * scale
            sc = jnp.where((pos[None, :] <= t[:, None])[None], sc, -jnp.inf)
            return jnp.einsum("hts,shd->thd",
                              rq(jax.nn.softmax(sc, axis=-1)), rq(v))

        starts = jnp.arange(0, s, QUERY_BLOCK)
        out = jax.lax.map(block, starts).reshape(-1, hb, dv)[:s]
        return acc + mm(out.reshape(s, hb * dv), w_o), None

    w_o = w["self_attn.o_proj.weight"]
    attn, _ = jax.lax.scan(heads_of, jnp.zeros_like(x), (
        by_heads("self_attn.q_b_proj.weight", nope + rope),
        by_heads("self_attn.kv_b_proj.weight", nope + dv),
        w_o.reshape(heads // hb, hb * dv, w_o.shape[1])))
    return x + attn


@functools.partial(jax.jit, static_argnames=("round_to",))
def _swiglu(z, wg, wu, wd, *, round_to):
    mm = functools.partial(_mm, round_to=round_to)
    return mm(jax.nn.silu(mm(z, wg)) * mm(z, wu), wd)


def _dense(z, wg, wu, wd, *, round_to):
    """The dense SwiGLU, `WIDTH_BLOCK` of its width at a time (each
    block's weights widened only while it runs)."""
    width = int(wg.shape[1])
    step = math.gcd(width, WIDTH_BLOCK)
    out = jnp.zeros_like(z)
    for j in range(0, width, step):
        out = out + _swiglu(z, wg[:, j:j + step], wu[:, j:j + step],
                            wd[j:j + step], round_to=round_to)
    return out


@functools.partial(jax.jit, static_argnames=("round_to",))
def _expert(z, wg, wu, wd, weight, e, *, round_to):
    """weight[:, None] * E_e(z) for held expert e of the stacked
    weights; only this expert is widened."""
    return weight[:, None] * _swiglu(z, wg[e], wu[e], wd[e],
                                     round_to=round_to)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "n_group", "topk_group", "norm", "scaling", "round_to"))
def route(z, wr, *, top_k, n_group, topk_group, norm, scaling, round_to):
    """[s, E] weights: the chosen experts' weights, else 0. `n_group`
    None: the plain top-k of all E."""
    p = jax.nn.softmax(_mm(z, wr, round_to), axis=-1)
    s, e = p.shape
    masked = p
    if n_group:
        best = p.reshape(s, n_group, e // n_group).max(-1)
        _, groups = jax.lax.top_k(best, topk_group)
        keep = jnp.zeros((s, n_group), bool).at[
            jnp.arange(s)[:, None], groups].set(True)
        masked = jnp.where(jnp.repeat(keep, e // n_group, axis=1), p, 0.0)
    w, idx = jax.lax.top_k(masked, top_k)
    w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20) if norm \
        else w * scaling
    return jnp.zeros_like(p).at[jnp.arange(s)[:, None], idx].set(w)


def moe_ffn(z, w, model, expert_share=(0, 1), shared=True, group_limit=True,
            round_to=None):
    """The expert layer's FFN(z), z [s, hidden] float32, for the share
    `expert_share` of the experts (`w` holds that share's stacked
    weights under "mlp.experts.*"). `shared=False` leaves the shared
    expert out (the share test counts it once)."""
    n_all = int(w["mlp.gate_weight"].shape[1])    # the router's width
    index, of = expert_share
    held = n_all // of
    weights = route(
        z, w["mlp.gate_weight"], top_k=int(model["num_experts_per_tok"]),
        n_group=int(model["n_group"]) if group_limit else None,
        topk_group=int(model["topk_group"]),
        norm=bool(model["norm_topk_prob"]),
        scaling=float(model["routed_scaling_factor"]), round_to=round_to)
    out = jnp.zeros_like(z)
    for e in range(held):
        out = out + _expert(z, w["mlp.experts.w1"], w["mlp.experts.w3"],
                            w["mlp.experts.w2"],
                            weights[:, index * held + e], e,
                            round_to=round_to)
    if shared:
        out = out + _swiglu(z, w["mlp.shared_experts.gate_proj.weight"],
                            w["mlp.shared_experts.up_proj.weight"],
                            w["mlp.shared_experts.down_proj.weight"],
                            round_to=round_to)
    return out


def logits(weights: dict, model: dict, ids, expert_share=(0, 1), *,
           rows_from=0, group_limit=True, yarn=True,
           round_to=None) -> jax.Array:
    """[s - rows_from, vocab] float32 logits of one sequence `ids` ([s]
    ints), for its positions from `rows_from` on. `weights` maps the
    model's parameter names to arrays of any float type; `model` is the
    configuration (published keys)."""
    eps = float(model["rms_norm_eps"])
    inv, cs, scale = rotary(model, yarn)
    with jax.default_matmul_precision("highest"):
        x = weights["embed_tokens.weight"][jnp.asarray(ids)].astype(_F32)
        for i in range(int(model["num_hidden_layers"])):
            p = f"layers.{i}."
            w = {k[len(p):]: a for k, a in weights.items()
                 if k.startswith(p)}
            x = _attention(
                x, {k: a for k, a in w.items()
                    if k.startswith(("self_attn.", "input_layernorm."))},
                heads=int(model["num_attention_heads"]),
                q_rank=int(model["q_lora_rank"]),
                kv_rank=int(model["kv_lora_rank"]),
                nope=int(model["qk_nope_head_dim"]),
                rope=int(model["qk_rope_head_dim"]),
                dv=int(model["v_head_dim"]), eps=eps,
                inv=tuple(float(f) for f in inv), cs=float(cs),
                scale=float(scale), round_to=round_to)
            z = _norm(x, w["post_attention_layernorm.weight"], eps=eps)
            if i >= int(model["first_k_dense_replace"]):
                x = x + moe_ffn(z, w, model, expert_share,
                                group_limit=group_limit, round_to=round_to)
            else:
                x = x + _dense(z, w["mlp.gate_proj.weight"],
                               w["mlp.up_proj.weight"],
                               w["mlp.down_proj.weight"],
                               round_to=round_to)
            # one layer's programs in flight at a time: a queued program
            # holds its temporaries from the moment it is dispatched
            x.block_until_ready()
        last = _norm(x[rows_from:], weights["norm.weight"], eps=eps)
        return _mm(last, weights["lm_head.weight"], round_to)
