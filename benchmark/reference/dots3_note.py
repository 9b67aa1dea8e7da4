"""The plain reference for `dots3-note-prev`: its forward pass in
straightforward `jax.numpy`, float32, matmuls at "highest" precision. No
kernel, no cache, no batching: one sequence, every query against every
key it may attend. It shares no code with `paddle_tpu`; it only reads
the built model's weights by parameter name. It is computed in blocks
(queries `QUERY_BLOCK` at a time, experts one at a time, each weight
widened to float32 only while it runs) so that it fits the chip beside
the bfloat16 model and its cache.

The layer equations (Linear weights `[in, out]`, no biases; `u` the
RMS-normed layer input, eps `rms_norm_eps`; `t` a query, `s` a key):

    h = x + Attn(norm1(x));  y = h + FFN(norm2(h));  final RMSNorm, head

Full-attention layer (DeepSeek-V3's MLA, H heads):
    c_q = RMSNorm(u W_qa) * sqrt(hidden / q_lora_rank)
    q   = c_q W_qb                      -> H x (nope + rope)
    [c_kv ; k_r] = u W_kva;  c_kv = RMSNorm(c_kv) * sqrt(hidden / kv_lora_rank)
    k_rope = RoPE(k_r) (one for all heads);  [k_nope ; v] = c_kv W_kvb
    score[h,t,s] = (q_nope.k_nope + RoPE(q_rope).k_rope) / sqrt(nope + rope)
  indexer (DeepSeek-V3.2): qI = c_q W_Iq -> J x dI, kI = LayerNorm(u W_Ik),
    RoPE on the first `rope` dims of both, w = u W_Iw / sqrt(J dI),
    I[t,s] = sum_j w[t,j] relu(qI[t,j].kI[s]);  S_t = the `index_topk`
    positions s <= t of largest I[t,s] (all while t < index_topk);
    softmax over S_t only.
  gate: g = sigmoid(u W_g), head h's output times g[h] before W_o.
Sliding layer: the same with the `swa_*` sizes and `swa_rope_theta`,
  keys 0 <= t - s < sliding_window_size, no indexer.
Expert layer (layers >= first_k_dense_replace): p = sigmoid(h' W_r); the
  top-k of p + b (b the `noaux_tc` bias buffer); weights p_e / sum of the
  chosen p, times routed_scaling_factor; sum_e w_e E_e(h') + E_shared(h'),
  E(z) = (silu(z W_g) * (z W_u)) W_d. `expert_share = (index, of)`: only
  the experts [index * E/of, (index + 1) * E/of) exist here; picks that
  fall on the others add nothing, and that partial sum goes on.
Leading dense layers: the same E(z) at `intermediate_size`.

Conventions the published config names but does not spell out (the
configuration file lists them under `assumed`): the lora rescale above
(LongCat-Flash's `mla_scale_*_lora`), on both layer kinds; the gate reads
the normed layer input and acts before W_o; the window counts the query
itself (513 = 512 + 1); rotary pairs are (i, i + d/2) (`rotate_half`);
the indexer's LayerNorm has eps 1e-6 and a bias.

Departures from published deployments: the indexer runs in the model's
dtype (deployments quantise it to fp8 after a Hadamard rotation, which
changes no dot product in exact arithmetic); the vision and audio towers
and the MTP module are not part of the language model's `config` and are
left out.

`select=False` / `window=False` switch the indexer's selection and the
sliding window off (plain causal attention): not the model, but what the
comparison is run against a second time, to show that it can tell.
`round_to` rounds every matmul operand to that dtype first: the reading
"one precision lower than the configuration states" of PERF.md.

Tolerances, used by `runners/serve_latent.py` on the chip (bfloat16
weights and cache against this float32 pass) and by the CPU tests (both
sides float32, held to 1e-4):

* LOGITS_ROW_TOL — the MEDIAN over the compared logit rows of
  ||system row - reference row|| / ||reference row||. bf16 rounds each
  operand to 2**-9, but that is not what sets the size of the error
  here: two DISCRETE choices sit on the path, each made on bf16 inputs
  (the router's top-8 of 256, the indexer's top-2048), and at the
  weights' scale (every matrix N(0, 0.02)) the softmax is sharp enough
  for a few keys to carry a head. A pick or a key that flips where two
  scores are nearly tied moves a token by a sizeable share of a logit,
  and which flips happen changes with the seed and with any reordering
  of the program's arithmetic: the error has a heavy tail, which is why
  the statistic is a median over rows and not their pooled sum.
  Measured on the chip at the cell's sizes (PERF.md section 6, PR 29):
  over nine seeds the system's pooled error reads 4.6-7.4%, once 11.2%
  (rows 3.1-7.0% where they were recorded); this reference with every
  matmul operand rounded to bfloat16 reads the same (5.1-7.4%): the
  error is the precision's, not the program's. With operands rounded to
  float8_e4m3 every row reads 30-47% (pooled 33-37%; e5m2 58-60%);
  against the reference with selection and window switched off the
  system reads 66%. The limit, 20%, lies between the largest bf16
  reading (11.2) and the smallest float8 row (30) with a factor of 1.8
  and 1.5; a float8 computation fails it on every seed tried.
* TOKEN_LOGIT_TOL — the reference's logit of the token the engine
  emitted lies below its best by at most this share of the row's range.
  A sanity check, not a separator: greedy decoding under bf16 picks
  another token only where two logits are nearly tied (the system read
  0-1.2%, the bf16-rounded reference up to 2.6%, float8 2.6-11.4%), so
  the limit, 8%, only catches a token from the wrong end of the row.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LOGITS_ROW_TOL = 0.20
TOKEN_LOGIT_TOL = 0.08
QUERY_BLOCK = 256

_F32 = jnp.float32
FULL = "full_attention"


def _f(a, round_to):
    """Widen to float32, through `round_to` when a lower precision is
    being simulated."""
    if round_to is not None:
        a = a.astype(round_to)
    return a.astype(_F32)


def _mm(a, w, round_to):
    return _f(a, round_to) @ _f(w, round_to)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(_F32)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(_F32) \
        + b.astype(_F32)


def _rope(t, pos, theta, n=None):
    """Half-rotation rotary on the first n (default all) entries of the
    last axis; t [s, ..., d], pos [s]."""
    d = t.shape[-1] if n is None else n
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=_F32) / d))
    ang = pos.astype(_F32)[:, None] * inv[None]
    ang = ang.reshape((ang.shape[0],) + (1,) * (t.ndim - 2) + (d // 2,))
    a, b = t[..., :d // 2], t[..., d // 2:d]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang),
                            t[..., d:]], -1)


def _sizes(model, kind):
    pre = "" if kind == FULL else "swa_"
    g = lambda k: model[pre + k]
    return dict(
        heads=int(g("num_attention_heads")), q_rank=int(g("q_lora_rank")),
        kv_rank=int(g("kv_lora_rank")), nope=int(g("qk_nope_head_dim")),
        rope=int(g("qk_rope_head_dim")), dv=int(g("v_head_dim")),
        theta=float(model["rope_theta" if kind == FULL
                          else "swa_rope_theta"]))


@functools.partial(jax.jit, static_argnames=(
    "heads", "q_rank", "kv_rank", "nope", "rope", "dv", "theta", "eps",
    "hidden", "rescale", "window", "topk", "idx_heads", "idx_dim",
    "round_to"))
def _attention(x, w, *, heads, q_rank, kv_rank, nope, rope, dv, theta, eps,
               hidden, rescale, window, topk, idx_heads, idx_dim, round_to):
    """x [s, hidden] -> x + Attn(norm1(x)). `window` None: no band;
    `topk` None: no selection (w then needs no indexer weights)."""
    mm = functools.partial(_mm, round_to=round_to)
    s = x.shape[0]
    pos = jnp.arange(s)
    u = _rms_norm(x, w["input_layernorm.weight"], eps)
    c_q = _rms_norm(mm(u, w["self_attn.q_a_proj.weight"]),
                    w["self_attn.q_a_layernorm.weight"], eps)
    kv = mm(u, w["self_attn.kv_a_proj_with_mqa.weight"])
    c_kv = _rms_norm(kv[:, :kv_rank], w["self_attn.kv_a_layernorm.weight"],
                     eps)
    if rescale:
        c_q = c_q * math.sqrt(hidden / q_rank)
        c_kv = c_kv * math.sqrt(hidden / kv_rank)
    q = mm(c_q, w["self_attn.q_b_proj.weight"]).reshape(s, heads,
                                                        nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, theta)
    k_rope = _rope(kv[:, kv_rank:], pos, theta)                   # [s, rope]
    kvb = mm(c_kv, w["self_attn.kv_b_proj.weight"]).reshape(s, heads,
                                                            nope + dv)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    gate = jax.nn.sigmoid(mm(u, w["self_attn.gate_proj.weight"]))  # [s, H]
    if topk is not None:
        qI = _rope(mm(c_q, w["self_attn.idx_q_proj.weight"])
                   .reshape(s, idx_heads, idx_dim), pos, theta, n=rope)
        kI = _rope(_layer_norm(mm(u, w["self_attn.idx_k_proj.weight"]),
                               w["self_attn.idx_k_norm.weight"],
                               w["self_attn.idx_k_norm.bias"], 1e-6),
                   pos, theta, n=rope)
        wI = mm(u, w["self_attn.idx_w_proj.weight"]) \
            / math.sqrt(idx_heads * idx_dim)
    scale = 1.0 / math.sqrt(nope + rope)
    rq = lambda a: _f(a, round_to)

    def block(q0):
        t = q0 + jnp.arange(QUERY_BLOCK)
        t = jnp.minimum(t, s - 1)          # a ragged last block repeats
        keep = pos[None, :] <= t[:, None]                        # [qb, s]
        if window is not None:
            keep &= t[:, None] - pos[None, :] < window
        if topk is not None:
            I = jnp.einsum("tj,tjs->ts", wI[t], jax.nn.relu(jnp.einsum(
                "tjd,sd->tjs", rq(qI[t]), rq(kI))))
            I = jnp.where(keep, I, -jnp.inf)
            order = jnp.argsort(-I, axis=-1, stable=True)
            keep &= jnp.argsort(order, axis=-1) < topk
        sc = (jnp.einsum("thd,shd->hts", rq(q_nope[t]), rq(k_nope))
              + jnp.einsum("thd,sd->hts", rq(q_rope[t]), rq(k_rope))) * scale
        sc = jnp.where(keep[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hts,shd->thd", rq(p), rq(v))

    starts = jnp.arange(0, s, QUERY_BLOCK)
    out = jax.lax.map(block, starts).reshape(-1, heads, dv)[:s] \
        if s % QUERY_BLOCK == 0 else jnp.concatenate(
            [block(q0) for q0 in range(0, s, QUERY_BLOCK)])[:s]
    out = (out * gate[..., None]).reshape(s, heads * dv)
    return x + mm(out, w["self_attn.o_proj.weight"])


@functools.partial(jax.jit, static_argnames=("round_to",))
def _swiglu(z, wg, wu, wd, *, round_to):
    mm = functools.partial(_mm, round_to=round_to)
    return mm(jax.nn.silu(mm(z, wg)) * mm(z, wu), wd)


@functools.partial(jax.jit, static_argnames=("round_to",))
def _expert(z, wg, wu, wd, weight, e, *, round_to):
    """weight[:, None] * E_e(z) for held expert e of the stacked
    weights; only this expert is widened."""
    return weight[:, None] * _swiglu(z, wg[e], wu[e], wd[e],
                                     round_to=round_to)


@functools.partial(jax.jit, static_argnames=("top_k", "norm", "scaling",
                                             "round_to"))
def _route(z, wr, bias, *, top_k, norm, scaling, round_to):
    """[s, E] weights: p_e (normalised, scaled) on the picks, else 0."""
    p = jax.nn.sigmoid(_mm(z, wr, round_to))
    _, idx = jax.lax.top_k(p + bias.astype(_F32)[None], top_k)
    w = jnp.take_along_axis(p, idx, axis=1)
    if norm:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    rows = jnp.arange(z.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, idx].set(w * scaling)


def moe_ffn(z, w, model, expert_share=(0, 1), shared=True, round_to=None):
    """The expert layer's FFN(z), z [s, hidden] float32, for the share
    `expert_share` of the experts (`w` holds that share's stacked
    weights under "mlp.experts.*"). `shared=False` leaves the shared
    expert out (the share test counts it once)."""
    n_all = int(w["mlp.gate_weight"].shape[1])    # the router's width
    index, of = expert_share
    held = n_all // of
    bias = w.get("mlp.e_score_correction_bias", jnp.zeros((n_all,), _F32))
    weights = _route(z, w["mlp.gate_weight"], bias,
                     top_k=int(model["num_experts_per_tok"]),
                     norm=bool(model.get("norm_topk_prob", True)),
                     scaling=float(model.get("routed_scaling_factor", 1.0)),
                     round_to=round_to)
    out = jnp.zeros_like(z)
    for e in range(held):
        out = out + _expert(z, w["mlp.experts.w1"], w["mlp.experts.w3"],
                            w["mlp.experts.w2"],
                            weights[:, index * held + e], e,
                            round_to=round_to)
    if shared and "mlp.shared_experts.gate_proj.weight" in w:
        out = out + _swiglu(z, w["mlp.shared_experts.gate_proj.weight"],
                            w["mlp.shared_experts.up_proj.weight"],
                            w["mlp.shared_experts.down_proj.weight"],
                            round_to=round_to)
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms_norm(x, w, eps)


def logits(weights: dict, model: dict, ids, expert_share=(0, 1),
           select=True, window=True, round_to=None) -> jax.Array:
    """[s, vocab] float32 logits of one sequence `ids` ([s] ints).
    `weights` maps the model's parameter (and buffer) names to arrays of
    any float type; `model` is the configuration (published keys)."""
    eps = float(model["rms_norm_eps"])
    kinds = list(model["layer_types"])
    with jax.default_matmul_precision("highest"):
        x = weights["embed_tokens.weight"][jnp.asarray(ids)].astype(_F32)
        for i in range(int(model["num_hidden_layers"])):
            p = f"layers.{i}."
            w = {k[len(p):]: a for k, a in weights.items()
                 if k.startswith(p)}
            full = kinds[i] == FULL
            x = _attention(
                x, {k: a for k, a in w.items()
                    if k.startswith(("self_attn.", "input_layernorm."))},
                **_sizes(model, kinds[i]), eps=eps,
                hidden=int(model["hidden_size"]),
                rescale=bool(model.get("apply_mla_qkv_lora_rescale", True)),
                window=int(model["sliding_window_size"])
                if window and not full else None,
                topk=int(model["index_topk"]) if select and full else None,
                idx_heads=int(model["index_n_heads"]),
                idx_dim=int(model["index_head_dim"]), round_to=round_to)
            z = _norm(x, w["post_attention_layernorm.weight"], eps=eps)
            if i >= int(model["first_k_dense_replace"]):
                x = x + moe_ffn(z, w, model, expert_share,
                                round_to=round_to)
            else:
                x = x + _swiglu(z, w["mlp.gate_proj.weight"],
                                w["mlp.up_proj.weight"],
                                w["mlp.down_proj.weight"],
                                round_to=round_to)
        return _mm(_norm(x, weights["norm.weight"], eps=eps),
                   weights["lm_head.weight"], round_to)


def model_weights(net) -> dict:
    """The built model's parameters and buffers as device arrays, by
    name."""
    from paddle_tpu.core.dispatch import unwrap
    out = {name: unwrap(p) for name, p in net.named_parameters()}
    out.update({name: unwrap(b) for name, b in net.named_buffers()})
    return out


def errors(got, want) -> dict:
    """Logit errors of rows `got` against `want` ([n, vocab]): each
    row's relative error (`rows`), their median (what LOGITS_ROW_TOL
    limits), the pooled root-mean-square error and the largest single
    difference over the largest reference logit."""
    got, want = jnp.asarray(got, _F32), jnp.asarray(want, _F32)
    rows = jnp.linalg.norm(got - want, axis=-1) \
        / jnp.linalg.norm(want, axis=-1)
    return {"median_row": float(jnp.median(rows)),
            "rms": float(jnp.sqrt(jnp.sum((got - want) ** 2)
                                  / jnp.sum(want ** 2))),
            "max": float(jnp.max(jnp.abs(got - want))
                         / jnp.max(jnp.abs(want))),
            "rows": [float(r) for r in rows]}
