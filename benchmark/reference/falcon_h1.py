"""The plain reference for `Falcon-H1-34B-Instruct`: its forward pass in
straightforward `jax.numpy`, float32, matmuls at "highest" precision. No
kernel, no cache, no state carried between calls, no chunk, no batching:
one sequence; the state-space recurrence token by token in a `lax.scan`,
the convolution as written, the attention as one masked softmax over the
whole sequence (queries `QUERY_BLOCK` at a time, a block's mixers and
its FFN as separate programs, the FFN's and the head's columns a block
at a time (`_columns`), so that 2,568 tokens fit beside the bfloat16
model and its caches with half a GB and not three). It shares no code with `paddle_tpu`; it
only reads the built model's weights by parameter name.

The block equations, as `transformers`' `modeling_falcon_h1.py` has them
(Linear weights `[in, out]`, no biases but the convolution's; eps
`rms_norm_eps`; u a block's input):

    x0 = embed[ids] * embedding_multiplier
    h  = RMSNorm_in(u)
    attention, on h' = h * attention_in_multiplier:
      q = h' W_q;  k = (h' W_k) * key_multiplier;  v = h' W_v
      rotate-half RoPE(q, k) at rope_theta over all head_dim dims
      attn = softmax(q k^T / sqrt(head_dim), causal) v W_o
    mixer, on h'' = h * ssm_in_multiplier:
      [z | xBC | dt] = (h'' W_in) * mup_vector    ssm_multipliers[0..4]
                                                  over z, x, B, C, dt
      xBC_t[c] = silu(sum_j w[j, c] xBC_{t-3+j}[c] + b[c])   zeros before 0
      [x | B | C] = split(xBC, d_ssm, G N, G N)
      dt = softplus(dt + dt_bias);  A = -exp(A_log)          one a head
      S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t[g(h)]
      y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]                S float32 [P, N]
      ssm = GroupRMSNorm(y * silu(z)) * w_norm  W_out        G slices of d_ssm
    u1 = u + ssm * ssm_out_multiplier + attn * attention_out_multiplier
    m  = RMSNorm_ff(u1)
    u2 = u1 + ((m W_up) * silu((m W_gate) * mlp_multipliers[0])) W_down
              * mlp_multipliers[1]
    logits = (RMSNorm_final(u_last) W_head) * lm_head_multiplier

Departures from the published description: none in the mathematics. The
published code computes a prompt in chunks of `mamba_chunk_size` and
keeps its state in the model's dtype; this pass computes every token by
the recurrence and keeps `S` in float32 (the configuration file's
`assumed` says so for the system too). `dt` is not clamped
(`time_step_limit` (0, inf) in the published code).

Switches: not the model, but what the comparison is run against a second
time, to show that it can tell (a system with that part off or wrong
would read against the reference what the system reads against the
switched reference). `mixer=False` / `attention=False` leave a branch
out of the residual; `rope=False` leaves q and k unrotated; `mup=False`
sets `ssm_multipliers` to ones; `lose_state_at` zeroes `S` before each
of the positions it names (a chunk that starts from zeros and not from
the slot's rows); `lose_tail_at` hides from the convolution every input
before the last named position at or below the token (a program whose
convolution starts from zeros and not from the slot's tail: name the
chunk's first position and every decode tick's). `round_to` rounds every
matmul operand, and the keys and values the softmax reads, to that dtype
first (the recurrence itself stays float32, as the configuration
states): the reading "one precision lower than the configuration states"
of PERF.md.

Tolerances, used by `runners/serve_ssm.py` on the chip (bfloat16 weights,
cache and convolution tail, float32 state, against this float32 pass)
and by the CPU tests (both sides float32, held to 1e-4); the statistics
are `reference/dots3_note.py`'s `errors`. Readings on the chip at the
cell's sizes (a 2,560-token prompt in two chunks + 8 tokens, in the last
slot beside 95 decoding fillers; my chip runs, PR 40 after review, 15
runs on 9 seeds, each printed by the runner itself; PERF.md section 6):

* LOGITS_ROW_TOL: the MEDIAN over the compared logit rows of
  ||system row - reference row|| / ||reference row||. No discrete
  choice sits on the path (no router), and the gated norm and the muP
  draw keep every matmul at unit scale, so the system reads 1.10-1.23%
  (single rows 1.02-1.45%), twice this reference with every matmul
  operand rounded to bfloat16 (0.54-0.60%, rows 0.49-0.69%): the
  system's activations, cache and tail are bfloat16 too. With operands
  rounded to float8_e4m3 every row reads 10.6-15.3% (medians
  11.2-12.6%). Against the switched reference the system reads, as the
  median over rows: RoPE off 33.8-38.4%, the state lost between the two
  chunks 38.3-55.5%, the tail lost between programs 42.0-62.2%, the
  attention off 43.5-47.4%, `ssm_multipliers` ones 101-104%, the mixer
  off 120-122%. The limit, 3.5%, lies between the largest bfloat16-level
  reading (the system's 1.23; its largest single row 1.45) and the
  smallest float8 row (10.6) with a factor of 2.8 and 3.0; float8 and
  every switch fail it on every seed tried, and the runner fails the
  run if one ever passes.
* TOKEN_LOGIT_TOL: the reference's logit of the token the engine
  emitted lies below its best by at most this share of the row's range.
  Greedy decoding under bfloat16 picks another token only where two
  logits are nearly tied: with a row error of 1.2% of the row's norm and
  a range of ~8 standard deviations over 32,640 logits, a wrong pick
  costs at most ~0.6% of the range; the system read 0-0.24% (2 of 120
  tokens differed). The switches read 4.0-60% at their largest row (the
  tail lost 4.0-35.9%, RoPE off 5.8-14.8%, the state lost 10.5-20.3%),
  float8_e4m3 0-2.7%. The limit, 2%, lies between the system's bound
  (0.6) and the smallest switch (4.0) with a factor of 3.3 and 2.0;
  float8 reads on either side of it and fails the row limit on every
  seed, which is the limit that is held to tell a precision.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .dots3_note import (_F32, _f, _mm, _norm, _rope, errors,  # noqa: F401
                         model_weights)

LOGITS_ROW_TOL = 0.035
TOKEN_LOGIT_TOL = 0.02
QUERY_BLOCK = 256
WIDE_BLOCK = 4096       # most columns of the FFN or the head widened at once


def _sizes(model):
    return dict(heads=int(model["mamba_n_heads"]),
                p=int(model["mamba_d_head"]),
                groups=int(model["mamba_n_groups"]),
                n=int(model["mamba_d_state"]))


@functools.partial(jax.jit, static_argnames=(
    "heads", "p", "groups", "n", "eps", "in_mult", "round_to"))
def _mixer(h, w, mup, reset, tap_mask, *, heads, p, groups, n, eps, in_mult,
           round_to):
    """Mixer(h) for one sequence h [s, hidden] float32 (normed). mup: the
    multiplier of each in_proj column; reset [s] bool: zero S before
    this token; tap_mask [s, K]: which of a token's taps (oldest first)
    may see their input."""
    mm = functools.partial(_mm, round_to=round_to)
    s = h.shape[0]
    d_ssm, gn = heads * p, groups * n
    proj = mm(h * in_mult, w["in_proj.weight"]) * mup
    z, xbc, dt = jnp.split(proj, [d_ssm, 2 * d_ssm + 2 * gn], -1)
    taps = w["conv_weight"].astype(_F32)                 # [K, conv_dim]
    K = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), _F32), xbc], 0)
    conv = sum(padded[j:j + s] * taps[j] * tap_mask[:, j:j + 1]
               for j in range(K)) + w["conv_bias"].astype(_F32)
    x, B, C = jnp.split(jax.nn.silu(conv), [d_ssm, d_ssm + gn], -1)
    x = x.reshape(s, heads, p)
    B = jnp.repeat(B.reshape(s, groups, n), heads // groups, axis=1)
    C = jnp.repeat(C.reshape(s, groups, n), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(_F32))     # [s, H]
    decay = jnp.exp(dt * -jnp.exp(w["A_log"].astype(_F32)))

    def token(S, t):
        x_t, B_t, C_t, dt_t, decay_t, reset_t = t
        S = jnp.where(reset_t, 0.0, S)
        S = decay_t[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.sum(S * C_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), _F32),
                        (x, B, C, dt, decay, reset))
    y = y + w["D"].astype(_F32)[:, None] * x
    y = (y.reshape(s, d_ssm) * jax.nn.silu(z)).reshape(s, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return mm(y.reshape(s, d_ssm) * w["norm_weight"].astype(_F32),
              w["out_proj.weight"])


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "d", "theta", "key_mult", "block", "round_to"))
def _attention(h, w, angle_pos, *, heads, kv_heads, d, theta, key_mult,
               block, round_to):
    """Attn(h) for one sequence h [s, hidden] float32 (normed, times
    attention_in_multiplier); angle_pos [s]: the position q and k are
    rotated by (all zeros: no rotation)."""
    mm = functools.partial(_mm, round_to=round_to)
    s = h.shape[0]
    rep = heads // kv_heads
    pos = jnp.arange(s)
    q = mm(h, w["q_proj.weight"]).reshape(s, heads, d)
    k = (mm(h, w["k_proj.weight"]) * key_mult).reshape(s, kv_heads, d)
    v = _f(mm(h, w["v_proj.weight"]).reshape(s, kv_heads, d), round_to)
    q = _rope(q, angle_pos, theta)
    k = _f(_rope(k, angle_pos, theta), round_to)
    pad = -s % block
    qp = jnp.pad(_f(q.reshape(s, kv_heads, rep, d), round_to),
                 ((0, pad), (0, 0), (0, 0), (0, 0)))

    def rows(args):
        qb, t = args                                     # [block, G, r, d]
        sc = jnp.einsum("qgrd,Lgd->grqL", qb, k) / math.sqrt(d)
        sc = jnp.where(pos[None, None, None, :] <= t[None, None, :, None],
                       sc, -jnp.inf)
        prob = _f(jax.nn.softmax(sc, axis=-1), round_to)
        return jnp.einsum("grqL,Lgd->qgrd", prob, v)

    m = (s + pad) // block
    out = jax.lax.map(rows, (qp.reshape(m, block, kv_heads, rep, d),
                             jnp.arange(s + pad).reshape(m, block)))
    return mm(out.reshape(s + pad, heads * d)[:s], w["o_proj.weight"])


def _columns(width):
    """(how many, how wide) the blocks of columns a wide matrix is taken
    in: the widest that divides `width` and `WIDE_BLOCK`."""
    block = math.gcd(int(width), WIDE_BLOCK)
    return int(width) // block, block


@functools.partial(jax.jit, static_argnames=("eps", "mults", "round_to"))
def _ffn(x, w, *, eps, mults, round_to):
    """x + FFN(norm(x)) for one sequence x [s, hidden] float32. The
    intermediate width is a sum over columns, taken a block at a time
    inside ONE program (the same mathematics), so that no more than
    three float32 blocks of weights are alive beside the bfloat16
    model."""
    mm = functools.partial(_mm, round_to=round_to)
    m = _norm(x, w["pre_ff_layernorm.weight"], eps=eps)
    up, gate, down = (w[f"feed_forward.{n}_proj.weight"]
                      for n in ("up", "gate", "down"))
    n, block = _columns(up.shape[1])

    def add(i, out):
        u, g = (jax.lax.dynamic_slice_in_dim(t, i * block, block, 1)
                for t in (up, gate))
        d = jax.lax.dynamic_slice_in_dim(down, i * block, block, 0)
        return out + mm(mm(m, u) * jax.nn.silu(mm(m, g) * mults[0]), d)

    return x + jax.lax.fori_loop(0, n, add, jnp.zeros_like(x)) * mults[1]


@functools.partial(jax.jit, static_argnames=("round_to",))
def _head(x, w, *, round_to):
    """x W for the head's W [hidden, vocab], a block of columns at a
    time."""
    n, block = _columns(w.shape[1])

    def put(i, out):
        cols = _mm(x, jax.lax.dynamic_slice_in_dim(w, i * block, block, 1),
                   round_to)
        return jax.lax.dynamic_update_slice_in_dim(out, cols, i * block, 1)

    return jax.lax.fori_loop(
        0, n, put, jnp.zeros((x.shape[0], w.shape[1]), _F32))


def _under(w, prefix):
    return {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}


def logits(weights: dict, model: dict, ids, *, rows_from=0, mixer=True,
           attention=True, rope=True, mup=True, lose_state_at=(),
           lose_tail_at=(), round_to=None) -> jax.Array:
    """[s - rows_from, vocab] float32 logits of one sequence `ids` ([s]
    ints), for its positions from `rows_from` on (the head is applied to
    those rows only). `weights` maps the model's parameter names to
    arrays of any float type; `model` is the configuration (published
    keys)."""
    eps = float(model["rms_norm_eps"])
    s, K = len(ids), int(model["mamba_d_conv"])
    tok = np.arange(s)
    reset = np.isin(tok, np.asarray(lose_state_at, np.int64))
    # the position the convolution of each token may look back to
    first = np.zeros(s, np.int64)
    for at in sorted(int(a) for a in lose_tail_at):
        first[at:] = at
    tap_mask = (tok[:, None] - (K - 1 - np.arange(K))[None] >= first[:, None])
    sizes = _sizes(model)
    d_ssm, gn = sizes["heads"] * sizes["p"], sizes["groups"] * sizes["n"]
    mup_vector = np.repeat(
        np.asarray(model["ssm_multipliers"] if mup else [1.0] * 5,
                   np.float32), [d_ssm, d_ssm, gn, gn, sizes["heads"]])
    with jax.default_matmul_precision("highest"):
        x = weights["embed_tokens.weight"][jnp.asarray(ids)].astype(_F32) \
            * float(model["embedding_multiplier"])
        for i in range(int(model["num_hidden_layers"])):
            w = _under(weights, f"layers.{i}.")
            h = _norm(x, w["input_layernorm.weight"], eps=eps)
            if mixer:
                x = x + float(model["ssm_out_multiplier"]) * _mixer(
                    h, _under(w, "mamba."), jnp.asarray(mup_vector),
                    jnp.asarray(reset), jnp.asarray(tap_mask, _F32), **sizes,
                    eps=eps, in_mult=float(model["ssm_in_multiplier"]),
                    round_to=round_to)
            if attention:
                x = x + float(model["attention_out_multiplier"]) * _attention(
                    h * float(model["attention_in_multiplier"]),
                    _under(w, "self_attn."),
                    jnp.asarray(tok if rope else 0 * tok),
                    heads=int(model["num_attention_heads"]),
                    kv_heads=int(model["num_key_value_heads"]),
                    d=int(model["head_dim"]),
                    theta=float(model["rope_theta"]),
                    key_mult=float(model["key_multiplier"]),
                    block=QUERY_BLOCK, round_to=round_to)
            x = _ffn(x, {k: a for k, a in w.items() if k.startswith(
                ("pre_ff_layernorm.", "feed_forward."))}, eps=eps,
                mults=tuple(float(m) for m in model["mlp_multipliers"]),
                round_to=round_to)
            # a queued program holds its temporaries from the moment it
            # is queued: wait a block out before the next is dispatched
            x = jax.block_until_ready(x)
        last = _norm(x[rows_from:], weights["final_layernorm.weight"],
                     eps=eps)
        return _head(last, weights["lm_head.weight"], round_to=round_to) \
            * float(model["lm_head_multiplier"])
