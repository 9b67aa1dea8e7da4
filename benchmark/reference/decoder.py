"""The plain reference: a Mistral/LLaMA-style decoder's forward pass and
mean cross-entropy in straightforward `jax.numpy`, float32, matmuls at
"highest" precision. No kernel, no cache, no batching: one sequence, the
whole `[s, s]` score matrix. It shares no code with `paddle_tpu`; it only
reads the built model's weights by parameter name.

Follows the published architecture (Mistral-7B-v0.1, Hugging Face
`modeling_mistral.py`): pre-norm RMSNorm, rotary embedding in the
half-rotation (`rotate_half`) layout with theta from the configuration,
grouped-query attention under a causal mask cut to the sliding window
(`0 <= q_pos - k_pos < window`), SwiGLU, a final RMSNorm and an untied
output head. Linear weights are stored `[in, out]`, as `paddle_tpu`
stores them, so a projection is `x @ W`.

Tolerances (used by the runners on the chip and by
`benchmark/tests/test_reference.py` on the CPU):

* LOGITS_TOL — largest |system - reference| logit over the largest
  |reference| logit. The system multiplies in bfloat16 with float32
  accumulation (bf16 autocast in training, bf16 weights and KV cache in
  serving): one bf16 rounding is 2**-9 = 0.2% relative, and a logit is a
  sum over a few thousand such products through 4-8 layers, so a few
  tenths of a percent to a percent of the logit range is expected. 3%
  passes that and fails a dropped mask, a wrong rotary layout, a wrong
  window or an fp8/int8 computation, all of which move logits by tens of
  percent of their range. The CPU test, where both sides run in float32,
  holds the system to 1e-4.
* LOSS_TOL — |system - reference| mean cross-entropy, absolute, in nats.
  With seeded random weights the loss sits near ln(vocab) whatever the
  model computes, so this bound is weak evidence beside the logits; it is
  here because the loss is what the trainer differentiates (fused
  linear + cross-entropy in chunks, which never builds the logits).
* TOKEN_LOGIT_TOL — serving: the reference's logit of the token the
  engine emitted may lie below the reference's largest logit by at most
  this share of the reference's logit range (max - min) at that
  position. Greedy decoding under bf16 picks another token than float32
  only where two logits are nearly tied; comparing logits, not tokens,
  survives such ties.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LOGITS_TOL = 3e-2
LOSS_TOL = 2e-2
TOKEN_LOGIT_TOL = 3e-2

_F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(t, pos, theta):
    """t: [s, heads, d]; pos: [s]. Half-rotation layout: the angle of
    dimension i and of i + d/2 is pos * theta**(-2i/d)."""
    d = t.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=_F32) / d))
    ang = pos.astype(_F32)[:, None] * inv[None, :]            # [s, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-t[..., d // 2:], t[..., :d // 2]], -1)
    return t * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "window",
                                             "eps", "theta"))
def _layer(x, ln1, wq, wk, wv, wo, ln2, wg, wu, wd, *, heads, kv_heads,
           window, eps, theta):
    f = lambda a: a.astype(_F32)
    s, h = x.shape
    d = wq.shape[1] // heads
    pos = jnp.arange(s)
    y = _rms_norm(x, f(ln1), eps)
    q = _rope((y @ f(wq)).reshape(s, heads, d), pos, theta)
    k = _rope((y @ f(wk)).reshape(s, kv_heads, d), pos, theta)
    v = (y @ f(wv)).reshape(s, kv_heads, d)
    rep = heads // kv_heads                    # query head i reads kv i//rep
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(_F32(d))
    dist = pos[:, None] - pos[None, :]
    seen = dist >= 0
    if window is not None:
        seen = seen & (dist < window)
    scores = jnp.where(seen[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(s, heads * d) @ f(wo)
    y = _rms_norm(x, f(ln2), eps)
    return x + (jax.nn.silu(y @ f(wg)) * (y @ f(wu))) @ f(wd)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, w, *, eps):
    return _rms_norm(x, norm.astype(_F32), eps) @ w.astype(_F32)


def logits(weights: dict, model: dict, ids) -> jax.Array:
    """[s, vocab] float32 logits of one sequence `ids` ([s] ints).
    `weights` maps the model's parameter names to arrays of any float
    type; each layer's are widened to float32 only while it runs."""
    kw = dict(heads=int(model["num_attention_heads"]),
              kv_heads=int(model["num_key_value_heads"]),
              window=(None if model.get("sliding_window") is None
                      else int(model["sliding_window"])),
              eps=float(model["rms_norm_eps"]),
              theta=float(model.get("rope_theta", 10000.0)))
    with jax.default_matmul_precision("highest"):
        x = weights["llama.embed_tokens.weight"][jnp.asarray(ids)] \
            .astype(_F32)
        for i in range(int(model["num_hidden_layers"])):
            p = f"llama.layers.{i}."
            x = _layer(
                x, weights[p + "input_layernorm.weight"],
                weights[p + "self_attn.q_proj.weight"],
                weights[p + "self_attn.k_proj.weight"],
                weights[p + "self_attn.v_proj.weight"],
                weights[p + "self_attn.o_proj.weight"],
                weights[p + "post_attention_layernorm.weight"],
                weights[p + "mlp.gate_proj.weight"],
                weights[p + "mlp.up_proj.weight"],
                weights[p + "mlp.down_proj.weight"], **kw)
        return _head(x, weights["llama.norm.weight"],
                     weights["lm_head.weight"], eps=kw["eps"])


def mean_cross_entropy(logit_rows, labels) -> jax.Array:
    logp = jax.nn.log_softmax(logit_rows.astype(_F32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[:, None], axis=-1)
    return -jnp.mean(picked)


def model_weights(net) -> dict:
    """The built model's parameters as device arrays, by name."""
    from paddle_tpu.core.dispatch import unwrap
    return {name: unwrap(p) for name, p in net.named_parameters()}


def max_normalised_error(got, want) -> float:
    got = jnp.asarray(got, _F32)
    want = jnp.asarray(want, _F32)
    return float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want))
                                                 + 1e-12))
