"""MoE gates: naive / switch (top-1) / gshard (top-2).

Reference: python/paddle/incubate/distributed/models/moe/gate/
(naive_gate.py, switch_gate.py, gshard_gate.py) + routing-helper kernels
limit_by_capacity / prune_gate_by_capacity / random_routing
(ops.yaml:2901,3866,3954).

TPU-native: routing is expressed as DENSE one-hot dispatch/combine
tensors with a static per-expert capacity (the GShard formulation) —
static shapes are what XLA needs, the dispatch einsum maps onto the MXU,
and sharding the expert dim over 'ep' turns it into the all-to-all the
reference's global_scatter kernel performs. Capacity overflow drops
tokens exactly like the reference's limit_by_capacity.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _capacity(num_tokens: int, num_experts: int,
              capacity_factor: float, top_k: int) -> int:
    cap = int(math.ceil(top_k * num_tokens / num_experts
                        * capacity_factor))
    return max(cap, 4)


def _one_round(logits, probs, expert_idx, position_from, capacity):
    """Dispatch mask for one routing round (one of the top-k choices).

    position_from: [N, E] running per-expert occupancy BEFORE this round.
    Returns (dispatch [N, E, C], gate_prob [N], new occupancy totals [E]).
    """
    n, e = logits.shape
    onehot = jax.nn.one_hot(expert_idx, e, dtype=logits.dtype)  # [N, E]
    # position of each token in its chosen expert's buffer: running count
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot + position_from
    pos = jnp.sum(pos_in_expert * onehot, axis=1).astype(jnp.int32)  # [N]
    keep = pos < capacity
    disp = (onehot * keep[:, None])  # [N, E]
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity),
                            capacity + 1, dtype=logits.dtype)[:, :capacity]
    dispatch = disp[:, :, None] * pos_oh[:, None, :]  # [N, E, C]
    gate_prob = jnp.sum(probs * onehot, axis=1) * keep
    new_totals = position_from + jnp.sum(onehot, axis=0, keepdims=True)
    return dispatch, gate_prob, new_totals


def apply_router_jitter(logits, jitter: float, train: bool, key):
    """Additive uniform router noise (Switch-style). The ONE definition
    both the single-group and group-wise dispatch paths share."""
    if jitter and train and key is not None:
        logits = logits + jitter * jax.random.uniform(
            key, logits.shape, logits.dtype, -1.0, 1.0)
    return logits


def topk_gating(logits, top_k: int, capacity: int, train: bool = True,
                key=None, switch_jitter: float = 0.0):
    """Compute (dispatch [N,E,C], combine [N,E,C], aux_loss).

    aux_loss is the GShard/Switch load-balancing loss
    E * sum_e mean_tokens(router_prob_e) * mean_tokens(is_routed_e).
    """
    n, e = logits.shape
    logits = apply_router_jitter(logits, switch_jitter, train, key)
    probs = jax.nn.softmax(logits, axis=-1)

    dispatches = []
    gates = []
    masked = probs
    occupancy = jnp.zeros((1, e), logits.dtype)
    chosen = []
    for _ in range(top_k):
        idx = jnp.argmax(masked, axis=-1)
        chosen.append(idx)
        d, g, occupancy = _one_round(logits, probs, idx, occupancy,
                                     capacity)
        dispatches.append(d)
        gates.append(g)
        masked = masked * (1.0 - jax.nn.one_hot(idx, e, dtype=probs.dtype))

    if top_k == 1:
        # Switch semantics: scale by the raw router probability
        combine = dispatches[0] * gates[0][:, None, None]
    else:
        # GShard semantics: renormalise the k gate probs per token
        denom = jnp.maximum(sum(gates), 1e-9)
        combine = sum(d * (g / denom)[:, None, None]
                      for d, g in zip(dispatches, gates))
    dispatch = sum(dispatches)
    dispatch = jnp.minimum(dispatch, 1.0)

    # load-balance aux loss over the FIRST choice (Switch/GShard)
    me = jnp.mean(probs, axis=0)                       # [E]
    ce = jnp.mean(jax.nn.one_hot(chosen[0], e, dtype=probs.dtype), axis=0)
    aux = e * jnp.sum(me * ce)
    return dispatch.astype(logits.dtype), combine.astype(logits.dtype), aux


def topk_gating_sparse(logits, top_k: int, capacity: int,
                       train: bool = True, key=None,
                       switch_jitter: float = 0.0, token_mask=None):
    """Sparse routing result for the scatter/gather dispatch path:
    (expert_idx [k, N], pos [k, N], keep [k, N], combine_w [k, N], aux).

    Identical routing decisions (argmax rounds, running per-expert
    occupancy, capacity drop, Switch/GShard combine weights, aux loss)
    to ``topk_gating`` — only the OUTPUT representation differs: indices
    instead of the dense [N, E, C] one-hot tensors, for the
    sort/segment dispatch whose cost is O(N * k * H) instead of the
    dispatch einsum's O(N * E * C * H).

    ``token_mask`` ([N] bool, optional) marks DEAD tokens False — the
    serving engine's idle decode lanes (prefill bucket-padding rides
    unmasked today: the model cannot see chunk lengths, and no-drop
    decode capacity keeps pad routing harmless — wasted expert work
    on short chunks, never a changed live token).
    Dead tokens are dropped from every round up front: they occupy no
    expert capacity (a dead lane must never push a live token past the
    capacity cut), their ``keep`` is False (no dispatch, no expert
    compute, no DMA on the fused kernel — its per-expert live counts
    are built from ``keep``), and live tokens route exactly as if the
    dead ones were not in the batch (their cumsum positions skip the
    masked rows).
    """
    n, e = logits.shape
    logits = apply_router_jitter(logits, switch_jitter, train, key)
    probs = jax.nn.softmax(logits, axis=-1)

    masked = probs
    occupancy = jnp.zeros((1, e), logits.dtype)
    idxs, poss, keeps, gates = [], [], [], []
    first_choice = None
    for r in range(top_k):
        idx = jnp.argmax(masked, axis=-1)
        if r == 0:
            first_choice = idx
        onehot = jax.nn.one_hot(idx, e, dtype=logits.dtype)    # [N, E]
        if token_mask is not None:
            # dead tokens claim no occupancy and are never kept
            onehot = onehot * token_mask[:, None].astype(onehot.dtype)
        pos_in = jnp.cumsum(onehot, axis=0) - onehot + occupancy
        pos = jnp.sum(pos_in * onehot, axis=1).astype(jnp.int32)
        keep = pos < capacity
        if token_mask is not None:
            keep = jnp.logical_and(keep, token_mask)
        g = jnp.sum(probs * onehot, axis=1) * keep
        occupancy = occupancy + jnp.sum(onehot, axis=0, keepdims=True)
        idxs.append(idx.astype(jnp.int32))
        poss.append(pos)
        keeps.append(keep)
        gates.append(g)
        masked = masked * (1.0 - onehot)

    if top_k == 1:
        weights = gates
    else:
        denom = jnp.maximum(sum(gates), 1e-9)
        weights = [g / denom for g in gates]

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(first_choice, e, dtype=probs.dtype),
                  axis=0)
    aux = e * jnp.sum(me * ce)
    return (jnp.stack(idxs), jnp.stack(poss), jnp.stack(keeps),
            jnp.stack(weights).astype(logits.dtype), aux)


class BaseGate:
    def __init__(self, num_experts: int, top_k: int,
                 capacity_factor: float = 1.25, jitter: float = 0.0):
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.jitter = jitter

    def capacity(self, num_tokens: int) -> int:
        return _capacity(num_tokens, self.num_experts,
                         self.capacity_factor, self.top_k)

    def __call__(self, logits, train=True, key=None):
        cap = self.capacity(logits.shape[0])
        return topk_gating(logits, self.top_k, cap, train=train, key=key,
                           switch_jitter=self.jitter)


class NaiveGate(BaseGate):
    """top-k argmax routing, no jitter (reference gate/naive_gate.py)."""

    def __init__(self, num_experts, top_k=2, capacity_factor=1.25):
        super().__init__(num_experts, top_k, capacity_factor, 0.0)


class SwitchGate(BaseGate):
    """top-1 routing with optional jitter (reference gate/switch_gate.py)."""

    def __init__(self, num_experts, capacity_factor=1.25, jitter=0.01):
        super().__init__(num_experts, 1, capacity_factor, jitter)


class GShardGate(BaseGate):
    """top-2 routing (reference gate/gshard_gate.py)."""

    def __init__(self, num_experts, capacity_factor=2.0):
        super().__init__(num_experts, 2, capacity_factor, 0.0)


def sigmoid_topk_routing(logits, bias, top_k: int, norm_topk_prob=True,
                         scaling: float = 1.0):
    """Sigmoid-score routing with a selection bias (DeepSeek-V3's
    `noaux_tc`): p = sigmoid(logits) [N, E]; the top_k experts of
    largest p + bias are chosen, and weighted by p itself (the bias
    steers the choice only), over the sum of the chosen p when
    norm_topk_prob, times `scaling`. No capacity: every pick is kept.
    Returns (expert_idx [N, k] int32, weights [N, k] float32)."""
    p = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(p + bias.astype(jnp.float32)[None], top_k)
    w = jnp.take_along_axis(p, idx, axis=1)
    if norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-20)
    return idx.astype(jnp.int32), w * jnp.float32(scaling)


def group_limited_softmax_routing(logits, top_k: int, n_group: int,
                                  topk_group: int, norm_topk_prob=False,
                                  scaling: float = 1.0):
    """Softmax-score routing limited to the best groups (DeepSeek-V2's
    `group_limited_greedy`): p = softmax(logits) [N, E] in float32; the
    E experts are `n_group` groups of consecutive ids, a group scores
    its highest p, and only the `topk_group` best groups (ties to the
    lower group) stay; the top_k experts of p inside them are chosen
    (ties to the lower id) and weighted by p. As the published code:
    with norm_topk_prob the weights are divided by their sum, else
    multiplied by `scaling` (`routed_scaling_factor`). No capacity.
    Returns (expert_idx [N, k] int32, weights [N, k] float32)."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    n, e = p.shape
    best = jnp.max(p.reshape(n, n_group, e // n_group), axis=-1)
    _, groups = jax.lax.top_k(best, topk_group)               # [N, G']
    kept = jnp.any(groups[:, :, None] == jnp.arange(n_group)[None, None],
                   axis=1)                                    # [N, G]
    w, idx = jax.lax.top_k(
        jnp.where(jnp.repeat(kept, e // n_group, axis=1), p, 0.0), top_k)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    else:
        w = w * jnp.float32(scaling)
    return idx.astype(jnp.int32), w


class CapacityFreeGate(BaseGate):
    """A router that drops nothing: ``route(logits, bias)`` gives each
    token's picks and their weights, and `MoELayer` takes its sorted
    dispatch (picks sorted by expert into grouped matmuls) for it.
    `has_bias`: the layer keeps a selection-bias buffer and passes it;
    `n_group`: experts come in that many groups of consecutive ids, or
    None."""

    has_bias = False
    n_group = None

    def __init__(self, num_experts, top_k):
        super().__init__(num_experts, top_k, 1.0, 0.0)

    def capacity(self, num_tokens: int) -> int:
        return int(num_tokens)      # nothing is ever dropped

    def route(self, logits, bias):
        """(expert_idx [N, k] int32, weights [N, k] float32) of float32
        router logits [N, E]; `bias` [E] or None (see `has_bias`)."""
        raise NotImplementedError


class SigmoidTopKGate(CapacityFreeGate):
    """Sigmoid scores, top-k of score + selection bias, normalised
    weights; routes without capacity (see sigmoid_topk_routing)."""

    has_bias = True

    def __init__(self, num_experts, top_k=8, norm_topk_prob=True,
                 routed_scaling_factor=1.0):
        super().__init__(num_experts, top_k)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)

    def route(self, logits, bias):
        from ..... import monitor
        monitor.counter("kernels.moe.route.sigmoid_topk").increase()
        return sigmoid_topk_routing(logits, bias, self.top_k,
                                    self.norm_topk_prob,
                                    self.routed_scaling_factor)


class GroupLimitedGate(CapacityFreeGate):
    """Softmax scores, the top-k inside the `topk_group` best of
    `n_group` groups, weights unnormalised times the scaling factor
    unless norm_topk_prob; no selection bias, no capacity (see
    group_limited_softmax_routing)."""

    def __init__(self, num_experts, top_k=6, n_group=8, topk_group=3,
                 norm_topk_prob=False, routed_scaling_factor=1.0):
        if num_experts % n_group or not 1 <= topk_group <= n_group:
            raise ValueError(
                f"{num_experts} experts in {n_group} groups, the best "
                f"{topk_group} kept: the groups must be whole and "
                f"1 <= topk_group <= n_group")
        super().__init__(num_experts, top_k)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)

    def route(self, logits, bias):
        from ..... import monitor
        monitor.counter("kernels.moe.route.group_limited").increase()
        return group_limited_softmax_routing(
            logits, self.top_k, self.n_group, self.topk_group,
            self.norm_topk_prob, self.routed_scaling_factor)
