"""paddle_tpu.inference.encoder — continuous-batching embedding service.

A genuinely different serving traffic shape from the decode Engine
(docs/SERVING.md "Embedding service"): encoder/embedding requests are
ONE forward each — no KV cache, no pages, no per-token latency chain —
so the whole problem is throughput-bound batch packing. This module
reuses the Engine's serving discipline (admission queue, per-request
deadlines on an injectable clock, tenant fairness, monitor counters,
``steady_state_recompiles() == 0``) over a bucketed continuous-batching
encoder:

* Requests queue per tenant; every ``step()`` forms ONE batch of up to
  ``max_batch`` requests, drawn round-robin across tenants (a flooding
  tenant slows, never starves, another) with the OLDEST waiting request
  always included — its length picks the sequence bucket, and only
  requests that fit that bucket join (shorter ones pad up; longer ones
  wait for their own turn at the head).
* Exactly ONE compiled executable per sequence bucket: the batch dim is
  pinned at ``max_batch`` (dead rows ride an all-zero attention mask
  and are discarded host-side), sequences pad to a ``bucket`` multiple,
  and the mean/CLS pooling choice rides as a TRACED per-row selector —
  any arrival mix of lengths, tenants and pooling modes bounces between
  the per-bucket executables with zero steady-state recompiles.
* The model is an ENCODER with reference semantics — BertModel's
  ``forward(input_ids, attention_mask=...) -> (sequence, pooled)``
  contract — so padding-masked attention rides the flash-SDPA boolean
  key-mask path (kernels.flash.sdpa.* counters name the path the
  executable baked in, docs/KERNELS.md "Encoder flash attention").
  Padding rows/positions cannot perturb real ones (key-masked
  attention + position-wise everything else), which makes a batched
  embedding equal to the same request encoded alone — the b=1
  exactness contract tests/test_serving_embed.py holds.

Pooling variants:

* ``"mean"`` — attention-mask-weighted mean of the final hidden states
  (the sentence-embedding default; padding positions contribute 0).
* ``"cls"``  — the model's pooled output (tanh pooler over [CLS], the
  reference BertPooler head).

``monitor`` surface (docs/OBSERVABILITY.md): counters
``serving.embed.requests`` / ``serving.embed.finished`` /
``serving.embed.batches`` / ``serving.embed.tokens`` /
``serving.embed.pad_tokens`` / ``serving.embed.timeouts`` /
``serving.embed.cancelled`` / ``serving.embed.steps``, gauges
``serving.embed.queue_depth`` / ``serving.embed.batch_fill`` /
``serving.embed.latency_ms``.
"""
from __future__ import annotations

import time
from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor
from ..core import tape as tape_mod
from ..jit.functional import (functional_call, get_buffers, get_frozen,
                              get_params)
from ..profiler.stats import CompileTracker
from .engine import _ceil_div, _normalize_prompt, serving_model_spec

POOLING_MODES = ("mean", "cls")


@dataclass
class EmbedParams:
    """Per-request embedding config (the encoder analog of
    SamplingParams — every field may differ per request inside one
    compiled batch)."""

    pooling: str = "mean"
    # reliability knobs, enforced at every tick start on the service's
    # injectable clock (same contract as the decode Engine's)
    deadline_ms: Optional[float] = None
    max_queue_steps: Optional[int] = None

    def validate(self):
        if self.pooling not in POOLING_MODES:
            raise ValueError(
                f"pooling must be one of {POOLING_MODES}, got "
                f"{self.pooling!r}")
        if self.deadline_ms is not None and float(self.deadline_ms) <= 0:
            raise ValueError(
                f"deadline_ms must be > 0, got {self.deadline_ms}")
        if self.max_queue_steps is not None \
                and int(self.max_queue_steps) < 1:
            raise ValueError(
                f"max_queue_steps must be >= 1, got "
                f"{self.max_queue_steps}")


@dataclass
class EmbedOutput:
    """One retired embedding request. ``embedding`` is the [hidden]
    float32 vector (None on failure); ``finish_reason`` is "done" or
    the failure name ("deadline" / "queue_timeout" / "cancelled")."""

    req_id: int
    embedding: Optional[np.ndarray]
    tokens: int                   # real (unpadded) sequence length
    pooling: str
    finish_reason: str
    latency_ms: float             # arrival -> embedding fetched
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None


@dataclass
class _EmbedRequest:
    req_id: int
    tokens: List[int]
    params: EmbedParams
    tenant: str
    arrival_t: float
    queued_step: int


class BatchEncoder:
    """Bucketed continuous-batching embedding service over an encoder.

        svc = BatchEncoder(bert_model, max_batch=8, bucket=32)
        rid = svc.add_request(ids, EmbedParams(pooling="mean"))
        for out in svc.step():
            ...                       # finished EmbedOutputs
        # or offline:
        outs = svc.run([ids_a, (ids_b, EmbedParams(pooling="cls"))])

    The model must follow the reference encoder contract —
    ``forward(input_ids, attention_mask=...)`` returning ``(sequence
    [b, s, h], pooled [b, h])`` (the in-tree BertModel does). Weights
    are snapshotted at construction, like the decode Engine.
    """

    def __init__(self, model, max_batch: int = 8, bucket: int = 32,
                 max_seq: Optional[int] = None, clock=None):
        spec = serving_model_spec(model)
        if spec.get("kind") == "decoder":
            raise ValueError(
                f"{type(model).__name__} is a DECODER — serve it "
                f"through the continuous-batching Engine "
                f"(inference.Engine, docs/SERVING.md); BatchEncoder "
                f"embeds with encoder models (BertModel)")
        import inspect
        try:
            fsig = inspect.signature(model.forward)
        except (TypeError, ValueError):
            fsig = None
        if fsig is None or "attention_mask" not in fsig.parameters:
            raise ValueError(
                f"BatchEncoder requires an encoder with an "
                f"attention_mask forward kwarg (padding-masked "
                f"batching); {type(model).__name__}.forward has none")
        if int(max_batch) < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if int(bucket) < 1:
            raise ValueError(f"bucket must be >= 1, got {bucket}")
        self.model = model
        self.serving_spec = spec
        self.max_batch = int(max_batch)
        self.bucket = int(bucket)
        self.max_seq = int(max_seq or spec["max_context"])
        self._st = (get_params(model), get_buffers(model),
                    get_frozen(model))
        self._clock = clock if clock is not None else time.perf_counter
        self._wait_s = 0.0
        # tenant fairness state: per-tenant FIFO queues walked
        # round-robin when a batch is formed (the Engine/DisaggEngine
        # fairness shape). OrderedDict keeps a stable walk order.
        self._queues: "OrderedDict[str, deque]" = OrderedDict()
        self._rr = 0
        self.requests: Dict[int, _EmbedRequest] = {}
        self._next_id = 0
        self._steps = 0
        self._fns: Dict[int, object] = {}
        self._tracker = CompileTracker().start()
        self._compiles = 0
        self._warm_compiles = 0
        self._last_compile_step = 0

    # -- compiled surface ----------------------------------------------------

    def _bucketed(self, n: int) -> int:
        return min(_ceil_div(n, self.bucket) * self.bucket,
                   self.max_seq)

    def _get_encode_fn(self, L: int):
        """ONE executable per sequence bucket L: the padded batch
        forward plus BOTH pooling reductions, the per-row traced
        selector picking which lands in the output row — so mean and
        CLS requests share every executable."""
        fn = self._fns.get(L)
        if fn is not None:
            return fn
        fn = jax.jit(self._encode_body())
        self._fns[L] = fn
        self._last_compile_step = self._steps
        return fn

    def _encode_body(self):
        model = self.model

        def body(st, ids, amask, sel):
            p, buf, frz = st
            out, _ = functional_call(
                model, p, buf, (ids,), {"attention_mask": amask},
                frozen=frz, training=False)
            x, pooled = out
            m = (amask > 0).astype(jnp.float32)            # [B, L]
            denom = jnp.maximum(jnp.sum(m, axis=1, keepdims=True), 1.0)
            mean = jnp.sum(jnp.asarray(x, jnp.float32)
                           * m[:, :, None], axis=1) / denom
            emb = jnp.where(sel[:, None] > 0,
                            jnp.asarray(pooled, jnp.float32), mean)
            return emb

        return body

    def _sync_timed(self, outs) -> None:
        """Block until the dispatched encode lands, charging the wait
        to the tick's DEVICE share (time blocked on the device, as
        the Engine's wait spans count it; the one sanctioned sync
        point the hot-path lint recognizes)."""
        t0 = time.perf_counter()
        jax.block_until_ready(outs)
        self._wait_s += time.perf_counter() - t0

    # -- hot-path lint (docs/ANALYSIS.md "Hot-path rules") -------------------

    def _hotpath_inventory(self):
        """One encode executable per warm sequence bucket (or the base
        bucket, cold); the full embedding batch is the service's
        DELIVERABLE, so its fetch is whitelisted. No resident device
        state — every batch legitimately uploads its ids/mask — so the
        steady-upload set is empty."""
        from ..analysis import hotpath_lint as hp
        import numpy as np
        B = self.max_batch
        st = hp.struct_of(self._st)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, np.int32)

        specs = [hp.ExecutableSpec(
            name=f"encode[{L}]", body=self._encode_body(),
            args=(st, i32(B, L), i32(B, L), i32(B)),
            donate=(), fetched=(0,), deliverable=(0,))
            for L in (tuple(sorted(self._fns)) or (self.bucket,))]
        return hp.HotpathInventory(
            subject="BatchEncoder", executables=specs,
            tick_functions=[self.step, self._form_batch, self._expire,
                            self._encode],
            steady_functions=(),
            cache_keys={"_fns": list(self._fns)}, file=__file__)

    def inspect_hotpath(self):
        """Device-free hot-path audit of the embedding service; routes
        per-rule counts through ``lint.hotpath.*``."""
        from ..analysis import hotpath_lint
        return hotpath_lint.emit_hotpath(
            hotpath_lint.lint_inventory(self._hotpath_inventory()))

    # -- public API ----------------------------------------------------------

    def add_request(self, ids, params=None,
                    tenant: str = "default") -> int:
        """Queue one sequence (1-D token ids, or [1, s]) for embedding
        under ``params``. Returns the request id; a later ``step()``
        batches and encodes it."""
        p = params or EmbedParams()
        if isinstance(p, dict):
            p = EmbedParams(**p)
        p.validate()
        tokens = _normalize_prompt(ids)
        rid = self._next_id
        if len(tokens) > self.max_seq:
            raise ValueError(
                f"request {rid} has {len(tokens)} tokens, beyond the "
                f"service's max_seq {self.max_seq}")
        self._next_id += 1
        req = _EmbedRequest(req_id=rid, tokens=tokens, params=p,
                            tenant=str(tenant),
                            arrival_t=self._clock(),
                            queued_step=self._steps)
        self.requests[rid] = req
        self._queues.setdefault(str(tenant), deque()).append(req)
        monitor.counter("serving.embed.requests").increase()
        return rid

    def cancel(self, req_id: int) -> Optional[EmbedOutput]:
        """Drop a queued request NOW; returns its failure Output (None
        for unknown/already-retired ids)."""
        req = self.requests.get(int(req_id))
        if req is None:
            return None
        monitor.counter("serving.embed.cancelled").increase()
        return self._fail(req, "cancelled")

    def step(self) -> List[EmbedOutput]:
        """One service tick: expire deadlines, form one fairness-walked
        bucket batch, encode it, retire its requests."""
        outs: List[EmbedOutput] = []
        wall0 = time.perf_counter()
        self._wait_s = 0.0
        c0 = self._tracker.compiles
        with tape_mod.no_grad_guard():
            outs.extend(self._expire())
            batch = self._form_batch()
            if batch:
                outs.extend(self._encode(batch))
        monitor.counter("serving.embed.steps").increase()
        monitor.gauge("serving.embed.queue_depth").set(
            self.num_waiting)
        # host/device attribution: same split Engine.step publishes —
        # device time is the block_until_ready wait on the encode
        # output, host time is everything else in the tick
        wall_ms = (time.perf_counter() - wall0) * 1e3
        dev_ms = min(self._wait_s * 1e3, wall_ms)
        monitor.gauge("serving.embed.host_ms_per_tick").set(
            wall_ms - dev_ms)
        monitor.gauge("serving.embed.device_ms_per_tick").set(dev_ms)
        self._compiles += self._tracker.compiles - c0
        if self._last_compile_step == self._steps:
            self._warm_compiles = self._compiles
        self._steps += 1
        return outs

    def run(self, requests: Sequence,
            max_steps: int = 100_000) -> List[EmbedOutput]:
        """Offline driver: queue every item — ``ids`` or ``(ids,
        EmbedParams)`` — then step until all retire. Returns outputs
        ordered by request id."""
        want = set()
        for item in requests:
            if isinstance(item, (tuple, list)) and len(item) == 2 and \
                    isinstance(item[1], (EmbedParams, dict)):
                want.add(self.add_request(item[0], item[1]))
            else:
                want.add(self.add_request(item))
        outs: List[EmbedOutput] = []
        for _ in range(max_steps):
            outs.extend(o for o in self.step() if o.req_id in want)
            if len(outs) == len(want):
                break
        else:
            raise RuntimeError(
                f"encoder did not drain in {max_steps} steps "
                f"({len(outs)}/{len(want)} finished)")
        return sorted(outs, key=lambda o: o.req_id)

    def steady_state_recompiles(self) -> int:
        """Compiles inside this service's step() calls after the last
        step that introduced a new bucket executable — 0 under any
        steady-state length/tenant/pooling mix."""
        return self._compiles - self._warm_compiles

    def close(self):
        self._tracker.stop()

    def __del__(self):
        try:
            self._tracker.stop()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    @property
    def num_waiting(self) -> int:
        return sum(len(q) for q in self._queues.values())

    @property
    def idle(self) -> bool:
        return self.num_waiting == 0

    # -- scheduler internals -------------------------------------------------

    def _expire(self) -> List[EmbedOutput]:
        outs: List[EmbedOutput] = []
        now = self._clock()
        for req in [r for q in self._queues.values() for r in q]:
            p = req.params
            if p.deadline_ms is not None and \
                    (now - req.arrival_t) * 1e3 > float(p.deadline_ms):
                monitor.counter("serving.embed.timeouts").increase()
                outs.append(self._fail(req, "deadline"))
            elif p.max_queue_steps is not None and \
                    self._steps - req.queued_step \
                    > int(p.max_queue_steps):
                monitor.counter("serving.embed.timeouts").increase()
                outs.append(self._fail(req, "queue_timeout"))
        return outs

    def _form_batch(self) -> List[_EmbedRequest]:
        """Pick up to max_batch requests round-robin across tenants.
        The OLDEST waiting request is always taken first — its length
        sets the bucket — and the walk then admits any request fitting
        that bucket, so short requests pad up beside a long head but a
        longer one never blocks it."""
        tenants = [t for t, q in self._queues.items() if q]
        if not tenants:
            return []
        oldest = min((self._queues[t][0] for t in tenants),
                     key=lambda r: r.req_id)
        L = self._bucketed(len(oldest.tokens))
        batch = [oldest]
        self._queues[oldest.tenant].remove(oldest)
        # fairness walk: one request per tenant per lap, starting past
        # the round-robin cursor
        names = list(self._queues.keys())
        start = self._rr % max(len(names), 1)
        progressed = True
        while len(batch) < self.max_batch and progressed:
            progressed = False
            for i in range(len(names)):
                t = names[(start + i) % len(names)]
                q = self._queues[t]
                # take the first request in this tenant's queue that
                # fits the bucket (FIFO within tenant)
                take = next((r for r in q if len(r.tokens) <= L), None)
                if take is not None:
                    q.remove(take)
                    batch.append(take)
                    progressed = True
                    if len(batch) >= self.max_batch:
                        break
        self._rr += 1
        return batch

    def _encode(self, batch: List[_EmbedRequest]) -> List[EmbedOutput]:
        L = self._bucketed(max(len(r.tokens) for r in batch))
        B = self.max_batch
        ids = np.zeros((B, L), np.int32)
        amask = np.zeros((B, L), np.int32)
        sel = np.zeros((B,), np.int32)
        for i, r in enumerate(batch):
            n = len(r.tokens)
            ids[i, :n] = r.tokens
            amask[i, :n] = 1
            sel[i] = 1 if r.params.pooling == "cls" else 0
        fn = self._get_encode_fn(L)
        out = fn(self._st, jnp.asarray(ids), jnp.asarray(amask),
                 jnp.asarray(sel))
        self._sync_timed(out)
        emb = np.asarray(out)
        now = self._clock()
        real = sum(len(r.tokens) for r in batch)
        monitor.counter("serving.embed.batches").increase()
        monitor.counter("serving.embed.tokens").increase(real)
        monitor.counter("serving.embed.pad_tokens").increase(
            B * L - real)
        monitor.gauge("serving.embed.batch_fill").set(
            len(batch) / float(B))
        outs = []
        for i, r in enumerate(batch):
            self.requests.pop(r.req_id, None)
            lat = (now - r.arrival_t) * 1e3
            monitor.gauge("serving.embed.latency_ms").set(lat)
            monitor.histogram("serving.hist.embed_latency_ms").record(lat)
            monitor.counter("serving.embed.finished").increase()
            outs.append(EmbedOutput(
                req_id=r.req_id, embedding=emb[i].copy(),
                tokens=len(r.tokens), pooling=r.params.pooling,
                finish_reason="done", latency_ms=lat))
        return outs

    def _fail(self, req: _EmbedRequest, reason: str) -> EmbedOutput:
        try:
            self._queues[req.tenant].remove(req)
        except (KeyError, ValueError):
            pass
        self.requests.pop(req.req_id, None)
        return EmbedOutput(
            req_id=req.req_id, embedding=None,
            tokens=len(req.tokens), pooling=req.params.pooling,
            finish_reason=reason,
            latency_ms=(self._clock() - req.arrival_t) * 1e3,
            error=reason)
