"""Disaggregated prefill/decode serving — many engines over a mesh.

The single-loop Engine (inference/engine.py) multiplexes prefill and
decode onto one set of compiled surfaces on one chip. Production
traffic wants them APART: prefill is compute-bound and bursty, decode
is bandwidth-bound and steady, and sharing one compiled surface means
a whale prefill and a latency-critical decode tick fight for the same
device. This module splits the loop MPMD-style — the JaxPP shape
(arXiv:2412.14374): a schedule-driven host DRIVER (:class:`DisaggEngine`)
over fixed compiled per-stage programs — with the stages being whole
workers:

* **Prefill workers** (:class:`PrefillWorker`): independent engines
  that ONLY run the bucketed prefill executables. Each owns its page
  pool, allocator, prefix cache and (with speculation on) mirrored
  draft pools. A finished prefill does not enter the worker's decode
  plane — the request parks in the MIGRATING state with its pages
  held until the driver moves it.
* **Decode workers** (:class:`DecodeWorker`): independent engines that
  ONLY run the fused decode/verify executables, each with its own pool
  and device-resident slot state. Requests enter via
  :meth:`DecodeWorker.admit_migrated` — pages allocated, migrated KV
  scattered in, the slot activated — never via a local prefill.
* **KV-page migration**: finished-prefill pages move prefill→decode as
  one fixed-shape gather (src pool rows) → collective redistribution →
  fixed-shape scatter (dst pool rows, donated). The redistribution is
  the portable formulation of arXiv:2112.01075 — an
  ``alltoall_single`` over a ``worker`` mesh axis where block ``d`` of
  every worker's contribution is the pages bound for worker ``d`` —
  so ``distributed.communication`` records it and
  ``analysis.shard_lint`` validates it DEVICE-FREE
  (:func:`lint_migration`, the MULTICHIP ``serving disagg`` gate's
  static half). In-process the axis is unbound and the collective is
  the identity on the local block; on a real multi-host mesh the same
  expression lowers to the ICI exchange.

Driver contract (the reason the split is safe to ship):

* **Token exactness.** A request served disaggregated emits EXACTLY
  the tokens the single-loop engine (and the b=1 ``generate``) emits —
  greedy and seeded sampling, with prefix hits, speculative decoding,
  preemption/resume round trips, and worker deaths in the trace. The
  migrated pages are bit-copies, the rng chain is a pure function of
  (seed, tokens emitted), and resume always flows through the same
  prefill machinery. tests/test_serving_disagg.py and the
  ``_dryrun_serving_disagg`` MULTICHIP phase hold this exact.
* **Fixed compiled surfaces per worker.** Each worker compiles its own
  family once (prefill buckets on prefill workers, decode/verify
  variants on decode workers, one gather/scatter pair for migration);
  ``steady_state_recompiles() == 0`` per worker across mixed traces.
* **Multi-tenant fairness.** ``add_request(..., tenant=)`` queues per
  tenant; dispatch round-robins one request per tenant per turn, so a
  flooding tenant can slow — never starve — another tenant's TTFT.
  Re-admissions (preempted / failed-over requests) bypass the tenant
  queues at the front: they hold partial progress and the
  single-engine semantics put resumed work first.
* **Worker-death chaos.** ``kill_worker(kind, i)`` (or the seeded
  ``worker.die_prefill`` / ``worker.die_decode`` fault sites) drops a
  worker WHOLESALE — pools, allocator, device state, no goodbye. Every
  request that lived there re-admits elsewhere from the host source of
  truth alone (prompt + tokens emitted so far + the replayed rng
  chain — :func:`replay_rng_key`; a dead worker's device is never
  read) and finishes token-exact.
* **Async streaming front door.** ``add_request`` returns immediately;
  ``stream(rid)`` / ``astream(rid)`` yield tokens as ticks produce
  them (the async variant yields control between ticks so many
  consumers interleave over one driver loop).

Observability (docs/OBSERVABILITY.md): counters
``serving.migrated_pages`` / ``serving.disagg.migrations`` /
``serving.disagg.worker_kills`` / ``serving.disagg.readmitted`` /
``serving.disagg.migration_preempts``, gauges
``serving.disagg.queue_depth`` / ``serving.disagg.migrating`` and
per-worker ``serving.disagg.<kind><i>.slots_active`` /
``serving.disagg.<kind><i>.pages_free``.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor
from ..profiler.stats import CompileTracker
from . import tracing
from .engine import (FAILED, FINISHED, PREEMPTED, WAITING, Engine,
                     Output, Request, SamplingParams, _ceil_div,
                     _normalize_prompt, host_prng_key)

#: lifecycle state between a finished prefill and decode admission:
#: the request holds its prefill-worker pages (the migration source)
#: but occupies no slot on either side
MIGRATING = "MIGRATING"

#: the worker mesh axis the migration collective redistributes over
WORKER_AXIS = "worker"

DISAGG_SNAPSHOT_VERSION = 1


def replay_rng_key(seed: int, n_generated: int,
                   temperature: float) -> np.ndarray:
    """The rng key a request's chain holds after ``n_generated``
    emitted tokens — recomputed from the HOST source of truth alone.

    Every engine sampler (prefill first token, decode tick, verify
    chain) consumes exactly one ``jax.random.split`` per emitted token
    when ``temperature > 0`` and none when greedy, and keeps
    ``split(key)[0]`` as the chain. So a dead worker's in-flight rng
    state is a pure function of (seed, tokens emitted) — the
    failover path re-admits without ever reading the lost device."""
    key = host_prng_key(seed)
    if float(temperature) > 0.0:
        for _ in range(int(n_generated)):
            key = jax.random.split(key)[0]
    return np.asarray(key, np.uint32)


def migration_collective(block_tree, n_workers: int, src: int, dst: int,
                         group=None):
    """Route one migrated page block through the portable
    collective-redistribution spelling (arXiv:2112.01075): every worker
    contributes ``[n_workers * MB, ...]`` — block ``d`` holds its pages
    bound for worker ``d`` — and ``alltoall_single`` over the worker
    axis deals block ``s`` of worker ``s``'s contribution to worker
    ``s``'s peer. Here the src worker's contribution carries the pages
    in block ``dst`` and zeros elsewhere.

    In-process (single controller, axis unbound) the collective is the
    identity, and the dst extracts the block the src placed for it —
    the degenerate one-rank view of the same program. Under
    ``analysis.shard_lint``'s recorder the call is captured with the
    full ``[W*MB, ...]`` shape and validated against the worker mesh
    device-free (:func:`lint_migration`)."""
    from ..distributed.communication import collectives as coll
    from ..distributed.communication.group import Group
    g = group if group is not None else Group(axis_name=WORKER_AXIS)
    W, d = int(n_workers), int(dst)

    def one(x):
        mb = x.shape[0]
        full = jnp.concatenate(
            [x if i == d else jnp.zeros_like(x) for i in range(W)],
            axis=0)
        out = coll.alltoall_single(None, full, group=g)
        return out[d * mb:(d + 1) * mb]

    return jax.tree_util.tree_map(one, block_tree)


def lint_migration(n_workers: int, max_blocks: int, kv_heads: int,
                   page_size: int, head_dim: int, layers: int = 1,
                   quant: bool = False) -> List[str]:
    """Device-free validation of the migration collective: run the
    redistribution expression for a worker mesh of ``n_workers`` under
    ``analysis.shard_lint``'s recorder + a fake ``{worker: W}`` mesh
    and lint the records. Returns finding strings (empty = the
    migration lowers to a valid, evenly split ``alltoall_single`` over
    the worker axis — the static half of the MULTICHIP ``serving
    disagg`` gate)."""
    from ..analysis import shard_lint
    from ..distributed import mesh as mesh_mod
    block = []
    for _ in range(int(layers)):
        leaf = jnp.zeros((int(max_blocks), int(kv_heads),
                          int(page_size), int(head_dim)), jnp.float32)
        entry = (leaf, leaf)
        if quant:
            s = jnp.zeros((int(max_blocks), int(kv_heads),
                           int(page_size)), jnp.float32)
            entry = entry + (s, s)
        block.append(entry)
    fake = mesh_mod.fake_mesh({WORKER_AXIS: int(n_workers)})
    with shard_lint.recording(fake) as rec:
        migration_collective(block, int(n_workers), src=0,
                             dst=int(n_workers) - 1)
    findings = shard_lint.lint_records(rec.records, fake)
    return [f"{f.rule}: {f.message}" for f in findings]


class PrefillWorker(Engine):
    """An Engine whose compiled surface is prefill-only: a finished
    prefill parks the request as MIGRATING (slot freed for the next
    prompt, pages held as the migration source) instead of entering
    the local decode plane. The decode/verify executables of this
    worker never compile."""

    def __init__(self, *args, **kwargs):
        self.ready: List[Request] = []
        super().__init__(*args, **kwargs)

    def _activate(self, req: Request) -> None:
        i = req.slot
        if i is not None:
            self._slots[i] = None
            req.slot = None
        req.state = MIGRATING
        self._open_span(req, tracing.MIGRATING, kind="pages")
        self.ready.append(req)


class DecodeWorker(Engine):
    """An Engine whose requests arrive pre-prefilled: admission copies
    the migrated KV block into this worker's pools and drops the
    request straight into a decode slot. The local prefill executables
    only ever run for nothing — the driver routes resume prefills back
    through the prefill fleet."""

    def can_admit(self, n_pages: int) -> bool:
        """True when a migrated request needing ``n_pages`` would be
        admitted right now (free slot + pages above the busy-engine
        watermark) — THE admission predicate, shared by the driver's
        cheap pre-check and ``admit_migrated`` itself so the two can
        never drift."""
        if not any(r is None for r in self._slots):
            return False
        busy = any(r is not None for r in self._slots)
        wm = self.watermark_pages if busy else 0
        return self._alloc.can_alloc(n_pages, wm)

    def admit_migrated(self, req: Request, block, n_pages: int) -> bool:
        """Take a MIGRATING request: allocate ``n_pages`` local pages,
        scatter the ``[max_blocks, ...]`` migrated block into this
        worker's pools at those rows (donated, one fixed-shape
        executable), and activate the slot. False = no slot or no
        pages free right now (the driver keeps the request MIGRATING —
        pages stay safe on the prefill side)."""
        if not self.can_admit(n_pages):
            return False
        slot = next(i for i, r in enumerate(self._slots) if r is None)
        pages = self._alloc.alloc(n_pages, seq=req.req_id)
        idx = np.zeros((self.max_blocks,), np.int32)
        idx[:n_pages] = pages
        self._scatter(block, self._up(idx))
        req.pages = pages
        req.shared_pages = None
        req.prefix_len = 0
        req.slot = slot
        self._slots[slot] = req
        self.requests[req.req_id] = req
        Engine._activate(self, req)
        # the pipelined step() dispatches decode FIRST (its
        # _ensure_pages pass runs post-harvest), so a slot activated
        # between steps must get its first write position covered NOW
        # — a migrated prompt that exactly fills its pages would
        # otherwise write token one into the scratch page
        self._ensure_pages()
        return True

    def _scatter_body(self):
        def body(pools, blk, rows):
            return jax.tree_util.tree_map(
                lambda p, r: p.at[rows].set(r.astype(p.dtype)),
                pools, blk)
        return body

    def _scatter(self, block, idx):
        """Write a migrated block into the pools at rows ``idx`` —
        pad entries point at row 0, the scratch page garbage may
        land in harmlessly. ONE executable (fixed [max_blocks]
        shape) however many pages migrate."""
        fn = getattr(self, "_scatter_fn", None)
        if fn is None:
            fn = jax.jit(self._scatter_body(), donate_argnums=(0,))
            self._scatter_fn = fn
        tgt, drf = block
        self._pools = fn(self._pools, tgt, idx)
        if self._spec is not None and drf is not None:
            self._spec._pools = fn(self._spec._pools, drf, idx)
        return self._pools

    def _hotpath_inventory(self):
        """Engine's inventory plus the migration scatter: destination
        pools donated (argnum 0), the incoming block is consumed but
        smaller than the pools, nothing fetched."""
        from ..analysis import hotpath_lint as hp
        inv = Engine._hotpath_inventory(self)
        pools = hp.struct_of(self._pools)
        blk = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(
                (self.max_blocks,) + tuple(l.shape[1:]), l.dtype),
            pools)
        inv.executables.append(hp.ExecutableSpec(
            name="scatter", body=self._scatter_body(),
            args=(pools, blk,
                  jax.ShapeDtypeStruct((self.max_blocks,), np.int32)),
            donate=(0,), fetched=(), per_tick=False))
        inv.tick_functions.extend([self.admit_migrated, self._scatter])
        return inv


class DisaggEngine:
    """Disaggregated serving driver: N prefill workers + M decode
    workers as independent compiled surfaces, KV pages migrating
    between them, one multi-tenant front door.

        eng = DisaggEngine(model, prefill_workers=2, decode_workers=2,
                           max_slots=4, page_size=8, pool_pages=64)
        rid = eng.add_request(ids, SamplingParams(max_new_tokens=32),
                              tenant="team-a")
        for tok in eng.stream(rid):
            ...
        # or drive it like the single-loop engine:
        outs = eng.run([(ids_a, pa), (ids_b, pb)])

    Geometry (page_size / prefill_bucket / max_context / cache_dtype /
    spec_k) is shared by every worker — the migration block shapes
    depend on it. ``max_slots`` / ``pool_pages`` size each DECODE
    worker; ``prefill_slots`` / ``prefill_pool_pages`` size each
    prefill worker (defaults mirror the decode side)."""

    def __init__(self, model, prefill_workers: int = 1,
                 decode_workers: int = 1, max_slots: int = 8,
                 page_size: int = 16,
                 pool_pages: Optional[int] = None,
                 prefill_slots: Optional[int] = None,
                 prefill_pool_pages: Optional[int] = None,
                 cache_dtype: str = "auto",
                 max_context: Optional[int] = None,
                 prefill_bucket: int = 32,
                 watermark_pages: Optional[int] = None,
                 prefix_cache: bool = False,
                 draft_model=None, spec_k: int = 4,
                 clock=None, fault_injector=None,
                 max_prefill_tokens_per_step: Optional[int] = None):
        if int(prefill_workers) < 1 or int(decode_workers) < 1:
            raise ValueError(
                f"need at least one worker of each kind, got "
                f"prefill_workers={prefill_workers} "
                f"decode_workers={decode_workers}")
        self.model = model
        self.label = "disagg"
        self._clock = clock if clock is not None else time.perf_counter
        # same arming contract as Engine (reliability.py): an explicit
        # FaultInjector, None = arm from FLAGS_serving_fault_* (ONE
        # injector shared by the driver and every worker, so the whole
        # fleet's chaos schedule replays from one seed), False = force
        # OFF even when the flags arm the process
        if fault_injector is False:
            self._injector = None
        elif fault_injector is None:
            from .reliability import injector_from_flags
            self._injector = injector_from_flags()
        else:
            self._injector = fault_injector
        common = dict(page_size=page_size, cache_dtype=cache_dtype,
                      max_context=max_context,
                      prefill_bucket=prefill_bucket,
                      watermark_pages=watermark_pages,
                      draft_model=draft_model, spec_k=spec_k,
                      clock=self._clock,
                      fault_injector=(self._injector
                                      if self._injector is not None
                                      else False))
        self.prefill: List[Optional[PrefillWorker]] = [
            PrefillWorker(
                model, max_slots=(prefill_slots or max_slots),
                pool_pages=(prefill_pool_pages
                            if prefill_pool_pages is not None
                            else pool_pages),
                prefix_cache=prefix_cache,
                max_prefill_tokens_per_step=max_prefill_tokens_per_step,
                label=f"prefill{i}", **common)
            for i in range(int(prefill_workers))]
        self.decode: List[Optional[DecodeWorker]] = [
            DecodeWorker(model, max_slots=max_slots,
                         pool_pages=pool_pages, prefix_cache=False,
                         label=f"decode{i}", **common)
            for i in range(int(decode_workers))]
        w0 = self.decode[0]
        self.page_size = w0.page_size
        self.max_blocks = w0.max_blocks
        self.max_context = w0.max_context
        self.prefill_bucket = w0.prefill_bucket
        self.cache_dtype = w0.cache_dtype
        self._lookahead = w0._lookahead
        for w in self.prefill:
            if w.max_blocks != self.max_blocks:
                raise RuntimeError(
                    "prefill/decode worker page geometry diverged "
                    f"({w.max_blocks} vs {self.max_blocks} blocks) — "
                    "migration blocks must be shape-identical")
        # front door: per-tenant FIFO queues, round-robin dispatch;
        # re-admissions (preemption sweep-backs, worker deaths) go to
        # _resume, serviced first — they carry partial progress
        self._queues: Dict[str, deque] = {}
        self._rr: deque = deque()
        self._resume: deque = deque()
        self._ready: List[Tuple[PrefillWorker, Request]] = []
        self.requests: Dict[int, Request] = {}
        self._tenant: Dict[int, str] = {}
        # DRIVER-side arrival order (req_id -> monotone seq): the one
        # ordering migration priority, parked-victim selection and
        # failover re-admission sort by. req.admit_seq is NOT usable
        # here — each prefill worker's slot admission overwrites it
        # with that worker's LOCAL counter, so cross-worker comparisons
        # of admit_seq would shuffle genuinely-older requests behind
        # younger ones on less-loaded workers.
        self._order: Dict[int, int] = {}
        self._next_id = 0
        self._admit_counter = 0
        self._steps = 0
        self._outputs: Dict[int, Output] = {}
        self._gather_fns: Dict[int, object] = {}
        self._routes: set = set()
        self._stream_cursor: Dict[int, int] = {}
        self._tracker = CompileTracker().start()
        self._compiles = 0
        self._warm_compiles = 0
        # per-worker utilization accounting (the replay tool's
        # per-worker report): steps the worker did real work
        self.worker_stats: Dict[str, Dict[str, int]] = {}
        for kind, fleet in (("prefill", self.prefill),
                            ("decode", self.decode)):
            for i in range(len(fleet)):
                self.worker_stats[f"{kind}{i}"] = {
                    "busy_steps": 0, "steps": 0, "migrations": 0,
                    "pages_migrated": 0}

    @classmethod
    def from_plan(cls, model, plan, **overrides) -> "DisaggEngine":
        """Build a disaggregated engine from a planner serving plan
        (``analysis.planner.plan_serving`` output, or any dict with
        ``prefill_workers``/``decode_workers``). ``decode_mp`` is the
        planner's answer to "how should decode workers shard?" — it
        takes effect through the ambient mp mesh (install the plan's
        mesh with ``jax.set_mesh`` before constructing; the workers
        commit kv-head-sharded pools against it exactly as in the
        TP-sharded decode path, docs/SERVING.md)."""
        kw = dict(prefill_workers=int(plan.get("prefill_workers", 1)),
                  decode_workers=int(plan.get("decode_workers", 1)))
        kw.update(overrides)
        eng = cls(model, **kw)
        eng.plan = dict(plan)
        return eng

    # -- front door ----------------------------------------------------------

    def add_request(self, ids, sampling_params=None,
                    tenant: str = "default") -> int:
        """Queue a prompt under ``tenant``'s share of the dispatch.
        Returns immediately with the request id — tokens stream out of
        ``stream(rid)`` / ``astream(rid)`` as later ``step()``s produce
        them, and the finished Output surfaces from ``step()`` like the
        single-loop engine's."""
        params = sampling_params or SamplingParams()
        if isinstance(params, dict):
            params = SamplingParams(**params)
        params.validate()
        prompt = _normalize_prompt(ids)
        rid = self._next_id
        need = len(prompt) + int(params.max_new_tokens)
        cap = self.max_blocks * self.page_size - (self._lookahead - 1)
        if self._pbucket(need) > cap:
            raise ValueError(
                f"request {rid} needs {need} token slots, beyond the "
                f"engine's max_context capacity {cap}")
        # decode-side lifetime demand: every written token plus the
        # per-tick write lookahead must fit ONE decode worker's pool
        worst = _ceil_div(need - 1 + self._lookahead, self.page_size)
        pool = min(w.pool_pages for w in self.decode if w is not None)
        if worst > pool:
            raise RuntimeError(
                f"request {rid} can never be scheduled: it needs up to "
                f"{worst} page(s) but the smallest decode worker pool "
                f"has {pool}")
        # prefill-side: the deepest resume prefix must fit too
        pworst = _ceil_div(max(len(prompt), need - 2), self.page_size)
        ppool = min(w.pool_pages for w in self.prefill if w is not None)
        if pworst > ppool:
            raise RuntimeError(
                f"request {rid} can never be prefilled: its prefix "
                f"needs up to {pworst} page(s) but the smallest "
                f"prefill worker pool has {ppool}")
        req = Request(req_id=rid, prompt=prompt, params=params,
                      arrival_t=self._clock(), queued_step=self._steps)
        req.key = host_prng_key(params.seed)
        tracing.open_span(req.spans, tracing.QUEUED,
                          req.arrival_t * 1e3, self.label)
        self._next_id += 1
        self.requests[rid] = req
        self._tenant[rid] = str(tenant)
        self._order[rid] = len(self._order)
        q = self._queues.get(str(tenant))
        if q is None:
            q = self._queues[str(tenant)] = deque()
            self._rr.append(str(tenant))
        q.append(req)
        monitor.counter("serving.requests").increase()
        return rid

    def cancel(self, req_id: int) -> Optional[Output]:
        """Abort a request at any lifecycle point (queued, prefilling,
        migrating, decoding): pages freed on whichever worker holds
        them, the partial Output returned."""
        req = self.requests.get(int(req_id))
        if req is None or req.state in (FINISHED, FAILED):
            return None
        # live on a worker: the worker's own cancel path frees the
        # pages (a MIGRATING request is still in its prefill worker's
        # requests dict, so this covers it too — the parked entry just
        # needs purging from the migration list)
        for fleet in (self.prefill, self.decode):
            for w in fleet:
                if w is not None and req.req_id in w.requests:
                    out = w.cancel(req.req_id)
                    if out is not None:
                        self._ready = [(pw, r) for pw, r in self._ready
                                       if r.req_id != req.req_id]
                        self._retired(out)
                        return out
        self._drop_from_queues(req)
        # same counter pair Engine.cancel emits (cancelled AND the
        # terminal-FAILED count): the metrics must not depend on where
        # in the pipeline the request happened to be when cancelled
        monitor.counter("serving.cancelled").increase()
        monitor.counter("serving.failed").increase()
        req.state = FAILED
        req.finish_reason = "cancelled"
        req.finish_t = self._clock()
        out = self._make_output(req, "cancelled", failed=True)
        self._retired(out)
        return out

    def stream(self, req_id: int):
        """Synchronous streaming iterator: yields tokens for ``rid``
        as engine ticks produce them, driving ``step()`` itself while
        the request is unfinished."""
        rid = int(req_id)
        while True:
            tok, done = self._stream_poll(rid)
            for t in tok:
                yield t
            if done:
                return
            if not tok:
                self.step()

    async def astream(self, req_id: int):
        """Async streaming iterator — the awaitable front door. Yields
        tokens as they decode and control between ticks, so many
        consumers interleave over one event loop; whichever consumer
        observes a stalled stream drives the next ``step()``."""
        import asyncio
        rid = int(req_id)
        while True:
            tok, done = self._stream_poll(rid)
            for t in tok:
                yield t
                await asyncio.sleep(0)
            if done:
                return
            if not tok:
                self.step()
                await asyncio.sleep(0)

    def _stream_poll(self, rid: int) -> Tuple[List[int], bool]:
        cur = self._stream_cursor.get(rid, 0)
        out = self._outputs.get(rid)
        if out is not None:
            toks = out.token_ids[cur:]
            # stream drained: drop this consumer's cursor (the Output
            # itself stays until the retention cap evicts it)
            self._stream_cursor.pop(rid, None)
            return toks, True
        req = self.requests.get(rid)
        if req is None:
            raise KeyError(f"unknown request id {rid}")
        toks = list(req.generated[cur:])
        self._stream_cursor[rid] = cur + len(toks)
        return toks, False

    # -- driver loop ---------------------------------------------------------

    def step(self) -> List[Output]:
        """One driver tick: chaos, deadline sweep over driver-held
        requests, tenant-fair dispatch to prefill workers, prefill
        steps, page migration, decode steps, preemption sweep-back.
        Returns every request that finished or failed this tick."""
        outs: List[Output] = []
        self._maybe_chaos()
        outs.extend(self._expire())
        self._dispatch()
        for i, w in enumerate(self.prefill):
            if w is None:
                continue
            busy = (w.num_prefilling > 0 or w.num_waiting > 0
                    or any(r is not None for r in w._slots))
            for out in w.step():
                self._retired(out)
                outs.append(out)
            st = self.worker_stats[f"prefill{i}"]
            st["steps"] += 1
            st["busy_steps"] += int(busy)
            for req in w.ready:
                self._ready.append((w, req))
            w.ready.clear()
        # driver-surface compile accounting: only the migration
        # section compiles driver-owned executables (the gather/
        # scatter pair per worker plus one redistribution program per
        # (src, dst) route — all bounded by the topology); a step that
        # first exercises a new worker or route folds its compiles
        # into warmup, anything after that is a genuine recompile.
        # Worker-step compiles are the workers' own accounting.
        c0 = self._tracker.compiles
        sig0 = self._surface_sig()
        self._migrate()
        self._compiles += self._tracker.compiles - c0
        if self._surface_sig() != sig0:
            self._warm_compiles = self._compiles
        for i, w in enumerate(self.decode):
            if w is None:
                continue
            busy = w.num_active > 0
            for out in w.step():
                self._retired(out)
                outs.append(out)
            st = self.worker_stats[f"decode{i}"]
            st["steps"] += 1
            st["busy_steps"] += int(busy)
            # sweep preempted requests back to the driver: their
            # resume prefill belongs on the prefill fleet, not on
            # this worker's (never-used) prefill surface
            while w._waiting:
                req = w._waiting.popleft()
                w.requests.pop(req.req_id, None)
                req.queued_step = self._steps
                self._resume.append(req)
                monitor.counter("serving.disagg.readmitted").increase()
        self._relieve_prefill_pressure()
        self._steps += 1
        self._publish_gauges()
        return outs

    def run(self, requests: Sequence, max_steps: int = 100_000
            ) -> List[Output]:
        """Offline driver: queue every (ids, SamplingParams) pair, step
        until all finish. Returns Outputs ordered by request id."""
        want = set()
        for item in requests:
            if isinstance(item, (tuple, list)) and len(item) == 2 and \
                    isinstance(item[1], (SamplingParams, dict)):
                want.add(self.add_request(item[0], item[1]))
            else:
                want.add(self.add_request(item))
        outs: List[Output] = []
        for _ in range(max_steps):
            outs.extend(o for o in self.step() if o.req_id in want)
            if len(outs) == len(want):
                break
        else:
            raise RuntimeError(
                f"disagg engine did not drain in {max_steps} steps "
                f"({len(outs)}/{len(want)} finished)")
        return sorted(outs, key=lambda o: o.req_id)

    # -- scheduling internals ------------------------------------------------

    def _pbucket(self, n: int) -> int:
        return _ceil_div(n, self.prefill_bucket) * self.prefill_bucket

    def _surface_sig(self) -> Tuple[int, int, int]:
        """The driver's compiled-surface inventory — growth marks a
        legitimate warmup step for steady_state_recompiles."""
        return (len(self._gather_fns),
                sum(1 for f in (self.prefill + self.decode)
                    if f is not None and hasattr(f, "_scatter_fn")),
                len(self._routes))

    def _expire(self) -> List[Output]:
        """Deadline/queue-budget sweep over DRIVER-held requests
        (queued or migrating; workers sweep their own live ones)."""
        outs: List[Output] = []
        now = self._clock()
        held = [r for q in self._queues.values() for r in q]
        held += list(self._resume)
        held += [r for _, r in self._ready]
        for req in held:
            if req.state in (FINISHED, FAILED):
                continue     # retired elsewhere, entry not yet purged
            p = req.params
            reason = None
            if p.deadline_ms is not None and \
                    (now - req.arrival_t) * 1e3 > float(p.deadline_ms):
                reason = "deadline"
            elif p.max_queue_steps is not None and \
                    req.state in (WAITING, PREEMPTED) and \
                    self._steps - req.queued_step \
                    > int(p.max_queue_steps):
                reason = "queue_timeout"
            if reason is None:
                continue
            monitor.counter("serving.timeouts").increase()
            for i, (pw, r) in enumerate(list(self._ready)):
                if r is req:
                    pw._alloc.free(req.pages)
                    pw.requests.pop(req.req_id, None)
                    req.pages = []
                    del self._ready[i]
                    break
            self._drop_from_queues(req)
            req.state = FAILED
            req.finish_reason = reason
            req.finish_t = now
            monitor.counter("serving.failed").increase()
            out = self._make_output(req, reason, failed=True)
            self._retired(out)
            outs.append(out)
        return outs

    def _next_candidate(self) -> Optional[Request]:
        if self._resume:
            return self._resume.popleft()
        for _ in range(len(self._rr)):
            tenant = self._rr[0]
            self._rr.rotate(-1)
            q = self._queues.get(tenant)
            if q:
                return q.popleft()
        return None

    def _dispatch(self) -> None:
        """Tenant-fair dispatch: hand queued requests to prefill
        workers with free slots, one per tenant per turn (resume
        re-admissions first). Stops when no worker can take more."""
        while True:
            targets = [w for w in self.prefill
                       if w is not None and
                       any(r is None for r in w._slots)
                       and len(w._waiting) == 0]
            if not targets:
                return
            req = self._next_candidate()
            if req is None:
                return
            # least-loaded prefill worker: most free pages breaks
            # slot-count ties (migrating backlogs show up as held pages)
            w = max(targets,
                    key=lambda x: (sum(1 for r in x._slots if r is None),
                                   x._alloc.free_pages))
            req.queued_step = w._steps
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
            w.requests[req.req_id] = req
            w._waiting.append(req)

    def _gather_body(self):
        def body(pools, rows):
            return jax.tree_util.tree_map(lambda p: p[rows], pools)
        return body

    def _gather(self, w: Engine, pages: List[int]):
        """Pull a request's page rows out of worker ``w``'s pools
        (target + draft) as one fixed-shape ``[max_blocks, ...]``
        block. One executable per worker; pad rows gather the scratch
        page."""
        idx = np.zeros((self.max_blocks,), np.int32)
        idx[:len(pages)] = pages
        fn = self._gather_fns.get(id(w))
        if fn is None:
            fn = jax.jit(self._gather_body())
            self._gather_fns[id(w)] = fn
        tgt = fn(w._pools, w._up(idx))
        drf = (fn(w._spec._pools, w._up(idx))
               if w._spec is not None else None)
        return (tgt, drf)

    # -- hot-path lint (docs/ANALYSIS.md "Hot-path rules") -------------------

    def _hotpath_inventory(self):
        """The DRIVER surface only: one gather executable per live
        worker (a READ — the source pools live on and the output block
        is smaller than any pool, so no donation is wanted) plus the
        driver's dispatch/migration tick path. The workers are full
        Engines and are swept separately by inspect_hotpath()."""
        from ..analysis import hotpath_lint as hp
        specs = []
        for kind, workers in (("p", self.prefill), ("d", self.decode)):
            for i, w in enumerate(workers):
                if w is None:
                    continue
                specs.append(hp.ExecutableSpec(
                    name=f"gather[{kind}{i}]", body=self._gather_body(),
                    args=(hp.struct_of(w._pools),
                          jax.ShapeDtypeStruct((self.max_blocks,),
                                               np.int32)),
                    donate=(), fetched=(), per_tick=False))
        return hp.HotpathInventory(
            subject="DisaggEngine[driver]", executables=specs,
            tick_functions=[self.step, self._expire, self._dispatch,
                            self._gather, self._migrate,
                            self._relieve_prefill_pressure],
            steady_functions=(),
            cache_keys={"_gather_fns": list(self._gather_fns)},
            file=__file__)

    def inspect_hotpath(self):
        """Hot-path audit over the whole disaggregated surface: the
        driver inventory plus every live prefill/decode worker's
        Engine inventory, one combined Report through the
        ``lint.hotpath.*`` counters."""
        from ..analysis import hotpath_lint
        report = hotpath_lint.lint_inventory(self._hotpath_inventory())
        for w in list(self.prefill) + list(self.decode):
            if w is not None:
                report.extend(hotpath_lint.lint_inventory(
                    w._hotpath_inventory()))
        return hotpath_lint.emit_hotpath(report)

    def _migrate(self) -> None:
        """Move every migration-ready request whose KV fits a decode
        worker: gather the page block from the prefill pool, run the
        recorded redistribution collective, scatter into the decode
        pool, free the prefill-side references (prefix-cache-shared
        pages live on under the cache's refs), activate the slot."""
        if not self._ready:
            return
        still: List[Tuple[PrefillWorker, Request]] = []
        # the worker AXIS is the fleet topology (killed workers keep
        # their coordinate — a real mesh does not renumber on failure)
        n_workers = len(self.prefill) + len(self.decode)
        for pw, req in sorted(
                self._ready,
                key=lambda e: self._order.get(e[1].req_id, 10**9)):
            if req.state != MIGRATING:
                continue     # cancelled/expired while parked
            # restamp with the DRIVER's global order before the
            # request enters a decode worker: the prefill worker's
            # slot admission overwrote admit_seq with its local
            # counter, and the decode worker's preempt-youngest
            # victim choice (max admit_seq across ITS slots) must
            # compare one global sequence, not per-worker ones
            req.admit_seq = self._order.get(req.req_id,
                                            req.admit_seq)
            n_pages = len(req.pages)
            targets = [(i, w) for i, w in enumerate(self.decode)
                       if w is not None]
            targets.sort(key=lambda e: (-sum(
                1 for r in e[1]._slots if r is None),
                -e[1]._alloc.free_pages))
            admitted = False
            src_block = None
            for di, dw in targets:
                # cheap capacity pre-check: a back-pressured tick must
                # not pay the gather + redistribution device copies
                # (or record a route) for an admission that will refuse
                if not dw.can_admit(n_pages):
                    continue
                if src_block is None:
                    src_block = self._gather(pw, req.pages)
                src_i = self.prefill.index(pw)
                block = migration_collective(
                    src_block, n_workers, src=src_i,
                    dst=len(self.prefill) + di)
                src_pages = req.pages
                if dw.admit_migrated(req, block, n_pages):
                    self._routes.add((src_i, len(self.prefill) + di))
                    pw._alloc.free(src_pages)
                    pw.requests.pop(req.req_id, None)
                    monitor.counter("serving.migrated_pages").increase(
                        n_pages)
                    monitor.counter(
                        "serving.disagg.migrations").increase()
                    pi = self.prefill.index(pw)
                    self.worker_stats[f"prefill{pi}"][
                        "pages_migrated"] += n_pages
                    self.worker_stats[f"decode{di}"]["migrations"] += 1
                    self.worker_stats[f"decode{di}"][
                        "pages_migrated"] += n_pages
                    admitted = True
                    break
            if not admitted:
                still.append((pw, req))
        self._ready = still

    def preempt_migrating(self, req_id: int) -> bool:
        """Mid-migration preemption: drop a MIGRATING request's
        prefill-side pages and requeue it at the resume front — the
        same tokens come out after its re-prefill (the rng chain never
        advanced while parked). The driver calls this under prefill
        pool pressure; tests exercise it directly."""
        for i, (pw, req) in enumerate(list(self._ready)):
            if req.req_id == int(req_id):
                pw._alloc.free(req.pages)
                pw.requests.pop(req.req_id, None)
                req.pages = []
                req.shared_pages = None
                req.prefix_len = 0
                req.written = 0
                req.preemptions += 1
                req.state = PREEMPTED if req.generated else WAITING
                req.queued_step = self._steps
                # aborted migration: the MIGRATING span closes without
                # a latency record (it never completed)
                tracing.open_span(req.spans, tracing.PREEMPTED,
                                  self._clock() * 1e3, self.label,
                                  kind="migration")
                del self._ready[i]
                self._resume.appendleft(req)
                monitor.counter("serving.preemptions").increase()
                monitor.counter(
                    "serving.disagg.migration_preempts").increase()
                return True
        return False

    def _relieve_prefill_pressure(self) -> None:
        """A prefill worker starved for pages while migration-ready
        requests sit parked (decode fleet full) preempts the YOUNGEST
        parked request — pages freed now, the request re-prefills once
        decode capacity returns. Without this the pool can wedge:
        every page held by parked requests nobody can admit."""
        for w in self.prefill:
            if w is None or not w._waiting:
                continue
            if w._alloc.free_pages * w.page_size >= w.prefill_bucket:
                continue
            parked = [r for pw, r in self._ready if pw is w]
            if parked:
                victim = max(parked, key=lambda r: self._order.get(
                    r.req_id, -1))
                self.preempt_migrating(victim.req_id)

    # -- chaos / worker death ------------------------------------------------

    def _maybe_chaos(self) -> None:
        if self._injector is None:
            return
        self._injector.on_step(self._steps)
        for kind, fleet in (("prefill", self.prefill),
                            ("decode", self.decode)):
            site = f"worker.die_{kind}"
            if not self._injector.fire(site, record=False):
                continue
            alive = [i for i, w in enumerate(fleet) if w is not None]
            if len(alive) <= 1:
                continue    # never kill the last worker of a kind
            self._injector.record(site)
            victim = alive[int(
                self._injector.rng.integers(0, len(alive)))]
            self.kill_worker(kind, victim)

    def kill_worker(self, kind: str, index: int) -> int:
        """Drop a worker wholesale — pools, allocator, device state,
        no goodbye — and re-admit every request that lived there from
        the host source of truth (prompt + emitted tokens + the
        replayed rng chain; the dead device is never read). Returns
        the number of requests re-admitted. The last worker of a kind
        cannot be killed (the fleet must still serve)."""
        if kind not in ("prefill", "decode"):
            raise ValueError(
                f"kill_worker kind must be 'prefill' or 'decode', "
                f"got {kind!r}")
        fleet = self.prefill if kind == "prefill" else self.decode
        index = int(index)
        if not 0 <= index < len(fleet):
            raise ValueError(
                f"kill_worker index {index} out of range for "
                f"{len(fleet)} {kind} worker(s)")
        w = fleet[index]
        if w is None:
            return 0
        if sum(1 for x in fleet if x is not None) <= 1:
            raise RuntimeError(
                f"cannot kill the last {kind} worker — the fleet "
                f"must keep serving")
        monitor.counter("serving.disagg.worker_kills").increase()
        # requests parked for migration out of this worker die with
        # their pages; the host truth re-prefills them elsewhere
        doomed: Dict[int, Request] = {}
        still: List[Tuple[PrefillWorker, Request]] = []
        for pw, req in self._ready:
            if pw is w:
                doomed[req.req_id] = req
            else:
                still.append((pw, req))
        self._ready = still
        for r in w.requests.values():
            if r.state not in (FINISHED, FAILED):
                doomed.setdefault(r.req_id, r)
        n = 0
        now_ms = self._clock() * 1e3
        zero_progress: List[Request] = []
        for req in sorted(doomed.values(), key=lambda r: (
                self._order.get(r.req_id, 10**9), r.req_id)):
            req.slot = None
            req.pages = []
            req.shared_pages = None
            req.prefix_len = 0
            req.written = 0
            req.preemptions += 1
            req.key = replay_rng_key(req.params.seed,
                                     len(req.generated),
                                     req.params.temperature)
            req.state = PREEMPTED if req.generated else WAITING
            tracing.open_span(
                req.spans,
                tracing.PREEMPTED if req.generated else tracing.QUEUED,
                now_ms, self.label, kind="failover")
            req.queued_step = self._steps
            if req.generated:
                # partial progress earns the resume fast lane
                self._resume.append(req)
            else:
                # a dispatched-but-unstarted request holds nothing —
                # it rejoins ITS TENANT's queue (front, it is the
                # tenant's oldest), not the fast lane: failover must
                # not let a flooding tenant's fresh requests jump
                # other tenants' older work
                zero_progress.append(req)
            monitor.counter("serving.disagg.readmitted").increase()
            n += 1
        for req in reversed(zero_progress):
            tenant = self._tenant.get(req.req_id, "default")
            q = self._queues.get(tenant)
            if q is None:
                q = self._queues[tenant] = deque()
                self._rr.append(tenant)
            q.appendleft(req)
        # the tick in flight dies with the worker, unharvested: every
        # request above left with the tokens the host held
        w._inflight = None
        w._prefilled = []
        w.close()
        fleet[index] = None
        return n

    # -- reliability surfaces ------------------------------------------------

    def snapshot(self) -> dict:
        """Crash-exact host-state snapshot of the whole disaggregated
        fleet — every queued / prefilling / MIGRATING / decoding
        request's host source of truth. Rng chains are REPLAYED from
        (seed, emitted tokens), never fetched from a device, so the
        same path serves live snapshots and post-mortem ones."""
        from dataclasses import asdict
        entries = []
        seen = set()
        reqs = []
        for fleet in (self.decode, self.prefill):
            for w in fleet:
                if w is None:
                    continue
                reqs.extend(r for r in w.requests.values()
                            if r.state not in (FINISHED, FAILED))
        reqs.extend(r for _, r in self._ready)
        reqs.extend(self._resume)
        for q in self._queues.values():
            reqs.extend(q)
        reqs.sort(key=lambda r: (self._order.get(r.req_id, 10**9),
                                 r.req_id))
        now = self._clock()
        for req in reqs:
            if req.req_id in seen:
                continue
            seen.add(req.req_id)
            entries.append({
                "req_id": int(req.req_id),
                "prompt": [int(t) for t in req.prompt],
                "generated": [int(t) for t in req.generated],
                "params": asdict(req.params),
                "tenant": self._tenant.get(req.req_id, "default"),
                "preemptions": int(req.preemptions),
                "elapsed_ms": (now - req.arrival_t) * 1e3,
                "spans": tracing.copy_spans(req.spans),
            })
        monitor.counter("serving.snapshot_saves").increase()
        return {
            "version": DISAGG_SNAPSHOT_VERSION,
            "kind": "disagg",
            "topology": {
                "prefill_workers": len(self.prefill),
                "decode_workers": len(self.decode),
            },
            "fingerprint": self._fingerprint(),
            "next_id": int(self._next_id),
            "admit_counter": int(self._admit_counter),
            "requests": entries,
        }

    def restore(self, snap: dict) -> int:
        """Re-admit a snapshot's requests into this (fresh) driver:
        requests with emitted tokens resume through the prefill fleet
        with their replayed rng chains, untouched ones queue under
        their tenant — outputs bit-identical to the uninterrupted
        run. Worker topology may differ (scheduling changes, tokens
        do not)."""
        if snap.get("kind") != "disagg" or \
                snap.get("version") != DISAGG_SNAPSHOT_VERSION:
            raise ValueError(
                f"not a disagg snapshot (kind={snap.get('kind')!r} "
                f"version={snap.get('version')!r})")
        if self.requests:
            raise RuntimeError(
                "restore onto a busy driver: "
                f"{len(self.requests)} live request(s) present")
        fp = self._fingerprint()
        saved = snap.get("fingerprint", {})
        diff = {k: (saved.get(k), v) for k, v in fp.items()
                if saved.get(k) != v}
        if diff:
            raise ValueError(
                f"snapshot is token-incompatible with this engine: "
                f"{diff} (saved vs current)")
        n = 0
        for ent in snap["requests"]:
            params = SamplingParams(**ent["params"])
            req = Request(
                req_id=int(ent["req_id"]),
                prompt=[int(t) for t in ent["prompt"]],
                params=params,
                state=PREEMPTED if ent["generated"] else WAITING,
                generated=[int(t) for t in ent["generated"]],
                preemptions=int(ent.get("preemptions", 0)),
                arrival_t=self._clock()
                - float(ent.get("elapsed_ms", 0.0)) / 1e3,
                queued_step=self._steps)
            req.key = replay_rng_key(params.seed, len(req.generated),
                                     params.temperature)
            req.spans = tracing.restore_spans(
                ent.get("spans"), req.arrival_t * 1e3,
                self._clock() * 1e3, self.label, bool(req.generated))
            tenant = str(ent.get("tenant", "default"))
            self.requests[req.req_id] = req
            self._tenant[req.req_id] = tenant
            self._order[req.req_id] = len(self._order)
            if req.generated:
                self._resume.append(req)
            else:
                q = self._queues.get(tenant)
                if q is None:
                    q = self._queues[tenant] = deque()
                    self._rr.append(tenant)
                q.append(req)
            n += 1
        self._next_id = max(self._next_id, int(snap.get("next_id", 0)))
        self._admit_counter = max(self._admit_counter,
                                  int(snap.get("admit_counter", 0)))
        monitor.counter("serving.snapshot_restores").increase()
        return n

    def _fingerprint(self) -> Dict[str, object]:
        cfg = self.model.config
        # spec_k from any LIVE decode worker — worker 0 may be a
        # killed slot (None), and a post-worker-death snapshot is
        # exactly the crash-recovery artifact this signature protects
        live = next(w for w in self.decode if w is not None)
        return {
            "vocab_size": int(cfg.vocab_size),
            "num_hidden_layers": int(cfg.num_hidden_layers),
            "hidden_size": int(cfg.hidden_size),
            "num_attention_heads": int(cfg.num_attention_heads),
            "num_key_value_heads": int(cfg.num_key_value_heads),
            "cache_dtype": str(np.dtype(self.cache_dtype).name),
            "spec_k": (int(live._spec.k)
                       if live._spec is not None else 0),
        }

    def leaked_pages(self) -> int:
        """Fleet-wide drained-engine leak check (Engine.leaked_pages
        per live worker — dead workers' pools died with them)."""
        return sum(w.leaked_pages()
                   for fleet in (self.prefill, self.decode)
                   for w in fleet if w is not None)

    def check_invariants(self, repair: bool = False) -> List[str]:
        findings: List[str] = []
        for kind, fleet in (("prefill", self.prefill),
                            ("decode", self.decode)):
            for i, w in enumerate(fleet):
                if w is None:
                    continue
                findings += [f"{kind}{i}: {f}"
                             for f in w.check_invariants(repair=repair)]
        return findings

    def steady_state_recompiles(self) -> int:
        """Per-worker compiled surfaces must stay fixed: the sum of
        every live worker's steady-state recompiles plus the driver's
        own (migration gather/scatter executables compile once)."""
        own = self._compiles - self._warm_compiles
        return own + sum(
            w.steady_state_recompiles()
            for fleet in (self.prefill, self.decode)
            for w in fleet if w is not None)

    def close(self):
        self._tracker.stop()
        for fleet in (self.prefill, self.decode):
            for w in fleet:
                if w is not None:
                    w.close()

    def __del__(self):
        try:
            self._tracker.stop()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    # -- bookkeeping ---------------------------------------------------------

    def _drop_from_queues(self, req: Request) -> None:
        for q in self._queues.values():
            try:
                q.remove(req)
            except ValueError:
                pass
        try:
            self._resume.remove(req)
        except ValueError:
            pass
        for fleet in (self.prefill, self.decode):
            for w in fleet:
                if w is not None and req.req_id in w.requests \
                        and req.slot is None and not req.pages:
                    w.requests.pop(req.req_id, None)
                    try:
                        w._waiting.remove(req)
                    except ValueError:
                        pass

    def _make_output(self, req: Request, reason: str,
                     failed: bool) -> Output:
        n = len(req.generated)
        got_first = req.first_token_t > 0.0
        ttft = ((req.first_token_t - req.arrival_t) * 1e3
                if got_first else 0.0)
        tpot = ((req.finish_t - req.first_token_t) / (n - 1) * 1e3
                if got_first and n > 1 else 0.0)
        tracing.seal(req.spans,
                     tracing.FAILED if failed else tracing.FINISHED,
                     req.finish_t * 1e3, self.label,
                     reason=reason if failed else None)
        return Output(req_id=req.req_id, prompt_ids=list(req.prompt),
                      token_ids=list(req.generated),
                      finish_reason=reason, ttft_ms=ttft, tpot_ms=tpot,
                      preemptions=req.preemptions,
                      error=reason if failed else None,
                      spans=tracing.copy_spans(req.spans))

    #: retired Outputs kept for late/streaming readers; beyond this
    #: many the OLDEST are evicted (a long-running server must not
    #: grow host memory per request served — step()'s return value is
    #: the durable delivery path)
    MAX_RETAINED_OUTPUTS = 4096

    def _retired(self, out: Output) -> None:
        self._outputs[out.req_id] = out
        self.requests.pop(out.req_id, None)
        tenant = self._tenant.pop(out.req_id, None)
        self._order.pop(out.req_id, None)
        # prune a drained tenant's queue + round-robin slot: unique
        # per-user tenant ids must not grow dispatch state forever
        # (add_request recreates both on the tenant's next request)
        q = self._queues.get(tenant)
        if q is not None and not q:
            del self._queues[tenant]
            try:
                self._rr.remove(tenant)
            except ValueError:
                pass
        while len(self._outputs) > self.MAX_RETAINED_OUTPUTS:
            oldest = next(iter(self._outputs))
            self._outputs.pop(oldest)
            self._stream_cursor.pop(oldest, None)

    def _publish_gauges(self):
        monitor.gauge("serving.disagg.queue_depth").set(
            self.num_waiting)
        monitor.gauge("serving.disagg.migrating").set(len(self._ready))
        for kind, fleet in (("prefill", self.prefill),
                            ("decode", self.decode)):
            for i, w in enumerate(fleet):
                if w is None:
                    continue
                monitor.gauge(
                    f"serving.disagg.{kind}{i}.slots_active").set(
                    sum(1 for r in w._slots if r is not None))
                monitor.gauge(
                    f"serving.disagg.{kind}{i}.pages_free").set(
                    w._alloc.free_pages)

    # -- introspection -------------------------------------------------------

    @property
    def num_waiting(self) -> int:
        return (sum(len(q) for q in self._queues.values())
                + len(self._resume))

    @property
    def num_migrating(self) -> int:
        return len(self._ready)

    @property
    def num_active(self) -> int:
        return sum(w.num_active for w in self.decode if w is not None)

    @property
    def num_prefilling(self) -> int:
        return sum(
            sum(1 for r in w._slots if r is not None)
            for w in self.prefill if w is not None)

    @property
    def idle(self) -> bool:
        return (self.num_waiting == 0 and self.num_active == 0
                and self.num_prefilling == 0
                and self.num_migrating == 0
                # a decode worker's tick in flight is still to harvest
                and all(w.idle for w in self.decode if w is not None))

    @property
    def pages_free(self) -> Dict[str, int]:
        return {f"{kind}{i}": w._alloc.free_pages
                for kind, fleet in (("prefill", self.prefill),
                                    ("decode", self.decode))
                for i, w in enumerate(fleet) if w is not None}

    @property
    def prefix_hit_rate(self) -> float:
        rates = [w.prefix_hit_rate for w in self.prefill
                 if w is not None and w._prefix is not None]
        return float(np.mean(rates)) if rates else 0.0

    @property
    def spec_accept_rate(self) -> float:
        drafted = sum(w._spec_drafted for w in self.decode
                      if w is not None)
        accepted = sum(w._spec_accepted for w in self.decode
                       if w is not None)
        return accepted / drafted if drafted else 0.0

    @property
    def pallas_eligible(self) -> bool:
        """True when every decode worker's page geometry admits the
        Pallas paged-decode kernel (validated once per worker at
        construction, docs/DECODE.md)."""
        return all(w.pallas_eligible for w in self.decode
                   if w is not None)

    @property
    def decode_fallback_reason(self) -> Optional[str]:
        for w in self.decode:
            if w is not None and w.decode_fallback_reason:
                return w.decode_fallback_reason
        return None

    def utilization(self) -> Dict[str, Dict[str, object]]:
        """Per-worker utilization snapshot for the replay report:
        busy-step fraction, migrations, pages migrated; dead workers
        report as ``alive: False``."""
        out: Dict[str, Dict[str, object]] = {}
        for kind, fleet in (("prefill", self.prefill),
                            ("decode", self.decode)):
            for i, w in enumerate(fleet):
                st = self.worker_stats[f"{kind}{i}"]
                out[f"{kind}{i}"] = {
                    "alive": w is not None,
                    "utilization": round(
                        st["busy_steps"] / max(st["steps"], 1), 4),
                    "migrations": st["migrations"],
                    "pages_migrated": st["pages_migrated"],
                }
        return out
