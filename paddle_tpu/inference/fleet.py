"""Elastic serving fleet — session-aware routing, live migration,
heartbeat failover and autoscaling over N engine replicas.

The single-loop Engine (inference/engine.py) serves one chip's worth
of traffic; the disaggregated driver (inference/disagg.py) splits ONE
request's prefill and decode across workers. Production wants the
third axis: many WHOLE engine replicas behind one front door, so the
fleet can ride load swings, survive replica loss, and keep shared
system prompts hot. This module is that front door — the MPMD
driver/replica shape of JaxPP (arXiv:2412.14374) applied one level up:
a schedule-driven host ROUTER over fixed compiled replicas, with
replica-to-replica state movement treated as portable redistribution
of HOST truth (cf. arXiv:2112.01075's device-free formulation) rather
than device state — a migrated request carries tokens + a replayed rng
chain, never KV bytes.

Four capabilities (docs/SERVING.md "Elastic fleet"):

* **Session-aware routing.** Requests sharing a system prefix hash to
  the same session key (the prefix cache's chained blake2b over the
  first page-aligned prompt chunk), and the router steers them to the
  replica whose prefix cache is WARM for that prefix — scored by the
  replica's own ``PrefixCache.lookup`` depth plus a router-side
  session→replica hint for prefixes still prefilling. Cold requests
  fall back least-loaded; per-tenant fairness is preserved ACROSS
  replicas (one fleet-level round-robin over tenant queues — a
  flooding tenant can slow, never starve, another tenant whichever
  replicas its requests land on). Fleet-wide
  ``serving.prefix_hit_rate`` is the number routing exists to
  maximize; ``router="round_robin"`` / ``"least_loaded"`` are the
  comparison baselines the tests hold it against.

* **Live request migration.** ``migrate_request(rid)`` moves one
  in-flight request between replicas WITHOUT dropping a token: the
  source's ``Engine.extract_request`` hook removes it (slot cleared,
  pages freed), the fleet replays its rng chain from host truth alone
  (``disagg.replay_rng_key(seed, tokens_emitted, temperature)`` — the
  device is never read), and the request re-admits on the target
  through the SAME preemption/resume-prefill machinery every other
  resume takes — so the continued stream is bit-identical to the
  never-migrated run, with prefix hits and speculative decoding on
  (tests hold the full matrix). Between extraction and re-admission
  the request is PARKED on the fleet (``num_parked``) — snapshot()
  serializes parked requests exactly. ``drain_replica(i)`` migrates
  every request off a replica (hot-spot relief, pre-maintenance) and
  blocks new dispatches to it until ``undrain_replica(i)``.

* **Heartbeat failover.** ``heartbeat_timeout=T`` attaches one
  ``distributed.watchdog.Heartbeat`` per replica, ticked by that
  replica's step; a replica whose loop stalls past T is killed and
  failed over at the next fleet tick. ``kill_replica(i)`` (and the
  seeded ``replica.die`` fault site) drops a replica WHOLESALE —
  pools, allocator, prefix cache, device state, no goodbye — and every
  request that lived there re-admits elsewhere from host truth alone
  (prompt + emitted tokens + replayed rng chain) and finishes
  token-exact. The last live replica can never be killed.

* **Autoscaling.** ``autoscale=AutoscalePolicy(...)`` (or ``True``)
  evaluates queue-depth and TTFT-percentile signals on the fleet's
  injectable clock every tick: sustained pressure scales UP (a fresh
  replica compiles its own executables — warmup, not steady-state
  recompiles), sustained low load scales DOWN by draining the
  least-loaded replica via migration, so a scale-down NEVER drops a
  request. Events land in ``scale_log`` and
  ``serving.fleet.scale_events``.

Contract: a request served by the fleet emits EXACTLY the tokens the
single-loop Engine (and the b=1 ``generate``) emits — greedy and
seeded sampling, through routing, migration, replica deaths,
preemptions on the target replica, and scale events — and every live
replica's ``steady_state_recompiles()`` stays 0 across those traces
(a replica compiles its fixed surface once; routing/migration adds no
compiled surface beyond the one-time rng replay warmup).

Observability (docs/OBSERVABILITY.md): counters
``serving.fleet.routed_warm`` / ``serving.fleet.routed_cold`` /
``serving.fleet.migrations`` / ``serving.fleet.replica_deaths`` /
``serving.fleet.readmitted`` / ``serving.fleet.scale_events``, gauges
``serving.fleet.queue_depth`` / ``serving.fleet.replicas`` /
``serving.fleet.parked`` and per-replica
``serving.fleet.replica<i>.queue_depth`` /
``serving.fleet.replica<i>.prefix_hit_rate``.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import monitor
from ..profiler.stats import CompileTracker
from . import tracing
from .disagg import replay_rng_key
from .engine import (FAILED, FINISHED, PREEMPTED, WAITING, Engine,
                     Output, Request, SamplingParams, _ceil_div,
                     _normalize_prompt, host_prng_key)
from .prefix_cache import _chunk_hash

FLEET_SNAPSHOT_VERSION = 1

#: router policies: "session" steers shared-prefix traffic to the
#: warm replica; the other two are the measurable baselines
ROUTERS = ("session", "least_loaded", "round_robin")

#: how many leading page chunks the router probes per replica cache
#: when scoring warmth — the signal saturates fast, and an uncapped
#: probe would re-digest a whole 8K prompt per replica per dispatch
#: attempt of a capacity-starved queue head, every tick
ROUTE_PROBE_CHUNKS = 8


@dataclass
class AutoscalePolicy:
    """Scale-up/down decision knobs, evaluated every fleet tick on the
    injectable clock (so replay tools and tests drive them on virtual
    time). Scale-up fires after ``patience`` consecutive ticks of
    pressure (fleet queue depth above ``scale_up_queue_depth``, or —
    when set — recent-request p95 TTFT above ``scale_up_ttft_p95_ms``);
    scale-down fires after ``scale_down_patience`` consecutive ticks
    where the fleet queue is empty and the live load would fit HALF of
    one fewer replica's slots. ``cooldown`` ticks separate any two
    scale events so one burst can't thrash the fleet size."""

    min_replicas: int = 1
    max_replicas: int = 4
    scale_up_queue_depth: int = 8
    scale_up_ttft_p95_ms: Optional[float] = None
    patience: int = 3
    scale_down_patience: int = 50
    cooldown: int = 20
    ttft_window: int = 32

    def __post_init__(self):
        if int(self.min_replicas) < 1:
            raise ValueError(
                f"min_replicas must be >= 1, got {self.min_replicas}")
        if int(self.max_replicas) < int(self.min_replicas):
            raise ValueError(
                f"max_replicas {self.max_replicas} < min_replicas "
                f"{self.min_replicas}")


class ServingFleet:
    """Front door over N in-process Engine replicas.

        fleet = ServingFleet(model, replicas=2, max_slots=4,
                             page_size=8, pool_pages=64)
        rid = fleet.add_request(ids, SamplingParams(max_new_tokens=32),
                                tenant="team-a")
        for tok in fleet.stream(rid):
            ...
        # or drive it like the single-loop engine:
        outs = fleet.run([(ids_a, pa), (ids_b, pb)])

    Geometry (page_size / prefill_bucket / max_context / cache_dtype /
    spec_k / pool_pages / max_slots) is shared by every replica — a
    request must be admissible anywhere the router may place it.
    ``prefix_cache`` defaults ON (session-aware routing exists to keep
    the per-replica caches warm; pass False for the cold baseline).
    """

    def __init__(self, model, replicas: int = 2, max_slots: int = 8,
                 page_size: int = 16,
                 pool_pages: Optional[int] = None,
                 cache_dtype: str = "auto",
                 max_context: Optional[int] = None,
                 prefill_bucket: int = 32,
                 watermark_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 draft_model=None, spec_k: int = 4,
                 clock=None, fault_injector=None,
                 max_prefill_tokens_per_step: Optional[int] = None,
                 router: str = "session",
                 heartbeat_timeout: Optional[float] = None,
                 autoscale=None):
        if int(replicas) < 1:
            raise ValueError(f"need at least one replica, got {replicas}")
        if router not in ROUTERS:
            raise ValueError(
                f"unknown router {router!r} — one of {ROUTERS}")
        self.model = model
        self.router = router
        self.label = "fleet"
        self._clock = clock if clock is not None else time.perf_counter
        # same arming contract as Engine/DisaggEngine: explicit
        # injector, None = arm from FLAGS_serving_fault_* (one injector
        # shared fleet-wide so the whole chaos schedule replays from
        # one seed), False = force OFF
        if fault_injector is False:
            self._injector = None
        elif fault_injector is None:
            from .reliability import injector_from_flags
            self._injector = injector_from_flags()
        else:
            self._injector = fault_injector
        self._ctor = dict(
            max_slots=int(max_slots), page_size=int(page_size),
            pool_pages=pool_pages, cache_dtype=cache_dtype,
            max_context=max_context, prefill_bucket=int(prefill_bucket),
            watermark_pages=watermark_pages,
            prefix_cache=bool(prefix_cache),
            draft_model=draft_model, spec_k=int(spec_k),
            clock=self._clock,
            fault_injector=(self._injector
                            if self._injector is not None else False),
            max_prefill_tokens_per_step=max_prefill_tokens_per_step)
        if autoscale is True:
            autoscale = AutoscalePolicy()
        self._policy: Optional[AutoscalePolicy] = autoscale
        self._heartbeat_timeout = heartbeat_timeout
        self._heartbeats: Dict[int, object] = {}
        self._stalled: set = set()
        self._last_step_t = time.monotonic()
        self._replicas: List[Optional[Engine]] = []
        self._replicas_created = 0
        self.replica_stats: Dict[int, Dict[str, int]] = {}
        for _ in range(int(replicas)):
            self._spawn_replica()
        w0 = next(w for w in self._replicas if w is not None)
        self.max_slots = w0.max_slots
        self.page_size = w0.page_size
        self.max_blocks = w0.max_blocks
        self.max_context = w0.max_context
        self.prefill_bucket = w0.prefill_bucket
        self.cache_dtype = w0.cache_dtype
        self.pool_pages = w0.pool_pages
        self._lookahead = w0._lookahead
        # front door: per-tenant FIFO queues with fleet-level
        # round-robin dispatch; PARKED requests (mid-migration,
        # failed-over, restored-with-progress) are serviced first —
        # they hold partial progress, the single-engine semantics put
        # resumed work at the queue front
        self._queues: Dict[str, deque] = {}
        self._rr: deque = deque()
        self._parked: "deque[Request]" = deque()
        self._migrate_dst: Dict[int, int] = {}
        self.requests: Dict[int, Request] = {}
        self._tenant: Dict[int, str] = {}
        self._home: Dict[int, int] = {}
        self._order: Dict[int, int] = {}
        # session routing state: session key (first-chunk chained
        # digest) -> replica index of the last dispatch, so a burst of
        # same-session requests sticks to one replica even before its
        # first prefill lands in the cache. Bounded (oldest evicted).
        self._sessions: Dict[bytes, int] = {}
        # per-request session key, digested ONCE at admission (the
        # dispatch loop re-routes queue heads every tick — re-hashing
        # the prompt there would be scheduler-hot-path waste)
        self._skey: Dict[int, Optional[bytes]] = {}
        self._draining: set = set()
        self._next_id = 0
        self._steps = 0
        self._outputs: Dict[int, Output] = {}
        self._stream_cursor: Dict[int, int] = {}
        self.scale_log: List[Dict[str, object]] = []
        self._up_streak = 0
        self._down_streak = 0
        self._cooldown = 0
        # autoscale TTFT signal: two rotating log-bucket histograms
        # (current + previous window) instead of an unbounded sample
        # list — O(1) record, p95 from the exact merge of both windows
        self._ttft_hist = monitor.Histogram("fleet.autoscale.ttft")
        self._ttft_hist_prev = monitor.Histogram(
            "fleet.autoscale.ttft.prev")
        self._ttft_sampled: set = set()
        # hit/lookup totals of replicas that died or scaled away, so
        # the fleet-wide prefix_hit_rate survives replica churn
        self._retired_hits = 0
        self._retired_lookups = 0
        self._tracker = CompileTracker().start()
        self._compiles = 0
        self._warm_compiles = 0
        self._replay_used = False
        # precompile the rng-replay surface (the key split) so a
        # steady-state migration/failover tick introduces no new
        # driver executable
        replay_rng_key(0, 1, 1.0)

    @classmethod
    def from_plan(cls, model, plan, **overrides) -> "ServingFleet":
        """Build a fleet from a planner serving plan
        (``analysis.planner.plan_serving`` output): ``replicas`` is the
        plan's chip-group count, ``decode_mp`` the per-replica TP
        degree (advisory — takes effect through the ambient mp mesh,
        one mesh group per replica on real hardware)."""
        kw = dict(replicas=int(plan.get("replicas", 2)))
        kw.update(overrides)
        fleet = cls(model, **kw)
        fleet.plan = dict(plan)
        return fleet

    # -- replica lifecycle ---------------------------------------------------

    def _spawn_replica(self, index: Optional[int] = None) -> int:
        """Construct one Engine replica (at ``index`` — a dead
        replica's coordinate — or appended). The new replica compiles
        its own fixed surface on first use: warmup by the per-engine
        accounting, never a steady-state recompile."""
        if index is None:
            index = len(self._replicas)
            self._replicas.append(None)
        w = Engine(self.model, label=f"replica{index}", **self._ctor)
        self._replicas[index] = w
        self._replicas_created += 1
        # a reused coordinate (scale-up after a death) is a NEW engine:
        # fresh stats, or the replay report would conflate two
        # incarnations under one row
        self.replica_stats[index] = {
            "steps": 0, "busy_steps": 0, "routed_warm": 0,
            "routed_cold": 0, "migrated_out": 0, "finished": 0}
        if self._heartbeat_timeout is not None:
            from ..distributed.watchdog import Heartbeat
            hb = Heartbeat(
                float(self._heartbeat_timeout),
                on_stall=lambda age, i=index: self._flag_stall(i),
                name=f"fleet-replica{index}")
            hb.start()
            self._heartbeats[index] = hb
        return index

    def _flag_stall(self, index: int) -> None:
        """Heartbeat callback (runs on the watchdog thread): record
        the verdict; the next fleet tick's sweep decides whether it
        was a real replica wedge or just a paused driver."""
        self._stalled.add(int(index))

    def _remove_replica(self, index: int) -> None:
        w = self._replicas[index]
        if w is None:
            return
        if w._prefix is not None:
            self._retired_hits += w._prefix.hits
            self._retired_lookups += w._prefix.lookups
        hb = self._heartbeats.pop(index, None)
        if hb is not None:
            hb.stop()
        w.close()
        self._replicas[index] = None
        self._draining.discard(index)
        self._stalled.discard(index)
        # stale session hints must not keep scoring a dead replica warm
        for k in [k for k, v in self._sessions.items() if v == index]:
            del self._sessions[k]

    def _alive(self) -> List[Tuple[int, Engine]]:
        return [(i, w) for i, w in enumerate(self._replicas)
                if w is not None]

    # -- front door ----------------------------------------------------------

    def add_request(self, ids, sampling_params=None,
                    tenant: str = "default") -> int:
        """Queue a prompt under ``tenant``'s share of the dispatch.
        Returns immediately with the request id; the router assigns a
        replica at a later ``step()`` and tokens stream out of
        ``stream(rid)`` / ``astream(rid)``."""
        params = sampling_params or SamplingParams()
        if isinstance(params, dict):
            params = SamplingParams(**params)
        params.validate()
        prompt = _normalize_prompt(ids)
        rid = self._next_id
        # admission math DELEGATED to a live replica (geometry is
        # fleet-wide, and at least one replica is always alive): the
        # fleet must never fork Engine's admission contract — a
        # request must be admissible anywhere the router may place it
        probe = next(w for _, w in self._alive())
        need = len(prompt) + int(params.max_new_tokens)
        cap = self.max_blocks * self.page_size - (self._lookahead - 1)
        chunk_cap = (need
                     if probe.max_prefill_tokens_per_step is not None
                     else probe._pbucket(need))
        if chunk_cap > cap:
            raise ValueError(
                f"request {rid} needs {need} token slots, beyond the "
                f"fleet's max_context capacity {cap}")
        worst = probe._lifetime_pages(len(prompt),
                                      int(params.max_new_tokens))
        if worst > self.pool_pages:
            raise RuntimeError(
                f"request {rid} can never be scheduled: it needs up "
                f"to {worst} page(s) but each replica's pool has "
                f"{self.pool_pages}")
        req = Request(req_id=rid, prompt=prompt, params=params,
                      arrival_t=self._clock(), queued_step=self._steps)
        req.key = host_prng_key(params.seed)
        tracing.open_span(req.spans, tracing.QUEUED,
                          req.arrival_t * 1e3, self.label)
        self._next_id += 1
        self.requests[rid] = req
        self._tenant[rid] = str(tenant)
        self._order[rid] = len(self._order)
        self._skey[rid] = self._session_key(prompt)
        q = self._queues.get(str(tenant))
        if q is None:
            q = self._queues[str(tenant)] = deque()
            self._rr.append(str(tenant))
        q.append(req)
        monitor.counter("serving.requests").increase()
        return rid

    def cancel(self, req_id: int) -> Optional[Output]:
        """Abort a request at any lifecycle point — queued on the
        fleet, parked mid-migration, or live on a replica."""
        req = self.requests.get(int(req_id))
        if req is None or req.state in (FINISHED, FAILED):
            return None
        home = self._home.get(req.req_id)
        if home is not None and self._replicas[home] is not None:
            out = self._replicas[home].cancel(req.req_id)
            if out is not None:
                self._retired(out)
                return out
        self._drop_from_queues(req)
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
        as fleet ticks produce them, driving ``step()`` itself while
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
        """Async streaming iterator — yields tokens as they decode and
        control between ticks so many consumers interleave over one
        event loop."""
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
        """One fleet tick: chaos + stall sweep, deadline sweep over
        fleet-held requests, session-aware dispatch, one step per live
        replica, autoscale evaluation. Returns every request that
        finished or failed this tick."""
        outs: List[Output] = []
        step_gap = time.monotonic() - self._last_step_t
        self._last_step_t = time.monotonic()
        c0 = self._tracker.compiles
        sig0 = self._surface_sig()
        inner = 0
        self._maybe_chaos()
        self._sweep_stalled(step_gap)
        outs.extend(self._expire())
        self._dispatch()
        for i, w in self._alive():
            busy = not w.idle
            rc0 = self._tracker.compiles
            for out in w.step():
                self._retired(out, replica=i)
                outs.append(out)
            inner += self._tracker.compiles - rc0
            st = self.replica_stats[i]
            st["steps"] += 1
            st["busy_steps"] += int(busy)
            hb = self._heartbeats.get(i)
            if hb is not None:
                hb.tick()
        self._sample_ttft()
        self._autoscale()
        self._steps += 1
        self._publish_gauges()
        # driver-surface compile accounting (the disagg pattern): the
        # fleet driver itself only compiles when a replica is BORN
        # (pool construction) or the rng-replay surface first runs —
        # both mark warmup via the surface signature; replica-step
        # compiles are each replica's own accounting
        self._compiles += (self._tracker.compiles - c0) - inner
        if self._surface_sig() != sig0:
            self._warm_compiles = self._compiles
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
                f"fleet did not drain in {max_steps} steps "
                f"({len(outs)}/{len(want)} finished)")
        return sorted(outs, key=lambda o: o.req_id)

    # -- routing -------------------------------------------------------------

    def _pbucket(self, n: int) -> int:
        return _ceil_div(n, self.prefill_bucket) * self.prefill_bucket

    def _session_key(self, prompt: List[int]) -> Optional[bytes]:
        """The request's session identity: the prefix cache's chained
        digest of the FIRST page-aligned prompt chunk (None for
        prompts shorter than one page — nothing cacheable to steer
        on). Same hash, same chunking as the per-replica caches, so a
        key collision can at worst cost a cold route, never a wrong
        token."""
        ps = self.page_size
        if len(prompt) < ps:
            return None
        return _chunk_hash(None, prompt[:ps])

    def _can_take_cold(self, w: Engine) -> bool:
        """A cold dispatch wants immediate admission: a free slot and
        an empty local queue."""
        return (not w._waiting
                and any(r is None for r in w._slots))

    def _can_take_warm(self, w: Engine) -> bool:
        """A warm (session-affine) dispatch may queue behind the
        replica's current work — bounded backlog, so affinity can't
        turn into unbounded head-of-line blocking."""
        return len(w._waiting) < w.max_slots

    def _route(self, req: Request) -> Tuple[Optional[int], bool]:
        """Pick a replica for ``req``: (index, routed_warm). None =
        no capacity anywhere this tick (the request stays queued)."""
        alive = [(i, w) for i, w in self._alive()
                 if i not in self._draining]
        if not alive:
            return None, False
        pinned = self._migrate_dst.get(req.req_id)
        if pinned is not None:
            if self._replicas[pinned] is not None \
                    and pinned not in self._draining:
                if self._can_take_warm(self._replicas[pinned]):
                    return pinned, False
                return None, False
            self._migrate_dst.pop(req.req_id, None)
        if self.router == "round_robin":
            pos = getattr(self, "_rr_pos", 0)
            for k in range(len(alive)):
                i, w = alive[(pos + k) % len(alive)]
                if self._can_take_cold(w):
                    self._rr_pos = (pos + k + 1) % len(alive)
                    return i, False
            return None, False
        if self.router == "session":
            skey = self._skey.get(req.req_id)
            if skey is None and req.req_id not in self._skey:
                skey = self._skey[req.req_id] = \
                    self._session_key(req.prompt)
            if skey is not None:
                hint = self._sessions.get(skey)
                best_i, best_score = None, 0
                probe = min((len(req.prompt) - 1) // self.page_size,
                            ROUTE_PROBE_CHUNKS)
                for i, w in alive:
                    depth = 0
                    if w._prefix is not None:
                        depth = w._prefix.lookup(req.prompt,
                                                 max_chunks=probe)
                    # the hint scores like one warm page: it steers a
                    # same-session burst to one replica before the
                    # first prefill has landed in that cache
                    score = depth + (self.page_size if i == hint else 0)
                    if score > best_score:
                        best_i, best_score = i, score
                if best_i is not None \
                        and self._can_take_warm(self._replicas[best_i]):
                    return best_i, True
        # least-loaded fallback (and the "least_loaded" router): most
        # free slots, then most free pages
        free = [(i, w) for i, w in alive if self._can_take_cold(w)]
        if not free:
            return None, False
        i, _ = max(free, key=lambda e: (
            sum(1 for r in e[1]._slots if r is None),
            e[1]._alloc.free_pages, -e[0]))
        return i, False

    def _assign(self, req: Request, index: int, warm: bool,
                front: bool) -> None:
        w = self._replicas[index]
        req.queued_step = w._steps
        if front:
            w._waiting.appendleft(req)
        else:
            w._waiting.append(req)
        w.requests[req.req_id] = req
        self._home[req.req_id] = index
        self._migrate_dst.pop(req.req_id, None)
        if self.router == "session":
            skey = self._skey.get(req.req_id)
            if skey is not None:
                self._sessions[skey] = index
                while len(self._sessions) > 4096:
                    self._sessions.pop(next(iter(self._sessions)))
        st = self.replica_stats[index]
        st["routed_warm" if warm else "routed_cold"] += 1
        monitor.counter("serving.fleet.routed_warm" if warm
                        else "serving.fleet.routed_cold").increase()

    def _dispatch(self) -> None:
        """Hand fleet-queued requests to replicas: parked requests
        first (partial progress resumes at the target's queue front),
        then one request per tenant per round-robin turn."""
        still: "deque[Request]" = deque()
        while self._parked:
            req = self._parked.popleft()
            if req.state in (FINISHED, FAILED):
                continue
            idx, warm = self._route(req)
            if idx is None:
                still.append(req)
                continue
            self._assign(req, idx, warm, front=True)
        self._parked = still
        stalls = 0
        while self._rr and stalls < len(self._rr):
            tenant = self._rr[0]
            self._rr.rotate(-1)
            q = self._queues.get(tenant)
            if not q:
                stalls += 1
                continue
            req = q[0]
            idx, warm = self._route(req)
            if idx is None:
                stalls += 1
                continue
            q.popleft()
            self._assign(req, idx, warm, front=False)
            stalls = 0

    # -- live migration ------------------------------------------------------

    def migrate_request(self, req_id: int,
                        dst: Optional[int] = None) -> bool:
        """Live-migrate one in-flight request off its replica. The
        request is EXTRACTED from the source (slot cleared, pages
        freed NOW), its rng chain replayed from host truth — (seed,
        tokens emitted); the source device is never read — and parked
        on the fleet for re-admission (at ``dst`` when given and
        alive, else wherever the router places it) through the
        resume-prefill machinery: the continued stream is
        bit-identical to the never-migrated run. False = unknown /
        already-retired / not currently on a replica."""
        rid = int(req_id)
        if dst is not None:
            dst = int(dst)
            if not 0 <= dst < len(self._replicas) \
                    or self._replicas[dst] is None:
                raise ValueError(
                    f"migrate_request dst {dst} is not a live replica")
        src = self._home.get(rid)
        if src is None or self._replicas[src] is None:
            return False
        w = self._replicas[src]
        req = w.extract_request(rid, device_key=False)
        if req is None:
            return False
        self._replay_used = True
        req.key = replay_rng_key(req.params.seed, len(req.generated),
                                 req.params.temperature)
        # extract_request opened the MIGRATING span (origin = source
        # replica); tag it as a LIVE migration for the trace
        if req.spans and req.spans[-1].get("phase") == tracing.MIGRATING:
            req.spans[-1].setdefault("detail", {})["kind"] = "live"
        req.preemptions += 1
        req.queued_step = self._steps
        self._home.pop(rid, None)
        if dst is not None:
            self._migrate_dst[rid] = dst
        self._parked.append(req)
        self.replica_stats[src]["migrated_out"] += 1
        monitor.counter("serving.fleet.migrations").increase()
        monitor.counter("serving.preemptions").increase()
        return True

    def drain_replica(self, index: int) -> int:
        """Migrate EVERY live request off replica ``index`` and block
        new dispatches to it (``undrain_replica`` re-opens it). The
        drain never drops a token — each request re-admits elsewhere
        through the same exact-resume path ``migrate_request`` takes.
        Returns the number of requests migrated."""
        index = int(index)
        if not 0 <= index < len(self._replicas) \
                or self._replicas[index] is None:
            raise ValueError(f"drain_replica: no live replica {index}")
        self._draining.add(index)
        w = self._replicas[index]
        rids = sorted(
            (r.req_id for r in w.requests.values()
             if r.state not in (FINISHED, FAILED)),
            key=lambda rid: self._order.get(rid, 10**9))
        n = 0
        for rid in rids:
            if self.migrate_request(rid):
                n += 1
        return n

    def undrain_replica(self, index: int) -> None:
        self._draining.discard(int(index))

    # -- failover ------------------------------------------------------------

    def _maybe_chaos(self) -> None:
        if self._injector is None:
            return
        self._injector.on_step(self._steps)
        if not self._injector.fire("replica.die", record=False):
            return
        alive = [i for i, _ in self._alive()]
        if len(alive) <= 1:
            return             # never kill the last replica
        self._injector.record("replica.die")
        victim = alive[int(
            self._injector.rng.integers(0, len(alive)))]
        self.kill_replica(victim)

    def _sweep_stalled(self, step_gap: float) -> None:
        """Heartbeat verdicts land here: a replica whose heartbeat
        stalled WHILE THE DRIVER KEPT STEPPING is wedged — kill and
        fail over (unless it is the last one — then the stall stays
        flagged for the next tick, when a scale-up may have replaced
        capacity). When the DRIVER itself paused past the timeout
        (idle service, stopped test loop), every heartbeat aged out
        together through no fault of the replicas: clear the flags and
        re-arm instead of self-inflicting a failover."""
        if not self._stalled:
            return
        if self._heartbeat_timeout is not None \
                and step_gap > float(self._heartbeat_timeout):
            self._stalled.clear()
            return
        for i in sorted(self._stalled):
            if self._replicas[i] is None:
                self._stalled.discard(i)
                continue
            if len(self._alive()) <= 1:
                continue
            self._stalled.discard(i)
            self.kill_replica(i)

    def kill_replica(self, index: int) -> int:
        """Drop a replica WHOLESALE — pools, allocator, prefix cache,
        device state, no goodbye — and re-admit every request that
        lived there from host truth alone (prompt + emitted tokens +
        the replayed rng chain; the dead device is never read). Each
        re-admitted request finishes token-exact. Returns the number
        re-admitted. The last live replica cannot be killed."""
        index = int(index)
        if not 0 <= index < len(self._replicas):
            raise ValueError(
                f"kill_replica index {index} out of range for "
                f"{len(self._replicas)} replica slot(s)")
        w = self._replicas[index]
        if w is None:
            return 0
        if len(self._alive()) <= 1:
            raise RuntimeError(
                "cannot kill the last replica — the fleet must keep "
                "serving")
        monitor.counter("serving.fleet.replica_deaths").increase()
        doomed = sorted(
            (r.req_id for r in w.requests.values()
             if r.state not in (FINISHED, FAILED)),
            key=lambda rid: (self._order.get(rid, 10**9), rid))
        n = 0
        zero_progress: List[Request] = []
        self._replay_used = self._replay_used or bool(doomed)
        for rid in doomed:
            # the SAME extraction path migration takes (device never
            # read — the pools are dying anyway; page frees on the
            # doomed allocator are harmless), so failover can never
            # drift from the live-migration state transition
            req = w.extract_request(rid, device_key=False)
            if req is None:
                continue
            req.preemptions += 1
            req.key = replay_rng_key(req.params.seed,
                                     len(req.generated),
                                     req.params.temperature)
            # has-progress: the extraction's MIGRATING span (origin =
            # dead replica) carries the failover; zero-progress goes
            # straight back to QUEUED — it never really moved
            if req.generated:
                if req.spans and \
                        req.spans[-1].get("phase") == tracing.MIGRATING:
                    req.spans[-1].setdefault(
                        "detail", {})["kind"] = "failover"
            else:
                tracing.open_span(req.spans, tracing.QUEUED,
                                  self._clock() * 1e3, self.label,
                                  kind="failover")
            req.queued_step = self._steps
            self._home.pop(req.req_id, None)
            self._migrate_dst.pop(req.req_id, None)
            if req.generated:
                # partial progress earns the parked fast lane
                self._parked.append(req)
            else:
                # an assigned-but-unstarted request holds nothing — it
                # rejoins ITS TENANT's queue front (it is the tenant's
                # oldest); failover must not let it jump other
                # tenants' older work
                zero_progress.append(req)
            monitor.counter("serving.fleet.readmitted").increase()
            n += 1
        for req in reversed(zero_progress):
            tenant = self._tenant.get(req.req_id, "default")
            q = self._queues.get(tenant)
            if q is None:
                q = self._queues[tenant] = deque()
                self._rr.append(tenant)
            q.appendleft(req)
        # the dead replica's tick in flight dies with it, unharvested:
        # every request left with the tokens the host held
        w._inflight = None
        w._prefilled = []
        self._remove_replica(index)
        return n

    # -- autoscaling ---------------------------------------------------------

    def _sample_ttft(self) -> None:
        """Collect TTFT samples (fleet clock) the moment a request
        reaches its first token — the autoscaler's latency signal must
        not wait for requests to FINISH."""
        if self._policy is None:
            return
        window = int(self._policy.ttft_window)
        for rid, req in self.requests.items():
            if req.first_token_t > 0.0 and rid not in self._ttft_sampled:
                self._ttft_sampled.add(rid)
                if self._ttft_hist.count >= window:
                    # rotate: the previous window ages out wholesale
                    self._ttft_hist_prev = self._ttft_hist
                    self._ttft_hist = monitor.Histogram(
                        "fleet.autoscale.ttft")
                self._ttft_hist.record(
                    (req.first_token_t - req.arrival_t) * 1e3)

    def _autoscale(self) -> None:
        pol = self._policy
        if pol is None:
            return
        live = self._alive()
        qd = self.num_waiting
        pressure = qd > int(pol.scale_up_queue_depth)
        if not pressure and pol.scale_up_ttft_p95_ms is not None:
            merged = monitor.Histogram("fleet.autoscale.ttft.merged")
            merged.merge(self._ttft_hist).merge(self._ttft_hist_prev)
            if merged.count >= 4:
                p95 = merged.percentile(95)
                pressure = p95 > float(pol.scale_up_ttft_p95_ms)
        self._up_streak = self._up_streak + 1 if pressure else 0
        load = sum(w.num_active + w.num_prefilling + len(w._waiting)
                   for _, w in live)
        fits = (len(live) > int(pol.min_replicas) and qd == 0
                and 2 * load <= (len(live) - 1) * self.max_slots)
        self._down_streak = self._down_streak + 1 if fits else 0
        if self._cooldown > 0:
            self._cooldown -= 1
            return
        if self._up_streak >= int(pol.patience) \
                and len(live) < int(pol.max_replicas):
            idx = next((i for i, w in enumerate(self._replicas)
                        if w is None), None)
            idx = self._spawn_replica(idx)
            self.scale_log.append({
                "step": self._steps, "action": "up", "replica": idx,
                "queue_depth": qd, "replicas": len(self._alive())})
            monitor.counter("serving.fleet.scale_events").increase()
            self._up_streak = 0
            self._down_streak = 0
            self._cooldown = int(pol.cooldown)
        elif self._down_streak >= int(pol.scale_down_patience):
            # drain-via-migration: the victim's requests re-admit
            # elsewhere token-exact BEFORE the replica closes — a
            # scale-down never drops a request
            idx, w = min(live, key=lambda e: (
                e[1].num_active + e[1].num_prefilling
                + len(e[1]._waiting), e[0]))
            moved = self.drain_replica(idx)
            self._remove_replica(idx)
            self.scale_log.append({
                "step": self._steps, "action": "down", "replica": idx,
                "migrated": moved, "replicas": len(self._alive())})
            monitor.counter("serving.fleet.scale_events").increase()
            self._up_streak = 0
            self._down_streak = 0
            self._cooldown = int(pol.cooldown)

    # -- reliability surfaces ------------------------------------------------

    def snapshot(self) -> dict:
        """Crash-exact host-state snapshot of the whole fleet — every
        queued / parked-mid-migration / live request's host source of
        truth. Rng chains are REPLAYED from (seed, emitted tokens),
        never fetched from a device, so the same path serves live
        snapshots and post-mortem ones."""
        from dataclasses import asdict
        reqs: List[Request] = []
        seen: set = set()
        for _, w in self._alive():
            reqs.extend(r for r in w.requests.values()
                        if r.state not in (FINISHED, FAILED))
        reqs.extend(self._parked)
        for q in self._queues.values():
            reqs.extend(q)
        reqs.sort(key=lambda r: (self._order.get(r.req_id, 10**9),
                                 r.req_id))
        now = self._clock()
        entries = []
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
                "parked": req in self._parked,
                "preemptions": int(req.preemptions),
                "elapsed_ms": (now - req.arrival_t) * 1e3,
                "spans": tracing.copy_spans(req.spans),
            })
        monitor.counter("serving.snapshot_saves").increase()
        return {
            "version": FLEET_SNAPSHOT_VERSION,
            "kind": "fleet",
            "topology": {"replicas": len(self._alive())},
            "fingerprint": self._fingerprint(),
            "next_id": int(self._next_id),
            "requests": entries,
        }

    def restore(self, snap: dict) -> int:
        """Re-admit a snapshot's requests into this (fresh) fleet:
        requests with emitted tokens — including those snapshotted
        PARKED mid-migration — resume via the parked lane with
        replayed rng chains; untouched ones queue under their tenant.
        Outputs are bit-identical to the uninterrupted run. Replica
        count may differ (scheduling changes, tokens do not)."""
        if snap.get("kind") != "fleet" or \
                snap.get("version") != FLEET_SNAPSHOT_VERSION:
            raise ValueError(
                f"not a fleet snapshot (kind={snap.get('kind')!r} "
                f"version={snap.get('version')!r})")
        if self.requests:
            raise RuntimeError(
                "restore onto a busy fleet: "
                f"{len(self.requests)} live request(s) present")
        fp = self._fingerprint()
        saved = snap.get("fingerprint", {})
        diff = {k: (saved.get(k), v) for k, v in fp.items()
                if saved.get(k) != v}
        if diff:
            raise ValueError(
                f"snapshot is token-incompatible with this fleet: "
                f"{diff} (saved vs current)")
        self._replay_used = True
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
            self._skey[req.req_id] = self._session_key(req.prompt)
            if req.generated:
                self._parked.append(req)
            else:
                q = self._queues.get(tenant)
                if q is None:
                    q = self._queues[tenant] = deque()
                    self._rr.append(tenant)
                q.append(req)
            n += 1
        self._next_id = max(self._next_id, int(snap.get("next_id", 0)))
        monitor.counter("serving.snapshot_restores").increase()
        return n

    def _fingerprint(self) -> Dict[str, object]:
        cfg = self.model.config
        live = next(w for w in self._replicas if w is not None)
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
        """Fleet-wide drained leak check (Engine.leaked_pages per live
        replica — dead replicas' pools died with them)."""
        return sum(w.leaked_pages() for _, w in self._alive())

    def check_invariants(self, repair: bool = False) -> List[str]:
        findings: List[str] = []
        for i, w in self._alive():
            findings += [f"replica{i}: {f}"
                         for f in w.check_invariants(repair=repair)]
        return findings

    def _surface_sig(self) -> Tuple[int, bool]:
        """Driver compiled-surface inventory: growth marks a
        legitimate warmup step (a replica born, or the rng-replay
        surface first exercised)."""
        return (self._replicas_created, self._replay_used)

    def steady_state_recompiles(self) -> int:
        """Sum of every live replica's steady-state recompiles plus
        the driver's own — the number that must be 0 across
        route/migrate/kill/scale traces."""
        own = self._compiles - self._warm_compiles
        return own + sum(w.steady_state_recompiles()
                         for _, w in self._alive())

    def per_replica_recompiles(self) -> Dict[int, int]:
        return {i: w.steady_state_recompiles()
                for i, w in self._alive()}

    # -- hot-path lint (docs/ANALYSIS.md "Hot-path rules") -------------------

    def _hotpath_inventory(self):
        """The fleet DRIVER compiles nothing of its own — its hot-path
        surface is the routing/sweep tick source; the replicas are
        full Engines, swept separately by inspect_hotpath()."""
        from ..analysis import hotpath_lint as hp
        return hp.HotpathInventory(
            subject="ServingFleet[driver]", executables=[],
            tick_functions=[self.step, self._dispatch,
                            self._sweep_stalled, self._expire,
                            self._sample_ttft, self._autoscale],
            steady_functions=(), cache_keys={}, file=__file__)

    def inspect_hotpath(self):
        """Hot-path audit over the fleet: driver tick path plus every
        live replica's Engine inventory, one combined Report through
        the ``lint.hotpath.*`` counters."""
        from ..analysis import hotpath_lint
        report = hotpath_lint.lint_inventory(self._hotpath_inventory())
        for _, w in self._alive():
            report.extend(hotpath_lint.lint_inventory(
                w._hotpath_inventory()))
        return hotpath_lint.emit_hotpath(report)

    def close(self):
        self._tracker.stop()
        for hb in self._heartbeats.values():
            hb.stop()
        self._heartbeats.clear()
        for _, w in self._alive():
            w.close()

    def __del__(self):
        try:
            self._tracker.stop()
            for hb in self._heartbeats.values():
                hb.stop()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    # -- bookkeeping ---------------------------------------------------------

    def _expire(self) -> List[Output]:
        """Deadline/queue-budget sweep over FLEET-held requests
        (tenant queues + parked; replicas sweep their own)."""
        outs: List[Output] = []
        now = self._clock()
        held = [r for q in self._queues.values() for r in q]
        held += list(self._parked)
        for req in held:
            if req.state in (FINISHED, FAILED):
                continue
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
            self._drop_from_queues(req)
            req.state = FAILED
            req.finish_reason = reason
            req.finish_t = now
            monitor.counter("serving.failed").increase()
            out = self._make_output(req, reason, failed=True)
            self._retired(out)
            outs.append(out)
        return outs

    def _drop_from_queues(self, req: Request) -> None:
        for q in self._queues.values():
            try:
                q.remove(req)
            except ValueError:
                pass
        try:
            self._parked.remove(req)
        except ValueError:
            pass
        self._migrate_dst.pop(req.req_id, None)
        home = self._home.get(req.req_id)
        if home is not None and self._replicas[home] is not None:
            w = self._replicas[home]
            if req.slot is None and not req.pages:
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
    #: many the OLDEST are evicted (step()'s return value is the
    #: durable delivery path)
    MAX_RETAINED_OUTPUTS = 4096

    def _retired(self, out: Output,
                 replica: Optional[int] = None) -> None:
        self._outputs[out.req_id] = out
        self.requests.pop(out.req_id, None)
        self._home.pop(out.req_id, None)
        self._migrate_dst.pop(out.req_id, None)
        self._skey.pop(out.req_id, None)
        self._ttft_sampled.discard(out.req_id)
        if replica is not None:
            self.replica_stats[replica]["finished"] += 1
        tenant = self._tenant.pop(out.req_id, None)
        self._order.pop(out.req_id, None)
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
        monitor.gauge("serving.fleet.queue_depth").set(self.num_waiting)
        monitor.gauge("serving.fleet.replicas").set(len(self._alive()))
        monitor.gauge("serving.fleet.parked").set(len(self._parked))
        for i, w in self._alive():
            monitor.gauge(
                f"serving.fleet.replica{i}.queue_depth").set(
                len(w._waiting))
            monitor.gauge(
                f"serving.fleet.replica{i}.prefix_hit_rate").set(
                w.prefix_hit_rate)

    # -- introspection -------------------------------------------------------

    @property
    def num_replicas(self) -> int:
        return len(self._alive())

    @property
    def num_waiting(self) -> int:
        return (sum(len(q) for q in self._queues.values())
                + len(self._parked))

    @property
    def num_parked(self) -> int:
        return len(self._parked)

    @property
    def num_active(self) -> int:
        return sum(w.num_active for _, w in self._alive())

    @property
    def num_prefilling(self) -> int:
        return sum(w.num_prefilling for _, w in self._alive())

    @property
    def idle(self) -> bool:
        return (self.num_waiting == 0
                and all(w.idle for _, w in self._alive()))

    @property
    def pages_free(self) -> Dict[str, int]:
        return {f"replica{i}": w._alloc.free_pages
                for i, w in self._alive()}

    @property
    def prefix_hit_rate(self) -> float:
        """FLEET-WIDE prefix reuse: total hits over total lookups
        across every replica that ever served (dead replicas' totals
        are folded in at removal) — the number session-aware routing
        exists to maximize."""
        hits = self._retired_hits
        lookups = self._retired_lookups
        for _, w in self._alive():
            if w._prefix is not None:
                hits += w._prefix.hits
                lookups += w._prefix.lookups
        return hits / lookups if lookups else 0.0

    @property
    def spec_accept_rate(self) -> float:
        drafted = sum(w._spec_drafted for _, w in self._alive())
        accepted = sum(w._spec_accepted for _, w in self._alive())
        return accepted / drafted if drafted else 0.0

    @property
    def pallas_eligible(self) -> bool:
        return all(w.pallas_eligible for _, w in self._alive())

    @property
    def decode_fallback_reason(self) -> Optional[str]:
        for _, w in self._alive():
            if w.decode_fallback_reason:
                return w.decode_fallback_reason
        return None

    def utilization(self) -> Dict[str, Dict[str, object]]:
        """Per-replica utilization snapshot for the replay report:
        busy-step fraction, warm/cold routing counts, migrations out,
        finishes, live prefix hit rate and queue depth; dead replicas
        report ``alive: False``."""
        out: Dict[str, Dict[str, object]] = {}
        for i in sorted(self.replica_stats):
            st = self.replica_stats[i]
            w = (self._replicas[i]
                 if i < len(self._replicas) else None)
            out[f"replica{i}"] = {
                "alive": w is not None,
                "utilization": round(
                    st["busy_steps"] / max(st["steps"], 1), 4),
                "routed_warm": st["routed_warm"],
                "routed_cold": st["routed_cold"],
                "migrated_out": st["migrated_out"],
                "finished": st["finished"],
                "prefix_hit_rate": (round(w.prefix_hit_rate, 4)
                                    if w is not None else None),
                "queue_depth": (len(w._waiting)
                                if w is not None else None),
            }
        return out
