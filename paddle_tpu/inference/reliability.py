"""Serving-engine reliability layer: fault injection + crash recovery.

The engine (inference/engine.py) multiplexes dynamic traffic onto a
small fixed set of compiled executables — which makes its HOST-side
bookkeeping (request lifecycles, the page allocator, the prefix-cache
index, per-slot rng chains) the single source of truth. Production
serving has to survive that bookkeeping being attacked from every
side: malformed requests, pool exhaustion, NaN-emitting slots, device
errors, and whole-process restarts. This module provides the two
mechanisms the engine's hardening is built and PROVEN on:

* **Deterministic fault injection** — a seeded :class:`FaultInjector`
  with named fault points wired through the engine's allocator,
  prefix cache, prefill/decode/verify executables and the draft loop.
  Faults are drawn from one ``numpy`` Generator in scheduler order (or
  forced by a :class:`FaultPlan` schedule), so a chaos run replays
  bit-identically from its seed: the soak tests and
  ``tools/serving_replay.py --chaos`` assert zero leaked pages, zero
  refcount skew and token-exact outputs for every SURVIVING request
  after hundreds of injected faults.

      ============================  =========================================
      fault point                   what fires
      ============================  =========================================
      ``alloc.exhausted``           the next page allocation raises the
                                    pool-exhausted RuntimeError even though
                                    pages are free (admission races, fragmented
                                    pools) — prefills requeue, decode growth
                                    preempts
      ``alloc.refcount_skew``       a stray extra reference lands on a live
                                    page (a lost ``free`` / doubled ``share``)
                                    — the per-step invariant audit must detect
                                    and repair it
      ``prefix.hash_collision``     the next root-chunk digest collides with a
                                    constant — the exact-token compare must
                                    degrade the hit to a miss
      ``prefix.stale_entry``        one cached entry's token metadata is
                                    corrupted — it must never be hit again and
                                    must be reclaimed
      ``prefill.nan``               the prefill chunk's sampling logits turn
                                    NaN — the request is quarantined, pages
                                    freed
      ``decode.nan``                one live slot's decode logits turn NaN —
                                    that slot alone fails; the rest keep
                                    serving
      ``prefill.device_error`` /    the executable call raises (simulated
      ``decode.device_error``       device loss) BEFORE dispatch, so host
                                    state stays coherent — prefills requeue,
                                    decode skips the tick and retries
      ``spec.disagree``             the drafted tokens are replaced with
                                    garbage (a draft/target divergence storm)
                                    — exact-match verification must reject
                                    them with output unchanged
      ============================  =========================================

* **Crash-exact snapshot/restore** — :func:`snapshot_engine` serializes
  the host-side source of truth (queued + live request tokens, rng key
  chains, sampling params, admission order, prefix-cache index
  metadata — NOT the KV pools, which are device state a crash loses
  anyway) as one JSON-able dict; :func:`restore_engine` re-admits every
  request on a fresh engine through the EXISTING preemption/resume-
  prefill machinery (tokens + rng kept, cache rebuilt by a resume
  prefill), so the restarted engine's outputs are bit-identical to an
  uninterrupted run — greedy and seeded sampling, with prefix hits and
  speculative decoding on. ``Engine.snapshot()/restore()`` are the
  public surface; ``distributed.watchdog.Heartbeat`` triggers a
  best-effort snapshot-and-report when a ``run()`` loop stalls.

Driven by flags/env (chaos in any engine-embedding process without
code changes — ``FLAGS_serving_fault_seed=7`` arms every Engine built
without an explicit ``fault_injector``; pass ``fault_injector=False``
to force one engine clean), or explicitly by the replay tool, which
always builds its clean passes with injection forced OFF::

    python tools/serving_replay.py trace.jsonl --chaos \
        --fault-seed 7 --fault-rate 0.05

Counters (docs/OBSERVABILITY.md): ``serving.fault_injected.<site>``,
``serving.invariant_repairs``, ``serving.snapshot_saves``,
``serving.snapshot_restores``, ``serving.stalls`` — next to the
lifecycle counters the engine's hardening emits
(``serving.timeouts`` / ``serving.cancelled`` / ``serving.failed`` /
``serving.nan_quarantines`` / ``serving.step_errors``).
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import monitor
from . import tracing
from ..core.flags import define_flag, get_flag

define_flag("serving_fault_seed", -1,
            "Seed for the serving engine's deterministic FaultInjector; "
            "-1 disables injection (production default)")
define_flag("serving_fault_rate", 0.02,
            "Per-query probability each armed fault point fires "
            "(FLAGS_serving_fault_seed >= 0 arms the injector)")
define_flag("serving_fault_sites", "",
            "Comma-separated fault-point filter for the injector; "
            "empty = every site armed")
define_flag("serving_debug_invariants", False,
            "Audit engine/allocator invariants after every step() and "
            "raise on the first finding (CI / debugging; the chaos "
            "paths audit WITH repair instead)")

#: every named fault point the engine queries, in the order a step
#: visits them (documentation + the injector's site validation)
FAULT_SITES = (
    "alloc.exhausted",
    "alloc.refcount_skew",
    "prefix.hash_collision",
    "prefix.stale_entry",
    "prefill.nan",
    "prefill.device_error",
    "decode.nan",
    "decode.device_error",
    "spec.disagree",
    # disaggregated serving (inference/disagg.py): a whole worker dies
    # — pools, allocator, device state lost — and its requests must
    # re-admit elsewhere token-exact. Never fires on the last worker
    # of a kind (recorded only when a kill actually landed).
    "worker.die_prefill",
    "worker.die_decode",
    # elastic fleet (inference/fleet.py): one whole engine REPLICA dies
    # — pools, allocator, prefix cache, device state lost — and its
    # requests must re-admit on surviving replicas token-exact from
    # host truth alone. Never fires on the last live replica (recorded
    # only when a kill actually landed).
    "replica.die",
)

SNAPSHOT_VERSION = 1


class InjectedFault(RuntimeError):
    """An injected failure (never raised in production). ``site`` names
    the fault point; ``transient`` marks faults the engine should
    absorb by retrying (requeue / next tick) rather than failing the
    request."""

    def __init__(self, site: str, transient: bool = True):
        super().__init__(f"injected fault at {site}")
        self.site = site
        self.transient = transient


@dataclass
class FaultPlan:
    """Explicit fault schedule: fire ``site`` the first time it is
    queried AT or AFTER engine step ``step`` (each entry fires once).
    Entries compose with (and take precedence over) the injector's
    rate-based draws, so a test can pin one fault to one step while a
    soak sprays the rest. Parseable from a flag-friendly string::

        FaultPlan.parse("12:decode.nan,30:alloc.exhausted")
    """

    entries: List[Tuple[int, str]] = field(default_factory=list)

    def __post_init__(self):
        for step, site in self.entries:
            if site not in FAULT_SITES:
                raise ValueError(
                    f"unknown fault site {site!r} in plan — known "
                    f"sites: {', '.join(FAULT_SITES)}")
        self._pending = sorted(
            ((int(s), site) for s, site in self.entries),
            key=lambda e: e[0])

    @classmethod
    def parse(cls, spec: str) -> "FaultPlan":
        entries = []
        for item in spec.split(","):
            item = item.strip()
            if not item:
                continue
            step, _, site = item.partition(":")
            entries.append((int(step), site.strip()))
        return cls(entries)

    def pop(self, site: str, step: int) -> bool:
        for i, (s, target) in enumerate(self._pending):
            if target == site and step >= s:
                del self._pending[i]
                return True
            if s > step:
                break
        return False

    @property
    def pending(self) -> List[Tuple[int, str]]:
        return list(self._pending)


class FaultInjector:
    """Seeded, replayable chaos source for the serving engine.

    The engine queries ``fire(site)`` at each named fault point; the
    injector answers from ONE ``numpy`` rng consumed in query order,
    so the same (seed, rate, sites, plan, trace) always produces the
    same fault schedule — a failing chaos run is reproduced by its
    seed alone. ``counts`` records what actually fired (also emitted
    as ``serving.fault_injected.<site>`` counters).
    """

    def __init__(self, seed: int = 0, rate: float = 0.0,
                 sites: Optional[Sequence[str]] = None,
                 plan: Optional[FaultPlan] = None):
        unknown = set(sites or ()) - set(FAULT_SITES)
        if unknown:
            raise ValueError(
                f"unknown fault site(s) {sorted(unknown)} — known "
                f"sites: {', '.join(FAULT_SITES)}")
        self.seed = int(seed)
        self.rate = float(rate)
        self.sites = frozenset(sites) if sites else frozenset(FAULT_SITES)
        self.plan = plan
        self.rng = np.random.default_rng(self.seed)
        self.counts: Dict[str, int] = {}
        self.step = 0

    def enabled(self, site: str) -> bool:
        return site in self.sites

    def on_step(self, step: int) -> None:
        """Engine hook: the current scheduler tick (plan entries key
        on it; purely informational for rate draws)."""
        self.step = int(step)

    def fire(self, site: str, record: bool = True) -> bool:
        """One fault-point query. Plan entries fire unconditionally;
        otherwise an armed site fires with probability ``rate``. The
        rng is consumed for every armed rate query — fired or not —
        so the draw sequence (and thus the whole chaos schedule) is a
        pure function of the seed and the query order."""
        if site not in FAULT_SITES:
            raise ValueError(f"unknown fault site {site!r}")
        hit = False
        if self.plan is not None and self.plan.pop(site, self.step):
            hit = True
        elif site in self.sites and self.rate > 0.0:
            hit = bool(self.rng.random() < self.rate)
        if hit and record:
            self.record(site)
        return hit

    def record(self, site: str) -> None:
        """Count an APPLIED fault. Sites whose application can be a
        no-op (no live pages to skew, an empty cache to corrupt) draw
        with ``fire(site, record=False)`` and call this only once the
        fault actually landed — the chaos report must never claim
        faults that did not happen."""
        self.counts[site] = self.counts.get(site, 0) + 1
        monitor.counter(f"serving.fault_injected.{site}").increase()

    @property
    def total_injected(self) -> int:
        return sum(self.counts.values())

    def __repr__(self):
        return (f"FaultInjector(seed={self.seed}, rate={self.rate}, "
                f"injected={self.total_injected})")


def injector_from_flags() -> Optional[FaultInjector]:
    """Build an injector from ``FLAGS_serving_fault_*`` (env-settable:
    ``FLAGS_serving_fault_seed=7``); None when injection is off (the
    default, seed -1)."""
    seed = int(get_flag("serving_fault_seed"))
    if seed < 0:
        return None
    sites_spec = str(get_flag("serving_fault_sites")).strip()
    sites = tuple(s.strip() for s in sites_spec.split(",")
                  if s.strip()) or None
    return FaultInjector(seed=seed,
                         rate=float(get_flag("serving_fault_rate")),
                         sites=sites)


# --------------------------------------------------------------------------
# crash-exact snapshot / restore
# --------------------------------------------------------------------------

def _fingerprint(eng) -> Dict[str, object]:
    """The compatibility signature a snapshot is only valid against.
    ``hard`` fields change the TOKENS a request would emit (model
    geometry, cache dtype, sampler surface) — restore refuses a
    mismatch; ``soft`` fields only change scheduling (pool geometry)
    — restore warns, because the preemption-exact engine emits the
    same tokens under any page/slot budget."""
    cfg = eng.model.config
    return {
        "hard": {
            "vocab_size": int(cfg.vocab_size),
            "num_hidden_layers": int(cfg.num_hidden_layers),
            "hidden_size": int(cfg.hidden_size),
            "num_attention_heads": int(cfg.num_attention_heads),
            "num_key_value_heads": int(cfg.num_key_value_heads),
            "cache_dtype": str(np.dtype(eng.cache_dtype).name),
            "spec_k": int(eng._spec.k) if eng._spec is not None else 0,
        },
        "soft": {
            "max_slots": eng.max_slots,
            "page_size": eng.page_size,
            "pool_pages": eng.pool_pages,
            "max_context": eng.max_context,
            "prefill_bucket": eng.prefill_bucket,
            "prefix_cache": eng._prefix is not None,
            "max_prefill_tokens_per_step":
                eng.max_prefill_tokens_per_step,
        },
    }


def snapshot_engine(eng, sync: bool = True) -> Dict[str, object]:
    """Serialize the engine's host-side source of truth as one
    JSON-able dict: every live + queued request (prompt, generated
    tokens, sampling params, CURRENT rng key — pulled from the
    device-resident chain for active slots — admission order, latency
    ages) plus the prefix-cache index metadata. KV pools are NOT
    serialized: they are device state a crash loses anyway, and the
    resume-prefill machinery rebuilds them token-exactly on restore.

    Called between ``step()`` calls (every request is WAITING,
    PREEMPTED, DECODE, or — under chunked prefill — mid-PREFILL at a
    slice boundary, where it serializes as a queued request: no rng
    was consumed yet, so a from-scratch resume prefill is exact), this
    is non-destructive: the engine keeps serving afterwards.

    ``sync=False`` (the stall-dump path) never touches the device —
    a wedged executable would block the fetch — and falls back to the
    host-mirror rng keys, which lag the device chain for mid-flight
    SAMPLING requests: best-effort diagnostics, not bit-exact.
    """
    from dataclasses import asdict

    from .engine import DECODE, PREEMPTED, WAITING
    now = eng._clock()
    keys_dev = None
    entries: List[Dict[str, object]] = []
    # queue order on restore = live requests first (they were running;
    # the resume machinery puts preempted work at the queue FRONT), in
    # admission order, then the waiting queue as-is
    live = sorted((r for r in eng._slots if r is not None),
                  key=lambda r: r.admit_seq)
    for req in list(live) + list(eng._waiting):
        if (sync and req.state == DECODE and req.slot is not None
                and req.slot not in eng._dirty):
            # the rng chain lives device-side between decode ticks;
            # one bulk fetch covers every live slot
            if keys_dev is None:
                keys_dev = np.asarray(eng._dev[5])
            key = keys_dev[req.slot]
        else:
            key = req.key
        entries.append({
            "req_id": int(req.req_id),
            "prompt": [int(t) for t in req.prompt],
            "generated": [int(t) for t in req.generated],
            "params": asdict(req.params),
            "key": [int(k) for k in np.asarray(key, np.uint32)],
            "live": req.state == DECODE,
            "preemptions": int(req.preemptions),
            "retries": int(req.retries),
            "elapsed_ms": (now - req.arrival_t) * 1e3,
            # a RUNNING (decoding OR mid-chunked-prefill) request has
            # no queue age — it re-enters the restored queue with a
            # fresh max_queue_steps budget (it was making progress;
            # only genuinely WAITING/PREEMPTED requests keep their
            # accumulated wait — counting a whale's in-slot prefill
            # ticks here would let restore spuriously queue_timeout a
            # request the uninterrupted run completes)
            "waited_steps": (eng._steps - req.queued_step
                             if req.state in (WAITING, PREEMPTED)
                             and req.queued_step >= 0 else 0),
            # span timeline: plain host state, rides the snapshot so a
            # restored request's stitched timeline stays contiguous
            "spans": tracing.copy_spans(req.spans),
        })
    prefix_index: List[Dict[str, object]] = []
    if eng._prefix is not None:
        for ent in eng._prefix._store.values():
            prefix_index.append({
                "key": ent.key.hex(),
                "parent": (ent.parent.hex()
                           if ent.parent is not None else None),
                "depth": int(ent.depth),
                "chunk": [int(t) for t in ent.chunk],
            })
    snap = {
        "version": SNAPSHOT_VERSION,
        "fingerprint": _fingerprint(eng),
        "next_id": int(eng._next_id),
        "admit_counter": int(eng._admit_counter),
        "steps": int(eng._steps),
        "requests": entries,
        # index METADATA only — the cached pages' KV content lives in
        # device pools a restart loses; restore starts with an empty
        # cache that re-fills from resume prefills (hit/miss never
        # changes tokens, so exactness is unaffected)
        "prefix_index": prefix_index,
    }
    monitor.counter("serving.snapshot_saves").increase()
    return snap


def restore_engine(eng, snap: Dict[str, object],
                   strict: bool = True) -> int:
    """Re-admit every snapshotted request into ``eng`` (normally a
    freshly constructed engine over the same weights after a restart).
    Requests with generated tokens enter as PREEMPTED — the existing
    resume-prefill path rebuilds their KV from the kept tokens and the
    saved rng key continues the chain exactly — and untouched requests
    enter as WAITING, in the snapshot's queue order, so the restarted
    engine's emissions are bit-identical to the uninterrupted run.
    Returns the number of requests re-admitted.

    ``strict=True`` raises on any fingerprint mismatch; strict or not,
    a HARD mismatch (model geometry / cache dtype / spec_k — anything
    that changes tokens) always raises.
    """
    import warnings

    from .engine import PREEMPTED, WAITING, Request, SamplingParams
    if snap.get("version") != SNAPSHOT_VERSION:
        raise ValueError(
            f"snapshot version {snap.get('version')!r} does not match "
            f"this engine's {SNAPSHOT_VERSION}")
    if eng.requests or any(r is not None for r in eng._slots):
        raise RuntimeError(
            "restore onto a busy engine: "
            f"{len(eng.requests)} live/queued request(s) present — "
            "restore targets a fresh (or fully drained) engine")
    fp = _fingerprint(eng)
    saved = snap.get("fingerprint", {})
    hard_diff = {k: (saved.get("hard", {}).get(k), v)
                 for k, v in fp["hard"].items()
                 if saved.get("hard", {}).get(k) != v}
    if hard_diff:
        raise ValueError(
            f"snapshot is token-incompatible with this engine: "
            f"{hard_diff} (saved vs current) — same model geometry, "
            f"cache dtype and spec_k are required for bit-exact "
            f"restore")
    soft_diff = {k: (saved.get("soft", {}).get(k), v)
                 for k, v in fp["soft"].items()
                 if saved.get("soft", {}).get(k) != v}
    if soft_diff:
        if strict:
            raise ValueError(
                f"snapshot scheduler geometry differs: {soft_diff} "
                f"(saved vs current) — pass strict=False to restore "
                f"anyway (tokens stay exact; only scheduling "
                f"latencies change)")
        warnings.warn(
            f"restoring across scheduler geometries: {soft_diff} "
            f"(saved vs current); outputs stay token-exact",
            RuntimeWarning, stacklevel=2)
    now = eng._clock()
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
            retries=int(ent.get("retries", 0)),
            arrival_t=now - float(ent.get("elapsed_ms", 0.0)) / 1e3,
            queued_step=eng._steps - int(ent.get("waited_steps", 0)),
        )
        req.key = np.asarray(ent["key"], np.uint32)
        req.spans = tracing.restore_spans(
            ent.get("spans"), req.arrival_t * 1e3, now * 1e3,
            eng.label, bool(req.generated))
        eng.requests[req.req_id] = req
        eng._waiting.append(req)
        n += 1
    eng._next_id = max(eng._next_id, int(snap.get("next_id", 0)))
    eng._admit_counter = max(eng._admit_counter,
                             int(snap.get("admit_counter", 0)))
    monitor.counter("serving.snapshot_restores").increase()
    return n


def save_snapshot(snap: Dict[str, object], path: str) -> str:
    """Atomic write (temp file + rename): the stall/crash paths call
    this precisely when the process may be killed mid-write — a
    truncated snapshot, or a previous good one clobbered by a partial
    rewrite, would destroy the recovery trail it exists to leave."""
    import os
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(snap, fh)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)
    return path


def load_snapshot(path: str) -> Dict[str, object]:
    with open(path) as fh:
        return json.load(fh)
