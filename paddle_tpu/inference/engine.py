"""paddle_tpu.inference.engine — in-process continuous-batching serving.

Reference capability: the serving layer the reference framework ships
around ``block_multihead_attention`` (PaddleNLP's dynamic-batch
predictor over paged KV blocks). PR 4 built every serving *primitive*
— head-major page pools with block tables, the scalar-prefetched
Pallas paged-decode kernel, int8 KV — but ``text.generate`` is a
static-batch API: all requests arrive together, pad to one length,
finish together. This module adds the missing host-side scheduler that
multiplexes DYNAMIC requests onto a SMALL FIXED SET of XLA executables
(the JaxPP split: a schedule-driven host driver over fixed compiled
per-stage programs).

Design (docs/SERVING.md has the full lifecycle):

* Request state machine: WAITING → PREFILL → DECODE → FINISHED, with
  PREEMPTED looping back into the waiting queue (pages freed, tokens
  and the RNG key kept, cache rebuilt by a resume prefill on
  re-admission — token-for-token identical to the uninterrupted run).
* Slot scheduler: ``max_slots`` decode lanes; every ``step()`` admits
  waiting requests into free slots while the page pool keeps
  ``watermark_pages`` of headroom (admission control: running
  sequences must be able to grow before new ones join).
* Paged allocator: allocator.PageAllocator over the shared pool; page
  0 is the scratch page every INACTIVE slot's block-table row points
  at, so masked lanes write garbage harmlessly. A sequence's pages are
  freed the step it finishes — not at end-of-call.
* Exactly TWO compiled step families, so steady-state recompiles are
  zero under any arrival mix: length-bucketed prefill executables
  (prompt padded to a ``prefill_bucket`` multiple, ``paged_write`` of
  the prompt KV, first token sampled) and the FUSED ``[max_slots]``
  decode step (single-token forward through the paged attention stack
  — the multi-sequence Pallas kernel on TPU — plus per-slot sampling,
  all in one executable over DEVICE-RESIDENT state: last tokens,
  cache positions, sampling params and rng keys stay on device
  between ticks, advanced in-graph; the host fetches only the emitted
  tokens and uploads only scheduler-touched slot rows. Three static
  sampler variants — all-greedy argmax, no-filter, full-filter —
  each compiled once). ``steady_state_recompiles()`` reads 0 after
  warmup.
* Token-exactness: a request decoded through the engine emits the
  SAME tokens as a ``batch=1 text.generate`` with the same seed —
  the sampler (generation.sample_token_arrays) mirrors pick_next's
  filter semantics and per-request RNG chains, and inactive lanes
  cannot perturb active rows (row-independent attention + scratch
  page). tests/test_serving_engine.py holds this exact.

Two opt-in accelerators ride on the same scheduler (this PR):

* Prefix caching (``prefix_cache=True``; prefix_cache.py): a
  content-addressed store of full KV pages maps the longest cached
  page-aligned prompt prefix straight into a new request's block
  table (allocator refcounts, copy-on-write for the partial tail
  page) so prefill runs only the uncached tail chunk.
* Speculative decoding (``draft_model=...``; speculative.py): a small
  draft proposes ``spec_k`` tokens per slot, the target verifies all
  k+1 positions in ONE forward, and exact-match acceptance keeps the
  output bit-identical to the draft-free engine — 1 to k+1 tokens
  per tick.
* Chunked prefill (``max_prefill_tokens_per_step=N``): long prompts
  are written as a sequence of bounded bucketed slices interleaved
  with decode ticks — a 32K-token whale prefills N tokens per step
  while every running request keeps emitting, so whale arrivals
  cannot starve small-request TTFT. Slices reuse the SAME bucketed
  prefill executables at their traced ``start`` offset (zero new
  compiled surfaces in steady state), a partially prefilled request
  holds its pages across slices and stays cancellable / preemptible /
  snapshot-able at slice boundaries, prefix-cache hits deeper than
  one bucket skip their cached chunks with the remaining tail still
  sliced, and the sliced prefix is token-exact vs the monolithic one
  (the paged prefill path reads in-chunk K/V back from the pools it
  writes). docs/SERVING.md "Chunked prefill".

``monitor`` surface (docs/OBSERVABILITY.md): gauges
``serving.slots_active`` / ``serving.pages_free`` /
``serving.queue_depth`` / ``serving.ttft_ms`` / ``serving.tpot_ms``
/ ``serving.prefix_hit_rate`` / ``serving.prefix_pages_shared`` /
``serving.spec_accept_rate`` /
``serving.prefill_tokens_per_step``, counters ``serving.requests`` /
``serving.tokens`` / ``serving.finished`` / ``serving.preemptions``
/ ``serving.steps`` / ``serving.prefill_tokens`` /
``serving.prefill_slices`` /
``serving.prefix_tokens_reused`` / ``serving.prefix_hits`` /
``serving.prefix_lookups`` / ``serving.spec_drafted`` /
``serving.spec_accepted`` / ``serving.decode_fallback`` (engine
built with a Pallas-ineligible page geometry — validated ONCE at
construction, docs/DECODE.md).

Every ``step()`` also leaves one row in ``Engine.step_log``
(tracing.StepLog; docs/OBSERVABILITY.md "Step record"): its phases,
timed where the ``engine.*`` spans are opened (``_span``), its lanes
and its programs, in a ring kept for the life of the engine; a slow
step is kept whole (``step_log.slow()``, ``serving.slow_steps``), and
``serving.host_ms_per_tick`` / ``serving.device_ms_per_tick`` are read
off the row.

Reliability layer (inference/reliability.py has the fault catalog and
the snapshot format):

* Request lifecycle hardening: per-request ``deadline_ms`` /
  ``max_queue_steps`` enforced on the engine's step clock, a
  ``cancel(request_id)`` API, and a terminal FAILED(reason) state —
  one bad request (capacity error, NaN logits, injected device error)
  is retired with its pages freed while every other slot keeps
  serving; the loop never raises out of ``step()`` for a per-request
  failure. NaN/inf on any slot's sampling logits is detected IN-GRAPH
  (a tiny ``ok`` flag vector rides out of each executable) and
  quarantines exactly the offending slot
  (``serving.nan_quarantines``).
* Deterministic fault injection: a seeded ``FaultInjector``
  (``fault_injector=`` or ``FLAGS_serving_fault_*``) fires named
  faults at the allocator, prefix cache, prefill/decode/verify
  executables and the draft loop; chaos runs replay bit-identically
  from the seed.
* Crash-exact snapshot/restore: ``snapshot()`` serializes the
  host-side source of truth (request tokens, rng chains, sampling
  params, admission order — not KV pools) and ``restore()`` re-admits
  everything through the preemption/resume-prefill machinery, so a
  restarted engine's outputs are bit-identical to an uninterrupted
  run. ``run(heartbeat_timeout=...)`` attaches a
  ``distributed.watchdog.Heartbeat`` that snapshots-and-reports when
  the loop stalls.

All of it stays on the fixed compiled surfaces:
``steady_state_recompiles() == 0`` holds across cancel / timeout /
fail / restore traces (the tests assert it).
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor
from ..core import place, tape as tape_mod
from ..distributed import mesh as _mesh_mod
from ..core.dispatch import unwrap
from ..core.flags import get_flag
from ..jit.functional import get_buffers, get_frozen, get_params
from ..kernels.paged_attention import paged_pallas_requirements
from ..profiler.profiler import RecordEvent
from ..profiler.stats import CompileTracker
from ..text.generation import (_model_forward, _resolve_cache_dtype,
                               sample_token_arrays, verify_token_arrays)
from . import tracing
from .allocator import PageAllocator
from .prefix_cache import PrefixCache
from .reliability import InjectedFault, injector_from_flags

# request lifecycle states
WAITING = "WAITING"
PREFILL = "PREFILL"
DECODE = "DECODE"
FINISHED = "FINISHED"
PREEMPTED = "PREEMPTED"
FAILED = "FAILED"

#: prefill attempts before a transiently failing request is FAILED
#: (injected device errors and unexpected prefill errors requeue up to
#: this many times; a deterministic failure burns through them in 3
#: ticks). Pool-pressure requeues (PoolPressure) are EXEMPT: under
#: chunked prefill, admission deliberately charges only the first
#: slice, so mid-prefill exhaustion is the normal backpressure path —
#: like preemption, it waits for pages, it doesn't consume a failure
#: budget.
MAX_PREFILL_RETRIES = 3


class PoolPressure(RuntimeError):
    """A prefill chunk could not get pages (pool exhausted after
    eviction) — the request backs off and retries WITHOUT burning its
    retry budget; running sequences finishing or preempting will free
    the pages it is waiting for."""


@dataclass
class SamplingParams:
    """Per-request generation config (the engine analog of generate's
    kwargs; every field may differ per request inside one batch)."""

    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    eos_token_id: Optional[int] = None
    seed: int = 0
    # reliability knobs (enforced on the engine's step clock, checked
    # at every tick start): a request past its wall deadline — or one
    # still waiting for a slot after max_queue_steps ticks — is FAILED
    # ("deadline" / "queue_timeout") with its pages freed, instead of
    # occupying capacity forever
    deadline_ms: Optional[float] = None
    max_queue_steps: Optional[int] = None
    # keep the float32 logits row each of this request's tokens was
    # sampled from (Output.logits); needs Engine(keep_logits=True)
    return_logits: bool = False

    def validate(self):
        if int(self.max_new_tokens) < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if float(self.temperature) < 0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.deadline_ms is not None and float(self.deadline_ms) <= 0:
            raise ValueError(
                f"deadline_ms must be > 0, got {self.deadline_ms}")
        if self.max_queue_steps is not None \
                and int(self.max_queue_steps) < 1:
            raise ValueError(
                f"max_queue_steps must be >= 1, got "
                f"{self.max_queue_steps}")
        if not -2 ** 63 <= int(self.seed) < 2 ** 63:
            raise ValueError(
                f"seed must fit 64 signed bits, got {self.seed}")


@dataclass
class Output:
    """One retired request: the generated continuation (including the
    eos token when one was emitted) plus serving latencies. A FAILED
    request also surfaces here — ``finish_reason`` names the failure
    ("cancelled" / "deadline" / "queue_timeout" / "nan_logits" /
    "error:…"), ``error`` carries it too, and ``token_ids`` holds
    whatever was generated before the failure."""

    req_id: int
    prompt_ids: List[int]
    token_ids: List[int]
    finish_reason: str            # "eos" | "length" | failure reason
    ttft_ms: float                # arrival -> first token
    tpot_ms: float                # mean inter-token latency after that
    preemptions: int = 0
    error: Optional[str] = None   # None iff the request FINISHED
    # the request's stitched span timeline (tracing.py contract):
    # QUEUED -> PREFILL slices -> DECODE -> ... -> FINISHED/FAILED,
    # contiguous on the engine's injectable clock, origin-labeled per
    # span across migrations and failovers
    spans: List[dict] = field(default_factory=list)
    # SamplingParams.return_logits: one [vocab] float32 row a token
    logits: Optional[List[np.ndarray]] = None

    @property
    def ok(self) -> bool:
        """True when the request ran to a normal completion."""
        return self.error is None


@dataclass
class Request:
    req_id: int
    prompt: List[int]
    params: SamplingParams
    state: str = WAITING
    generated: List[int] = field(default_factory=list)
    key: Optional[np.ndarray] = None      # [2] uint32 rng chain state
    slot: Optional[int] = None
    pages: List[int] = field(default_factory=list)
    # prefix-cache state: pages acquired (refcounted) at admission for
    # the longest cached prefix, and how many tokens they cover; None
    # until the admission lookup ran (reset on preemption — the resume
    # prefix is re-looked-up against the cache's current contents)
    shared_pages: Optional[List[int]] = None
    prefix_len: int = 0
    written: int = 0                      # tokens in the paged cache
    admit_seq: int = -1                   # admission order (preemption)
    preemptions: int = 0
    retries: int = 0                      # failed prefill attempts
    queued_step: int = -1                 # step the request last queued
    arrival_t: float = 0.0
    first_token_t: float = 0.0
    # when the newest token was appended (engine clock; 0.0 = none on
    # THIS engine yet): the inter-token gap histogram reads it. Reset
    # by extract_request and absent from snapshots, so no gap spans a
    # migration or a restore; a preemption's stall IS a gap
    last_token_t: float = 0.0
    finish_t: float = 0.0
    finish_reason: Optional[str] = None
    # host-truth span log (tracing.py): plain dicts on the engine
    # clock, so the timeline serializes through snapshot/restore and
    # rides extract_request across workers/replicas untouched
    spans: List[dict] = field(default_factory=list)
    # SamplingParams.return_logits: the rows, one a generated token
    logits: List[np.ndarray] = field(default_factory=list)

    def resume_tokens(self) -> List[int]:
        """The prefix a (re-)prefill must write into the cache: the
        prompt plus every generated token except the newest (which is
        consumed — and written — by the next decode step)."""
        if self.generated:
            return self.prompt + self.generated[:-1]
        return self.prompt

    def resume_len(self) -> int:
        """len(resume_tokens()) without materializing the concat —
        the chunked-prefill scheduler reads this every tick."""
        if self.generated:
            return len(self.prompt) + len(self.generated) - 1
        return len(self.prompt)


def _ceil_div(a: int, b: int) -> int:
    return -(-int(a) // int(b))


#: config attributes the legacy (no-serving_spec) probe derives the KV
#: geometry from — named in the error when a model carries neither
_SPEC_CONFIG_ATTRS = ("num_hidden_layers", "num_key_value_heads",
                      "num_attention_heads", "hidden_size",
                      "max_position_embeddings", "vocab_size")


def serving_model_spec(model) -> dict:
    """The engine's model-geometry probe. A model that knows how it
    serves publishes ``model.serving_spec()`` (LlamaForCausalLM,
    ErnieMoEForCausalLM, BertModel do) — a plain dict with at least
    ``kind`` ("decoder" | "encoder") plus, for decoders, the KV
    geometry (``num_layers`` / ``kv_heads`` / ``head_dim`` /
    ``max_context`` / ``vocab_size``) and optionally a ``moe`` block
    (fused-dispatch eligibility diagnostics). A decoder whose layers
    do not share one geometry gives ``cache_layers`` in place of
    ``kv_heads`` / ``head_dim``: the flat list of what it keeps, in the
    order its forward takes ``kv_caches`` (see _make_spec_pools; a block
    may give two entries, paged keys and values and a state). Models
    WITHOUT the hook
    fall back to the llama-shaped config attribute read that used to
    be inlined in ``Engine.__init__`` — with a loud error naming the
    missing attributes instead of an AttributeError mid-constructor."""
    fn = getattr(model, "serving_spec", None)
    if callable(fn):
        spec = dict(fn())
        if spec.get("kind") == "decoder":
            # a spec that lists what it keeps ("cache_layers": a flat
            # list of entries, see _make_spec_pools) has no one
            # kv_heads x head_dim to name
            geometry = ("num_layers", "max_context") \
                if spec.get("cache_layers") is not None else \
                ("num_layers", "kv_heads", "head_dim", "max_context")
            missing = [k for k in geometry
                       if spec.get(k) is None]
            if missing:
                raise ValueError(
                    f"{type(model).__name__}.serving_spec() is missing "
                    f"decoder geometry key(s) {missing}")
        return spec
    cfg = getattr(model, "config", None)
    missing = [a for a in _SPEC_CONFIG_ATTRS
               if getattr(cfg, a, None) is None]
    if cfg is None or missing:
        raise ValueError(
            f"cannot derive a serving spec for "
            f"{type(model).__name__}: no serving_spec() method and "
            f"model.config lacks {missing or 'a config'} — add a "
            f"serving_spec() returning the KV geometry "
            f"(docs/SERVING.md 'Model polymorphism')")
    return {
        "kind": "decoder",
        "num_layers": int(cfg.num_hidden_layers),
        "kv_heads": int(cfg.num_key_value_heads),
        "head_dim": int(cfg.hidden_size) // int(cfg.num_attention_heads),
        "max_context": int(cfg.max_position_embeddings),
        "vocab_size": int(cfg.vocab_size),
    }


def _normalize_prompt(ids) -> List[int]:
    """One prompt as a python int list — the shared admission
    normalization for every serving front door (Engine.add_request and
    the disaggregated driver's): [s] or [1, s] Tensor/array in, loud
    errors for batches and empties. Shapes both doors accept must stay
    identical or the token-exactness contract between them breaks at
    admission."""
    arr = np.asarray(unwrap(ids))
    if arr.ndim == 2 and arr.shape[0] == 1:
        arr = arr[0]
    if arr.ndim != 1:
        raise ValueError(
            f"add_request takes ONE prompt ([s] or [1, s] ids); got "
            f"shape {arr.shape} — queue a batch as separate "
            f"requests (silently concatenating the rows would "
            f"decode from a nonsense combined context)")
    prompt = [int(t) for t in arr]
    if not prompt:
        raise ValueError("empty prompt")
    return prompt


def host_prng_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)`` as a [2] uint32 host array, built
    WITHOUT the device: ``PRNGKey`` is a device program plus a fetch,
    and with a decode tick always in flight (Engine.step) that fetch
    queues behind the tick and stalls the caller of ``add_request``
    for the rest of it. For the default threefry implementation the
    key is the seed's two 32-bit halves (``jax_random_seed_offset``
    added first, as jax does); any other configured implementation
    falls back to ``PRNGKey`` itself. The one key constructor of every
    serving front door (Engine, DisaggEngine, ServingFleet)."""
    seed = int(seed)
    if jax.config.jax_default_prng_impl == "threefry2x32" \
            and jax.config.jax_enable_x64:
        bits = (seed + int(jax.config.jax_random_seed_offset)) \
            & (2 ** 64 - 1)
        return np.array([bits >> 32, bits & 0xFFFFFFFF], np.uint32)
    return np.asarray(jax.random.PRNGKey(seed), np.uint32)


def _make_paged_pools(layers, rows, hkv, page_size, hd, dtype, quant):
    """Per-layer paged KV pool tuples — (k, v[, ks, vs]) zeros in the
    head-major layout kernels/paged_attention.py expects. The ONE
    constructor for both the target's pools and the draft's
    (speculative.py mirrors the engine's layout exactly — a layout
    change here reaches both models)."""
    return [
        (jnp.zeros((rows, hkv, page_size, hd), dtype),
         jnp.zeros((rows, hkv, page_size, hd), dtype))
        + ((jnp.zeros((rows, hkv, page_size), jnp.float32),
            jnp.zeros((rows, hkv, page_size), jnp.float32))
           if quant else ())
        for _ in range(layers)]


def _cache_kinds(spec) -> List[str]:
    """The kind of each cache the model takes, in its order: a spec with
    ONE geometry is "kv" on every layer; a ``cache_layers`` spec says,
    entry by entry (as many as the model keeps: nothing holds the list
    to ``num_layers``)."""
    layers = spec.get("cache_layers")
    if layers is None:
        return ["kv"] * int(spec["num_layers"])
    return [str(layer.get("kind")) for layer in layers]


def _make_spec_pools(spec, rows, page_size, dtype, quant, slots=0):
    """What a serving spec asks the engine to hold, one tuple an entry. A
    spec with ONE geometry (``kv_heads`` x ``head_dim``: LLaMA, Mistral,
    ERNIE-MoE) gets _make_paged_pools' (k, v[, ks, vs]) exactly, one a
    layer. A spec with ``cache_layers`` gets what each entry's ``kind``
    says, in the list's order (one entry a layer, or two for a block
    that keeps paged keys and values AND a state):

    * ``"kv"`` (``kv_heads``, ``head_dim``): that same paged (k, v) pair
      with heads, for one layer.
    * ``"latent"`` (``rows``): one pool [rows, page_size, width] per
      entry of its ``rows``: one vector a token and no head dimension
      (a latent-attention layer's [c_kv ; k_rope] row, its indexer's
      key).
    * ``"state"`` (``arrays``: name -> (shape, dtype)): one array
      [slots, *shape] per entry, in order: what a layer keeps by SLOT
      and not by page, whatever the context's length (a linear-attention
      layer's recurrent state and its convolution's tail; a
      sliding-window layer's ring of its last `window` keys and values).
      A dtype of None is the cache's.

    The same block tables, the same in-place write (_scatter_tokens) and
    the same page walk serve the two paged kinds."""
    layers = spec.get("cache_layers")
    if layers is None:
        return _make_paged_pools(
            int(spec["num_layers"]), rows, int(spec["kv_heads"]),
            page_size, int(spec["head_dim"]), dtype, quant)
    pools = []
    for layer in layers:
        kind = layer.get("kind")
        if kind == "kv":
            pools += _make_paged_pools(1, rows, int(layer["kv_heads"]),
                                       page_size, int(layer["head_dim"]),
                                       dtype, quant)
        elif kind == "latent":
            pools.append(tuple(jnp.zeros((rows, page_size, int(w)), dtype)
                               for w in layer["rows"]))
        elif kind == "state":
            pools.append(tuple(
                jnp.zeros((int(slots),) + tuple(int(n) for n in shape),
                          jnp.dtype(dt or dtype))
                for shape, dt in layer["arrays"].values()))
        else:
            raise ValueError(
                f"cache_layers entry {layer!r}: the kinds known are 'kv' "
                f"(paged keys and values with heads), 'latent' (one row a "
                f"token per pool) and 'state' (arrays by slot: a recurrent "
                f"state, a window's ring)")
    return pools


@dataclass
class _PendingTick:
    """One in-flight decode dispatch (the pipelined tick loop's
    handoff between dispatch and harvest): the device output futures,
    a snapshot of the (slot, request) pairs the dispatch covered —
    harvest skips rows whose request was retired while the tick was
    in flight."""

    kind: str                 # "single" | "spec"
    data: tuple               # device outputs to sync + fetch
    active: list              # [(slot, Request)] snapshot at dispatch
    extras: tuple = ()        # _tick_extras outputs, still on the device
    # `engine.decode.dispatch` arguments: what the program reads
    span_args: dict = field(default_factory=dict)


@dataclass
class _PendingPrefill:
    """One prefill chunk dispatched and not yet waited for: while a
    decode tick is in flight the chunk's wait moves to the NEXT
    step(), behind that step's dispatch, so the device goes from the
    chunk straight into a tick (docs/SERVING.md "Dispatch
    pipelining"). Until `_prefill_harvest` the request stays PREFILL
    with `written` at the chunk's start."""

    req: "Request"
    data: tuple               # (tok, key2, okf), still on the device
    extras: tuple             # _tick_extras outputs (the logits row)
    toks: list                # the tokens the request resumes from
    start: int                # first position the chunk wrote
    tokens: int               # real tokens of the chunk
    fresh: bool               # no token generated yet: sample the first


class _Emitted:
    """The tokens one harvest appends. They share one clock reading,
    so their gaps to each request's previous token are grouped by
    value: a 48-slot tick records one histogram entry of weight 48,
    not 48 locked calls."""

    __slots__ = ("now", "tokens", "gaps")

    def __init__(self, now: float):
        self.now = now
        self.tokens = 0
        self.gaps: Dict[float, int] = {}

    def append(self, req: Request, tok: int) -> None:
        req.generated.append(tok)
        if req.first_token_t == 0.0:
            req.first_token_t = self.now
        if req.last_token_t > 0.0:
            gap = self.now - req.last_token_t
            self.gaps[gap] = self.gaps.get(gap, 0) + 1
        req.last_token_t = self.now
        self.tokens += 1

    def record(self, mon) -> None:
        """Into ``serving.tokens`` and ``serving.hist.itl_ms``: one
        counter call, and one histogram call per DISTINCT gap."""
        if self.tokens:
            mon.counter("serving.tokens").increase(self.tokens)
        hist = mon.histogram("serving.hist.itl_ms")
        for gap, n in self.gaps.items():
            hist.record(gap * 1e3, n)


@jax.jit
def _merge_rows(dev, packed):
    """Fold host-updated slot rows (admissions, preemptions, finishes)
    into the device-resident decode state: row i comes from the host's
    mirrors where the scheduler touched the slot since the last decode
    step, else from the state the last decode executable produced.
    ``packed`` is Engine._pack_rows' ONE int32 array [slots, 11]: the
    nine mirrors column by column (a float or uint32 column by its
    bits, the keys two columns) and the dirty mask last, so a flush is
    one upload and not ten. ONE fixed-shape executable whatever the
    number of dirty slots — a per-index scatter would compile a fresh
    tiny program per dirty-set shape and show up as steady-state
    recompiles."""
    mask = packed[:, -1] > 0
    out, col = [], 0
    for d in dev:
        width = d.size // d.shape[0]
        h = jax.lax.bitcast_convert_type(packed[:, col:col + width],
                                         d.dtype).reshape(d.shape)
        out.append(jnp.where(
            mask.reshape((-1,) + (1,) * (d.ndim - 1)), h, d))
        col += width
    return tuple(out)


def _named(body, name: str):
    """Name a traceable body before ``jax.jit``: the XLA program is
    then ``jit_<name>`` on a profiler trace's ``XLA Modules`` line
    (and in compile logs), so the decode program and each prefill
    bucket can be told apart instead of all reading ``jit_body``."""
    body.__name__ = body.__qualname__ = name
    return body


def _lint_armed() -> bool:
    """PADDLE_TPU_LINT=1: arm the steady-tick transfer guard (read per
    tick through analysis.lint_enabled so tests can toggle the env)."""
    from .. import analysis
    return analysis.lint_enabled()


class Engine:
    """In-process continuous-batching engine over the paged KV stack.

        eng = Engine(model, max_slots=8, page_size=16, pool_pages=256)
        rid = eng.add_request(ids, SamplingParams(max_new_tokens=32))
        while ...:
            for out in eng.step():
                ...                      # finished requests
        # or offline:
        outs = eng.run([(ids_a, pa), (ids_b, pb)])

    The model must support the ``kv_caches``/``cache_index`` forward
    kwargs (the in-tree LlamaForCausalLM does). Weights are snapshotted
    at construction (the executables close over nothing — params ride
    as arguments — but the engine reads them once; rebuild the engine
    after mutating the model).
    """

    def __init__(self, model, max_slots: int = 8, page_size: int = 16,
                 pool_pages: Optional[int] = None,
                 cache_dtype: str = "auto",
                 max_context: Optional[int] = None,
                 prefill_bucket: int = 32,
                 watermark_pages: Optional[int] = None,
                 prefix_cache: bool = False,
                 draft_model=None, spec_k: int = 4,
                 clock=None, fault_injector=None,
                 debug_invariants: Optional[bool] = None,
                 max_prefill_tokens_per_step: Optional[int] = None,
                 label: Optional[str] = None,
                 keep_logits: bool = False):
        # model polymorphism (docs/SERVING.md): geometry comes from the
        # serving_spec probe, not hard-coded llama config attribute
        # names — an encoder or a spec-less model gets a pointed error
        # instead of an AttributeError three constructors deep
        spec = serving_model_spec(model)
        if spec.get("kind") == "encoder":
            raise ValueError(
                f"{type(model).__name__} is an ENCODER — it has no KV "
                f"decode surface for the continuous-batching Engine. "
                f"Serve it through the embedding service "
                f"(inference.BatchEncoder, docs/SERVING.md "
                f"'Embedding service') instead")
        import inspect
        try:
            fsig = inspect.signature(model.forward)
        except (TypeError, ValueError):
            fsig = None
        if fsig is None or "kv_caches" not in fsig.parameters:
            raise ValueError(
                "Engine requires a model with kv_caches/cache_index "
                "forward kwargs (KV-cache decode support); "
                f"{type(model).__name__}.forward has none — use "
                "text.generate(use_cache=False) for padded one-shot "
                "generation instead")
        self.serving_spec = spec
        self.model = model
        # what the spec keeps, entry by entry; what this engine does not
        # yet do for a per-layer spec is refused by name, not run wrong
        self._cache_kinds = _cache_kinds(spec)
        self._per_layer = spec.get("cache_layers") is not None
        self._has_state = "state" in self._cache_kinds
        # some windowed layer's cache is by page (a one-geometry spec's
        # every layer; a per-layer spec's entries that give a `window`)
        self._paged_window = any(
            e.get("window") is not None and e.get("kind") != "state"
            for e in spec.get("cache_layers") or [spec])
        self._spec_name = (
            f"{type(model).__name__}'s per-layer cache spec (kinds "
            f"{', '.join(sorted(set(self._cache_kinds)))})")
        if self._per_layer:
            for option, on in (("prefix_cache", bool(prefix_cache)),
                               ("draft_model", draft_model is not None),
                               ("cache_dtype='int8'",
                                str(cache_dtype) == "int8")):
                if on:
                    raise ValueError(
                        f"{option} is not supported for "
                        f"{self._spec_name} (docs/SERVING.md 'Model "
                        f"polymorphism')")
        # keep_logits: the decode and prefill programs also return the
        # float32 logits they sampled from (left on the device; a row is
        # fetched only for a request that set return_logits)
        self.keep_logits = bool(keep_logits)
        self._tick_stats = tuple(spec.get("tick_stats") or ())
        self.max_slots = int(max_slots)
        self.page_size = int(page_size)
        self.prefill_bucket = int(prefill_bucket)
        # chunked prefill (docs/SERVING.md "Chunked prefill"): when set,
        # a prompt is written as a sequence of bounded slices — at most
        # this many tokens of prefill run per step() — interleaved with
        # decode ticks, so one 32K-token whale can never stall TTFT for
        # the small requests decoding beside it. Rounded UP to the
        # bucket so every slice is a whole compiled prefill bucket.
        # None = monolithic (the whole tail in one chunk, as before).
        if max_prefill_tokens_per_step is not None:
            if int(max_prefill_tokens_per_step) < 1:
                raise ValueError(
                    f"max_prefill_tokens_per_step must be >= 1, got "
                    f"{max_prefill_tokens_per_step}")
            max_prefill_tokens_per_step = self._pbucket(
                int(max_prefill_tokens_per_step))
        self.max_prefill_tokens_per_step = max_prefill_tokens_per_step
        self._pf_step_tokens = 0
        self.max_context = int(max_context or spec["max_context"])
        # speculative decoding writes k+1 positions per tick (the
        # drafted chunk), so the block tables carry that lookahead of
        # extra slots past max_context — a verify write must never
        # clip into a request's LAST live page
        self._lookahead = (int(spec_k) + 1) if draft_model is not None \
            else 1
        self.max_blocks = _ceil_div(
            self._pbucket(self.max_context) + self._lookahead - 1,
            self.page_size)
        if pool_pages is None:
            # default: every slot can hold a max-context sequence — no
            # preemption unless the caller sizes the pool tighter
            pool_pages = self.max_slots * self.max_blocks
        self.pool_pages = int(pool_pages)
        self.watermark_pages = (max(1, self.pool_pages // 50)
                                if watermark_pages is None
                                else int(watermark_pages))
        self._st = (get_params(model), get_buffers(model),
                    get_frozen(model))
        self.cache_dtype = _resolve_cache_dtype(cache_dtype, self._st[0])
        self._quant = self.cache_dtype == jnp.dtype(jnp.int8)
        # the paged layers with heads (all of a one-geometry spec's)
        heads = [(int(e["kv_heads"]), int(e["head_dim"]))
                 for e in (spec["cache_layers"] if self._per_layer
                           else [dict(spec, kind="kv")])
                 if e.get("kind") == "kv"]
        hkv = heads[0][0] if heads else 1
        # pool row 0 is the scratch page (inactive lanes) — the
        # allocator hands out ids [1, pool_pages]
        rows = self.pool_pages + 1
        self._alloc = PageAllocator(self.pool_pages, base=1)
        # TP-sharded decode (docs/SERVING.md "TP-sharded decode"):
        # under an mp>1 mesh the KV pools shard over the kv-head axis
        # — the placement GSPMD would pick anyway from the TP attention
        # compute — and the tiny decode state replicates. Committing
        # BOTH at every host→device upload matters beyond bandwidth:
        # an uncommitted (UnspecifiedValue) upload compiles a second
        # copy of the decode executable the first time a donated
        # output comes back with concrete shardings, which reads as a
        # steady-state recompile. One sharding from tick zero keeps
        # the per-worker compiled surface unique.
        self._mp_rep = None
        mesh = _mesh_mod.get_mesh()
        abstract_cls = getattr(jax.sharding, "AbstractMesh", None)
        if mesh is None or (abstract_cls is not None
                            and isinstance(mesh, abstract_cls)):
            # paddle's global is unset (or a device-free fake): on a
            # jax with NATIVE set_mesh, `with jax.set_mesh(mesh):`
            # populates only jax's ambient context — read the concrete
            # mesh from there so TP detection works on both runtimes
            # (the same fallback mesh_mod.axis_degree applies for the
            # TP layer selection)
            mesh = _mesh_mod.ambient_concrete_mesh()
        mp = _mesh_mod.mesh_axis_sizes(mesh).get("mp", 1) \
            if mesh is not None else 1
        self._mp_mesh = None
        self._mp_degree = 1
        if mesh is not None \
                and not (abstract_cls is not None
                         and isinstance(mesh, abstract_cls)) \
                and mp > 1:
            from jax.sharding import NamedSharding, PartitionSpec
            self._mp_mesh = mesh
            self._mp_degree = mp
            self._mp_rep = NamedSharding(mesh, PartitionSpec())
        if self._mp_rep is None:
            # Commitment churn guard beyond mp>1: a model whose params
            # are COMMITTED to a mesh even at degree 1 — MoE expert
            # weights go through shard_tensor at construction — makes
            # every executable output committed too, so donated pools/
            # state uploaded UNCOMMITTED here would flip to committed
            # NamedShardings after their first run and recompile each
            # executable family exactly once (read: 1-2 phantom
            # steady-state recompiles per engine). Commit our uploads
            # to the params' own mesh, replicated, from tick zero.
            from jax.sharding import NamedSharding, PartitionSpec
            for leaf in jax.tree_util.tree_leaves(self._st):
                sh = getattr(leaf, "sharding", None)
                if isinstance(sh, NamedSharding) \
                        and getattr(leaf, "committed", False):
                    self._mp_mesh = sh.mesh
                    self._mp_rep = NamedSharding(sh.mesh,
                                                 PartitionSpec())
                    break
        if self._per_layer and self._mp_degree > 1:
            raise ValueError(
                f"an mp={self._mp_degree} mesh is not supported for "
                f"{self._spec_name}")
        self._pools = self._commit_pools(_make_spec_pools(
            spec, rows, self.page_size, self.cache_dtype, self._quant,
            self.max_slots), hkv)
        S, MB = self.max_slots, self.max_blocks
        self._bt = np.zeros((S, MB), np.int32)
        self._pos = np.zeros((S,), np.int32)
        self._last = np.zeros((S,), np.int32)
        self._temps = np.zeros((S,), np.float32)
        self._topks = np.zeros((S,), np.int32)
        self._topps = np.zeros((S,), np.float32)
        self._keys = np.zeros((S, 2), np.uint32)
        self._live = np.zeros((S,), np.int32)
        # per-slot eos token id (-1 = none; emitted ids are >= 0 so -1
        # never matches) and the max_new_tokens budget left at
        # activation: the programs count the budget down in-graph (an
        # eos zeroes it), so a lane goes DEAD on the tick after its
        # request's last token without the host saying so — what lets
        # a tick be dispatched before the one before it is harvested
        self._eos = np.full((S,), -1, np.int32)
        self._bud = np.zeros((S,), np.int32)
        # the decode state — (last, pos, temps, topks, topps, keys,
        # live, eos, budget) — LIVES ON DEVICE between ticks: the fused
        # decode executable advances it in place (donated), so a
        # steady-state tick ships nothing host→device and fetches only
        # the emitted tokens. The numpy mirrors above are the
        # scheduler's view; rows the scheduler touches are marked
        # dirty and merged in before the next decode step
        # (_flush_state).
        self._dev = tuple(self._up(m) for m in self._mirrors())
        self._dirty: set = set()
        self._bt_dev = self._up(self._bt)
        self._bt_dirty = False
        self._slots: List[Optional[Request]] = [None] * S
        self._waiting: "deque[Request]" = deque()
        self.requests: Dict[int, Request] = {}
        self._next_id = 0
        self._admit_counter = 0
        self._steps = 0
        self._last_compile_step = 0
        self._compiles = 0        # compiles inside OUR step() calls
        self._warm_compiles = 0
        self._prefill_fns: Dict[int, object] = {}
        self._decode_fns: Dict[str, object] = {}
        self._verify_fns: Dict[str, object] = {}
        # shared-prefix KV reuse (prefix_cache.py): content-addressed
        # full pages mapped into many block tables via allocator
        # refcounts; idle entries are evicted before admission is
        # refused or a live sequence preempted
        self._prefix = (PrefixCache(self._alloc, self.page_size)
                        if prefix_cache else None)
        # draft/verify speculative decoding (speculative.py): the
        # draft's paged pools mirror this engine's page ids exactly
        self._spec = None
        self._spec_drafted = 0
        self._spec_accepted = 0
        if draft_model is not None:
            from .speculative import SpeculativeDecoder
            self._spec = SpeculativeDecoder(self, draft_model, spec_k)
        # reliability surfaces (inference/reliability.py): the step
        # clock every deadline is measured on (injectable so replay
        # tools and tests run deterministic virtual time), the seeded
        # fault injector (explicit, or armed process-wide via
        # FLAGS_serving_fault_seed), and the per-step invariant audit
        self._clock = clock if clock is not None else time.perf_counter
        # observability plane (docs/OBSERVABILITY.md "Serving
        # timelines & histograms"): `label` names this engine in span
        # timelines and scopes its metrics — a fleet replica or disagg
        # worker writes both the unlabeled aggregate and its
        # serving.<label>.… twin; a plain engine stays unlabeled.
        self.label = str(label) if label is not None else "engine"
        self._mon = monitor.scope(label)
        if self._has_state:
            # what the engine holds by slot rather than by page
            self._mon.gauge("serving.state.bytes").set(sum(
                a.nbytes for kind, layer in zip(self._cache_kinds,
                                                self._pools)
                if kind == "state" for a in layer))
        # the step record (docs/OBSERVABILITY.md "Step record"): one row
        # a step(), timed at the spans' own sites (_span), kept for the
        # life of the engine and after it in tracing.step_logs()
        self.step_log = tracing.StepLog(self.label)
        # fault_injector: an explicit FaultInjector, None = arm from
        # FLAGS_serving_fault_* (off by default), False = force OFF
        # even when the flags arm the process (the chaos tooling's
        # clean baseline passes)
        if fault_injector is False:
            self._injector = None
        elif fault_injector is None:
            self._injector = injector_from_flags()
        else:
            self._injector = fault_injector
        self._debug_invariants = (
            bool(get_flag("serving_debug_invariants"))
            if debug_invariants is None else bool(debug_invariants))
        # the NaN-injection vector riding into every decode/verify
        # step: all-zeros (one resident device array, re-uploaded only
        # on the rare fault tick) added to the sampling logits — a NaN
        # row turns that slot's in-graph `ok` flag off
        self._poison_zeros = self._up(np.zeros((S,), np.float32))
        self._poison_dev = self._poison_zeros
        self._poisoned = False
        # run-ahead (docs/SERVING.md "Dispatch pipelining"): the
        # single-tick dispatch still on the device when step() returns;
        # the next step() dispatches its successor BEFORE waiting for
        # it, so the device never idles through the host's harvest.
        # _held: Outputs of ticks harvested outside a step (a forced
        # drain), handed out by the next step()
        self._inflight: Optional[_PendingTick] = None
        self._held: List[Output] = []
        # prefill chunks dispatched beside a tick in flight; the next
        # step() (or a drain) waits for them AFTER its own dispatch
        self._prefilled: List[_PendingPrefill] = []
        self.last_stall_snapshot: Optional[dict] = None
        from ..distributed import watchdog as _watchdog
        self._watchdog = _watchdog
        self._tracker = CompileTracker().start()
        # Pallas paged-decode eligibility is a STATIC property of
        # (head_dim, page_size, cache_dtype) — validate it once here
        # instead of letting every decode step silently gather: an
        # ineligible geometry on a TPU backend costs a full-cache copy
        # per token and previously only showed up as slow numbers.
        # (a latent or a state layer's kernel is the model's own matter:
        # on a TPU it raises at trace time for what it cannot take)
        self.decode_fallback_reason = next(filter(None, (
            paged_pallas_requirements(hd, self.page_size, self.cache_dtype)
            for hd in sorted({hd for _, hd in heads}))), None)
        self.pallas_eligible = self.decode_fallback_reason is None
        if not self.pallas_eligible:
            monitor.counter("serving.decode_fallback").increase()
            if place.accelerator_available():
                warnings.warn(
                    f"Engine decode steps will take the XLA gather "
                    f"path (full-cache copy per token): "
                    f"{self.decode_fallback_reason}. Pick a page_size/"
                    f"cache_dtype from docs/DECODE.md's eligibility "
                    f"table to serve on the Pallas kernel.",
                    RuntimeWarning, stacklevel=2)
        # MoE models (docs/SERVING.md "MoE serving"): probe the fused
        # grouped-matmul dispatch eligibility ONCE here, through the
        # SAME fallback ladder the decode trace will take (the model's
        # own MoELayer), so an ineligible geometry/backend is a named
        # diagnostic at construction instead of a silently slower
        # scatter path. serving.moe.decode_path.* counters (republished
        # from the trace-time kernels.moe.decode_path.* deltas each
        # compile-bearing step) then PROVE which dispatch the compiled
        # decode executables actually baked in.
        self._moe_layer = spec.get("moe_layer")
        self.moe_spec = spec.get("moe")
        self.moe_fallback_reason = None
        self.moe_pallas_eligible = None
        self._moe_paths: Dict[str, int] = {}
        # baseline the GLOBAL trace-time counters now, so the per-step
        # republish attributes only deltas that landed after this
        # engine existed (another engine's warmup must not read as ours)
        self._moe_seen: Dict[str, int] = {
            k: int(v) for k, v in monitor.snapshot().items()
            if k.startswith("kernels.moe.decode_path.")}
        # compile count at the last _moe_seen sync: compiles landing
        # BETWEEN our steps (another engine's warmup, a generate()
        # call) re-baseline instead of republishing — see step()
        self._moe_tracker_mark = self._tracker.compiles
        if self._moe_layer is not None:
            # dtype is inert in the eligibility check (lane-width
            # constraints only) — None keeps the probe trace-free
            self.moe_fallback_reason = self._moe_layer.\
                _pallas_fallback_reason(self.max_slots, None,
                                        cap=self.max_slots)
            self.moe_pallas_eligible = self.moe_fallback_reason is None
            if not self.moe_pallas_eligible:
                monitor.counter("serving.moe.decode_fallback").increase()
                if place.accelerator_available():
                    warnings.warn(
                        f"MoE decode ticks will take the sparse "
                        f"scatter dispatch, not the fused Pallas "
                        f"grouped-matmul: {self.moe_fallback_reason} "
                        f"(docs/KERNELS.md eligibility).",
                        RuntimeWarning, stacklevel=2)

    # -- compiled step shapes ------------------------------------------------

    def _mirrors(self):
        """The host mirrors, in the device state's order."""
        return (self._last, self._pos, self._temps, self._topks,
                self._topps, self._keys, self._live, self._eos,
                self._bud)

    def _pack_rows(self) -> np.ndarray:
        """The host mirrors and the dirty mask as ONE int32 array
        [slots, 11] (_merge_rows unpacks it): every mirror is 32 bits a
        value, so a column carries a float or a key word by its bits."""
        mask = np.zeros((self.max_slots, 1), np.int32)
        mask[list(self._dirty)] = 1
        return np.concatenate(
            [m.view(np.int32).reshape(self.max_slots, -1)
             for m in self._mirrors()] + [mask], axis=1)

    def _up(self, x):
        """Host→device upload of engine state, committed to the
        replicated sharding under an mp>1 mesh (see __init__) — plain
        jnp.asarray otherwise."""
        if self._mp_rep is None:
            return jnp.asarray(x)
        return jax.device_put(np.asarray(x), self._mp_rep)

    def _commit_pools(self, pools, kv_heads: int):
        """Commit freshly built KV pools to the kv-head-sharded mp
        placement (identity off-mesh). Shared with the draft model's
        mirrored pools (speculative.py) — the spec is chosen per
        POOL's kv-head count: a 1-kv-head draft beside an 8-head
        target replicates instead of crashing on an indivisible
        partition."""
        if self._mp_mesh is None:
            return pools
        from jax.sharding import NamedSharding, PartitionSpec
        spec = (PartitionSpec(None, "mp")
                if self._mp_degree > 1
                and int(kv_heads) % self._mp_degree == 0
                else PartitionSpec())
        return jax.device_put(pools, NamedSharding(self._mp_mesh, spec))

    def _pbucket(self, n: int) -> int:
        return _ceil_div(n, self.prefill_bucket) * self.prefill_bucket

    def _lifetime_pages(self, plen: int, max_new: int) -> int:
        """Peak page demand of a request over its whole lifetime — the
        can-it-EVER-be-scheduled admission check. Monolithic prefill
        peaks at the whole-prompt bucket padding; CHUNKED prefill pads
        only one slice at a time, so a long prompt is charged its
        per-slice peak (the incremental fit) instead of the bucketed
        whole — the reason a near-pool-sized prompt that fits slice by
        slice is admitted under max_prefill_tokens_per_step but
        rejected without it."""
        need = plen + max_new
        if self.max_prefill_tokens_per_step is None:
            # monolithic: the historical conservative bound (whole-need
            # bucket rounding) — kept for admission-behavior stability
            return _ceil_div(
                self._pbucket(need) + self._lookahead - 1,
                self.page_size)
        # chunked: prefill allocates pages for REAL tokens only (bucket
        # padding writes to the scratch page), so the lifetime peak is
        # simply the decode-side maximum — every written token plus the
        # per-tick write lookahead (_ensure_pages' growth target at the
        # final token). This covers the resume-prefill path too: a
        # resume prefix is at most need - 2 tokens.
        return _ceil_div(need - 1 + self._lookahead, self.page_size)

    def _inject_bt(self, caches, bt, slots=None, n_valid=None):
        """Engine state -> the model's cache tuples, one an entry. A paged
        layer takes its pools and the block table, engine state shared
        by every layer and injected at call time: (k, v, bt[, ks, vs])
        with heads, its pools with the block table last when latent. A
        state layer takes its arrays, then `slots` (the rows of the
        batch's sequences; None in the decode program, where row i is
        slot i) and `n_valid` (how many of each sequence's tokens are
        real: 0 for a lane that is not decoding, a chunk's real
        length)."""
        return [tuple(c) + (slots, n_valid) if kind == "state"
                else tuple(c) + (bt,) if kind == "latent"
                else (c[0], c[1], bt) + tuple(c[2:])
                for kind, c in zip(self._cache_kinds, caches)]

    def _strip_bt(self, kv):
        """The model's new cache tuples -> what the engine holds (a
        state layer hands back its arrays alone)."""
        return [tuple(t) if kind == "state"
                else tuple(t[:-1]) if kind == "latent"
                else (t[0], t[1]) + tuple(t[3:])
                for kind, t in zip(self._cache_kinds, kv)]

    def _tick_extras(self, cur, stats=True):
        """What a decode or prefill program returns beyond today's
        outputs, as one tuple (empty for a model and an engine that ask
        for neither, so their programs are unchanged): the model's
        ``tick_stats`` vector (decode ticks only), read off the model
        right after its forward inside the same trace, and the logits
        `cur` when keep_logits."""
        extras = ()
        if stats and self._tick_stats:
            extras += (self.model.serving_tick_stats(),)
        if self.keep_logits:
            extras += (cur,)
        return extras

    def _take_extras(self, extras, stats=True):
        """Host side of _tick_extras: add the stats to their counters
        (one small fetch, with the tick's sync already behind it) and
        hand back the logits array, still on the device, or None."""
        extras = list(extras)
        if stats and self._tick_stats:
            for name, v in zip(self._tick_stats,
                               np.asarray(extras.pop(0))):
                self._mon.counter(name).increase(int(v))
        return extras.pop(0) if self.keep_logits else None

    def _get_decode_fn(self, variant: str):
        """The fused [max_slots] decode executable — ONE compiled step
        that consumes the device-resident state (last tokens, cache
        positions, per-slot sampling params, rng keys), runs the model
        forward, samples every slot's next token IN-GRAPH, and returns
        the advanced state. The host fetches only the emitted tokens;
        nothing else crosses per tick.

        Keyed STATICALLY on the cheapest sampler the active slots
        need — three variants, each compiled once, so any greedy/
        sampled arrival mix bounces between fixed executables with
        zero steady-state recompiles:

        * ``"greedy"``  — every active slot at temperature 0: plain
          argmax, no rng consumed (keys pass through untouched,
          pick_next semantics).
        * ``"plain"``   — sampling slots but NO top-k/top-p anywhere:
          the no-filter sampler (``use_filters=False``) skips the
          full-vocab argsort the traced filters would force. Greedy
          rows ride inside it unchanged, so mixed greedy+temperature
          traffic collapses onto this one executable.
        * ``"filtered"`` — some slot filters: the full per-slot
          argsort sampler (work XLA can't dead-code out when top_k/
          top_p ride as traced arrays).
        """
        fn = self._decode_fns.get(variant)
        if fn is not None:
            return fn
        fn = jax.jit(_named(self._decode_body(variant),
                            f"serve_decode_{variant}"),
                     donate_argnums=(1, 3))
        self._decode_fns[variant] = fn
        self._note_compile()
        return fn

    def _decode_body(self, variant: str):
        """The decode step's traceable body, separate from the jitted
        wrapper so hotpath_lint can abstract-trace the exact program
        `_get_decode_fn` compiles (same closure, same donation
        contract declared in the inventory)."""
        model = self.model

        def body(st, caches, bt, state, poison):
            last, pos, temps, topks, topps, keys, live, eosv, bud = state
            # idle lanes ride at cache_index -1: their context_lens
            # (pos + 1) is then 0, so the multi-sequence decode kernel
            # treats them as DEAD slots — no page DMA, no compute —
            # and their scratch write clips into page 0. Only live
            # lanes advance their position; an idle lane's pos must
            # not drift upward tick over tick (it would re-enter the
            # kernel as a growing fake context and stream scratch
            # pages forever). A lane whose budget ran out (or whose
            # last token was its eos) is idle the same way: the host
            # learns of the finish a tick late (run-ahead) and this
            # tick was dispatched with the lane still marked live.
            alive = (live > 0) & (bud > 0)
            idx = jnp.where(alive, pos, -jnp.ones_like(pos))
            # a state layer updates the rows of the lanes that are alive
            # and leaves every other slot's rows as they are: a slot
            # between two prefill chunks, a free one, a dead lane
            kv = self._inject_bt(caches, bt, None, alive.astype(jnp.int32))
            logits, new_kv = _model_forward(model, st, last[:, None],
                                            kv, idx)
            # poison (normally all zeros, NaN at a fault-injected
            # slot) rides into the sampling logits so the in-graph
            # NaN/inf detector exercises the SAME path a genuinely
            # NaN-emitting model would hit; `ok` is the per-slot
            # quarantine flag the host checks before trusting a token
            cur = logits[:, -1].astype(jnp.float32) + poison[:, None]
            ok = jnp.isfinite(cur).all(axis=-1)
            if variant == "greedy":
                nxt = jnp.argmax(cur, axis=-1).astype(jnp.int32)
                keys2 = keys
            else:
                nxt, keys2 = sample_token_arrays(
                    cur, keys, temps, topks, topps,
                    use_filters=variant == "filtered")
            bud2 = jnp.where(alive, jnp.where(nxt == eosv,
                                              jnp.zeros_like(bud),
                                              bud - 1), bud)
            state2 = (jnp.where(alive, nxt, last),
                      pos + alive.astype(pos.dtype), temps, topks,
                      topps, keys2, live, eosv, bud2)
            return (nxt, ok, state2, self._strip_bt(new_kv)) \
                + self._tick_extras(cur)

        return body

    def _get_verify_fn(self, variant: str):
        """The speculative verify executable — ONE fixed-shape
        ``[max_slots, k+1]`` target forward per static sampler variant
        (same three variants as the decode step): scores the drafted
        chunk at every position, walks the acceptance chain with the
        target's own sampler and rng keys (verify_token_arrays — the
        exact-match rule that keeps output bit-identical to the
        draft-free engine), and advances the device-resident state by
        each slot's accepted count + 1 in-graph. The host fetches only
        the candidate tokens and the accept counts."""
        fn = self._verify_fns.get(variant)
        if fn is not None:
            return fn
        fn = jax.jit(_named(self._verify_body(variant),
                            f"serve_verify_{variant}"),
                     donate_argnums=(1, 3))
        self._verify_fns[variant] = fn
        self._note_compile()
        return fn

    def _verify_body(self, variant: str):
        model = self.model

        def body(st, caches, bt, state, drafts, poison):
            last, pos, temps, topks, topps, keys, live, eosv, bud = state
            kv = self._inject_bt(caches, bt)
            # idle lanes at cache_index -1 (context 0), like the plain
            # decode step — their k+1 scratch writes clip into page 0
            idx = jnp.where(live > 0, pos, -jnp.ones_like(pos))
            toks_in = jnp.concatenate([last[:, None], drafts], axis=1)
            logits, new_kv = _model_forward(model, st, toks_in, kv, idx)
            scored = logits.astype(jnp.float32) \
                + poison[:, None, None]
            ok = jnp.isfinite(scored).all(axis=(1, 2))
            toks, acc, keys2 = verify_token_arrays(
                scored, drafts, keys, temps, topks,
                topps, use_filters=variant == "filtered",
                greedy=variant == "greedy")
            # live rows consumed acc+1 context tokens; idle rows must
            # not drift (same contract as the decode step)
            new_last = jnp.take_along_axis(toks, acc[:, None],
                                           axis=1)[:, 0]
            # the budget follows the accepted chain; a chain that ends
            # its request (eos, or the budget inside it) is retired by
            # the harvest of this same step, which rewrites the row
            state2 = (jnp.where(live > 0, new_last, last),
                      pos + (acc + 1) * live, temps, topks, topps,
                      jnp.where(live[:, None] > 0, keys2, keys), live,
                      eosv, bud - (acc + 1) * live)
            return toks, acc, ok, state2, self._strip_bt(new_kv)

        return body

    def _get_prefill_fn(self, pb: int):
        fn = self._prefill_fns.get(pb)
        if fn is not None:
            return fn
        fn = jax.jit(_named(self._prefill_body(), f"serve_prefill_{pb}"),
                     donate_argnums=(1,))
        self._prefill_fns[pb] = fn
        self._note_compile()
        return fn

    def _prefill_body(self):
        model = self.model

        def body(st, caches, bt_row, prompt, plen, start, temps, topks,
                 topps, keys, poison, slot):
            # a state layer starts from zeros where `start` is 0 and from
            # the rows of `slot` otherwise, stops at the chunk's `plen`
            # real tokens, and writes the rows back
            kv = self._inject_bt(caches, bt_row, slot, plen)
            # `start` is the page-aligned token offset the chunk begins
            # at — 0 for a cold prefill, the cached-prefix length on a
            # prefix-cache hit (the chunk attends the shared pages
            # through the block table; only the tail is computed). It
            # rides as a TRACED [1] array so every hit depth reuses
            # this one bucket executable.
            logits, new_kv = _model_forward(model, st, prompt, kv,
                                            start)
            # last REAL chunk position's logits (the chunk is padded
            # to the bucket; causality keeps the pad out of this row)
            idx = jnp.reshape(plen - 1, (1, 1, 1)).astype(jnp.int32)
            last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
            cur = last.astype(jnp.float32) + poison[:, None]
            ok = jnp.isfinite(cur).all(axis=-1)
            nxt, keys2 = sample_token_arrays(
                cur, keys, temps, topks, topps)
            return (nxt, keys2, ok, self._strip_bt(new_kv)) \
                + self._tick_extras(cur, stats=False)

        return body

    def _note_compile(self):
        """Record that THIS step legitimately introduced a new
        executable (warmup accounting for steady_state_recompiles)."""
        self._last_compile_step = self._steps

    # -- hot-path lint (docs/ANALYSIS.md "Hot-path rules") -------------------

    def _hotpath_inventory(self):
        """The engine's compiled-executable inventory + scheduler tick
        path, in hotpath_lint's terms: every per-tick body with its
        abstract args and donation/fetch contract, the tick functions
        to source-walk, the steady-path subset the upload discipline
        applies to, and the executable-cache key sets."""
        from ..analysis import hotpath_lint as hp
        S, MB = self.max_slots, self.max_blocks

        def s(shape, dt):
            return jax.ShapeDtypeStruct(shape, np.dtype(dt))

        st = hp.struct_of(self._st)
        pools = hp.struct_of(self._pools)
        state = hp.struct_of(self._dev)
        bt = hp.struct_of(self._bt_dev)
        poison = hp.struct_of(self._poison_dev)
        specs = []
        variants = tuple(self._decode_fns) or ("greedy", "plain",
                                               "filtered")
        for v in variants:
            specs.append(hp.ExecutableSpec(
                name=f"decode[{v}]", body=self._decode_body(v),
                args=(st, pools, bt, state, poison),
                donate=(1, 3), fetched=(0, 1)))
        if self._spec is not None:
            k = self._spec.k
            for v in tuple(self._verify_fns) or variants:
                specs.append(hp.ExecutableSpec(
                    name=f"verify[{v}]", body=self._verify_body(v),
                    args=(st, pools, bt, state, s((S, k), np.int32),
                          poison),
                    donate=(1, 3), fetched=(0, 1, 2)))
            specs.extend(self._spec.hotpath_specs())
        pbs = tuple(sorted(self._prefill_fns)) or (self.prefill_bucket,)
        for pb in pbs:
            specs.append(hp.ExecutableSpec(
                name=f"prefill[{pb}]", body=self._prefill_body(),
                args=(st, pools, s((1, MB), np.int32),
                      s((1, pb), np.int32), s((1,), np.int32),
                      s((1,), np.int32), s((1,), np.float32),
                      s((1,), np.int32), s((1,), np.float32),
                      s((1, 2), np.uint32), s((1,), np.float32),
                      s((1,), np.int32)),
                donate=(1,), fetched=(0, 1, 2), per_tick=False))
        cache_keys = {"_decode_fns": list(self._decode_fns),
                      "_verify_fns": list(self._verify_fns),
                      "_prefill_fns": list(self._prefill_fns)}
        if self._spec is not None:
            cache_keys["_spec._prefill_fns"] = \
                list(self._spec._prefill_fns)
        tick = [self.step, self._admit, self._expire,
                self._run_prefills, self._safe_prefill, self._prefill,
                self._settle_prefills, self._prefill_harvest,
                self._ensure_pages, self._safe_decode,
                self._decode_dispatch, self._dispatch_spec,
                self._lanes, self._drain, self._decode_harvest,
                self._harvest_single, self._harvest_spec,
                self._flush_state, self._poison_slot, self._unpoison]
        return hp.HotpathInventory(
            subject=f"{type(self).__name__}[{self.label}]",
            executables=specs, tick_functions=tick,
            steady_functions=("_decode_dispatch", "_dispatch_spec",
                              "_flush_state", "_poison_slot",
                              "_unpoison"),
            cache_keys=cache_keys, file=__file__)

    def inspect_hotpath(self):
        """Device-free hot-path audit (missed donation, fetch-set
        bloat, host syncs in the tick, steady-tick uploads, recompile-
        risk cache keys): returns the findings Report and routes its
        per-rule counts through the ``lint.hotpath.*`` counters."""
        from ..analysis import hotpath_lint
        return hotpath_lint.emit_hotpath(
            hotpath_lint.lint_inventory(self._hotpath_inventory()))

    def _lanes(self) -> list:
        """(slot, request, ahead) of every lane the NEXT dispatch
        computes: the DECODE slots the host does not already know to
        be out of budget. ``ahead`` is 1 for a lane of the tick in
        flight: the device is that one tick past the host's mirrors
        (``req.written``, ``_pos``, ``len(req.generated)``), which
        follow only at its harvest."""
        flying = dict(self._inflight.active) \
            if self._inflight is not None else {}
        lanes = []
        for i, req in enumerate(self._slots):
            if req is None or req.state != DECODE:
                continue
            ahead = 1 if flying.get(i) is req else 0
            if int(req.params.max_new_tokens) - len(req.generated) \
                    - ahead > 0:
                lanes.append((i, req, ahead))
        return lanes

    def _dispatch_span_args(self, lanes, variant: str) -> dict:
        """`engine.decode.dispatch` arguments, for the positions the
        dispatched program READS (the host mirrors plus the tick in
        flight): its lanes, their context, and for a spec with a
        sparse selection or a window the tokens its attention has to
        read, a slot's context counted up to `index_topk` on the
        layers that select (sel_tokens) and up to `window` on the
        layers that slide (win_tokens). `ticks` is the device ticks a
        dispatch covers, always 1 (the benchmark's readers name it)."""
        pos = [int(self._pos[i]) + ahead for i, _, ahead in lanes]
        args = dict(variant=variant, slots=len(lanes),
                    ctx_tokens=sum(pos), ticks=1,
                    inflight=int(self._inflight is not None))
        spec = self.serving_spec
        for name, cap in (("sel_tokens", spec.get("index_topk")),
                          ("win_tokens", spec.get("window"))):
            if cap is not None:
                args[name] = sum(min(p + 1, int(cap)) for p in pos)
        if self._has_state:
            # lanes whose per-slot state the dispatched program updates
            args["state_slots"] = len(lanes)
        return args

    def _pending(self, kind: str, data: tuple, lanes, variant: str,
                 extras: tuple = ()) -> _PendingTick:
        """The handoff record of the dispatch just made over `lanes`."""
        return _PendingTick(
            kind=kind, data=data,
            active=[(i, req) for i, req, _ in lanes], extras=extras,
            span_args=self._dispatch_span_args(lanes, variant))

    def _span(self, name: str, **args) -> tracing.StepSpan:
        """THE site an engine phase is timed at: a `RecordEvent` of
        that name and those arguments (what a profiler trace and the
        benchmark's span readers see) whose duration, when it closes,
        also lands in the step record's open row under its name."""
        return tracing.StepSpan(self.step_log, name, **args)

    def _dispatch_steady(self, steady, fn, *args):
        """Dispatch one tick executable. On a STEADY tick (warm
        executable, no dirty rows, no fault poison) with
        ``PADDLE_TPU_LINT=1``, the call runs under
        ``jax.transfer_guard("disallow")``: any implicit host<->device
        transfer the static hotpath lint missed raises here instead of
        silently syncing. The guard wraps ONLY the dispatch — the
        attributed np.asarray fetches stay outside it."""
        if steady and _lint_armed():
            monitor.counter("lint.hotpath.guarded_ticks").increase()
            with jax.transfer_guard("disallow"):
                return fn(*args)
        return fn(*args)

    # -- public API ----------------------------------------------------------

    def add_request(self, ids, sampling_params=None) -> int:
        """Queue a prompt (1-D token ids, or a [1, s] Tensor/array) for
        generation under ``sampling_params``. Returns the request id;
        the request is admitted to a slot by a later ``step()``."""
        with self._span("engine.add_request") as span:
            params = sampling_params or SamplingParams()
            if isinstance(params, dict):
                params = SamplingParams(**params)
            params.validate()
            if params.return_logits and not self.keep_logits:
                raise ValueError(
                    "SamplingParams.return_logits needs an engine built "
                    "with keep_logits=True (its programs then return "
                    "the logits they sample from)")
            prompt = _normalize_prompt(ids)
            # validate the whole lifetime's page demand UP FRONT, naming
            # the request and the pages it needs — an oversized request
            # must never get as far as a mid-prefill _page_slots failure
            rid = self._next_id
            need = len(prompt) + int(params.max_new_tokens)
            cap = self.max_blocks * self.page_size - (self._lookahead - 1)
            # chunked prefill pads only ONE slice at a time (and clips that
            # padding at the block table), so capacity is bounded by the
            # REAL tokens; monolithic prefill buckets the whole prompt up
            # front and must reserve the padded length
            chunk_cap = (need if self.max_prefill_tokens_per_step is not None
                         else self._pbucket(need))
            if chunk_cap > cap:
                raise ValueError(
                    f"request {rid} needs {need} token slots (prompt "
                    f"{len(prompt)} + {params.max_new_tokens} new = "
                    f"{_ceil_div(self._pbucket(need), self.page_size)} "
                    f"pages), beyond the engine's max_context capacity "
                    f"{cap}")
            worst_pages = self._lifetime_pages(len(prompt),
                                               int(params.max_new_tokens))
            if worst_pages > self.pool_pages:
                raise RuntimeError(
                    f"request {rid} can never be scheduled: it needs up "
                    f"to {worst_pages} page(s) (prompt {len(prompt)} + "
                    f"{params.max_new_tokens} new tokens at page_size "
                    f"{self.page_size}) but the pool has "
                    f"{self.pool_pages} — grow pool_pages or shrink the "
                    f"request")
            span.set(req=rid, prompt_tokens=len(prompt))
            req = Request(req_id=rid, prompt=prompt, params=params,
                          arrival_t=self._clock(),
                          queued_step=self._steps)
            req.key = host_prng_key(params.seed)
            self._next_id += 1
            # LIVE requests only (see _finish)
            self.requests[req.req_id] = req
            self._waiting.append(req)
            tracing.open_span(req.spans, tracing.QUEUED,
                              req.arrival_t * 1e3, self.label)
            self._mon.counter("serving.requests").increase()
            return req.req_id

    def step(self) -> List[Output]:
        """One scheduler tick, run ONE TICK AHEAD of the device (JAX
        async dispatch): the single-tick decode program for tick t is
        dispatched FIRST and stays in flight across the return; only
        then does the host wait for tick t-1 (dispatched by the LAST
        step), harvest its tokens, and run the scheduling for tick
        t+1 — deadline sweeps, admission, prefill slices, page growth
        — all while the device executes tick t. A prefill slice is only
        DISPATCHED here (behind tick t); the next step waits for it
        after dispatching tick t+1, so the device goes from the slice
        into that tick, and its slot joins tick t+2. The programs carry
        each lane's eos id and token budget, so a lane whose request
        ended at tick t-1 is dead in tick t without the host saying
        so; the host learns of a finish one tick late and discards
        nothing it would have kept (docs/SERVING.md "Dispatch
        pipelining"). Returns the requests that finished OR failed
        during this tick — a per-request failure (deadline, NaN
        logits, prefill error) retires that request and never raises
        out of here.

        A speculative dispatch is sized from what the last harvest
        left, so it is dispatched, waited for and harvested inside one
        step. Deadline / queue-timeout enforcement lands on step
        boundaries, so a request can overrun its deadline_ms by at
        most one step before _expire retires it."""
        log = self.step_log
        decoding, waiting, prefilling = \
            self.num_active, self.num_waiting, self.num_prefilling
        with RecordEvent("engine.step", step=self._steps, active=decoding,
                         waiting=waiting, prefilling=prefilling):
            log.begin(self._steps, decoding, prefilling, waiting)
            # what forced drains since the last step retired comes out
            # first; a drain inside this step appends here too
            outputs = self._held
            c0 = self._tracker.compiles
            if self._moe_layer is not None and c0 != self._moe_tracker_mark:
                # compiles landed OUTSIDE our steps since the last sync
                # (a sibling worker's warmup in disagg/fleet, a one-shot
                # generate): fold their kernels.moe.decode_path.* deltas
                # into the baseline WITHOUT republishing — a foreign trace
                # must never read as this engine's dispatch proof
                self._moe_seen = {
                    k: int(v) for k, v in monitor.snapshot().items()
                    if k.startswith("kernels.moe.decode_path.")}
                self._moe_tracker_mark = c0
            if self._injector is not None:
                self._injector.on_step(self._steps)
                self._prefix_faults()
            with tape_mod.no_grad_guard():
                # (a) dispatch the decode executable for the slots settled
                # by the LAST step — tick t queues behind tick t-1 and
                # starts the instant that one ends
                with self._span("engine.decode.dispatch") as span:
                    pending = self._safe_decode()
                    if pending is not None:
                        span.set(**pending.span_args)
                        log.variant = pending.span_args["variant"]
                        log.inflight = pending.span_args["inflight"]
                # (b) a single-tick dispatch stays in flight; the tick the
                # last step left in flight is waited for and harvested now.
                # (Nothing dispatched: the tick in flight, if any, is
                # simply harvested. A speculative engine never leaves
                # one.)
                ahead = pending is not None and pending.kind == "single"
                carried, self._inflight = \
                    self._inflight, pending if ahead else None
                outputs.extend(self._decode_harvest(carried))
                # the prefill chunks the last step dispatched behind
                # that tick: the device goes from them into tick t
                outputs.extend(self._settle_prefills())
                # (c) tick-t+1 host scheduling, beside the device.
                # Exactness is order-insensitive here (rows are
                # independent; a request admitted now joins the NEXT
                # dispatch), and a request _expire retires with its lane
                # in the tick in flight has that token discarded at
                # harvest — a token the request's stream never held.
                with self._span("engine.expire"):
                    outputs.extend(self._expire())
                self._pf_step_tokens = 0
                with self._span("engine.admit") as span:
                    log.admitted = len(self._admit())
                    span.set(admitted=log.admitted)
                outputs.extend(self._run_prefills())
                if self._inflight is None:
                    # no tick in flight for the next dispatch to queue
                    # behind: nothing would hide the chunks' wait
                    outputs.extend(self._settle_prefills())
                self._watchdog.maybe_start_and_tick()
                if not ahead:
                    # spec: block on THIS step's dispatch
                    outputs.extend(self._decode_harvest(pending))
                # (d) page growth for the NEXT dispatch, counting the tick
                # in flight (a preemption drains the tick in flight before
                # it reads the victim's key)
                self._ensure_pages()
            if self._injector is not None and \
                    self._injector.fire("alloc.refcount_skew",
                                        record=False):
                # a stray reference lands on a live page (the lost-free /
                # doubled-share failure mode) — the audit below must
                # detect and repair it before it can become a leak;
                # recorded only when a live page existed to skew
                held = [p for r in self._slots if r is not None
                        for p in r.pages]
                if held:
                    self._injector.record("alloc.refcount_skew")
                    self._alloc.share(
                        held[int(self._injector.rng.integers(0, len(held)))])
            with self._span("engine.bookkeeping"):
                self._maybe_audit()
                self._mon.counter("serving.steps").increase()
                self._publish_gauges()
                # MoE path proof (docs/OBSERVABILITY.md "serving.moe.*"): a
                # tick that traced something re-publishes the trace-time
                # kernels.moe.decode_path.* deltas into the serving
                # namespace — in steady state (zero recompiles) this branch
                # never runs, so the per-step cost is one int compare
                if self._moe_layer is not None \
                        and self._tracker.compiles != c0:
                    self._republish_moe_paths()
                    self._moe_tracker_mark = self._tracker.compiles
                # O(1) warmup accounting, attributed to THIS engine: only
                # compiles that land inside this step() count (the jax
                # listener is process-global — another engine or a generate()
                # call between ticks must not read as our recompile), and a
                # tick that introduced a new executable folds its compiles
                # into warmup. (Not tracker.on_step(): its per-step list
                # would grow one entry per tick forever in a serving process.)
                log.compiles = self._tracker.compiles - c0
                self._compiles += log.compiles
                if self._last_compile_step == self._steps:
                    self._warm_compiles = self._compiles
                self._steps += 1
                self._held = []
            # host/device tick attribution falls out of the row: device
            # time is what the step spent BLOCKED on dispatched results
            # (its two wait spans), host time is the rest of its wall
            # time, hidden under the tick in flight or not. Wall clock,
            # never the injectable one: timelines stay deterministic.
            wall_ms, dev_ms, slow = log.end(len(outputs))
            mon = self._mon
            mon.gauge("serving.host_ms_per_tick").set(wall_ms - dev_ms)
            mon.gauge("serving.device_ms_per_tick").set(dev_ms)
            mon.histogram("serving.hist.host_ms_per_tick").record(
                wall_ms - dev_ms)
            mon.histogram("serving.hist.device_ms_per_tick").record(dev_ms)
            mon.histogram("serving.hist.tick_ms").record(wall_ms)
            if slow:
                mon.counter("serving.slow_steps").increase()
                mon.counter("serving.slow_step_ms").increase(wall_ms)
            return outputs

    def run(self, requests: Sequence, max_steps: int = 100_000,
            heartbeat_timeout: Optional[float] = None,
            snapshot_path: Optional[str] = None) -> List[Output]:
        """Offline driver: queue every (ids, SamplingParams) pair —
        bare ids get default params — then step until all finish (or
        fail: failed requests surface as Outputs with ``error`` set).
        Returns Outputs ordered by request id. Drains only its own
        requests; drive a shared/online engine with step() instead
        (other requests' outputs surfacing mid-run would be dropped
        here).

        ``heartbeat_timeout=T`` attaches an in-process
        ``distributed.watchdog.Heartbeat``: every completed step ticks
        it, and a loop that makes no progress for T seconds triggers
        ``_stall_report`` — a per-thread stack dump plus a best-effort host-state snapshot
        (to ``snapshot_path`` when given, always kept on
        ``last_stall_snapshot``) so a wedged serving process leaves a
        recoverable trail before the pod is killed. It fires WHILE a
        step hangs; where a step that returned had sat is said
        afterwards by the step record (``step_log.slow()``)."""
        ids_list = []
        for item in requests:
            if isinstance(item, (tuple, list)) and len(item) == 2 and \
                    isinstance(item[1], (SamplingParams, dict)):
                ids_list.append(self.add_request(item[0], item[1]))
            else:
                ids_list.append(self.add_request(item))
        want = set(ids_list)
        hb = None
        if heartbeat_timeout is not None:
            from ..distributed.watchdog import Heartbeat
            hb = Heartbeat(
                float(heartbeat_timeout),
                on_stall=lambda age: self._stall_report(
                    age, snapshot_path))
            hb.start()
        outs: List[Output] = []
        try:
            for _ in range(max_steps):
                outs.extend(o for o in self.step() if o.req_id in want)
                if hb is not None:
                    hb.tick()
                if len(outs) == len(want):
                    break
            else:
                raise RuntimeError(
                    f"engine did not drain in {max_steps} steps "
                    f"({len(outs)}/{len(want)} finished)")
        finally:
            if hb is not None:
                hb.stop()
        # an eos leaves one tick in flight behind its request
        self._drain("api")
        return sorted(outs, key=lambda o: o.req_id)

    def cancel(self, req_id: int) -> Optional[Output]:
        """Abort a live or queued request NOW: its slot is freed, its
        pages return to the pool, and its Output (``finish_reason
        "cancelled"``, tokens generated so far) is returned. Unknown
        or already-retired ids return None. Safe at any lifecycle
        point — waiting, preempted, or mid-decode (the fixed-shape
        decode step simply sees one more idle lane next tick)."""
        # the Output holds every token the device has produced
        self._drain("api")
        req = self.requests.get(int(req_id))
        if req is None or req.state in (FINISHED, FAILED):
            return None
        self._mon.counter("serving.cancelled").increase()
        return self._fail(req, "cancelled")

    def extract_request(self, req_id: int,
                        device_key: bool = True) -> Optional[Request]:
        """Remove a live request from this engine ENTIRELY — slot
        cleared, pages freed, dropped from the queue and the request
        table — and return it as host source of truth (prompt, tokens
        generated so far, sampling params, rng chain), ready for
        re-admission elsewhere through the preemption/resume-prefill
        machinery. The live-migration hook the serving fleet
        (inference/fleet.py) moves in-flight requests between replicas
        with: re-admitting the returned Request on another engine over
        the same weights continues the token stream bit-exactly.

        ``device_key=True`` pulls the request's rng chain down from the
        device-resident decode state (the same fetch preemption does);
        ``device_key=False`` skips the device read — the caller must
        then set ``req.key`` itself (the fleet replays it from
        (seed, tokens emitted) via ``disagg.replay_rng_key``, the
        host-truth-only migration contract; the tick in flight is
        then left alone too: the request leaves with the tokens the
        host holds and that tick's token for its lane is discarded,
        to be produced again where the request resumes). Returns None
        for unknown or already-retired ids."""
        self._refuse_for_state("extract_request")
        if device_key:
            self._drain("api")
        req = self.requests.get(int(req_id))
        if req is None or req.state in (FINISHED, FAILED):
            return None
        i = req.slot
        if device_key and i is not None and req.state == DECODE \
                and i not in self._dirty:
            # the rng chain lives device-side between decode ticks
            # (see _preempt); a dirty slot's freshest key is already
            # the host mirror
            req.key = np.asarray(self._dev[5])[i].astype(np.uint32)
        self._clear_slot(req)
        try:
            self._waiting.remove(req)
        except ValueError:
            pass
        self.requests.pop(req.req_id, None)
        # PREEMPTED is the has-progress resume state: a re-admission
        # rebuilds the cache from the kept tokens and the rng chain
        # continues exactly (WAITING when no token was emitted yet —
        # no rng was consumed, a from-scratch prefill is exact)
        req.state = PREEMPTED if req.generated else WAITING
        req.last_token_t = 0.0      # no token gap spans a migration
        # the extraction IS the migration's start: the open span
        # (DECODE/PREFILL/QUEUED) closes here and MIGRATING runs until
        # the destination engine's next span — origin stays the SOURCE
        # label, so a stitched timeline shows where the request left
        tracing.open_span(req.spans, tracing.MIGRATING,
                          self._clock() * 1e3, self.label)
        return req

    def _refuse_for_state(self, what: str) -> None:
        """The entries that move a request between engines carry its
        tokens and no per-slot state: for a spec with state they are
        refused by name, not run with the state dropped in silence."""
        if self._has_state:
            raise ValueError(
                f"{what} is not supported for {self._spec_name}: what "
                f"a slot keeps by state (a recurrent state, a window's "
                f"ring of keys and values) is not part of what it moves "
                f"(docs/SERVING.md 'Model polymorphism')")

    def snapshot(self, sync: bool = True) -> dict:
        """Crash-exact host-state snapshot (reliability.py has the
        format): queued + live request tokens, rng chains, sampling
        params, admission order, prefix-index metadata — NOT KV pools.
        ``sync=False`` skips the device fetch of live rng rows (the
        stall-dump path, where the device may be wedged) at the cost
        of exactness for mid-flight SAMPLING requests."""
        from .reliability import snapshot_engine
        self._refuse_for_state("snapshot")
        if sync:
            # the rng rows fetched belong to the newest token; without
            # the sync the snapshot is the host's view, one tick behind
            self._drain("api")
        return snapshot_engine(self, sync=sync)

    def restore(self, snap: dict, strict: bool = True) -> int:
        """Re-admit a snapshot's requests into this (fresh or drained)
        engine through the preemption/resume-prefill machinery; the
        restored run's outputs are bit-identical to the uninterrupted
        one. Returns the number of requests re-admitted."""
        from .reliability import restore_engine
        self._refuse_for_state("restore")
        self._drain("api")
        return restore_engine(self, snap, strict=strict)

    def snapshot_to(self, path: str, sync: bool = True) -> str:
        from .reliability import save_snapshot
        return save_snapshot(self.snapshot(sync=sync), path)

    def restore_from(self, path: str, strict: bool = True) -> int:
        from .reliability import load_snapshot
        return self.restore(load_snapshot(path), strict=strict)

    def check_invariants(self, repair: bool = False) -> List[str]:
        """Cross-check the allocator against every reference the
        engine can account for (live requests' pages + one per
        prefix-cache entry) plus the allocator's own free-list/
        refcount consistency and the prefix index's digest integrity.
        Returns findings (empty = healthy); ``repair=True`` also fixes
        them (the chaos-recovery path). Auto-run each step under
        ``FLAGS_serving_debug_invariants`` (raise on findings) or an
        active fault injector (repair + count)."""
        self._drain("api")
        return self._audit(repair)

    def _audit(self, repair: bool) -> List[str]:
        """check_invariants on the host's view as it stands (inside a
        step, with a tick in flight: the audit reads no device)."""
        expected: Dict[int, int] = {}
        for r in self.requests.values():
            held = r.pages if r.pages else (r.shared_pages or [])
            for p in held:
                expected[p] = expected.get(p, 0) + 1
        if self._prefix is not None:
            for ent in self._prefix._store.values():
                expected[ent.page] = expected.get(ent.page, 0) + 1
        findings = self._alloc.check_invariants(expected=expected,
                                                repair=repair)
        if self._prefix is not None:
            findings += self._prefix.check_integrity(repair=repair)
        return findings

    def _republish_moe_paths(self) -> None:
        """Mirror the trace-time ``kernels.moe.decode_path.*`` counters
        (bumped while a prefill/decode/verify executable over an MoE
        model traces) into ``serving.moe.decode_path.*`` — the
        engine-scoped proof that its compiled surfaces run the fused
        Pallas dispatch and never silently fell back (docs/SERVING.md
        "MoE serving"; tests and the replay tool assert on these)."""
        prefix = "kernels.moe.decode_path."
        for key, val in monitor.snapshot().items():
            if not key.startswith(prefix):
                continue
            delta = int(val) - self._moe_seen.get(key, 0)
            if delta > 0:
                suffix = key[len(prefix):]
                monitor.counter(
                    "serving.moe.decode_path." + suffix).increase(delta)
                self._moe_paths[suffix] = \
                    self._moe_paths.get(suffix, 0) + delta
            self._moe_seen[key] = int(val)

    def moe_decode_path(self) -> Dict[str, int]:
        """THIS engine's MoE dispatch-path breakdown (suffix -> count;
        the per-engine slice of ``serving.moe.decode_path.*``): which
        MoE dispatch its compiled executables baked in. Empty for
        non-MoE models; ``{"pallas": n}`` with no ``fallback.*`` keys
        is the no-silent-fallback proof the acceptance tests assert."""
        return dict(self._moe_paths)

    def steady_state_recompiles(self) -> int:
        """XLA compiles INSIDE this engine's step() calls after the
        last step that legitimately introduced a new executable (a new
        prefill bucket or a decode variant) — the number that must be
        0 under steady-state mixed traffic. Compiles by other code in
        the process (another engine, a generate() call) don't count."""
        return self._compiles - self._warm_compiles

    def close(self):
        """Detach the engine's compile tracker from the global
        jax.monitoring fan-out (listener hygiene for processes that
        build many engines; also runs at garbage collection). A tick
        in flight is harvested first."""
        self._drain("api")
        self._tracker.stop()

    def __del__(self):
        try:
            self._tracker.stop()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    @property
    def num_waiting(self) -> int:
        return len(self._waiting)

    @property
    def num_active(self) -> int:
        return sum(1 for r in self._slots
                   if r is not None and r.state == DECODE)

    @property
    def num_prefilling(self) -> int:
        """Slots holding a request mid-prefill between ticks — nonzero
        only under chunked prefill (monolithic prefills complete inside
        the step that admits them). Idle checks must include it: an
        engine with a half-written whale and no decoders is NOT idle."""
        return sum(1 for r in self._slots
                   if r is not None and r.state == PREFILL)

    @property
    def idle(self) -> bool:
        """True when a step() would do no work: nothing queued, nothing
        decoding, nothing mid-prefill, no tick in flight to harvest and
        no Output of a forced drain to hand out. The drive-loop check
        for replay tools and offline batch drivers (fast-forwarding a
        virtual clock, or sleeping to the next arrival, is only safe
        here)."""
        return (not self._waiting and self.num_active == 0
                and self.num_prefilling == 0
                and self._inflight is None and not self._held)

    @property
    def pages_free(self) -> int:
        return self._alloc.free_pages

    def leaked_pages(self) -> int:
        """Pages still allocated after idle prefix-cache references
        are released — THE drained-engine leak check the bench and
        replay chaos gates share (0 on a healthy drained engine).
        Destructive to the prefix cache's idle entries: call it only
        on a drained engine at gate time."""
        if self._prefix is not None:
            self._prefix.clear()
        return self.pool_pages - self.pages_free

    # -- reliability internals -----------------------------------------------

    def _fault(self, site: str) -> bool:
        """One fault-point query against the injector (False when no
        injector is armed — the production fast path)."""
        return self._injector is not None and self._injector.fire(site)

    def _fault_raise(self, site: str) -> None:
        if self._fault(site):
            raise InjectedFault(site)

    def _prefix_faults(self) -> None:
        """Per-step prefix-cache fault points: a forced digest
        collision (the exact-token compare must degrade it to a miss)
        and a corrupted-stale entry (must never be hit again; the
        audit/eviction reclaims it)."""
        if self._prefix is None:
            return
        if self._fault("prefix.hash_collision"):
            self._prefix.force_collision()
        if self._injector.fire("prefix.stale_entry", record=False) \
                and len(self._prefix):
            # recorded only when there was an entry to corrupt — the
            # chaos report never claims faults that did not land
            self._injector.record("prefix.stale_entry")
            self._prefix.corrupt_entry(self._injector.rng)

    def _maybe_audit(self) -> None:
        auditing = self._debug_invariants or (
            self._injector is not None
            and self._injector.enabled("alloc.refcount_skew"))
        if not auditing:
            return
        repair = self._injector is not None
        findings = self._audit(repair)
        if findings:
            if repair:
                monitor.counter("serving.invariant_repairs").increase(
                    len(findings))
            else:
                raise RuntimeError(
                    "engine invariant audit failed "
                    "(FLAGS_serving_debug_invariants):\n  "
                    + "\n  ".join(findings))

    def _expire(self) -> List[Output]:
        """Tick-start deadline sweep: fail every request past its
        wall deadline (waiting OR mid-decode — its pages free this
        tick) and every waiting request past its queue-step budget.

        Enforcement granularity is one step(): a deadline can be
        overrun by at most one step before this sweep retires the
        request, and an expired request's in-flight token is discarded
        at harvest. ``max_queue_steps`` counts step() calls."""
        outs: List[Output] = []
        now = self._clock()
        for req in list(self._waiting) + [r for r in self._slots
                                          if r is not None]:
            p = req.params
            if p.deadline_ms is not None and \
                    (now - req.arrival_t) * 1e3 > float(p.deadline_ms):
                self._mon.counter("serving.timeouts").increase()
                outs.append(self._fail(req, "deadline"))
            elif p.max_queue_steps is not None and \
                    req.state in (WAITING, PREEMPTED) and \
                    self._steps - req.queued_step \
                    > int(p.max_queue_steps):
                self._mon.counter("serving.timeouts").increase()
                outs.append(self._fail(req, "queue_timeout"))
        return outs

    def _stall_report(self, age: float,
                      snapshot_path: Optional[str] = None) -> None:
        """Heartbeat stall callback (watchdog thread): dump every
        thread's stack to stderr and best-effort snapshot the host
        state — the recoverable trail a wedged serving process leaves
        before its pod is killed. ``sync=False``: the device may be
        the thing that's wedged, so no device fetch."""
        import faulthandler
        monitor.counter("serving.stalls").increase()
        print(f"engine watchdog: run() loop stalled for {age:.1f}s at "
              f"step {self._steps} ({self.num_active} active, "
              f"{len(self._waiting)} waiting, "
              f"{self._alloc.free_pages} pages free) — dumping stacks "
              f"and snapshotting", flush=True)
        try:
            faulthandler.dump_traceback(all_threads=True)
        except Exception:  # noqa: BLE001 — diagnostics must not raise
            pass
        try:
            self.last_stall_snapshot = self.snapshot(sync=False)
            if snapshot_path:
                from .reliability import save_snapshot
                save_snapshot(self.last_stall_snapshot, snapshot_path)
        except Exception as e:  # noqa: BLE001 — best-effort dump
            print(f"engine watchdog: stall snapshot failed: {e}",
                  flush=True)

    def _safe_prefill(self, req: Request, half, arg) -> Optional[Output]:
        """Isolation wrapper around either half of a prefill chunk
        (`_prefill`, the dispatch; `_prefill_harvest`, the wait and
        the handover): a failing prefill retires or requeues THIS
        request — it never takes down the step() loop (the other
        slots' state is untouched; the failed call's pages are rolled
        back)."""
        try:
            return half(req, arg)
        except PoolPressure as e:
            # resource pressure, not a failure: admission (chunked)
            # charges only the first slice, so a mid-prefill dry pool
            # is the NORMAL backpressure path — wait for pages without
            # burning the retry budget (an admitted request always
            # fits the pool alone; running sequences finishing or
            # preempting unblocks it)
            return self._requeue(req, str(e).partition("\n")[0],
                                 count_retry=False)
        except InjectedFault:
            monitor.counter("serving.step_errors").increase()
            return self._requeue(req, "injected device error")
        except RuntimeError as e:
            # other transient prefill errors: back off and retry on a
            # later tick, against the retry budget
            return self._requeue(req, str(e).partition("\n")[0]
                                 or type(e).__name__)
        except Exception as e:  # noqa: BLE001 — request isolation
            monitor.counter("serving.step_errors").increase()
            return self._fail(req, f"error:{type(e).__name__}")

    def _requeue(self, req: Request, why: str,
                 count_retry: bool = True) -> Optional[Output]:
        self._rollback_prefill(req)
        if count_retry:
            req.retries += 1
            if req.retries > MAX_PREFILL_RETRIES:
                return self._fail(req, f"error:prefill ({why})")
        req.state = PREEMPTED if req.generated else WAITING
        req.queued_step = self._steps
        self._waiting.appendleft(req)
        return None

    def _rollback_prefill(self, req: Request) -> None:
        """Undo a partially executed prefill: drop every page
        reference the request holds — merged (req.pages) or still
        admission-only (shared_pages) — and hand its slot back."""
        self._clear_slot(req)

    def _safe_decode(self) -> Optional[_PendingTick]:
        """Isolation wrapper around the batched decode/verify
        dispatch: an injected device error fires BEFORE dispatch (host
        state still coherent), so the engine just skips the tick and
        retries — requests see one step of extra latency, never
        corruption."""
        try:
            return self._decode_dispatch()
        except InjectedFault:
            monitor.counter("serving.step_errors").increase()
            return None

    # -- scheduler internals -------------------------------------------------

    def _admit(self) -> List[Request]:
        admitted = []
        reserved = 0          # pages already promised this tick: the
        while self._waiting:  # prefills run AFTER the admit loop
            slot = next((i for i, r in enumerate(self._slots)
                         if r is None), None)
            if slot is None:
                break
            req = self._waiting[0]
            toks = req.resume_tokens()
            if self._prefix is not None and req.shared_pages is None:
                # map the longest cached prefix NOW (references taken,
                # so the pages can't be evicted out from under the
                # admission decision), capped so at least one real
                # token is left for the tail prefill — the append page
                # stays private even when its contents are cached (the
                # copy-on-write fork, docs/SERVING.md)
                req.shared_pages, req.prefix_len = self._prefix.acquire(
                    toks, max_chunks=(len(toks) - 1) // self.page_size)
                self._mon.counter("serving.prefix_lookups").increase()
                if req.prefix_len:
                    self._mon.counter("serving.prefix_hits").increase()
            # shared pages are already resident — admission charges
            # only the UNCACHED tail (a would-be-shared prefix must
            # not inflate apparent pool pressure; each shared page is
            # one pool slot however many block tables map it). Under
            # chunked prefill only the FIRST slice is charged: later
            # slices allocate as they run, so a long prompt that fits
            # incrementally is admitted (the per-slice alloc path backs
            # off and requeues if the pool tightens meanwhile).
            tail = len(toks) - req.prefix_len
            if self.max_prefill_tokens_per_step is not None:
                tail = min(tail, self.max_prefill_tokens_per_step)
            need = _ceil_div(self._pbucket(tail), self.page_size)
            # the watermark reserves growth headroom for RUNNING
            # sequences; an otherwise-empty engine admits with the
            # whole pool (a big request must not starve behind
            # headroom nobody needs)
            busy = any(r is not None for r in self._slots)
            wm = self.watermark_pages if busy else 0
            if not self._alloc.can_alloc(need + reserved, wm):
                # reclaim idle prefix-cache pages (refcount==0 users,
                # LRU) before refusing admission
                short = need + reserved + wm - self._alloc.free_pages
                if self._prefix is None or \
                        self._prefix.evict(short) < short:
                    break
            reserved += need
            self._waiting.popleft()
            req.slot = slot
            req.state = PREFILL
            # of its queue time, the wait for a SLOT ends here; the rest,
            # to its first slice, is the wait for prefill budget
            tracing.mark_admitted(req.spans, self._clock() * 1e3)
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
            self._slots[slot] = req
            admitted.append(req)
        return admitted

    def _open_span(self, req: Request, phase: str,
                   slot: Optional[int] = None, **detail) -> None:
        """Open the request's next timeline span at the engine clock,
        closing the prior one at the same instant (contiguity is
        structural). Span-derived latency histograms record at the
        phase boundary: a QUEUED/PREEMPTED span closing into PREFILL
        is the queue wait; a MIGRATING span closing anywhere is the
        migration latency (recorded by the DESTINATION engine's scope
        — where the request landed)."""
        t = self._clock() * 1e3
        closed = tracing.close_open(req.spans, t)
        if closed is not None:
            dur = closed["t1_ms"] - closed["t0_ms"]
            if closed["phase"] == tracing.MIGRATING:
                self._mon.histogram(
                    "serving.hist.migration_ms").record(dur)
            elif closed["phase"] in (tracing.QUEUED,
                                     tracing.PREEMPTED) \
                    and phase == tracing.PREFILL:
                self._mon.histogram(
                    "serving.hist.queue_wait_ms").record(dur)
        tracing.open_span(req.spans, phase, t, self.label, slot=slot,
                          **detail)

    def _sync_timed(self, outs) -> None:
        """Block until dispatched device results land: THE attributed
        wait of the tick loop (the hot-path lint knows it by name),
        called inside an `engine.decode.wait` / `engine.prefill.wait`
        span, which is what times it. The np.asarray consumers that
        follow read ready buffers."""
        jax.block_until_ready(outs)

    def _run_prefills(self) -> List[Output]:
        """Run this tick's prefill work over every PREFILL-state slot.

        Monolithic mode (``max_prefill_tokens_per_step=None``): each
        pending request writes its whole tail in one bucketed chunk, in
        admission order — exactly the pre-chunking behavior.

        Chunked mode: each pending request gets at most ONE slice, in
        SHORTEST-REMAINING-FIRST order (a small request admitted beside
        a mid-prefill whale reaches its first token on the next tick
        instead of after the whale's whole prompt); each slice is
        capped at the budget REMAINING when its turn comes, so the
        step's total stays within the budget (± one bucket of
        rounding). The OLDEST pending request always gets at least a
        one-bucket slice even with the budget exhausted — a sustained
        flood of small prefills can slow the whale, never starve it.
        Then the decode tick below runs for every DECODE slot — the
        interleave that bounds whale-induced TTFT inflation to one
        slice."""
        pending = [r for r in self._slots
                   if r is not None and r.state == PREFILL]
        if not pending:
            return []
        budget = self.max_prefill_tokens_per_step
        if budget is None:
            order = sorted(pending, key=lambda r: r.admit_seq)
            oldest = None
        else:
            # remaining REAL work: a fresh request whose head is a
            # prefix-cache hit has written == 0 until its first slice,
            # but its cached prefix_len never runs through a prefill —
            # rank it by the uncached tail it will actually execute
            order = sorted(
                pending,
                key=lambda r: (r.resume_len()
                               - max(r.written, r.prefix_len),
                               r.admit_seq))
            oldest = min(pending, key=lambda r: r.admit_seq)
        outs: List[Output] = []
        for req in order:
            cap = None
            if budget is not None:
                left = budget - self._pf_step_tokens
                if left <= 0 and req is not oldest:
                    # the wait for prefill BUDGET, not for a slot
                    self.step_log.starved += 1
                    continue
                cap = max(self.prefill_bucket, left)
            out = self._safe_prefill(req, self._prefill, cap)
            if out is not None:
                outs.append(out)
        return outs

    def _prefill(self, req: Request,
                 cap: Optional[int] = None) -> Optional[Output]:
        """Write the next chunk of the request's prefix into the pool
        (the whole tail in monolithic mode, one bounded slice — at
        most ``cap`` tokens, the scheduler's remaining step budget —
        under ``max_prefill_tokens_per_step``); fresh requests sample
        their
        first token on the FINAL chunk (TTFT). Resumed (preempted)
        requests only rebuild their cache — the sampled token and key
        are discarded, so the request's RNG chain continues exactly
        where it stopped. A partially prefilled request keeps its slot
        and pages across slices (state PREFILL, ``req.written`` marks
        progress) and stays cancellable / deadline-expirable /
        preemptible / snapshot-able at every slice boundary.

        With the prefix cache on, the shared pages acquired at
        admission land directly in the block table and ONLY the
        uncached tail runs through the model — a hit deeper than one
        bucket skips all of its cached chunks, and a long uncached
        tail is still sliced. All writes stay in private pages: the
        cached prefix is page-aligned and every page from the tail
        onward is freshly allocated.

        Token-exactness vs monolithic prefill: every slice runs the
        SAME bucketed executables at a traced start offset, and the
        in-chunk attention reads K/V back from the paged pools (the
        multi-token paged path gathers the cache it just wrote), so a
        sliced prefix produces bit-identical cache contents and first
        tokens — under any cache_dtype."""
        with self._span("engine.prefill", req=req.req_id) as span:
            toks = req.resume_tokens()
            fresh = not req.generated
            P = len(toks)
            if not req.pages:
                # first chunk: the shared prefix pages acquired at
                # admission land in the block table now; every page the
                # request writes from here on is private
                req.pages = list(req.shared_pages or [])
                req.written = req.prefix_len   # page-aligned by construction
            start = req.written
            T = P - start
            if self.max_prefill_tokens_per_step is not None:
                limit = self.max_prefill_tokens_per_step
                if cap is not None:
                    # the scheduler's remaining step budget, floored at one
                    # bucket so a scheduled request always makes progress
                    limit = min(limit, max(self.prefill_bucket, int(cap)))
                T = min(T, limit)
            final = start + T >= P
            # bucket the chunk, but never past the block table: a deep
            # cached prefix (or a near-max_context prompt) leaves less than
            # one full bucket of room, and the padding positions would
            # overflow the [1, max_blocks] row (add_request guarantees the
            # REAL tokens always fit, so clipping only ever drops padding).
            pb = min(self._pbucket(T),
                     self.max_blocks * self.page_size - start)
            span.set(bucket=pb, tokens=T, start=start, final=int(final))
            if self._has_state:
                # 1: the chunk starts from the slot's rows, not from zeros
                span.set(state_carry=int(start > 0))
            # allocate pages for REAL tokens only: block-table rows beyond
            # them stay 0, so the chunk's bucket-padding writes land in the
            # shared scratch page (the masked-lane convention) instead of
            # transiently holding pool pages that would be trimmed right
            # back — the request's peak page demand never exceeds its real
            # token count, which is what _lifetime_pages charges
            need = _ceil_div(start + T, self.page_size) - len(req.pages)
            if self._fault("alloc.exhausted"):
                # simulated admission race / fragmented pool: surfaces as
                # pool pressure, which _safe_prefill turns into a clean
                # budget-free requeue-and-retry
                raise PoolPressure(
                    f"injected pool exhaustion: sequence {req.req_id} "
                    f"requested {need} page(s)")
            if need > 0:
                try:
                    priv = self._alloc.alloc(need, seq=req.req_id)
                except RuntimeError:
                    # admission charged only the first slice (or a test may
                    # drive _prefill directly): reclaim idle cached pages,
                    # then surface ANY remaining shortfall as backpressure
                    # (a partial evict must not turn into a retry-budget-
                    # burning RuntimeError)
                    if self._prefix is not None:
                        self._prefix.evict(need)
                    try:
                        priv = self._alloc.alloc(need, seq=req.req_id)
                    except RuntimeError as e2:
                        raise PoolPressure(str(e2)) from e2
                req.pages = req.pages + priv
            bt_row = np.zeros((1, self.max_blocks), np.int32)
            bt_row[0, :len(req.pages)] = req.pages
            prompt = np.zeros((1, pb), np.int32)
            prompt[0, :T] = toks[start:start + T]
            p = req.params
            # one timeline span per slice: the QUEUED (or PREEMPTED /
            # MIGRATING) wait closes here, consecutive slices chain
            self._open_span(req, tracing.PREFILL, slot=req.slot,
                            start=int(start), tokens=int(T))
            fn = self._get_prefill_fn(pb)
            bt_dev = jnp.asarray(bt_row)
            prompt_dev = jnp.asarray(prompt)
            start_dev = jnp.asarray([start], jnp.int32)
            self._fault_raise("prefill.device_error")
            # the one-value arguments go to the program as host arrays:
            # its call uploads them together, where a jnp.asarray each
            # is a dispatch of its own (and, from a Python float, a
            # device program to convert it) with the chip often idle
            poison = np.asarray(
                [float("nan") if self._fault("prefill.nan") else 0.0],
                np.float32)
            tok, key2, okf, self._pools, *extras = fn(
                self._st, self._pools, bt_dev, prompt_dev,
                np.asarray([T], np.int32), start_dev,
                np.asarray([p.temperature], np.float32),
                np.asarray([p.top_k], np.int32),
                np.asarray([p.top_p], np.float32),
                np.asarray(req.key[None], np.uint32), poison,
                np.asarray([req.slot], np.int32))
            if self._spec is not None:
                # mirror the chunk into the draft pools (same pages, same
                # positions) so drafting attends the full context
                self._spec.prefill(pb, bt_dev, prompt_dev, start_dev)
            self._prefilled.append(_PendingPrefill(
                req=req, data=(tok, key2, okf), extras=tuple(extras),
                toks=toks, start=start, tokens=T, fresh=fresh))
            log = self.step_log
            log.chunks += 1
            log.chunk_tokens += T
            log.largest_bucket = max(log.largest_bucket, pb)
            if final and self._prefix is not None:
                # register this prefix's full pages (newly computed chunks
                # only; chunks matched at admission are already cached)
                # NOW, for the router that looks between steps: whatever
                # maps them is dispatched behind this chunk, and a chunk
                # that comes back NaN takes them out again at its harvest
                self._prefix.insert(toks, req.pages, P)
            self._mon.counter("serving.prefill_tokens").increase(pb)
            self._mon.counter("serving.prefill_slices").increase()
            self._pf_step_tokens += pb
            if self._has_state:
                if start == 0:
                    self._mon.counter("serving.state.resets").increase()
                if final and not fresh:
                    self._mon.counter(
                        "serving.state.recomputes").increase()
            if start == req.prefix_len:
                monitor.counter(
                    "serving.prefix_tokens_reused").increase(start)
            return None

    def _settle_prefills(self) -> List[Output]:
        """Wait for and harvest every chunk dispatched and not yet
        waited for, in dispatch order. step() calls it behind its own
        decode dispatch (or at once, with no tick in flight to hide
        the wait behind); a drain calls it after the tick's harvest."""
        chunks, self._prefilled = self._prefilled, []
        outs = (self._safe_prefill(p.req, self._prefill_harvest, p)
                for p in chunks)
        return [o for o in outs if o is not None]

    def _prefill_harvest(self, req: Request,
                         p: _PendingPrefill) -> Optional[Output]:
        """The host half of a chunk: the request's first token and key
        go through the host into the slot's row; a final chunk
        activates the slot for the NEXT dispatch."""
        with self._span("engine.prefill.harvest", req=req.req_id):
            # key2 rides in the sync set: the fresh-request path below
            # reads it (np.asarray) and an unsynced fetch would be an
            # un-attributed host sync (hotpath.host-sync-in-tick)
            tok, key2, okf = p.data
            if req.slot is None or self._slots[req.slot] is not req:
                # it left its slot with the chunk in flight
                # (extract_request(device_key=False) drains nothing;
                # it is admitted again only after this harvest): the
                # chunk has nothing to hand over
                return None
            final = p.start + p.tokens >= len(p.toks)
            ok = False
            try:
                with self._span("engine.prefill.wait"):
                    self._sync_timed(p.data)
                ok = bool(np.asarray(okf)[0])
            finally:
                if not ok and final and self._prefix is not None:
                    self._prefix.discard(
                        p.toks, req.pages,
                        req.prefix_len // self.page_size)
            if not ok:
                # NaN/inf on the chunk's sampling logits: quarantine the
                # request (pages freed, nothing stays in the prefix
                # cache) — the other slots never see it
                self._mon.counter("serving.nan_quarantines").increase()
                return self._fail(req, "nan_logits")
            row = self._take_extras(p.extras, stats=False)
            req.written = p.start + p.tokens
            if not final:
                return None       # stays PREFILL; a later tick continues
            if p.fresh:
                t = int(np.asarray(tok)[0])
                req.key = np.asarray(key2)[0].astype(np.uint32)
                req.generated.append(t)
                if req.params.return_logits:
                    req.logits.append(np.asarray(row[0]))
                req.first_token_t = req.last_token_t = self._clock()
                self._mon.counter("serving.tokens").increase()
                reason = self._finish_reason(req, t)
                if reason:
                    return self._finish(req, reason)
            self._activate(req)
            return None

    def _activate(self, req: Request):
        i = req.slot
        self._bt[i] = 0
        self._bt[i, :len(req.pages)] = req.pages
        self._pos[i] = req.written
        self._last[i] = req.generated[-1]
        self._temps[i] = req.params.temperature
        self._topks[i] = req.params.top_k
        self._topps[i] = req.params.top_p
        self._keys[i] = req.key
        self._live[i] = 1
        eos = req.params.eos_token_id
        self._eos[i] = -1 if eos is None else int(eos)
        self._bud[i] = int(req.params.max_new_tokens) - len(req.generated)
        self._dirty.add(i)
        self._bt_dirty = True
        req.state = DECODE
        # one tick-aggregated DECODE span from activation to
        # finish/preempt/migrate (not per tick — the timeline stays
        # O(lifecycle transitions), not O(tokens))
        self._open_span(req, tracing.DECODE, slot=i)

    def _ensure_pages(self):
        """Before the NEXT dispatch, every lane it computes must own
        every page its writes land in — one position past the tick in
        flight for the plain decode step, k+1 for a speculative
        draft/verify tick; allocate lazily, preempting the YOUNGEST
        sequence when the pool runs dry (after reclaiming idle
        prefix-cache pages; a tick in flight is drained first)."""
        with self._span("engine.ensure_pages") as span:
            allocated = 0
            # a preemption is the only thing here that queues a request
            waiting0 = len(self._waiting)
            for i, req, ahead in self._lanes():
                if req.state != DECODE:
                    continue      # ended or preempted inside this loop
                # req.written lags the device by the tick in flight
                need = _ceil_div(req.written + ahead + self._lookahead,
                                 self.page_size)
                while len(req.pages) < need:
                    page = self._alloc_or_preempt(req)
                    if page is None:      # req itself got preempted
                        break
                    req.pages.extend(page)
                    allocated += len(page)
                    self._bt[i, :len(req.pages)] = req.pages
                    self._bt_dirty = True
            span.set(allocated=allocated,
                     preempted=len(self._waiting) - waiting0)

    def _alloc_or_preempt(self, req: Request):
        while True:
            try:
                if self._fault("alloc.exhausted"):
                    # simulated mid-decode pool pressure: flows
                    # through the SAME evict-or-preempt ladder a real
                    # dry pool takes (the retry loop re-queries, so
                    # one injection costs at most one eviction)
                    raise RuntimeError(
                        f"injected pool exhaustion: sequence "
                        f"{req.req_id} requested 1 page")
                return self._alloc.alloc(1, seq=req.req_id)
            except RuntimeError:
                # idle cached pages go first: evicting a cold prefix
                # is free, preempting a live sequence costs a resume
                # prefill. Mid-prefill (chunked) requests are victims
                # too — they sit at a slice boundary, and their resume
                # is the same re-prefill every preemption pays — so a
                # whale's half-written prompt can never wedge the pool
                # against running decodes.
                if self._prefix is not None and self._prefix.evict(1):
                    continue
                if self._inflight is not None:
                    # a victim's sampler key is read from the device
                    # (_preempt), which the tick in flight has moved
                    # past the tokens the host holds: harvest it first.
                    # That may free pages, or end `req` itself
                    self._drain("preempt")
                    if req.state != DECODE:
                        return None
                    continue
                victims = [r for r in self._slots
                           if r is not None
                           and r.state in (DECODE, PREFILL)]
                if not victims:
                    raise
                victim = max(victims, key=lambda r: r.admit_seq)
                self._preempt(victim)
                if victim is req:
                    return None

    def _preempt(self, req: Request):
        """Evict back to the waiting queue (front): pages freed, tokens
        and RNG chain kept — a resume prefill rebuilds the cache."""
        self._mon.counter("serving.preemptions").increase()
        self.step_log.preempted += 1
        req.preemptions += 1
        self._open_span(req, tracing.PREEMPTED, kind="pages")
        i = req.slot
        if i is not None and i not in self._dirty \
                and req.state == DECODE:
            # the RNG chain lives device-side between decode steps;
            # pull this slot's key down so the resumed request
            # continues it exactly. (A dirty slot was just activated —
            # req.key is already the freshest value. Fetch the whole
            # array, slice host-side: a device-side row gather would
            # compile a tiny executable per slot index.)
            req.key = np.asarray(self._dev[5])[i].astype(np.uint32)
            self._keys[i] = req.key
        self._clear_slot(req)
        # a mid-PREFILL victim with no generated tokens re-enters as
        # WAITING (PREEMPTED is the has-progress resume state; its rng
        # chain was never consumed, so a from-scratch prefill is exact)
        req.state = PREEMPTED if req.generated else WAITING
        req.queued_step = self._steps       # fresh queue-age budget
        self._waiting.appendleft(req)

    def _flush_state(self) -> None:
        """Host→device sync of the slot rows the scheduler touched
        since the last decode step (admissions, preemptions,
        finishes) plus the block table when a sequence crossed a page
        boundary. A steady-state decode tick — no scheduling events,
        no page growth — uploads NOTHING."""
        with self._span("engine.flush_state", rows=len(self._dirty),
                        block_table=int(self._bt_dirty)):
            if self._dirty:
                self._dev = _merge_rows(self._dev,
                                        self._up(self._pack_rows()))
                self._dirty.clear()
            if self._bt_dirty:
                self._bt_dev = self._up(self._bt)
                self._bt_dirty = False

    def _decode_dispatch(self) -> Optional[_PendingTick]:
        """Dispatch this step's decode work and return WITHOUT
        waiting. A single-tick dispatch runs AHEAD: the tick the last
        step dispatched may still be in flight, and this one queues
        behind it on the device-resident state that tick advances
        (sampled rows carry their keys there), so nothing here may
        need that tick's tokens. The lanes and the sampler variant
        come from the host mirrors plus what the host knows of the
        tick in flight (_lanes) — exactly the rows the dispatched
        executable computes, but for a lane that tick ends by its eos.
        A speculative dispatch is sized from host decisions made on
        the last harvest (the drafts) and is harvested inside its own
        step, so it never finds a tick in flight."""
        lanes = self._lanes()
        if not lanes:
            return None
        active = [i for i, _, _ in lanes]
        sampling = [i for i in active if self._temps[i] > 0.0]
        if not sampling:
            variant = "greedy"
        elif any(self._topks[i] > 0 or 0.0 < self._topps[i] < 1.0
                 for i in sampling):
            variant = "filtered"
        else:
            variant = "plain"
        # injected device loss fires BEFORE dispatch: host state is
        # still coherent, _safe_decode skips the tick and retries
        self._fault_raise("decode.device_error")
        self._poison_slot(active)
        if self._spec is not None:
            return self._dispatch_spec(lanes, variant)
        # steady = the dirty-row-merge discipline says this tick
        # uploads nothing and dispatches a warm executable — the
        # PADDLE_TPU_LINT transfer guard may wrap the dispatch
        steady = (variant in self._decode_fns and not self._dirty
                  and not self._bt_dirty and not self._poisoned)
        fn = self._get_decode_fn(variant)
        if self._inflight is not None:
            self._mon.counter("serving.runahead.dispatches").increase()
        self._flush_state()
        # the fused step: forward + per-slot sampling + state advance
        # in ONE executable; only the emitted tokens (and the tiny
        # NaN-quarantine flags) come back
        nxt, okv, self._dev, self._pools, *extras = \
            self._dispatch_steady(
                steady, fn, self._st, self._pools, self._bt_dev,
                self._dev, self._poison_dev)
        self._unpoison()
        return self._pending("single", (nxt, okv), lanes, variant,
                             extras=tuple(extras))

    def _drain(self, cause: str) -> None:
        """Wait for and harvest the tick in flight, then the prefill
        chunks dispatched behind it, NOW: what reads the
        device-resident state or the host's view of a slot as of the
        newest token (a preemption's key fetch, the public entries
        that move requests) calls this
        first. The Outputs it retires come out of the step() that is
        running, or of the next one. Counted by cause:
        ``serving.runahead.drains.<cause>``."""
        pend, self._inflight = self._inflight, None
        if pend is not None:
            self._mon.counter(
                "serving.runahead.drains." + cause).increase()
            self._held.extend(self._decode_harvest(pend))
        self._held.extend(self._settle_prefills())

    def _decode_harvest(self, pend: Optional[_PendingTick]
                        ) -> List[Output]:
        """Wait for the in-flight dispatch and retire its tokens. Rows
        whose request left DECODE while it was in flight (deadline
        expiry, cancel) are skipped — their in-flight tokens are discarded, exactly what
        the sequential expire-before-decode order produced."""
        if pend is None:
            return []
        with self._span("engine.decode.wait"):
            self._sync_timed(pend.data)
        harvest = self._harvest_spec if pend.kind == "spec" \
            else self._harvest_single
        with self._span("engine.harvest") as span:
            emitted = _Emitted(self._clock())
            outs = harvest(pend, emitted)
            emitted.record(self._mon)
            span.set(tokens=emitted.tokens, finished=len(outs))
        return outs

    def _harvest_single(self, pend: _PendingTick,
                        emitted: _Emitted) -> List[Output]:
        nxt = np.asarray(pend.data[0])
        okv = np.asarray(pend.data[1])
        rows = self._take_extras(pend.extras)
        outs: List[Output] = []
        dead = 0
        for i, req in pend.active:
            if self._slots[i] is not req or req.state != DECODE:
                # the request ended (eos, expiry, cancel, quarantine,
                # preemption) after this tick was dispatched with its
                # lane in it: the lane's token is no part of its stream
                dead += 1
                continue
            if not bool(okv[i]):
                # NaN/inf logits on THIS slot only: quarantine it
                # (token discarded, pages freed, slot back to the
                # pool) while every other lane keeps decoding
                self._mon.counter("serving.nan_quarantines").increase()
                outs.append(self._fail(req, "nan_logits"))
                continue
            tok = int(nxt[i])
            req.written += 1          # the step wrote last_token
            # mirror the device-side advance (NOT marked dirty: the
            # device already holds these values; the mirrors keep the
            # scheduler's view coherent for later dirty merges)
            self._pos[i] = req.written
            emitted.append(req, tok)
            self._last[i] = tok
            if req.params.return_logits:
                req.logits.append(np.asarray(rows[i]))
            reason = self._finish_reason(req, tok)
            if reason:
                # its pages are free from here on, while the tick in
                # flight may still hold the lane (an eos the host had
                # not seen); that lane is dead in-graph and writes the
                # scratch page, and a lane retired live (cancel,
                # expiry) writes a page it owned at dispatch: the
                # device runs its programs in order, so a prefill or
                # tick that is handed the page is dispatched, and
                # writes, after it
                outs.append(self._finish(req, reason))
        if dead:
            self._mon.counter(
                "serving.runahead.dead_lane_ticks").increase(dead)
        return outs

    def _poison_slot(self, active: List[int]) -> None:
        """decode.nan fault point: pick one active slot (seeded rng)
        and ride a NaN into its sampling logits this tick — the
        in-graph detector must flip exactly that slot's ok flag."""
        if active and self._fault("decode.nan"):
            victim = active[int(
                self._injector.rng.integers(0, len(active)))]
            pz = np.zeros((self.max_slots,), np.float32)
            pz[victim] = np.nan
            self._poison_dev = self._up(pz)
            self._poisoned = True

    def _unpoison(self) -> None:
        if self._poisoned:
            self._poison_dev = self._poison_zeros
            self._poisoned = False

    def _dispatch_spec(self, lanes, variant: str) -> _PendingTick:
        """Dispatch one draft/verify tick: the draft loop proposes k
        tokens per slot (one executable), the target scores all k+1
        positions in ONE batched forward — the accept walk happens at
        harvest. Each slot will emit its accepted chain + one free
        target token, every one bit-identical to what the plain decode
        loop would have emitted (verify_token_arrays' exact-match
        rule). Fault/poison points already fired in _decode_dispatch."""
        # steady tick: warm verify + draft-loop executables, nothing
        # dirty — the lint transfer guard may wrap the verify dispatch
        steady = (variant in self._verify_fns
                  and self._spec._loop_fn is not None
                  and not self._dirty and not self._bt_dirty
                  and not self._poisoned)
        self._flush_state()
        drafts = self._spec.draft(self._bt_dev, self._dev[0],
                                  self._dev[1], self._dev[6])
        if self._fault("spec.disagree"):
            # draft/target divergence storm: the drafted tokens are
            # replaced with garbage — exact-match verification must
            # reject them with the emitted stream unchanged (each
            # tick still yields >= 1 target-chain token)
            drafts = self._spec.sabotage(drafts)
        fn = self._get_verify_fn(variant)
        toks, acc, okv, self._dev, self._pools = self._dispatch_steady(
            steady, fn, self._st, self._pools, self._bt_dev, self._dev,
            drafts, self._poison_dev)
        self._unpoison()
        return self._pending("spec", (toks, acc, okv), lanes, variant)

    def _harvest_spec(self, pend: _PendingTick,
                      emitted: _Emitted) -> List[Output]:
        toks = np.asarray(pend.data[0])
        acc = np.asarray(pend.data[1])
        okv = np.asarray(pend.data[2])
        k = self._spec.k
        outs: List[Output] = []
        for i, req in pend.active:
            if self._slots[i] is not req or req.state != DECODE:
                continue          # retired in the overlap window
            if not bool(okv[i]):
                # NaN/inf across this slot's verify logits (spec-
                # verify divergence): quarantine the slot, keep the
                # rest of the batch serving
                self._mon.counter("serving.nan_quarantines").increase()
                outs.append(self._fail(req, "nan_logits"))
                continue
            n_acc = int(acc[i])
            self._spec_drafted += k
            self._spec_accepted += n_acc
            monitor.counter("serving.spec_drafted").increase(k)
            monitor.counter("serving.spec_accepted").increase(n_acc)
            finished = False
            for j in range(n_acc + 1):
                tok = int(toks[i, j])
                req.written += 1      # position pos+j held this input
                emitted.append(req, tok)
                reason = self._finish_reason(req, tok)
                if reason:
                    # mid-chain eos/budget: the tail of the chain is
                    # discarded exactly like the plain loop would
                    # never have generated it; _finish dirties the
                    # slot so the device state is overwritten
                    outs.append(self._finish(req, reason))
                    finished = True
                    break
            if not finished:
                # mirror the device-side advance (device already holds
                # these values — not dirty)
                self._pos[i] = req.written
                self._last[i] = req.generated[-1]
        return outs

    def _finish_reason(self, req: Request, tok: int) -> Optional[str]:
        p = req.params
        if p.eos_token_id is not None and tok == int(p.eos_token_id):
            return "eos"
        if len(req.generated) >= int(p.max_new_tokens):
            return "length"
        return None

    def _clear_slot(self, req: Request):
        i = req.slot
        if i is not None:
            self._bt[i] = 0
            self._pos[i] = 0
            self._last[i] = 0
            self._temps[i] = 0.0
            self._topks[i] = 0
            self._topps[i] = 0.0
            self._live[i] = 0
            self._eos[i] = -1
            self._bud[i] = 0
            self._slots[i] = None
            self._dirty.add(i)
            self._bt_dirty = True
            req.slot = None
        if req.pages:
            # one reference drop per page: private pages return to the
            # free list, shared prefix pages live on under the cache's
            # (or another request's) reference
            self._alloc.free(req.pages)
            req.pages = []
        elif req.shared_pages:
            # prefix refs taken at admission but never merged into
            # pages (a prefill that failed before assignment): drop
            # them here or they leak
            self._alloc.free(req.shared_pages)
        # a re-admission re-walks the prefix cache (the resume prefix
        # is longer, and entries may have been evicted meanwhile) and
        # restarts any partial (chunked) prefill from scratch
        req.shared_pages = None
        req.prefix_len = 0
        req.written = 0

    def _finish(self, req: Request, reason: str) -> Output:
        self._mon.counter("serving.finished").increase()
        return self._retire(req, reason, FINISHED)

    def _fail(self, req: Request, reason: str) -> Output:
        """Terminal FAILED(reason): the request is retired NOW — slot
        cleared, pages freed, removed from the queue — and surfaced as
        an Output with ``error`` set. The step() loop keeps serving
        every other request."""
        self._mon.counter("serving.failed").increase()
        return self._retire(req, reason, FAILED)

    def _retire(self, req: Request, reason: str, state: str) -> Output:
        req.finish_t = self._clock()
        req.state = state
        req.finish_reason = reason
        try:
            self._waiting.remove(req)     # failed while queued
        except ValueError:
            pass
        self._clear_slot(req)         # pages freed NOW, not end-of-call
        # `requests` tracks LIVE requests only — retaining finished
        # ones (full token lists) would grow without bound in a
        # long-running serving process; the Output carries everything
        self.requests.pop(req.req_id, None)
        n = len(req.generated)
        got_first = req.first_token_t > 0.0
        ttft_ms = ((req.first_token_t - req.arrival_t) * 1e3
                   if got_first else 0.0)
        tpot_ms = ((req.finish_t - req.first_token_t)
                   / (n - 1) * 1e3) if got_first and n > 1 else 0.0
        if got_first:
            self._mon.gauge("serving.ttft_ms").set(ttft_ms)
            self._mon.histogram("serving.hist.ttft_ms").record(ttft_ms)
        if got_first and n > 1:
            self._mon.gauge("serving.tpot_ms").set(tpot_ms)
            self._mon.histogram("serving.hist.tpot_ms").record(tpot_ms)
        # terminal span: timeline sealed at finish_t, the Output
        # carries its own copy (the Request object may be reused by
        # restore paths)
        tracing.seal(req.spans,
                     tracing.FINISHED if state == FINISHED
                     else tracing.FAILED,
                     req.finish_t * 1e3, self.label,
                     reason=None if state == FINISHED else reason)
        return Output(req_id=req.req_id, prompt_ids=list(req.prompt),
                      token_ids=list(req.generated),
                      finish_reason=reason, ttft_ms=ttft_ms,
                      tpot_ms=tpot_ms, preemptions=req.preemptions,
                      error=None if state == FINISHED else reason,
                      spans=tracing.copy_spans(req.spans),
                      logits=list(req.logits)
                      if req.params.return_logits else None)

    def _publish_gauges(self):
        mon = self._mon
        mon.gauge("serving.slots_active").set(self.num_active)
        mon.gauge("serving.pages_free").set(self._alloc.free_pages)
        mon.gauge("serving.queue_depth").set(len(self._waiting))
        mon.gauge("serving.prefill_tokens_per_step").set(
            self._pf_step_tokens)
        window = self.serving_spec.get("window")
        if window is not None:
            # pages a windowed layer's pool holds that no later query
            # can read any more (they stay allocated: one block table
            # serves every layer), over the slots that are decoding;
            # none where every windowed layer keeps a ring by slot
            mon.gauge("serving.cache.swa_pages_outside_window").set(sum(
                max(0, r.written - (int(window) - 1)) // self.page_size
                for r in self._slots
                if r is not None and r.state == DECODE)
                if self._paged_window else 0)
        if self._prefix is not None:
            mon.gauge("serving.prefix_hit_rate").set(
                self._prefix.hit_rate)
            mon.gauge("serving.prefix_pages_shared").set(
                self._alloc.shared_pages)
        if self._spec is not None and self._spec_drafted:
            mon.gauge("serving.spec_accept_rate").set(
                self._spec_accepted / self._spec_drafted)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admission-time prefix lookups that mapped at
        least one cached page (0.0 with the cache off)."""
        if self._prefix is None:
            return 0.0
        return self._prefix.hit_rate

    @property
    def spec_accept_rate(self) -> float:
        """Fraction of drafted tokens the target accepted (0.0 before
        any draft ran or with speculation off)."""
        if not self._spec_drafted:
            return 0.0
        return self._spec_accepted / self._spec_drafted
