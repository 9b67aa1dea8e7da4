"""Hot-path lint: the device-free serving-executable analyzer
(analysis/hotpath_lint.py, docs/ANALYSIS.md "Hot-path rules").

The contract under test, both directions:

- DETECTION — every ``hotpath.*`` rule fires EXACTLY ONCE on its
  seeded-defect fixture (tests/fixtures/hotpath_defects.py), with the
  user's file:line on the finding;
- SILENCE — the shipped serving stack (Engine, DisaggEngine,
  ServingFleet, BatchEncoder) lints CLEAN warm: zero findings after a
  real drive, so the rules carry no false positives on the code they
  exist to police.

Plus the runtime half: ``PADDLE_TPU_LINT=1`` arms jax.transfer_guard
around steady decode ticks without changing a single token or adding
a recompile, and serving_replay's ``--expect-hotpath-clean`` gate
(exit 13) wires the same report into the replay harness.
"""
import json
import os
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.analysis import findings as F
from paddle_tpu.analysis import hotpath_lint
from paddle_tpu.inference.engine import Engine, SamplingParams
from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tests", "fixtures"))
import hotpath_defects  # noqa: E402


def _tiny_net(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=2)
    cfg.use_flash_attention = False
    net = LlamaForCausalLM(cfg)
    net.eval()
    return net


def _prompts(rng, lens, vocab=64):
    return [rng.integers(1, vocab, (n,)).astype(np.int64)
            for n in lens]


def _drive(eng, prompts, n=4):
    done = {}
    for p in prompts:
        eng.add_request(p, SamplingParams(max_new_tokens=n))
    for _ in range(200):
        for out in eng.step():
            done[out.req_id] = out
        if len(done) == len(prompts):
            break
    assert len(done) == len(prompts)
    return done


# -- seeded defects: every rule fires exactly once ---------------------------

@pytest.mark.parametrize("cls,rule", [
    (hotpath_defects.UndonatedPoolEngine, F.MISSED_DONATION),
    (hotpath_defects.OverFetchingExecutable, F.FETCH_SET_BLOAT),
    (hotpath_defects.ItemInStepScheduler, F.HOST_SYNC_IN_TICK),
    (hotpath_defects.UnguardedUploadScheduler, F.STEADY_TICK_UPLOAD),
    (hotpath_defects.FloatKeyedCache, F.RECOMPILE_RISK_KEY),
], ids=lambda v: getattr(v, "__name__", str(v).split(".")[-1]))
def test_each_rule_fires_exactly_once(cls, rule):
    rep = hotpath_lint.lint_surface(cls())
    found = list(rep)
    assert len(found) == 1, rep.format()
    assert found[0].rule == rule
    assert found[0].file.endswith("hotpath_defects.py")
    if rule != F.RECOMPILE_RISK_KEY:
        # executable/AST rules point at the defect's source line; the
        # cache-key rule anchors to the inventory itself
        assert found[0].line > 0


def test_clean_toy_engine_zero_findings():
    """The sanctioned pattern for every rule in one surface — the
    false-positive guard for the rule set itself."""
    rep = hotpath_lint.lint_surface(hotpath_defects.CleanToyEngine())
    assert not rep, rep.format()


def test_rules_are_cataloged():
    for rule in (F.MISSED_DONATION, F.FETCH_SET_BLOAT,
                 F.HOST_SYNC_IN_TICK, F.STEADY_TICK_UPLOAD,
                 F.RECOMPILE_RISK_KEY):
        assert rule in F.HOTPATH_RULES
        assert rule.startswith("hotpath.")


def test_emit_hotpath_counters():
    """hotpath.* rule ids land as lint.hotpath.<rule> monitor counters
    through the shared emit path, and every inspection is counted."""
    insp = monitor.counter("lint.hotpath.inspections").get()
    don = monitor.counter(f"lint.{F.MISSED_DONATION}").get()
    rep = hotpath_lint.lint_surface(
        hotpath_defects.UndonatedPoolEngine())
    hotpath_lint.emit_hotpath(rep)
    assert monitor.counter("lint.hotpath.inspections").get() == insp + 1
    assert monitor.counter(f"lint.{F.MISSED_DONATION}").get() == don + 1


# -- the shipped stack lints clean -------------------------------------------

def test_engine_inspect_hotpath_clean(rng):
    """Satellite: the real Engine, driven warm (prefill + decode
    executables compiled), reports ZERO hot-path findings."""
    eng = Engine(_tiny_net(), max_slots=2, page_size=8, pool_pages=32,
                 max_context=64)
    _drive(eng, _prompts(rng, (5, 7)))
    rep = eng.inspect_hotpath()
    assert not rep, rep.format()
    inv = eng._hotpath_inventory()
    # the inventory really enumerates the compiled set: decode
    # variants, prefill buckets, tick + steady scheduler functions
    names = [s.name for s in inv.executables]
    assert any(n.startswith("decode[") for n in names)
    assert any(n.startswith("prefill[") for n in names)
    assert inv.steady_functions


def test_serving_stack_sweeps_clean():
    """Satellite: all five hot-path surfaces — Engine, DisaggEngine,
    ServingFleet, BatchEncoder, MpmdRingExecutor — built tiny and
    linted: zero findings each (the acceptance bar for the whole PR).
    Cold build — the inventories' default variant sets cover every
    executable body; the warm-driven proof runs in the slow tier and
    in the CLI ``--hotpath`` sweep."""
    reports = hotpath_lint.sweep_serving_stack(drive=False)
    assert set(reports) == {"engine", "disagg", "fleet", "encoder",
                            "mpmd"}
    for name, rep in reports.items():
        assert not rep, f"{name}:\n{rep.format()}"


@pytest.mark.slow
def test_serving_stack_sweeps_clean_warm():
    """The same five surfaces driven warm first, so the runtime-
    populated executable caches (decode variants, prefill buckets,
    ring hop programs — the recompile-risk rule's richest input) are
    linted too."""
    reports = hotpath_lint.sweep_serving_stack()
    assert set(reports) == {"engine", "disagg", "fleet", "encoder",
                            "mpmd"}
    for name, rep in reports.items():
        assert not rep, f"{name}:\n{rep.format()}"


# -- transfer-guard enforcement ----------------------------------------------

def test_transfer_guard_steady_ticks_token_exact(rng, monkeypatch):
    """PADDLE_TPU_LINT=1 wraps steady decode dispatches in
    jax.transfer_guard('disallow'): tokens stay bit-identical to the
    unguarded run, steady-state recompiles stay zero, and the guard
    provably ARMED (lint.hotpath.guarded_ticks advanced)."""
    prompts = _prompts(rng, (5, 9, 3))

    def run():
        eng = Engine(_tiny_net(), max_slots=2, page_size=8,
                     pool_pages=32, max_context=64)
        done = _drive(eng, prompts, n=6)
        return ([done[k].token_ids for k in sorted(done)], eng)

    monkeypatch.delenv("PADDLE_TPU_LINT", raising=False)
    base, _ = run()
    monkeypatch.setenv("PADDLE_TPU_LINT", "1")
    before = monitor.counter("lint.hotpath.guarded_ticks").get()
    guarded, eng = run()
    assert guarded == base
    assert eng.steady_state_recompiles() == 0
    assert monitor.counter("lint.hotpath.guarded_ticks").get() > before


def test_dirty_ticks_are_not_guarded(monkeypatch):
    """The guard must NEVER wrap a non-steady tick: a dirty-flagged
    dispatch (uploads pending) goes through unguarded even when
    PADDLE_TPU_LINT=1 — arming on a dirty tick would turn the
    sanctioned dirty-row merge into a false failure."""
    monkeypatch.setenv("PADDLE_TPU_LINT", "1")
    eng = Engine(_tiny_net(), max_slots=2, page_size=8, pool_pages=32,
                 max_context=64)
    calls = []

    def probe(*args):
        calls.append(True)
        return args

    # steady=False must not enter the guard (probe runs bare)
    out = eng._dispatch_steady(False, probe, 1, 2)
    assert out == (1, 2) and calls


@pytest.mark.parametrize("front", ["engine", "disagg", "fleet"])
def test_add_request_runs_no_device_program(rng, front):
    """With a decode tick always in flight, anything `add_request`
    fetched from the device would wait for the rest of that tick: the
    request's sampler key is built on the host (`host_prng_key`), so
    the whole call passes under jax.transfer_guard('disallow') on every
    front door, with a tick of the engine in flight."""
    from paddle_tpu.inference.disagg import DisaggEngine
    from paddle_tpu.inference.fleet import ServingFleet
    kw = dict(max_slots=2, page_size=8, pool_pages=32, max_context=64)
    net = _tiny_net()
    eng = {"engine": lambda: Engine(net, **kw),
           "disagg": lambda: DisaggEngine(net, **kw),
           "fleet": lambda: ServingFleet(net, replicas=2, **kw)}[front]()
    first, second = _prompts(rng, (5, 7))
    eng.add_request(first, SamplingParams(max_new_tokens=12))
    for _ in range(4):
        eng.step()
    if front == "engine":
        assert eng._inflight is not None
    with jax.transfer_guard("disallow"):
        rid = eng.add_request(second, SamplingParams(
            max_new_tokens=4, temperature=0.8, seed=2 ** 40 + 7))
    np.testing.assert_array_equal(
        eng.requests[rid].key,
        np.asarray(jax.random.PRNGKey(2 ** 40 + 7)))
    eng.close()


# -- serving_replay gate ------------------------------------------------------

def _replay():
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import serving_replay
    finally:
        sys.path.pop(0)
    return serving_replay


def test_replay_expect_hotpath_clean(capsys):
    """--expect-hotpath-clean on the stock trace: exit 0, the report
    carries the hotpath block and the lint.hotpath.* counter deltas."""
    serving_replay = _replay()
    trace = os.path.join(_REPO, "tests", "fixtures",
                         "serving_trace.jsonl")
    rc = serving_replay.main([trace, "--expect-hotpath-clean",
                              "--expect-zero-recompiles", "--json"])
    report = json.loads(capsys.readouterr().out.strip()
                        .splitlines()[-1])
    assert rc == 0
    assert report["hotpath"] == {"findings": 0, "rules": {}}
    assert report["counters"]["lint.hotpath.inspections"] == 1


def test_replay_hotpath_gate_fails_loud(capsys, monkeypatch):
    """A surface reporting ANY hot-path finding exits 13 (the new gate
    code, distinct from every other replay gate)."""
    serving_replay = _replay()
    from paddle_tpu.analysis.findings import Finding, Report

    def dirty(self):
        return Report([Finding(
            rule=F.MISSED_DONATION, severity=F.ERROR,
            message="seeded for the exit-13 gate test",
            file="engine.py", line=1)], subject="Engine[test]")

    monkeypatch.setattr(Engine, "inspect_hotpath", dirty)
    trace = os.path.join(_REPO, "tests", "fixtures",
                         "serving_trace.jsonl")
    rc = serving_replay.main([trace, "--expect-hotpath-clean"])
    err = capsys.readouterr().err
    assert rc == 13
    assert "--expect-hotpath-clean FAILED" in err
    assert F.MISSED_DONATION in err


def test_replay_embedding_hotpath_clean(capsys):
    """The gate rides the --embedding path too (BatchEncoder's
    inventory), sharing the exit-13 contract."""
    serving_replay = _replay()
    trace = os.path.join(_REPO, "tests", "fixtures",
                         "serving_trace_embed.jsonl")
    rc = serving_replay.main([trace, "--embedding",
                              "--expect-hotpath-clean", "--json"])
    report = json.loads(capsys.readouterr().out.strip()
                        .splitlines()[-1])
    assert rc == 0
    assert report["hotpath"]["findings"] == 0
