"""Auto-tuner — search over parallelism configs.

Reference: python/paddle/distributed/auto_tuner/tuner.py:21 (AutoTuner:
grid/random search over dp/mp/pp/sharding/micro-batch degrees, trial
launches, memory-model pruning).

TPU-native: candidates are mesh-degree dicts whose product divides the
chip count; pruning uses a parameter+activation memory model against
per-chip HBM, and trials run a user-supplied `trial_fn(config) ->
throughput` (e.g. a few compiled steps of the real model on a small
mesh, or the cost model below).

With a `model_spec` (`analysis.planner.ModelSpec`), the auto-parallel
planner becomes the search backend: every pruned candidate is scored
by its PREDICTED step time — the shard_lint-pruned, abstract-traced
roofline combiner of `analysis.planner` — instead of the bare memory
model, so `tune()` ranks by speed, device-free, and illegal configs
(indivisible TP splits, starved pipelines) lose with a finding instead
of a launch failure."""
from __future__ import annotations

import itertools
import math
import random
from typing import Callable, Dict, List, Optional


class TunerConfig:
    def __init__(self, num_devices: int, mode: str = "grid",
                 max_trials: int = 0, hbm_bytes: float = 16e9,
                 model_params: float = 0.0, hidden_size: int = 0,
                 seq_len: int = 0, micro_batches=(1, 2, 4, 8),
                 axes=("dp", "mp", "pp", "sharding"),
                 model_spec=None, machine=None):
        self.num_devices = num_devices
        self.mode = mode
        self.max_trials = max_trials
        self.hbm_bytes = hbm_bytes
        self.model_params = model_params
        self.hidden_size = hidden_size
        self.seq_len = seq_len
        self.micro_batches = tuple(micro_batches)
        self.axes = tuple(axes)
        # analysis.planner.ModelSpec / MachineSpec: arms the planner
        # search backend
        self.model_spec = model_spec
        self.machine = machine


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


class AutoTuner:
    def __init__(self, config: TunerConfig,
                 trial_fn: Optional[Callable[[Dict], float]] = None):
        self.config = config
        self.trial_fn = trial_fn
        self.history: List[Dict] = []

    # -- candidate generation (reference search space) -----------------------
    def candidates(self) -> List[Dict]:
        n = self.config.num_devices
        cands = []
        for degs in itertools.product(_divisors(n),
                                      repeat=len(self.config.axes)):
            if math.prod(degs) != n:
                continue
            cfg = dict(zip(self.config.axes, degs))
            for mb in self.config.micro_batches:
                c = dict(cfg)
                c["accumulate_steps"] = mb
                if c.get("pp", 1) > 1 and mb < c["pp"]:
                    continue  # pipeline needs >= pp microbatches
                cands.append(c)
        if self.config.mode == "random":
            random.shuffle(cands)
        if self.config.max_trials:
            cands = cands[:self.config.max_trials]
        return cands

    # -- memory-model pruning (reference prune-by-memory) --------------------
    def estimate_memory(self, cfg: Dict) -> float:
        """Bytes/chip: params+grads+Adam moments sharded over mp*pp*
        sharding, plus an activation term scaled by dp microbatching."""
        p = self.config.model_params
        if p <= 0:
            return 0.0
        shard = cfg.get("mp", 1) * cfg.get("pp", 1) * \
            cfg.get("sharding", 1)
        param_bytes = p * (2 + 4 + 8) / shard   # bf16 w + fp32 g + moments
        act = (self.config.hidden_size * self.config.seq_len * 34
               * max(cfg.get("accumulate_steps", 1), 1)
               / max(cfg.get("pp", 1), 1) * 2)
        return param_bytes + act

    def prune(self, cands: List[Dict]) -> List[Dict]:
        return [c for c in cands
                if self.estimate_memory(c) <= self.config.hbm_bytes]

    # -- measured trials (reference: tuner launches real trial runs) ---------
    def launch_trial(self, cfg: Dict, steps: int = 4,
                     timeout: float = 300.0) -> float:
        """Run one candidate as a subprocess dryrun on the virtual mesh
        and return measured steps/sec (-inf on failure, so broken
        configs lose instead of aborting the search). Reference:
        auto_tuner/tuner.py launches each pruned candidate and records
        its metric."""
        import json
        import os
        import re
        import subprocess
        import sys

        # run trial.py BY PATH, not -m: python -m would import the
        # paddle_tpu parent package (and initialize the default jax
        # backend) before the trial can force the virtual-CPU platform
        trial_path = os.path.join(os.path.dirname(__file__), "trial.py")
        cmd = [sys.executable, trial_path,
               "--config", json.dumps(cfg),
               "--num-devices", str(self.config.num_devices),
               "--steps", str(steps)]
        env = dict(os.environ)
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       env.get("XLA_FLAGS", "")).strip()
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            f"{self.config.num_devices}").strip()
        env["JAX_PLATFORMS"] = "cpu"
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        try:
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=timeout, env=env)
        except subprocess.TimeoutExpired:
            return -float("inf")
        for line in reversed(out.stdout.strip().splitlines()):
            try:
                res = json.loads(line)
            except json.JSONDecodeError:
                continue
            if res.get("ok"):
                return float(res["steps_per_sec"])
            break
        return -float("inf")

    # -- planner backend (analysis.planner as the search scorer) -------------
    def _plan_of(self, cfg: Dict):
        from ...analysis.planner import Plan
        degrees = {ax: int(cfg.get(ax, 1)) for ax in
                   ("dp", "mp", "pp", "sharding", "sep", "ep")}
        m = max(int(cfg.get("accumulate_steps", 1) or 1),
                degrees["pp"] if degrees["pp"] > 1 else 1)
        return Plan(degrees=degrees,
                    schedule_mode=str(cfg.get("schedule_mode",
                                              "FThenB")),
                    n_micro=m,
                    shard_weight_update=degrees["sharding"] > 1)

    def _planner_hbm_budget(self) -> float:
        """The HBM gate for planner-scored candidates: an explicit
        MachineSpec describes the target chip and wins over the legacy
        memory-model default."""
        if self.config.machine is not None:
            return float(self.config.machine.hbm_bytes)
        return float(self.config.hbm_bytes)

    def planner_score(self, cfg: Dict) -> float:
        """-predicted step seconds for one candidate via the
        auto-parallel planner's analytic prescore (the closed-form twin
        of the traced combiner — cheap enough for the whole grid) —
        -inf when the plan is illegal or over the HBM budget, so broken
        configs lose instead of aborting the search. tune() re-verifies
        the winner with the full traced score_plan."""
        from ...analysis.findings import ERROR
        from ...analysis.planner import prescore_plan
        step_s, hbm, findings = prescore_plan(
            self.config.model_spec, self._plan_of(cfg),
            machine=self.config.machine)
        if any(f.severity == ERROR for f in findings) \
                or hbm > self._planner_hbm_budget():
            return -float("inf")
        return -step_s

    # -- search loop ---------------------------------------------------------
    def tune(self, measure: bool = False, top_k: int = 4) -> Dict:
        """Pick the best config. measure=False scores by predicted step
        time when the config carries a `model_spec` (the planner
        backend), else by the memory model; measure=True launches the
        top_k pruned candidates as subprocess trials and picks the
        measured-fastest."""
        # the planner backend does its own legality + HBM gating (per
        # the machine spec), so the legacy memory model must not
        # pre-prune its grid with a different budget — but ONLY when
        # the planner actually scores (an explicit trial_fn wins the
        # scoring elif below, so it keeps the memory-model prune)
        if self.config.model_spec is not None and not measure \
                and self.trial_fn is None:
            pruned = self.candidates()
        else:
            pruned = self.prune(self.candidates())
        if not pruned:
            raise RuntimeError("auto-tuner: every candidate was pruned "
                               "by the memory model")
        if measure:
            # rank by the memory model first so the measured trials spend
            # time on the likeliest candidates
            pruned = sorted(pruned, key=self.estimate_memory)[:top_k]
        best, best_score = None, -float("inf")
        for cfg in pruned:
            if measure:
                score = self.launch_trial(cfg)
            elif self.trial_fn:
                score = self.trial_fn(cfg)
            elif self.config.model_spec is not None:
                score = self.planner_score(cfg)
            else:
                score = -self.estimate_memory(cfg)
            self.history.append({"config": cfg, "score": score})
            if best is None or score > best_score:
                best, best_score = cfg, score
        if not math.isfinite(best_score) and measure:
            raise RuntimeError(
                "auto-tuner: every measured trial failed; see history "
                f"for configs tried: {[h['config'] for h in self.history]}")
        if self.config.model_spec is not None and not measure \
                and self.trial_fn is None:
            # confirm the prescore winner with the full traced score
            # (lint_sharded prune + per-axis cost); fall down the
            # ranking if the abstract trace rejects it. A winner the
            # trace rejected must never be returned — all-rejected is
            # an error, exactly like the all-trials-failed measure path.
            from ...analysis.planner import score_plan
            verified = False
            for h in sorted(self.history, key=lambda h: -h["score"]):
                if not math.isfinite(h["score"]):
                    break
                sp = score_plan(self.config.model_spec,
                                self._plan_of(h["config"]),
                                machine=self.config.machine,
                                hbm_budget=self._planner_hbm_budget())
                h["traced"] = sp.ok
                if sp.ok:
                    best, best_score = h["config"], -sp.step_s
                    verified = True
                    break
            if not verified:
                raise RuntimeError(
                    "auto-tuner(planner): no candidate survived the "
                    "planner's legality/HBM gates for "
                    f"{self.config.model_spec.name} on "
                    f"{self.config.num_devices} device(s); see history "
                    "for per-candidate scores")
        return {"best_config": best, "best_score": best_score,
                "n_trials": len(self.history)}
