"""Tiny stand-ins for the two cells, for CPU rehearsals and tests: the
same runners, loaders and metric files, on a model of a few thousand
parameters. Sizes live here, never in run.py's interface."""
from __future__ import annotations

import json
import pathlib
import time

from benchmark.harness import load
from benchmark.harness.job import Job

REAL_ROOT = load.REPO_ROOT

TINY_MODEL = dict(
    vocab_size=256, hidden_size=256, intermediate_size=512,
    num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
    max_position_embeddings=512, rms_norm_eps=1e-5, rope_theta=10000.0,
    sliding_window=96, tie_word_embeddings=False)

TRAIN_CFG = dict(TINY_MODEL, trainer=dict(
    learning_rate=3e-4, moment_dtype="bfloat16", amp_dtype="bfloat16",
    fused_linear_ce=True, fused_ce_chunks=4, use_flash_attention=True,
    recompute=False, pallas_calls_per_layer=3))
SERVE_CFG = dict(TINY_MODEL, serving=dict(weight_dtype="bfloat16"),
                 engine=dict(max_slots=4, page_size=16, prefill_bucket=16,
                             max_context=96, cache_dtype="auto"))
TRAIN_TRAFFIC = dict(runner="train", batch=2, seq=128, reference_tokens=128,
                     warmup_steps=1, traced_steps=2)
SERVE_TRAFFIC = dict(runner="serve",
                     arrival=dict(kind="closed", clients=4),
                     prompt_tokens=[8, 48], output_tokens=[4, 12],
                     shared_prefix_tokens=0, block=16, ramp_seconds=0.2,
                     steady_seconds=0.5,
                     traced_seconds=0.5, reference_prompt_tokens=16,
                     reference_new_tokens=4)


END_TO_END = [
    dict(name="train_tokens_per_s", unit="tokens/s", better="higher",
         bound=0.01, source="host_clock", workloads=["tiny-train"]),
    dict(name="serve_tokens_per_s", unit="tokens/s", better="higher",
         bound=0.01, source="host_clock", workloads=["tiny-serve"]),
    dict(name="tpot_p90_ms", unit="ms", better="lower", bound=0.01,
         source="host_clock", workloads=["tiny-serve"]),
    dict(name="setup_s", unit="s", better="lower", bound=0.1,
         source="host_clock")]


def tiny_tree(root: pathlib.Path) -> pathlib.Path:
    """A BENCHMARK.json under `root` with one tiny cell a runner,
    `tiny-train` and `tiny-serve`, each reporting every per-layer metric
    file whose name starts with its runner's; returns `root`. It depends
    on no cell of the real BENCHMARK.json."""
    cells = {"train": (TRAIN_CFG, TRAIN_TRAFFIC),
             "serve": (SERVE_CFG, SERVE_TRAFFIC)}
    per_layer = []
    for path in sorted((REAL_ROOT / "benchmark" / "metrics").glob("*.py")):
        m = load.load_metric(path.stem)
        per_layer.append(dict(
            name=m.NAME, unit=m.UNIT, better=m.BETTER, source=m.SOURCE,
            layer=m.LAYER, moves=m.MOVES,
            workloads=[f"tiny-{m.NAME.split('.')[0]}"]))
    bench = dict(
        configs=[dict(name=f"tiny-{r}", source="test", reduced=[], why="test",
                      file=f"benchmark/configs/tiny-{r}.json") for r in cells],
        workloads=[dict(name=f"tiny-{r}", config=f"tiny-{r}",
                        traffic=f"tiny-{r}", chips=1, why="test")
                   for r in cells],
        end_to_end=END_TO_END, per_layer=per_layer)
    for sub in ("configs", "traffic"):
        (root / "benchmark" / sub).mkdir(parents=True, exist_ok=True)
    for r, (config, traffic) in cells.items():
        (root / f"benchmark/configs/tiny-{r}.json").write_text(
            json.dumps(config))
        (root / f"benchmark/traffic/tiny-{r}.json").write_text(
            json.dumps(traffic))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_tiny(root: pathlib.Path, cell_name: str, seconds=1.0, seed=3):
    """One untraced run of a tiny cell on whatever device JAX has."""
    from benchmark.harness import device
    cell = load.load_cell(cell_name, root)
    job = Job(cell=cell, seed=seed, seconds=seconds, trace=False,
              trace_dir=str(root / "trace"),
              process_start=time.perf_counter(), device=device.describe())
    return job, load.load_runner(cell.traffic["runner"]).run(job)
