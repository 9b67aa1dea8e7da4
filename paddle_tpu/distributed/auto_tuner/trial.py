"""Auto-tuner trial worker — one candidate config, measured.

Reference: python/paddle/distributed/auto_tuner/tuner.py:21 launches each
pruned candidate as a real training run and records its throughput. Here
a trial is a subprocess that builds the candidate's mesh on the virtual
CPU platform (n forced host devices), runs a few compiled steps of a
small hybrid model exercising the candidate's axes (dp/sharding/mp via
GSPMD, pp via the compiled pipeline schedule), and prints ONE JSON line
with the measured steps/sec for the parent tuner to score.

Run:  python -m paddle_tpu.distributed.auto_tuner.trial \
          --config '{"dp": 2, "mp": 2, "accumulate_steps": 2}' \
          --num-devices 4
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def run_trial(cfg: dict, num_devices: int, steps: int = 4,
              hidden: int = 32) -> float:
    # the parent (AutoTuner.launch_trial) set XLA_FLAGS/JAX_PLATFORMS on
    # this process's env and runs this file BY PATH, so no paddle_tpu
    # import has happened yet; pin cpu before the backend initializes
    import re
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", "")).strip()
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={num_devices}"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod

    degrees = {k: int(v) for k, v in cfg.items()
               if k in ("dp", "mp", "pp", "sharding", "sep", "ep")}
    acc = int(cfg.get("accumulate_steps", 1) or 1)
    mesh_mod.set_mesh(mesh_mod.build_mesh(degrees))
    paddle.seed(0)

    pp = degrees.get("pp", 1)
    dp = degrees.get("dp", 1) * degrees.get("sharding", 1)
    # PipelineParallel raises accumulate_steps to >= pp; the batch must
    # stay divisible by the EFFECTIVE microbatch count or pp configs
    # would spuriously score -inf
    acc_eff = max(acc, pp) if pp > 1 else max(acc, 1)
    batch = 4 * max(dp, 1) * acc_eff
    rng = np.random.default_rng(0)

    if pp > 1:
        from paddle_tpu.distributed.fleet.meta_parallel import (
            LayerDesc, PipelineLayer, PipelineParallel)

        class Block(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(hidden, hidden)

            def forward(self, x):
                return x + paddle.tanh(self.fc(x))

        pl = PipelineLayer(layers=[LayerDesc(Block) for _ in range(pp * 2)],
                           num_stages=pp, loss_fn=nn.MSELoss())
        strategy = fleet.DistributedStrategy()
        strategy.pipeline_configs["accumulate_steps"] = max(acc, pp)
        model = PipelineParallel(pl, strategy=strategy)
        opt = paddle.optimizer.AdamW(1e-3, parameters=pl.parameters())
        x = paddle.to_tensor(
            rng.standard_normal((batch, hidden)).astype(np.float32))
        y = paddle.to_tensor(
            rng.standard_normal((batch, hidden)).astype(np.float32))

        def one_step():
            return model.train_batch((x, y), opt)
        ctx = jax.set_mesh(mesh_mod.get_mesh())
    else:
        from paddle_tpu.distributed.fleet.meta_parallel import (
            ColumnParallelLinear, RowParallelLinear)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.up = ColumnParallelLinear(hidden, 4 * hidden,
                                               gather_output=False)
                self.down = RowParallelLinear(4 * hidden, hidden,
                                              input_is_parallel=True)
                self.head = nn.Linear(hidden, 8)

            def forward(self, x):
                return self.head(
                    x + self.down(paddle.nn.functional.gelu(self.up(x))))

        net = Net()
        opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
        step = paddle.jit.TrainStep(net, nn.CrossEntropyLoss(), opt)
        x = paddle.to_tensor(
            rng.standard_normal((batch, hidden)).astype(np.float32))
        y = paddle.to_tensor(rng.integers(0, 8, batch))

        def one_step():
            return step(x, y)
        ctx = jax.set_mesh(mesh_mod.get_mesh())

    with ctx:
        float(one_step().numpy())          # compile
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = one_step()
        float(loss.numpy())
        dt = (time.perf_counter() - t0) / steps
    return 1.0 / dt


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True, help="candidate JSON")
    p.add_argument("--num-devices", type=int, required=True)
    p.add_argument("--steps", type=int, default=4)
    ns = p.parse_args(argv)
    cfg = json.loads(ns.config)
    try:
        sps = run_trial(cfg, ns.num_devices, steps=ns.steps)
        print(json.dumps({"ok": True, "steps_per_sec": sps,
                          "config": cfg}))
    except Exception as exc:  # noqa: BLE001 — trial failure is a score
        print(json.dumps({"ok": False, "error": f"{type(exc).__name__}: "
                                                f"{exc}", "config": cfg}))
        sys.exit(1)


if __name__ == "__main__":
    main()
