"""Multichip dry-run: compile + execute one hybrid-parallel train step.

Driver contract (__graft_entry__.dryrun_multichip): given n virtual
devices, build an n-device mesh with real dp/sharding(fsdp)/mp degrees,
jit the FULL training step (forward + loss + backward + optimizer) with
batch/param/optimizer-state shardings, run ONE step on tiny shapes, and
verify the loss is finite.
"""
from __future__ import annotations

import numpy as np


def _factor_degrees(n: int):
    """Split n devices into dp × sharding × mp, preferring balance."""
    degs = {"dp": 1, "sharding": 1, "mp": 1}
    order = ["mp", "sharding", "dp"]  # fill inner (fastest) axes first
    i = 0
    m = n
    while m > 1:
        for p in (2, 3, 5, 7):
            if m % p == 0:
                degs[order[i % len(order)]] *= p
                m //= p
                i += 1
                break
        else:
            degs["dp"] *= m
            break
    return degs


def _ensure_devices(n_devices: int):
    """Get an n-device jax backend by forcing the virtual-CPU platform —
    unconditionally: this is the CPU dry run, never a path to real chips
    (chip_smoke.py --chips 4 is). Must run before any other jax backend
    use in this process to take effect."""
    import os
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    # replace any pre-existing count (a smaller ambient value would
    # otherwise win and leave us short of devices)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   flags).strip()
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()

    # the env var covers a jax not yet imported, the config update one
    # that is; both only take effect before backend init
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


def _single_device_losses(jax, build_and_run):
    """Run `build_and_run()` on a 1-device mesh (the reference side of
    the align check — reference test model:
    test/auto_parallel/hybrid_strategy/semi_auto_llama.py acc-align
    between dist and single-card runs)."""
    from paddle_tpu.distributed import mesh as mesh_mod

    prev = mesh_mod.get_mesh()
    mesh_mod.set_mesh(mesh_mod.build_mesh(
        {"dp": 1}, devices=[jax.devices()[0]]))
    try:
        return build_and_run()
    finally:
        if prev is not None:
            mesh_mod.set_mesh(prev)
        else:
            mesh_mod._global_mesh = None


def _assert_aligned(tag, dist_losses, single_losses,
                    rtol=2e-3, atol=2e-4):
    dist_losses = [float(x) for x in dist_losses]
    single_losses = [float(x) for x in single_losses]
    if not np.allclose(dist_losses, single_losses, rtol=rtol, atol=atol):
        raise AssertionError(
            f"dryrun {tag}: dist/single loss mismatch "
            f"{dist_losses} vs {single_losses}")
    print(f"dryrun {tag} align ok: dist="
          f"{[round(v, 4) for v in dist_losses]} single="
          f"{[round(v, 4) for v in single_losses]}")


# ---------------------------------------------------------------------------
# shard-lint model zoo (device-free)
# ---------------------------------------------------------------------------
# The dryrun phases above need n real (virtual) devices; these builders
# expose the same program SHAPES to `analysis.shard_lint` with zero
# devices — consumed by `tools/paddle_lint.py --shard-check` and the
# tier-1 regression test, which expect every case to lint clean.

def _zoo_collectives(x):
    """Representative well-formed collective program: every op family
    shard_lint validates, at divisible shapes on the zoo mesh."""
    import jax.numpy as jnp

    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.communication.collectives import p2p_shift
    from paddle_tpu.distributed.communication.group import Group

    mp, dp = Group(axis_name="mp"), Group(axis_name="dp")
    y = dist.all_reduce(x, group=mp)
    gathered = dist.all_gather(None, y, group=dp)
    scattered = dist.reduce_scatter(None, y, group=mp)
    single = dist.alltoall_single(None, y, group=mp)
    ring = p2p_shift(y, "dp", 1)
    return (jnp.sum(gathered) + jnp.sum(scattered) + jnp.sum(single)
            + jnp.sum(ring))


class _ZooBlock:
    """Placeholder so type names in lint output read well."""


def shard_lint_zoo(n_devices: int = 8):
    """Build the shard-lint cases: a list of (name, kind, payload) where
    kind is "sharded" (payload: fn, arg shapes, mesh degrees — run
    through `analysis.lint_sharded`) or "pipeline" (payload:
    PipelineLayer, lint_pipeline kwargs). Everything is constructed
    device-free under a fake mesh; nothing executes."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel import (
        ColumnParallelLinear, LayerDesc, PipelineLayer, RowParallelLinear)
    from paddle_tpu.jit.api import InputSpec

    pp = 4 if n_devices % 4 == 0 else 2
    dp, mp = 2, n_devices // 2
    hidden = 16

    cases = []
    cases.append(("collectives", "sharded", {
        "fn": _zoo_collectives,
        "args": [jax.ShapeDtypeStruct((mp * 2, 4), np.float32)],
        "mesh": {"dp": dp, "mp": mp},
    }))

    prev = mesh_mod.get_mesh()
    mesh_mod._global_mesh = mesh_mod.fake_mesh({"dp": dp, "mp": mp})
    try:
        class TPBlock(nn.Layer):
            def __init__(self):
                super().__init__()
                self.up = ColumnParallelLinear(hidden, 4 * hidden,
                                               gather_output=False)
                self.down = RowParallelLinear(4 * hidden, hidden,
                                              input_is_parallel=True)

            def forward(self, x):
                return x + self.down(
                    paddle.nn.functional.gelu(self.up(x)))

        tp_net = TPBlock()
    finally:
        mesh_mod._global_mesh = prev
    cases.append(("tp-mlp", "inspect", {
        "net": tp_net,
        "input_spec": [InputSpec([4, hidden])],
        "mesh": {"dp": dp, "mp": mp},
    }))

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(hidden, hidden)

        def forward(self, x):
            return x + paddle.tanh(self.fc(x))

    def pipe(n_layers, **kw):
        return PipelineLayer(
            layers=[LayerDesc(Block) for _ in range(n_layers)],
            num_stages=pp, loss_fn=nn.MSELoss(), **kw)

    spec = InputSpec([4, hidden])
    cases.append(("pipeline-gpipe", "pipeline", {
        "pipe": pipe(2 * pp),
        "kwargs": {"n_micro": 2 * pp, "input_spec": spec},
    }))
    cases.append(("pipeline-vpp", "pipeline", {
        "pipe": pipe(2 * pp * 2, num_virtual_pipeline_stages=2),
        "kwargs": {"n_micro": 2 * pp, "vpp_degree": 2,
                   "schedule_mode": "VPP", "input_spec": spec},
    }))
    cases.append(("pipeline-zb", "pipeline", {
        "pipe": pipe(2 * pp),
        "kwargs": {"n_micro": 2 * pp, "schedule_mode": "ZBH1",
                   "input_spec": spec},
    }))
    return cases


def shard_lint_zoo_reports(n_devices: int = 8):
    """Run shard_lint over the zoo; returns [(name, Report)]. The
    regression contract (tier-1 + `paddle_lint --shard-check`): every
    report is empty."""
    from paddle_tpu import analysis
    from paddle_tpu.jit.api import to_static

    out = []
    for name, kind, payload in shard_lint_zoo(n_devices):
        if kind == "sharded":
            rep = analysis.lint_sharded(
                payload["fn"], payload["args"], mesh=payload["mesh"],
                subject=name)
        elif kind == "inspect":
            rep = to_static(payload["net"],
                            input_spec=payload["input_spec"]).inspect(
                mesh=payload["mesh"])
            rep.subject = name
        else:
            rep = analysis.lint_pipeline(
                payload["pipe"], subject=name, **payload["kwargs"])
        out.append((name, rep))
    return out


def mpmd_phase_reports(n_devices: int = 8):
    """Statically verify EVERY MULTICHIP phase's schedule as an MPMD
    event graph — including the 8 phases the pinned runtime cannot
    execute (XLA SPMD PartitionId / native shard_map): their schedules
    are still fully checkable device-free. Returns [(phase, Report)];
    the regression contract (tier-1 + `paddle_lint --mpmd-check` +
    `_dryrun_mpmd_lint`) is that every report is empty.

    Geometries mirror each `_dryrun_*` phase at n_devices=8; the
    planner leg model-checks every PIPELINED calibration plan through
    the same `plan_graph` extraction the score_plan prune uses."""
    from paddle_tpu.analysis import lint_mpmd, planner
    from paddle_tpu.distributed import mpmd_graph as mg

    pp = 4 if n_devices % 4 == 0 else (2 if n_devices % 2 == 0 else 1)
    sep = 4 if n_devices % 4 == 0 else 2
    out = []

    def add(phase, graph, **kw):
        out.append((phase, lint_mpmd(graph, **kw)))

    # pure-SPMD phases: no cross-stage schedule — the trivial graph
    add("hybrid", mg.single_stage_graph(1, subject="mpmd(hybrid)"))
    add("pp", mg.schedule_graph("FThenB", pp, 2 * pp))
    add("vpp", mg.schedule_graph("VPP", pp, 2 * pp, 2))
    add("zb", mg.schedule_graph("ZBH1", pp, 2 * pp))
    add("zbvpp", mg.schedule_graph("ZBVPP", pp, 2 * pp, 2))
    add("het", mg.schedule_graph("FThenB", pp, pp))     # uneven segs,
    # same event structure — stage weight lives in the descriptors
    add("ep", mg.single_stage_graph(1, subject="mpmd(ep)"))
    add("sep", mg.ring_graph(sep))
    add("3d", mg.schedule_graph("FThenB", 2, 2))
    add("dcn", mg.single_stage_graph(1, subject="mpmd(dcn)"))
    add("llama4d", mg.schedule_graph("FThenB", 2, 2))
    add("llama-sep", mg.ring_graph(2))
    add("sep8k", mg.ring_graph(2))
    add("serving-disagg", mg.disagg_graph(2, 2, 5))
    planner_rep = None
    for name, spec, plan in planner.dryrun_calibration_configs():
        if plan.degree("pp") <= 1:
            continue
        rep = lint_mpmd(plan, spec=spec)
        rep.subject = f"mpmd(planner:{name})"
        if planner_rep is None or (rep and not planner_rep):
            planner_rep = rep
    out.append(("planner", planner_rep))
    return out


def _dryrun_mpmd_lint(jax, n_devices: int) -> None:
    """Phase 0b: device-free MPMD schedule verification of all 15
    MULTICHIP phases (the static_verified column of the ledger)."""
    reports = mpmd_phase_reports(n_devices)
    dirty = [(p, r) for p, r in reports if r]
    for p, r in dirty:
        print(f"dryrun mpmd lint DIRTY [{p}]:\n{r.format()}")
    assert not dirty, f"mpmd lint found defects in: " \
                      f"{[p for p, _ in dirty]}"
    print(f"dryrun mpmd lint ok: {len(reports)}/15 phase schedules "
          f"statically verified (deadlock/p2p/buffer/dataflow/"
          f"stale-weight clean)")


def _mpmd_execution_legs(jax, n_devices: int):
    """The blocked-by-runtime phases as executable legs of the MPMD
    runtime (distributed/mpmd_runtime.py) — the ROADMAP item-2 driver.

    Every leg is a schedule the pinned jax-0.4.x runtime cannot run as
    one SPMD program (XLA SPMD PartitionId aborts on the
    lax.scan+ppermute pipeline; no native shard_map for the ring):
    pp / vpp / zb / zbvpp / 3d / llama4d via ``schedule_mode="MPMD*"``
    on PipelineParallel (per-stage fixed compiled programs, the
    verified event graph driven tick-by-tick on the host, cross-stage
    activations as explicit device_put edges), and sep / llama-sep /
    sep8k via MpmdRingExecutor (per-device ring-hop programs, k/v
    rotation as driver edges). Each leg executes against the SAME
    single-device reference geometry its blocked SPMD phase uses.

    Returns ``{tag: thunk}`` in ledger order; each thunk runs its leg
    and returns ``(dist, ref, steady_state_recompiles)`` — consumed by
    ``_dryrun_mpmd`` (align-asserting) and ``run_mpmd_execution``
    (the ``paddle_lint --mpmd-run`` CLI). ``None`` when n_devices
    cannot host the geometries."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel import (
        ColumnParallelLinear, LayerDesc, PipelineLayer, PipelineParallel,
        RowParallelLinear)
    from paddle_tpu.distributed.mpmd_runtime import MpmdRingExecutor
    from paddle_tpu.kernels.ring_attention import ring_attention_arrays
    import jax.numpy as jnp

    if n_devices % 8 != 0:
        return None
    pp, dp = 4, n_devices // 4
    hidden = 16
    legs = {}

    class Plain(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(hidden, hidden)

        def forward(self, x):
            return paddle.tanh(self.fc(x))

    class Res(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(hidden, hidden)

        def forward(self, x):
            return x + paddle.tanh(self.fc(x))

    class TPBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            self.up = ColumnParallelLinear(hidden, 4 * hidden,
                                           gather_output=False)
            self.down = RowParallelLinear(4 * hidden, hidden,
                                          input_is_parallel=True)

        def forward(self, x):
            return x + self.down(
                paddle.nn.functional.gelu(self.up(x)))

    def pipe_leg(tag, mode, degrees, build, data, M):
        """Train 2 steps under schedule_mode=MPMD*, then run the same
        geometry on the 1-device reference mesh."""
        def thunk():
            mesh_mod.set_mesh(mesh_mod.build_mesh(degrees))
            strat = fleet.DistributedStrategy()
            strat.pipeline_configs["accumulate_steps"] = M
            strat.pipeline_configs["schedule_mode"] = mode
            pl = build(degrees["pp"], None)
            model = PipelineParallel(pl, strategy=strat)
            opt = paddle.optimizer.AdamW(1e-3,
                                         parameters=pl.parameters())
            x_np, y_np = data
            with jax.set_mesh(mesh_mod.get_mesh()):
                dist = [float(model.train_batch(
                    (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
                    opt).numpy()) for _ in range(2)]

            def single_run():
                strat1 = fleet.DistributedStrategy()
                strat1.pipeline_configs["accumulate_steps"] = M
                pl1 = build(1, 1)
                m1 = PipelineParallel(pl1, strategy=strat1)
                o1 = paddle.optimizer.AdamW(
                    1e-3, parameters=pl1.parameters())
                return [float(m1.train_batch(
                    (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
                    o1).numpy()) for _ in range(2)]

            ref = _single_device_losses(jax, single_run)
            return dist, ref, model.mpmd_driver.steady_state_recompiles()

        legs[tag] = thunk

    # -- pp (geometry of _dryrun_pipeline, schedule as MPMD FThenB) --
    def build_pp(num_stages, vpp):
        paddle.seed(0)
        return PipelineLayer(
            layers=[LayerDesc(Plain) for _ in range(2 * pp)],
            num_stages=num_stages, loss_fn=nn.MSELoss())

    rng = np.random.default_rng(1)
    pipe_leg("pp", "MPMD", {"pp": pp, "dp": dp}, build_pp,
             (rng.standard_normal((8 * dp, hidden)).astype(np.float32),
              rng.standard_normal((8 * dp, hidden)).astype(np.float32)),
             M=pp)

    # -- vpp (geometry of _dryrun_vpp: embed prefix + LM head suffix) --
    vocab = 32

    def build_vpp(num_stages, vpp):
        paddle.seed(0)
        layers = [nn.Embedding(vocab, hidden)] + \
            [LayerDesc(Res) for _ in range(2 * pp * 2)] + \
            [nn.Linear(hidden, vocab)]
        return PipelineLayer(
            layers=layers, num_stages=num_stages,
            loss_fn=nn.CrossEntropyLoss(),
            num_virtual_pipeline_stages=vpp or 2)

    rng = np.random.default_rng(7)
    pipe_leg("vpp", "MPMD-VPP", {"pp": pp, "dp": dp}, build_vpp,
             (rng.integers(0, vocab, (4 * dp, 8)).astype(np.int64),
              rng.integers(0, vocab, (4 * dp, 8)).astype(np.int64)),
             M=pp)

    # -- zb / zbvpp (geometries of _dryrun_zb / _dryrun_zbvpp) --
    def build_zb(num_stages, vpp):
        paddle.seed(0)
        return PipelineLayer(
            layers=[LayerDesc(Res) for _ in range(2 * pp)],
            num_stages=num_stages, loss_fn=nn.MSELoss())

    rng = np.random.default_rng(3)
    pipe_leg("zb", "MPMD-ZBH1", {"pp": pp, "dp": dp}, build_zb,
             (rng.standard_normal((8 * dp, hidden)).astype(np.float32),
              rng.standard_normal((8 * dp, hidden)).astype(np.float32)),
             M=2 * pp)

    def build_zbvpp(num_stages, vpp):
        paddle.seed(0)
        return PipelineLayer(
            layers=[LayerDesc(Res) for _ in range(2 * pp * 2)],
            num_stages=num_stages, loss_fn=nn.MSELoss(),
            num_virtual_pipeline_stages=vpp or 2)

    rng = np.random.default_rng(5)
    pipe_leg("zbvpp", "MPMD-ZBVPP", {"pp": pp, "dp": dp}, build_zbvpp,
             (rng.standard_normal((8 * dp, hidden)).astype(np.float32),
              rng.standard_normal((8 * dp, hidden)).astype(np.float32)),
             M=pp)

    # -- 3d (geometry of _dryrun_hybrid_3d: TP blocks inside stages) --
    def build_3d(num_stages, vpp):
        paddle.seed(0)
        return PipelineLayer(
            layers=[LayerDesc(TPBlock) for _ in range(4)],
            num_stages=num_stages, loss_fn=nn.MSELoss())

    dp3 = n_devices // 4
    rng = np.random.default_rng(4)
    pipe_leg("3d", "MPMD", {"pp": 2, "dp": dp3, "mp": 2}, build_3d,
             (rng.standard_normal((4 * dp3, hidden)).astype(np.float32),
              rng.standard_normal((4 * dp3, hidden)).astype(np.float32)),
             M=2)

    # -- llama4d (geometry of _dryrun_llama_4d: the REAL flagship
    # module tree — GQA + sliding window + TP layers + ZeRO-3 stacked
    # block params over 'sharding') --
    from paddle_tpu.text.models import build_llama_pipe, force_tp_layers
    cfg = _llama_tiny_cfg(layers=4)
    dp4 = n_devices // 8

    def build_llama(num_stages, vpp):
        paddle.seed(0)
        with force_tp_layers():
            return build_llama_pipe(cfg, num_stages=num_stages)

    rng = np.random.default_rng(21)
    pipe_leg("llama4d", "MPMD",
             {"pp": 2, "dp": dp4, "sharding": 2, "mp": 2}, build_llama,
             (rng.integers(0, cfg.vocab_size,
                           (4 * dp4, 16)).astype(np.int64),
              rng.integers(0, cfg.vocab_size,
                           (4 * dp4, 16)).astype(np.int64)),
             M=2)

    # -- sep legs: the ring data path (fwd + counter-rotating bwd)
    # through MpmdRingExecutor vs the single-device flash reference,
    # seeded by the same quadratic loss both sides differentiate --
    def ring_leg(tag, R, q, k, v, window=None):
        def thunk():
            numel = float(np.prod(q.shape))
            scale_l = 1e2

            def dout_fn(r, out_block):
                # dL/dout for L = mean(out^2) * scale_l, elementwise
                return out_block.astype(jnp.float32) * (
                    2.0 * scale_l / numel)

            ex = MpmdRingExecutor(R, causal=True, window=window)
            for _ in range(2):  # run 1 = warmup compile, run 2 = steady
                out, grads = ex.run(q, k, v, dout_fn=dout_fn)
            loss = float(jnp.mean(jnp.square(
                out.astype(jnp.float32))) * scale_l)
            gnorm = float(sum(jnp.sum(
                jnp.square(g.astype(jnp.float32))) for g in grads))
            dist = [loss, gnorm]
            assert all(np.isfinite(x) for x in dist), dist

            def single_run():
                def loss_fn(qq, kk, vv):
                    o = ring_attention_arrays(qq, kk, vv, causal=True,
                                              window=window)
                    return jnp.mean(jnp.square(
                        o.astype(jnp.float32))) * scale_l

                l, gs = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
                    q, k, v)
                gn = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in gs)
                return [float(l), float(gn)]

            ref = _single_device_losses(jax, single_run)
            return dist, ref, ex.steady_state_recompiles()

        legs[tag] = thunk

    sep = 4 if n_devices % 4 == 0 else 2
    rng = np.random.default_rng(3)
    b, h, s, d = 2 * dp, 2, 8 * sep, 8   # _dryrun_context_parallel dims
    ring_leg("sep", sep,
             jnp.asarray(rng.standard_normal(
                 (b, h, s, d)).astype(np.float32)),
             jnp.asarray(rng.standard_normal(
                 (b, h, s, d)).astype(np.float32)),
             jnp.asarray(rng.standard_normal(
                 (b, h, s, d)).astype(np.float32)))

    # llama-sep: the flagship attention geometry — GQA 4 q heads over
    # 2 kv heads, sliding window 6 crossing the shard boundary
    rng = np.random.default_rng(22)
    ring_leg("llama-sep", 2,
             jnp.asarray(rng.standard_normal(
                 (2, 4, 32, 8)).astype(np.float32)),
             jnp.asarray(rng.standard_normal(
                 (2, 2, 32, 8)).astype(np.float32)),
             jnp.asarray(rng.standard_normal(
                 (2, 2, 32, 8)).astype(np.float32)),
             window=6)

    # sep8k: long context at seq 8192 (_dryrun_sep_8k dims)
    rng = np.random.default_rng(8)
    ring_leg("sep8k", 2,
             jnp.asarray(rng.standard_normal(
                 (1, 1, 8192, 32)).astype(np.float32) * 0.3),
             jnp.asarray(rng.standard_normal(
                 (1, 1, 8192, 32)).astype(np.float32) * 0.3),
             jnp.asarray(rng.standard_normal(
                 (1, 1, 8192, 32)).astype(np.float32)))

    return legs


def _dryrun_mpmd(jax, n_devices: int) -> None:
    """Phase 0c: EXECUTE the blocked-by-runtime phases through the
    MPMD runtime — every leg align-gated vs its single-device
    reference, with ZERO steady-state recompiles from the driver's
    CompileTracker (one executable per stage per (phase, shape)
    family)."""
    legs = _mpmd_execution_legs(jax, n_devices)
    if legs is None:
        print("dryrun mpmd: skipped (needs a multiple of 8 devices)")
        return
    green = []
    for tag, thunk in legs.items():
        dist, ref, ssr = thunk()
        _assert_aligned(f"mpmd {tag}", dist, ref)
        assert ssr == 0, f"mpmd {tag}: {ssr} steady-state recompiles"
        green.append(tag)
    print(f"dryrun mpmd ok: {len(green)}/9 blocked-by-runtime "
          f"phases executed align-green via the MPMD driver "
          f"({', '.join(green)}), zero steady-state recompiles")


def run_mpmd_execution(phases=None, n_devices: int = 8):
    """``tools/paddle_lint --mpmd-run`` entry: execute named MPMD legs
    on this host's virtual CPU devices and diff each against its
    single-device reference. Returns ``{tag: row}`` with
    ``row = {dist, ref, aligned, steady_state_recompiles, ok}``;
    callers exit nonzero when any ``ok`` is False. Must run before
    any other jax backend use in the process (same contract as
    ``run_dryrun``)."""
    jax = _ensure_devices(n_devices)
    legs = _mpmd_execution_legs(jax, n_devices)
    if legs is None:
        raise ValueError(
            f"--mpmd-run needs a multiple of 8 devices, got {n_devices}")
    if phases:
        unknown = [p for p in phases if p not in legs]
        if unknown:
            raise ValueError(
                f"unknown mpmd phase(s) {unknown}; known: {list(legs)}")
        legs = {p: legs[p] for p in phases}
    results = {}
    for tag, thunk in legs.items():
        dist, ref, ssr = thunk()
        aligned = bool(np.allclose(dist, ref, rtol=2e-3, atol=2e-4))
        results[tag] = {
            "dist": [float(v) for v in dist],
            "ref": [float(v) for v in ref],
            "aligned": aligned,
            "steady_state_recompiles": int(ssr),
            "ok": aligned and ssr == 0,
        }
    return results


def run_dryrun(n_devices: int) -> None:
    jax = _ensure_devices(n_devices)

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.meta_parallel import (
        ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
        VocabParallelEmbedding)
    from paddle_tpu.distributed.parallel_step import DistributedTrainStep

    assert len(jax.devices()) >= n_devices, (
        f"need {n_devices} devices, have {len(jax.devices())}")

    degrees = _factor_degrees(n_devices)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": degrees["dp"],
        "mp_degree": degrees["mp"],
        "sharding_degree": degrees["sharding"],
    }
    strategy.sharding_configs = dict(strategy.sharding_configs, stage=3,
                                     degree=degrees["sharding"])
    fleet.init(is_collective=True, strategy=strategy)

    vocab, hidden, seq, batch = 64, 32, 8, 4 * max(1, degrees["dp"])
    paddle.seed(0)

    class TinyTPLM(nn.Layer):
        """Embedding → TP MLP → vocab-parallel head + CE."""

        def __init__(self):
            super().__init__()
            self.embed = VocabParallelEmbedding(vocab, hidden)
            self.up = ColumnParallelLinear(hidden, 4 * hidden,
                                           gather_output=False)
            self.act = nn.GELU()
            self.down = RowParallelLinear(4 * hidden, hidden,
                                          input_is_parallel=True)
            self.norm = nn.LayerNorm(hidden)
            self.head = ColumnParallelLinear(hidden, vocab,
                                             gather_output=True)

        def forward(self, ids):
            h = self.embed(ids)
            h = h + self.down(self.act(self.up(h)))
            h = self.norm(h)
            return self.head(h)

    net = TinyTPLM()
    model = fleet.distributed_model(net)
    opt = fleet.distributed_optimizer(
        paddle.optimizer.AdamW(1e-3, parameters=net.parameters()))
    loss_fn = ParallelCrossEntropy()

    def ce(logits, labels):
        return loss_fn(logits, labels).mean()

    step = DistributedTrainStep(net, ce, opt,
                                sharding_stage=3 if
                                degrees["sharding"] > 1 else 0)

    rng = np.random.default_rng(0)
    ids_np = rng.integers(0, vocab, (batch, seq)).astype(np.int64)
    lab_np = rng.integers(0, vocab, (batch, seq)).astype(np.int64)
    ids = paddle.to_tensor(ids_np)
    labels = paddle.to_tensor(lab_np)
    loss = step(ids, labels)
    val = float(loss.numpy())
    assert np.isfinite(val), f"dryrun loss not finite: {val}"
    loss2 = float(step(ids, labels).numpy())
    assert np.isfinite(loss2)
    assert loss2 < val + 1.0, "loss diverged after one step"
    print(f"dryrun ok: mesh={degrees} loss0={val:.4f} loss1={loss2:.4f}")

    def single_run():
        paddle.seed(0)
        net1 = TinyTPLM()
        opt1 = paddle.optimizer.AdamW(1e-3, parameters=net1.parameters())
        step1 = paddle.jit.TrainStep(net1, ce, opt1)
        return [float(step1(paddle.to_tensor(ids_np),
                            paddle.to_tensor(lab_np)).numpy())
                for _ in range(2)]

    _assert_aligned("hybrid", [val, loss2],
                    _single_device_losses(jax, single_run))

    _dryrun_mpmd_lint(jax, n_devices)
    _dryrun_mpmd(jax, n_devices)
    _dryrun_pipeline(jax, n_devices)
    _dryrun_vpp(jax, n_devices)
    _dryrun_zb(jax, n_devices)
    _dryrun_zbvpp(jax, n_devices)
    _dryrun_het(jax, n_devices)
    _dryrun_moe(jax, n_devices)
    _dryrun_context_parallel(jax, n_devices)
    _dryrun_hybrid_3d(jax, n_devices)
    _dryrun_dcn(jax, n_devices)
    _dryrun_llama_4d(jax, n_devices)
    _dryrun_llama_sep(jax, n_devices)
    _dryrun_sep_8k(jax, n_devices)
    _dryrun_serving_disagg(jax, n_devices)
    _dryrun_planner(jax, n_devices)


def _dryrun_pipeline(jax, n_devices: int) -> None:
    """Phase 2: compiled GPipe over a pp x dp mesh (PipelineParallel)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel import (
        LayerDesc, PipelineLayer, PipelineParallel)

    pp = 4 if n_devices % 4 == 0 else (2 if n_devices % 2 == 0 else 1)
    if pp == 1:
        print("dryrun pp: skipped (n_devices not divisible)")
        return
    dp = n_devices // pp
    mesh_mod.set_mesh(mesh_mod.build_mesh({"pp": pp, "dp": dp}))

    hidden, batch = 16, 8 * dp
    paddle.seed(0)

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(hidden, hidden)

        def forward(self, x):
            return paddle.tanh(self.fc(x))

    pl = PipelineLayer(
        layers=[LayerDesc(Block) for _ in range(2 * pp)],
        num_stages=pp, loss_fn=nn.MSELoss())
    strategy = fleet.DistributedStrategy()
    strategy.pipeline_configs["accumulate_steps"] = pp
    model = PipelineParallel(pl, strategy=strategy)
    opt = paddle.optimizer.AdamW(1e-3, parameters=pl.parameters())

    rng = np.random.default_rng(1)
    x_np = rng.standard_normal((batch, hidden)).astype(np.float32)
    y_np = rng.standard_normal((batch, hidden)).astype(np.float32)
    x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)
    with jax.set_mesh(mesh_mod.get_mesh()):
        l0 = float(model.train_batch((x, y), opt).numpy())
        l1 = float(model.train_batch((x, y), opt).numpy())
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    print(f"dryrun pp ok: pp={pp} dp={dp} loss0={l0:.4f} loss1={l1:.4f}")

    def single_run():
        paddle.seed(0)
        pl1 = PipelineLayer(
            layers=[LayerDesc(Block) for _ in range(2 * pp)],
            num_stages=1, loss_fn=nn.MSELoss())
        m1 = PipelineParallel(pl1, strategy=strategy)
        o1 = paddle.optimizer.AdamW(1e-3, parameters=pl1.parameters())
        return [float(m1.train_batch(
            (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
            o1).numpy()) for _ in range(2)]

    _assert_aligned("pp", [l0, l1], _single_device_losses(jax, single_run))


def _dryrun_vpp(jax, n_devices: int) -> None:
    """Phase 2b: interleaved (VPP) schedule — pp=4, vpp_degree=2, with a
    real prefix (embedding) and suffix (head) whose params/opt state are
    sharded over the pp axis instead of replicated (VERDICT r2 item 1)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel import (
        LayerDesc, PipelineLayer, PipelineParallel)

    if n_devices % 4 != 0:
        print("dryrun vpp: skipped (needs a multiple of 4 devices)")
        return
    pp, dp = 4, n_devices // 4
    mesh_mod.set_mesh(mesh_mod.build_mesh({"pp": pp, "dp": dp}))

    vocab, hidden, batch, seq = 32, 16, 4 * dp, 8
    paddle.seed(0)

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(hidden, hidden)

        def forward(self, x):
            return x + paddle.tanh(self.fc(x))

    n_blocks = 2 * pp * 2  # 2 blocks per (stage, virtual chunk)

    def build(num_stages, vpp):
        paddle.seed(0)
        layers = [nn.Embedding(vocab, hidden)] + \
            [LayerDesc(Block) for _ in range(n_blocks)] + \
            [nn.Linear(hidden, vocab)]
        return PipelineLayer(
            layers=layers, num_stages=num_stages,
            loss_fn=nn.CrossEntropyLoss(),
            num_virtual_pipeline_stages=vpp)

    strategy = fleet.DistributedStrategy()
    strategy.pipeline_configs["accumulate_steps"] = pp

    rng = np.random.default_rng(7)
    ids_np = rng.integers(0, vocab, (batch, seq)).astype(np.int64)
    lab_np = rng.integers(0, vocab, (batch, seq)).astype(np.int64)

    pl = build(pp, 2)
    model = PipelineParallel(pl, strategy=strategy)
    assert model.vpp_degree == 2
    opt = paddle.optimizer.AdamW(1e-3, parameters=pl.parameters())
    with jax.set_mesh(mesh_mod.get_mesh()):
        l0 = float(model.train_batch(
            (paddle.to_tensor(ids_np), paddle.to_tensor(lab_np)),
            opt).numpy())
        l1 = float(model.train_batch(
            (paddle.to_tensor(ids_np), paddle.to_tensor(lab_np)),
            opt).numpy())
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    print(f"dryrun vpp ok: pp={pp} vpp=2 dp={dp} loss0={l0:.4f} "
          f"loss1={l1:.4f}")

    def single_run():
        pl1 = build(1, 1)
        m1 = PipelineParallel(pl1, strategy=strategy)
        o1 = paddle.optimizer.AdamW(1e-3, parameters=pl1.parameters())
        return [float(m1.train_batch(
            (paddle.to_tensor(ids_np), paddle.to_tensor(lab_np)),
            o1).numpy()) for _ in range(2)]

    _assert_aligned("vpp", [l0, l1],
                    _single_device_losses(jax, single_run))


def _dryrun_zb(jax, n_devices: int) -> None:
    """Phase 2c: zero-bubble (ZBH1) schedule — the dX/dW-split backward
    (zero_bubble.py) must train align-green with the single-device run
    (VERDICT r3 missing #1)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel import (
        LayerDesc, PipelineLayer, PipelineParallel)

    pp = 4 if n_devices % 4 == 0 else (2 if n_devices % 2 == 0 else 1)
    if pp == 1:
        print("dryrun zb: skipped (n_devices not divisible)")
        return
    dp = n_devices // pp
    mesh_mod.set_mesh(mesh_mod.build_mesh({"pp": pp, "dp": dp}))

    hidden, batch = 16, 8 * dp
    paddle.seed(0)

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(hidden, hidden)

        def forward(self, x):
            return x + paddle.tanh(self.fc(x))

    def build(num_stages):
        paddle.seed(0)
        return PipelineLayer(
            layers=[LayerDesc(Block) for _ in range(2 * pp)],
            num_stages=num_stages, loss_fn=nn.MSELoss())

    strategy = fleet.DistributedStrategy()
    strategy.pipeline_configs["accumulate_steps"] = 2 * pp
    strategy.pipeline_configs["schedule_mode"] = "ZBH1"

    rng = np.random.default_rng(3)
    x_np = rng.standard_normal((batch, hidden)).astype(np.float32)
    y_np = rng.standard_normal((batch, hidden)).astype(np.float32)

    pl = build(pp)
    model = PipelineParallel(pl, strategy=strategy)
    assert model.schedule_mode == "ZBH1"
    opt = paddle.optimizer.AdamW(1e-3, parameters=pl.parameters())
    with jax.set_mesh(mesh_mod.get_mesh()):
        l0 = float(model.train_batch(
            (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
            opt).numpy())
        l1 = float(model.train_batch(
            (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
            opt).numpy())
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    print(f"dryrun zb ok: pp={pp} dp={dp} loss0={l0:.4f} loss1={l1:.4f}")

    def single_run():
        pl1 = build(1)
        m1 = PipelineParallel(pl1, strategy=strategy)
        o1 = paddle.optimizer.AdamW(1e-3, parameters=pl1.parameters())
        return [float(m1.train_batch(
            (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
            o1).numpy()) for _ in range(2)]

    _assert_aligned("zb", [l0, l1], _single_device_losses(jax, single_run))


def _dryrun_zbvpp(jax, n_devices: int) -> None:
    """Phase 2c': zero-bubble interleaved (ZBVPP) — the dX/dW-split
    backward over the VPP chunk placement, align-green vs single-device
    (reference pipeline_zero_bubble.py ZBVPP)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel import (
        LayerDesc, PipelineLayer, PipelineParallel)

    if n_devices % 4 != 0:
        print("dryrun zbvpp: skipped (needs a multiple of 4 devices)")
        return
    pp, dp = 4, n_devices // 4
    mesh_mod.set_mesh(mesh_mod.build_mesh({"pp": pp, "dp": dp}))

    hidden, batch = 16, 8 * dp

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(hidden, hidden)

        def forward(self, x):
            return x + paddle.tanh(self.fc(x))

    def build(num_stages, vpp):
        paddle.seed(0)
        return PipelineLayer(
            layers=[LayerDesc(Block) for _ in range(2 * pp * 2)],
            num_stages=num_stages, loss_fn=nn.MSELoss(),
            num_virtual_pipeline_stages=vpp)

    strategy = fleet.DistributedStrategy()
    strategy.pipeline_configs["accumulate_steps"] = pp
    strategy.pipeline_configs["schedule_mode"] = "ZBVPP"

    rng = np.random.default_rng(5)
    x_np = rng.standard_normal((batch, hidden)).astype(np.float32)
    y_np = rng.standard_normal((batch, hidden)).astype(np.float32)

    pl = build(pp, 2)
    model = PipelineParallel(pl, strategy=strategy)
    assert model.schedule_mode == "ZBVPP" and model.vpp_degree == 2
    opt = paddle.optimizer.AdamW(1e-3, parameters=pl.parameters())
    with jax.set_mesh(mesh_mod.get_mesh()):
        l0 = float(model.train_batch(
            (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
            opt).numpy())
        l1 = float(model.train_batch(
            (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
            opt).numpy())
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    print(f"dryrun zbvpp ok: pp={pp} vpp=2 dp={dp} loss0={l0:.4f} "
          f"loss1={l1:.4f}")

    def single_run():
        pl1 = build(1, 1)
        strat1 = fleet.DistributedStrategy()
        strat1.pipeline_configs["accumulate_steps"] = pp
        m1 = PipelineParallel(pl1, strategy=strat1)
        o1 = paddle.optimizer.AdamW(1e-3, parameters=pl1.parameters())
        return [float(m1.train_batch(
            (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
            o1).numpy()) for _ in range(2)]

    _assert_aligned("zbvpp", [l0, l1],
                    _single_device_losses(jax, single_run))


def _dryrun_het(jax, n_devices: int) -> None:
    """Phase 2d: heterogeneous stages — explicit non-uniform seg_method
    bounds with stage-varying layer widths (het_pipeline.py; VERDICT r3
    missing #3). Align-checked against the sequential run."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel import (
        PipelineLayer, PipelineParallel)

    pp = 4 if n_devices % 4 == 0 else (2 if n_devices % 2 == 0 else 1)
    if pp == 1:
        print("dryrun het: skipped (n_devices not divisible)")
        return
    mesh_mod.set_mesh(mesh_mod.build_mesh({"pp": pp}))

    class Wide(nn.Layer):
        def __init__(self, din, dout):
            super().__init__()
            self.fc = nn.Linear(din, dout)

        def forward(self, x):
            return paddle.tanh(self.fc(x))

    widths = [(8, 8)] * (pp - 1) + [(8, 12), (12, 8)] + [(8, 8)]
    seg = [1] * (pp - 1) + [3]           # non-uniform: last stage gets 3

    def build(num_stages, seg_method):
        paddle.seed(0)
        return PipelineLayer(
            layers=[Wide(a, b) for a, b in widths],
            num_stages=num_stages, loss_fn=nn.MSELoss(),
            seg_method=seg_method)

    strategy = fleet.DistributedStrategy()
    strategy.pipeline_configs["accumulate_steps"] = pp

    rng = np.random.default_rng(11)
    x_np = rng.standard_normal((4 * pp, 8)).astype(np.float32)
    y_np = rng.standard_normal((4 * pp, 8)).astype(np.float32)

    pl = build(pp, seg)
    model = PipelineParallel(pl, strategy=strategy)
    assert model._het, "non-uniform bounds must select the het schedule"
    opt = paddle.optimizer.AdamW(1e-3, parameters=pl.parameters())
    with jax.set_mesh(mesh_mod.get_mesh()):
        l0 = float(model.train_batch(
            (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
            opt).numpy())
        l1 = float(model.train_batch(
            (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
            opt).numpy())
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    print(f"dryrun het ok: pp={pp} seg={seg} loss0={l0:.4f} "
          f"loss1={l1:.4f}")

    def single_run():
        pl1 = build(1, "uniform")
        m1 = PipelineParallel(pl1, strategy=strategy)
        o1 = paddle.optimizer.AdamW(1e-3, parameters=pl1.parameters())
        return [float(m1.train_batch(
            (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
            o1).numpy()) for _ in range(2)]

    _assert_aligned("het", [l0, l1],
                    _single_device_losses(jax, single_run))


def _dryrun_dcn(jax, n_devices: int) -> None:
    """Phase 6: multi-slice mesh — data parallelism over the DCN (slice)
    dimension, sharding+mp over ICI within each slice (SURVEY §7.3
    multi-slice; VERDICT r2 item 5: dcn_dp=2 x ici=4)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import mesh as mesh_mod

    if n_devices % 8 != 0:
        print("dryrun dcn: skipped (needs a multiple of 8 devices)")
        return
    mesh_mod.set_mesh(mesh_mod.build_mesh(
        {"dp": n_devices // 8, "sharding": 2, "mp": 2},
        dcn_degrees={"dp": 2}))
    assert mesh_mod.axis_degree("dp") == n_devices // 4

    hidden, batch = 16, 4 * mesh_mod.axis_degree("dp")
    paddle.seed(0)

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(hidden, 4 * hidden)
            self.fc2 = nn.Linear(4 * hidden, 8)

        def forward(self, x):
            return self.fc2(paddle.nn.functional.gelu(self.fc1(x)))

    net = Net()
    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
    step = paddle.jit.TrainStep(net, nn.CrossEntropyLoss(), opt)
    rng = np.random.default_rng(6)
    x_np = rng.standard_normal((batch, hidden)).astype(np.float32)
    y_np = rng.integers(0, 8, batch)
    with jax.set_mesh(mesh_mod.get_mesh()):
        l0 = float(step(paddle.to_tensor(x_np),
                        paddle.to_tensor(y_np)).numpy())
        l1 = float(step(paddle.to_tensor(x_np),
                        paddle.to_tensor(y_np)).numpy())
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    print(f"dryrun dcn ok: dcn_dp=2 x ici=(sharding=2,mp=2) "
          f"loss0={l0:.4f} loss1={l1:.4f}")

    def single_run():
        paddle.seed(0)
        n1 = Net()
        o1 = paddle.optimizer.AdamW(1e-3, parameters=n1.parameters())
        s1 = paddle.jit.TrainStep(n1, nn.CrossEntropyLoss(), o1)
        return [float(s1(paddle.to_tensor(x_np),
                         paddle.to_tensor(y_np)).numpy())
                for _ in range(2)]

    _assert_aligned("dcn", [l0, l1],
                    _single_device_losses(jax, single_run))


def _dryrun_moe(jax, n_devices: int) -> None:
    """Phase 3: expert parallelism — MoE dispatch/combine all-to-all over
    an ep x dp mesh."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.incubate.distributed.models.moe import MoELayer

    ep = 4 if n_devices % 4 == 0 else (2 if n_devices % 2 == 0 else 1)
    if ep == 1:
        print("dryrun ep: skipped (n_devices not divisible)")
        return
    dp = n_devices // ep
    mesh_mod.set_mesh(mesh_mod.build_mesh({"dp": dp, "ep": ep}))

    hidden, batch, seq = 16, 4 * dp, 8
    paddle.seed(0)

    class MoENet(nn.Layer):
        def __init__(self):
            super().__init__()
            self.moe = MoELayer(d_model=hidden, d_hidden=2 * hidden,
                                num_experts=ep, gate="gshard")
            self.head = nn.Linear(hidden, 8)

        def forward(self, x):
            return self.head(self.moe(x))

    net = MoENet()
    ce = nn.CrossEntropyLoss()

    def loss_fn(out, labels):
        return ce(out, labels) + 0.01 * net.moe.l_aux

    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
    step = paddle.jit.TrainStep(net, loss_fn, opt)
    rng = np.random.default_rng(2)
    x_np = rng.standard_normal((batch, seq, hidden)).astype(np.float32)
    y_np = rng.integers(0, 8, (batch, seq))
    x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)
    with jax.set_mesh(mesh_mod.get_mesh()):
        l0 = float(step(x, y).numpy())
        l1 = float(step(x, y).numpy())
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    print(f"dryrun ep ok: ep={ep} dp={dp} loss0={l0:.4f} loss1={l1:.4f}")

    def single_run():
        paddle.seed(0)
        n1 = MoENet()
        ce1 = nn.CrossEntropyLoss()

        def lf(out, labels):
            return ce1(out, labels) + 0.01 * n1.moe.l_aux

        o1 = paddle.optimizer.AdamW(1e-3, parameters=n1.parameters())
        s1 = paddle.jit.TrainStep(n1, lf, o1)
        return [float(s1(paddle.to_tensor(x_np),
                         paddle.to_tensor(y_np)).numpy())
                for _ in range(2)]

    _assert_aligned("ep", [l0, l1], _single_device_losses(jax, single_run))


def _dryrun_context_parallel(jax, n_devices: int) -> None:
    """Phase 4: sequence/context parallelism — ring attention over 'sep'
    inside a full train step on a sep x dp mesh."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.kernels.ring_attention import ring_flash_attention

    sep = 4 if n_devices % 4 == 0 else (2 if n_devices % 2 == 0 else 1)
    if sep == 1:
        print("dryrun sep: skipped (n_devices not divisible)")
        return
    dp = n_devices // sep
    mesh_mod.set_mesh(mesh_mod.build_mesh({"dp": dp, "sep": sep}))

    hidden, heads, seq, batch = 16, 2, 8 * sep, 2 * dp
    paddle.seed(0)

    class CPAttnNet(nn.Layer):
        def __init__(self):
            super().__init__()
            self.qkv = nn.Linear(hidden, 3 * hidden)
            self.out = nn.Linear(hidden, hidden)
            self.head = nn.Linear(hidden, 8)

        def forward(self, x):
            b, s, _ = x.shape
            qkv = self.qkv(x).reshape([b, s, 3, heads, hidden // heads])
            from paddle_tpu.ops.manipulation import split as _split
            q, k, v = [t.squeeze(2) for t in _split(qkv, 3, axis=2)]
            a = ring_flash_attention(q, k, v, causal=True)
            h = self.out(a.reshape([b, s, hidden]))
            return self.head(h)

    net = CPAttnNet()
    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
    step = paddle.jit.TrainStep(net, nn.CrossEntropyLoss(), opt)
    rng = np.random.default_rng(3)
    x_np = rng.standard_normal((batch, seq, hidden)).astype(np.float32)
    y_np = rng.integers(0, 8, (batch, seq))
    x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)
    with jax.set_mesh(mesh_mod.get_mesh()):
        l0 = float(step(x, y).numpy())
        l1 = float(step(x, y).numpy())
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    print(f"dryrun sep ok: sep={sep} dp={dp} loss0={l0:.4f} "
          f"loss1={l1:.4f}")

    def single_run():
        paddle.seed(0)
        n1 = CPAttnNet()
        o1 = paddle.optimizer.AdamW(1e-3, parameters=n1.parameters())
        s1 = paddle.jit.TrainStep(n1, nn.CrossEntropyLoss(), o1)
        return [float(s1(paddle.to_tensor(x_np),
                         paddle.to_tensor(y_np)).numpy())
                for _ in range(2)]

    _assert_aligned("sep", [l0, l1],
                    _single_device_losses(jax, single_run))


def _dryrun_hybrid_3d(jax, n_devices: int) -> None:
    """Phase 5: the BASELINE config-4 composition — TP blocks inside the
    compiled pipeline on a pp x dp x mp mesh."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel import (
        ColumnParallelLinear, LayerDesc, PipelineLayer, PipelineParallel,
        RowParallelLinear)

    if n_devices % 8 != 0:
        print("dryrun 3d: skipped (needs a multiple of 8 devices)")
        return
    dp = n_devices // 4
    mesh_mod.set_mesh(mesh_mod.build_mesh({"pp": 2, "dp": dp, "mp": 2}))

    hidden, batch = 16, 4 * dp
    paddle.seed(0)

    class TPBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            self.up = ColumnParallelLinear(hidden, 4 * hidden,
                                           gather_output=False)
            self.down = RowParallelLinear(4 * hidden, hidden,
                                          input_is_parallel=True)

        def forward(self, x):
            return x + self.down(
                paddle.nn.functional.gelu(self.up(x)))

    pl = PipelineLayer(layers=[LayerDesc(TPBlock) for _ in range(4)],
                       num_stages=2, loss_fn=nn.MSELoss())
    strategy = fleet.DistributedStrategy()
    strategy.pipeline_configs["accumulate_steps"] = 2
    model = PipelineParallel(pl, strategy=strategy)
    opt = paddle.optimizer.AdamW(1e-3, parameters=pl.parameters())
    rng = np.random.default_rng(4)
    x_np = rng.standard_normal((batch, hidden)).astype(np.float32)
    y_np = rng.standard_normal((batch, hidden)).astype(np.float32)
    x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)
    with jax.set_mesh(mesh_mod.get_mesh()):
        l0 = float(model.train_batch((x, y), opt).numpy())
        l1 = float(model.train_batch((x, y), opt).numpy())
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    assert l1 < l0, (l0, l1)  # deterministic seed: one step must improve
    print(f"dryrun 3d ok: pp=2 dp={dp} mp=2 loss0={l0:.4f} "
          f"loss1={l1:.4f}")

    def single_run():
        paddle.seed(0)
        pl1 = PipelineLayer(layers=[LayerDesc(TPBlock) for _ in range(4)],
                            num_stages=1, loss_fn=nn.MSELoss())
        m1 = PipelineParallel(pl1, strategy=strategy)
        o1 = paddle.optimizer.AdamW(1e-3, parameters=pl1.parameters())
        return [float(m1.train_batch(
            (paddle.to_tensor(x_np), paddle.to_tensor(y_np)),
            o1).numpy()) for _ in range(2)]

    _assert_aligned("3d", [l0, l1], _single_device_losses(jax, single_run))


def _llama_tiny_cfg(layers=4):
    """The flagship model at dryrun geometry: every feature the bench
    config exercises — GQA (4 q heads over 2 kv heads), sliding window,
    flash attention (XLA fallback under shard_map on CPU) — at sizes
    that divide mp=2 / sharding=2 cleanly."""
    from paddle_tpu.text.models import LlamaConfig
    return LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=layers, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64,
        sliding_window=6, use_flash_attention=True)


def _dryrun_llama_4d(jax, n_devices: int) -> None:
    """Phase 7: flagship composition — the REAL LlamaForCausalLM module
    tree (GQA + sliding window + flash fallback + TP layers) trained
    through the compiled pipeline on a pp x dp x sharding x mp mesh,
    stacked block params ZeRO-3-sharded over 'sharding', acc-aligned
    vs the single-device run (VERDICT r4 next #1; reference
    test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py
    + fleet/base/topology.py:306 axis order)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel import PipelineParallel
    from paddle_tpu.text.models import build_llama_pipe, force_tp_layers

    if n_devices % 8 != 0:
        print("dryrun llama4d: skipped (needs a multiple of 8 devices)")
        return
    pp, sh, mp = 2, 2, 2
    dp = n_devices // 8
    mesh_mod.set_mesh(mesh_mod.build_mesh(
        {"pp": pp, "dp": dp, "sharding": sh, "mp": mp}))

    cfg = _llama_tiny_cfg(layers=4)
    batch, seq = 4 * dp, 16
    strategy = fleet.DistributedStrategy()
    strategy.pipeline_configs["accumulate_steps"] = 2

    rng = np.random.default_rng(21)
    ids_np = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    lab_np = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int64)

    def run(num_stages):
        paddle.seed(0)
        with force_tp_layers():
            pl = build_llama_pipe(cfg, num_stages=num_stages)
        model = PipelineParallel(pl, strategy=strategy)
        opt = paddle.optimizer.AdamW(1e-3, parameters=pl.parameters())
        with jax.set_mesh(mesh_mod.get_mesh()):
            return [float(model.train_batch(
                (paddle.to_tensor(ids_np), paddle.to_tensor(lab_np)),
                opt).numpy()) for _ in range(2)]

    losses = run(pp)
    assert all(np.isfinite(v) for v in losses), losses
    print(f"dryrun llama4d ok: pp={pp} dp={dp} sharding={sh} mp={mp} "
          f"gqa=4/2 window=6 loss0={losses[0]:.4f} loss1={losses[1]:.4f}")
    _assert_aligned("llama 4d", losses,
                    _single_device_losses(jax, lambda: run(1)))


def _dryrun_llama_sep(jax, n_devices: int) -> None:
    """Phase 8: flagship long-context composition — the REAL
    LlamaForCausalLM with ring attention over 'sep' composed with
    ZeRO-3 'sharding' + mp (+dp), fused linear CE loss head, acc-aligned
    vs the single-device run (VERDICT r4 next #1 second point; the
    reference snapshot has no CP — SURVEY §2.3 requires it here)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.parallel_step import DistributedTrainStep
    from paddle_tpu.text.models import LlamaForCausalLM, force_tp_layers

    if n_devices % 8 != 0:
        print("dryrun llama-sep: skipped (needs a multiple of 8 devices)")
        return
    dp = n_devices // 8
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": dp, "mp_degree": 2, "sharding_degree": 2,
        "sep_degree": 2}
    strategy.sharding_configs = dict(strategy.sharding_configs, stage=3,
                                     degree=2)
    fleet.init(is_collective=True, strategy=strategy)

    cfg = _llama_tiny_cfg(layers=2)
    cfg.fused_linear_ce = True
    cfg.fused_ce_chunks = 2
    batch, seq = 2 * dp, 16   # seq divides sep=2; window=6 crosses shards

    rng = np.random.default_rng(22)
    ids_np = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    lab_np = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int64)

    def loss_fn(out, _):
        return out   # fused_linear_ce: forward(ids, labels) IS the loss

    def dist_run():
        paddle.seed(0)
        net = LlamaForCausalLM(cfg)
        fleet.distributed_model(net)
        opt = fleet.distributed_optimizer(
            paddle.optimizer.AdamW(1e-3, parameters=net.parameters()))
        step = DistributedTrainStep(net, loss_fn, opt, sharding_stage=3)
        return [float(step(
            (paddle.to_tensor(ids_np), paddle.to_tensor(lab_np)),
            paddle.to_tensor(0.0)).numpy()) for _ in range(2)]

    losses = dist_run()
    assert all(np.isfinite(v) for v in losses), losses
    print(f"dryrun llama-sep ok: dp={dp} sharding=2 sep=2 mp=2 "
          f"fused_ce=on loss0={losses[0]:.4f} loss1={losses[1]:.4f}")

    def single_run():
        paddle.seed(0)
        with force_tp_layers():
            net1 = LlamaForCausalLM(cfg)
        opt1 = paddle.optimizer.AdamW(1e-3, parameters=net1.parameters())
        step1 = paddle.jit.TrainStep(net1, loss_fn, opt1)
        return [float(step1(
            (paddle.to_tensor(ids_np), paddle.to_tensor(lab_np)),
            paddle.to_tensor(0.0)).numpy()) for _ in range(2)]

    _assert_aligned("llama sep", losses,
                    _single_device_losses(jax, single_run))


def _dryrun_sep_8k(jax, n_devices: int) -> None:
    """Phase 9: LONG-CONTEXT context parallelism — ring attention over
    sep=2 at seq 8192 (the ROADMAP item-4 / VERDICT long-context ask),
    fwd + bwd, align-gated against the single-device flash reference.

    Device-free in the dryrun sense (virtual CPU devices, no chip):
    the 8K sequence is sharded 4096/4096 over the ring, each device's
    K/V blocks rotate via ppermute, and the single-device side runs
    the SAME ring_attention_arrays entry on a 1-device mesh (which
    lowers to the exact flash/XLA path) — so the align check holds the
    whole sep data path, including the backward counter-rotation, to
    the dense-attention numerics at a length where the dense mask
    alone is a 256 MB tensor."""
    import jax.numpy as jnp

    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.kernels.ring_attention import ring_attention_arrays

    if n_devices % 2 != 0:
        print("dryrun sep8k: skipped (n_devices not divisible by 2)")
        return
    b, h, s, d = 1, 1, 8192, 32
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)).astype(np.float32)
                    * 0.3)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)).astype(np.float32)
                    * 0.3)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)).astype(np.float32))

    def run():
        def loss_fn(qq, kk, vv):
            out = ring_attention_arrays(qq, kk, vv, causal=True)
            return jnp.mean(jnp.square(out.astype(jnp.float32))) * 1e2

        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
            q, k, v)
        gnorm = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in grads)
        return [float(loss), float(gnorm)]

    mesh_mod.set_mesh(mesh_mod.build_mesh(
        {"dp": n_devices // 2, "sep": 2}))
    dist = run()
    assert all(np.isfinite(x) for x in dist), dist
    print(f"dryrun sep8k ok: sep=2 s={s} loss={dist[0]:.4f} "
          f"gnorm={dist[1]:.4f}")
    _assert_aligned("sep8k", dist, _single_device_losses(jax, run))


def _dryrun_planner(jax, n_devices: int) -> None:
    """Phase 11: the AUTO-PARALLEL PLANNER picks the mesh (ISSUE 14).

    Two halves, mirroring the planner's contract:

    * CALIBRATION GATE (device-free): the planner must reproduce the
      frozen relative ordering of the 13 align-green dryrun
      configurations above (rank correlation >= 0.9, every plan-family
      ordering correct) BEFORE it may pick new ones — a planner that
      cannot rank the known-good configs has not earned the right to
      choose.
    * EXECUTION: search the dp/sharding/mp space for this phase's
      workload, take the winner, build its CONCRETE mesh + strategy
      (Plan.build_mesh / Plan.strategy — the executable surface), and
      train it end-to-end: two steps, loss finite, align-green vs the
      single-device run, ZERO steady-state recompiles.
    """
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.analysis import planner
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel import (
        ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
        VocabParallelEmbedding)
    from paddle_tpu.distributed.parallel_step import DistributedTrainStep
    from paddle_tpu.profiler.stats import CompileTracker

    rep = planner.calibration_report()
    assert rep["spearman"] >= 0.9, (
        f"planner calibration: rank correlation {rep['spearman']:.3f} "
        f"< 0.9 (predicted {rep['order']}, "
        f"ledger {rep['expected_order']})")
    assert rep["all_lint_clean"], (
        "planner calibration: a known-good dryrun config lints dirty: "
        f"{[r for r in rep['configs'] if not r['ok']]}")
    assert rep["families_ok"], (
        f"planner calibration: family ordering wrong: {rep['families']}")
    n_cfg = len(rep["configs"])
    n_ok = sum(1 for r in rep["configs"] if r["ok"])
    n_fam = len(rep["families"])
    n_fam_ok = sum(1 for f in rep["families"].values() if f["ok"])
    print(f"dryrun planner calibration ok: {n_ok}/{n_cfg} configs "
          f"lint-clean, rank corr {rep['spearman']:.2f}, "
          f"{n_fam_ok}/{n_fam} families")

    vocab, hidden, seq = 64, 32, 8
    spec = planner.ModelSpec(
        "dryrun-planner", hidden=hidden, layers=1, seq=seq,
        global_batch=8, intermediate=4 * hidden, vocab=vocab)
    n_cands = len(planner.enumerate_plans(
        spec, n_devices, axes=("dp", "sharding", "mp")))
    best = planner.best_plan(spec, n_devices,
                             axes=("dp", "sharding", "mp"))
    plan = best.plan
    print(f"dryrun planner pick: {plan.describe()} "
          f"predicted {best.time.step_s * 1e6:.2f} us/step "
          f"over {n_cands} candidates")

    mesh_mod.set_mesh(plan.build_mesh())
    strategy = plan.strategy()
    fleet.init(is_collective=True, strategy=strategy)
    dp_total = plan.degree("dp") * plan.degree("sharding")
    batch = spec.global_batch
    paddle.seed(0)

    class PlannedLM(nn.Layer):
        """The hybrid-phase model family: embedding -> TP MLP ->
        vocab-parallel head + CE (what the spec describes)."""

        def __init__(self):
            super().__init__()
            self.embed = VocabParallelEmbedding(vocab, hidden)
            self.up = ColumnParallelLinear(hidden, 4 * hidden,
                                           gather_output=False)
            self.act = nn.GELU()
            self.down = RowParallelLinear(4 * hidden, hidden,
                                          input_is_parallel=True)
            self.head = ColumnParallelLinear(hidden, vocab,
                                             gather_output=True)

        def forward(self, ids):
            h = self.embed(ids)
            h = h + self.down(self.act(self.up(h)))
            return self.head(h)

    net = PlannedLM()
    fleet.distributed_model(net)
    opt = fleet.distributed_optimizer(
        paddle.optimizer.AdamW(1e-3, parameters=net.parameters()))
    loss_fn = ParallelCrossEntropy()

    def ce(logits, labels):
        return loss_fn(logits, labels).mean()

    step = DistributedTrainStep(
        net, ce, opt,
        sharding_stage=3 if plan.shard_weight_update
        and plan.degree("sharding") > 1 else 0)

    rng = np.random.default_rng(14)
    ids_np = rng.integers(0, vocab, (batch, seq)).astype(np.int64)
    lab_np = rng.integers(0, vocab, (batch, seq)).astype(np.int64)
    ids, labels = paddle.to_tensor(ids_np), paddle.to_tensor(lab_np)

    tracker = CompileTracker().start()
    losses = []
    try:
        # warmup is TWO steps: step 0 compiles the program, step 1
        # compiles the committed-layout/donated variant once (the same
        # warm-up contract the serving engine's fused step has); from
        # there every step must reuse the executables
        for _ in range(4):
            losses.append(float(step(ids, labels).numpy()))
            tracker.on_step()
    finally:
        tracker.stop()
    assert all(np.isfinite(v) for v in losses), losses
    recompiles = tracker.steady_state_recompiles(warmup_steps=2)
    assert recompiles == 0, (
        f"planner-chosen plan recompiles in steady state: {recompiles} "
        f"(per-step {tracker.per_step})")
    print(f"dryrun planner ok: plan={plan.describe()} "
          f"dp_total={dp_total} loss0={losses[0]:.4f} "
          f"loss1={losses[1]:.4f} recompiles={recompiles}")

    def single_run():
        paddle.seed(0)
        net1 = PlannedLM()
        opt1 = paddle.optimizer.AdamW(1e-3, parameters=net1.parameters())
        step1 = paddle.jit.TrainStep(net1, ce, opt1)
        return [float(step1(paddle.to_tensor(ids_np),
                            paddle.to_tensor(lab_np)).numpy())
                for _ in range(4)]

    _assert_aligned("planner", losses,
                    _single_device_losses(jax, single_run))


def _dryrun_serving_disagg(jax, n_devices: int) -> None:
    """Phase 10: DISAGGREGATED serving — prefill workers and decode
    workers as independent compiled surfaces with separate page pools,
    KV pages migrating between them (inference/disagg.py).

    Device-free gate, two halves:

    * STATIC: the page-migration step's collective-redistribution
      expression (alltoall_single over the `worker` axis, the
      arXiv:2112.01075 formulation) records and validates clean under
      the shard_lint recorder against a fake worker mesh.
    * DYNAMIC: a mixed greedy + seeded-sampling trace — prefix-cache
      hits crossing the migration boundary, speculative decoding,
      decode-pool preemption, and a mid-trace decode-worker KILL with
      failover re-admission — must emit TOKEN-IDENTICAL streams to
      the single-loop Engine on the same weights. The disaggregation
      is a scheduler split, never a numeric one.
    """
    import paddle_tpu as paddle
    from paddle_tpu.inference.disagg import DisaggEngine, lint_migration
    from paddle_tpu.inference.engine import Engine, SamplingParams
    from paddle_tpu.text.models import LlamaForCausalLM

    cfg = _llama_tiny_cfg(layers=2)
    cfg.use_flash_attention = False
    paddle.seed(0)
    net = LlamaForCausalLM(cfg)
    net.eval()
    paddle.seed(1)
    dcfg = _llama_tiny_cfg(layers=1)
    dcfg.use_flash_attention = False
    draft = LlamaForCausalLM(dcfg)
    draft.eval()

    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int64)
    prompts = [np.concatenate(
        [system, rng.integers(0, cfg.vocab_size, (n,))]).astype(np.int64)
        for n in (5, 9, 3, 7, 6)]
    cfgs = [dict(max_new_tokens=8),
            dict(max_new_tokens=7, temperature=0.9, seed=3),
            dict(max_new_tokens=9),
            dict(max_new_tokens=6, temperature=0.7, top_k=8, seed=7),
            dict(max_new_tokens=8)]

    findings = lint_migration(4, max_blocks=8, kv_heads=int(
        cfg.num_key_value_heads), page_size=8, head_dim=int(
        cfg.hidden_size // cfg.num_attention_heads), layers=2)
    assert not findings, f"migration collective lint: {findings}"

    def build(cls, **kw):
        return cls(net, page_size=8, max_context=64, prefix_cache=True,
                   draft_model=draft, spec_k=3, **kw)

    single = build(Engine, max_slots=4, pool_pages=96)
    ref = single.run([(p, SamplingParams(**c))
                      for p, c in zip(prompts, cfgs)])
    single.close()

    eng = build(DisaggEngine, prefill_workers=2, decode_workers=2,
                max_slots=1, pool_pages=10, prefill_pool_pages=48,
                watermark_pages=0)
    ids = [eng.add_request(p, SamplingParams(**c))
           for p, c in zip(prompts, cfgs)]
    done = {}
    killed = False
    preempts0 = None
    for _ in range(300):
        for o in eng.step():
            done[o.req_id] = o
        if not killed and eng.num_active > 0:
            loads = [(sum(1 for r in w._slots if r is not None), i)
                     for i, w in enumerate(eng.decode)
                     if w is not None]
            eng.kill_worker("decode", max(loads)[1])
            killed = True
        if len(done) == len(ids):
            break
    assert killed and len(done) == len(ids), (
        f"disagg dryrun did not drain ({len(done)}/{len(ids)})")
    mismatched = [rid for rid, r in zip(ids, ref)
                  if done[rid].token_ids != r.token_ids]
    assert not mismatched, f"disagg token mismatch: {mismatched}"
    recompiles = eng.steady_state_recompiles()
    assert recompiles == 0, f"disagg steady-state recompiles: {recompiles}"
    leaks = eng.check_invariants()
    assert not leaks, f"disagg invariant findings: {leaks}"
    from paddle_tpu import monitor
    migs = int(monitor.counter("serving.disagg.migrations").get())
    eng.close()
    print(f"dryrun serving disagg ok: prefill=2 decode=2 "
          f"migrations={migs} worker_kill=1 recompiles={recompiles}")
    print(f"dryrun serving disagg align ok: "
          f"{len(ids)}/{len(ids)} requests token-exact vs single-loop "
          f"(greedy+sampled, prefix+spec on, preempt+kill)")
