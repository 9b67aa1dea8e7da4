"""paddle.distributed.rpc tests (reference python/paddle/distributed/rpc:
init_rpc + rpc_sync/rpc_async between workers; here the transport is the
stdlib connection listener with TCPStore rendezvous)."""
import os
import subprocess
import sys
import textwrap

import socket

import numpy as np
import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mul(a, b):
    return a * b


def test_rpc_self_loopback():
    """Single worker: the full init -> serve -> call -> shutdown path."""
    import paddle_tpu.distributed as dist

    dist.rpc.init_rpc("self", rank=0, world_size=1,
                      master_endpoint=f"127.0.0.1:{_free_port()}")
    try:
        assert dist.rpc.rpc_sync("self", max, args=(3, 5)) == 5
        fut = dist.rpc.rpc_async("self", _mul, args=(6, 7))
        assert fut.wait() == 42
        # numpy payloads round-trip
        out = dist.rpc.rpc_sync("self", np.sum,
                                args=(np.arange(5, dtype=np.int64),))
        assert int(out) == 10
        # remote exceptions propagate
        with pytest.raises(ZeroDivisionError):
            dist.rpc.rpc_sync("self", divmod, args=(1, 0))
        info = dist.rpc.get_worker_info("self")
        assert info.rank == 0
        assert [w.name for w in dist.rpc.get_all_worker_infos()] == ["self"]
        assert dist.rpc.get_current_worker_info().name == "self"
    finally:
        dist.rpc.shutdown()
    # re-init after shutdown works
    dist.rpc.init_rpc("again", rank=0, world_size=1,
                      master_endpoint=f"127.0.0.1:{_free_port()}")
    assert dist.rpc.rpc_sync("again", len, args=((1, 2, 3),)) == 3
    dist.rpc.shutdown()


@pytest.mark.nightly
def test_rpc_cross_process(tmp_path):
    worker = tmp_path / "w.py"
    port = _free_port()
    worker.write_text(textwrap.dedent("""
        import sys
        import paddle_tpu.distributed as dist

        rank = int(sys.argv[1])
        dist.rpc.init_rpc(f"worker{rank}", rank=rank, world_size=2,
                          master_endpoint="127.0.0.1:PORT")
        if rank == 0:
            assert dist.rpc.rpc_sync("worker1", pow, args=(2, 10)) == 1024
            fut = dist.rpc.rpc_async("worker1", sorted,
                                     args=([3, 1, 2],))
            assert fut.wait() == [1, 2, 3]
            print("RPC OK", flush=True)
        dist.rpc.shutdown()
    """).replace("PORT", str(port)))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    p1 = subprocess.Popen([sys.executable, str(worker), "1"], env=env,
                          stdout=subprocess.PIPE, text=True)
    p0 = subprocess.Popen([sys.executable, str(worker), "0"], env=env,
                          stdout=subprocess.PIPE, text=True)
    out0, _ = p0.communicate(timeout=180)
    out1, _ = p1.communicate(timeout=180)
    assert p0.returncode == 0, out0
    assert p1.returncode == 0, out1
    assert "RPC OK" in out0
