"""Native C++ component tests: TCPStore rendezvous + monitors.

Cross-process test mirrors the reference's TCPStore usage: the launcher
master hosts the store, workers rendezvous/barrier through it."""
import os
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu import csrc

pytestmark = pytest.mark.skipif(csrc.lib() is None,
                                reason="no native toolchain")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_store_set_get_add_wait():
    from paddle_tpu.distributed.store import TCPStore
    master = TCPStore("127.0.0.1", 38761, is_master=True, world_size=1)
    try:
        master.set("x", b"abc")
        assert master.get("x") == b"abc"
        assert master.add("n", 2) == 2
        assert master.add("n", 40) == 42
        master.wait(["x"])
        assert master.delete_key("x")
        assert not master.check("x")
    finally:
        master.close()


@pytest.mark.nightly
def test_store_blocking_get_across_processes(tmp_path):
    """get() must BLOCK until another process sets the key."""
    import socket
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    worker = tmp_path / "w.py"
    worker.write_text(textwrap.dedent(f"""
        import sys, time
        from paddle_tpu.distributed.store import TCPStore
        role = sys.argv[1]
        s = TCPStore("127.0.0.1", {port}, is_master=(role == "master"),
                     world_size=2)
        if role == "master":
            time.sleep(0.5)           # let the getter block first
            s.set("token", b"ready")
            s.barrier("done", timeout=30)
        else:
            v = s.get("token")        # blocks server-side
            assert v == b"ready", v
            s.barrier("done", timeout=30)
        print("OK", role, flush=True)
    """))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    pm = subprocess.Popen([sys.executable, str(worker), "master"],
                          env=env, stdout=subprocess.PIPE, text=True)
    pw = subprocess.Popen([sys.executable, str(worker), "worker"],
                          env=env, stdout=subprocess.PIPE, text=True)
    out_m, _ = pm.communicate(timeout=120)
    out_w, _ = pw.communicate(timeout=120)
    assert pm.returncode == 0 and "OK master" in out_m
    assert pw.returncode == 0 and "OK worker" in out_w


def test_monitors_and_host_memory():
    from paddle_tpu.device import monitor as M
    M.monitor_reset("t")
    M.monitor_add("t", 10)
    M.monitor_add("t", -2)
    st = M.monitor_get("t")
    assert st == {"sum": 8, "count": 2, "min": -2, "max": 10}
    assert M.monitor_get("missing") is None
    assert M.host_memory_rss() > 0
    assert M.host_memory_peak() >= M.host_memory_rss() // 2
