"""The device this run is on, its published peaks, and the compile cache."""
from __future__ import annotations

# Published peaks, keyed by jax's device_kind. One table; a kind that is
# not here is an error, never a default. (The bf16 row is bench.py's
# `_peak()` table, copied: the yardstick does not import the program's.)
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}


class NoAccelerator(RuntimeError):
    pass


def describe() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require(chips: int) -> dict:
    """The device line of a result; raises unless JAX sits on at least
    `chips` TPU chips whose kind the peak table knows."""
    dev = describe()
    if dev["platform"] != "tpu":
        raise NoAccelerator(f"the benchmark measures a TPU; JAX reports {dev}")
    if dev["count"] < chips:
        raise NoAccelerator(f"the cell needs {chips} chip(s); JAX reports "
                            f"{dev}")
    peak(dev["kind"])
    return dev


def peak(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peak for device_kind {kind!r}: add a "
                       f"row with its source to benchmark/harness/device.py")
    return PEAKS[kind]


def memory_peak_bytes(chips: int) -> int:
    """peak_bytes_in_use on the fullest of the first `chips` devices."""
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()[:chips]]
    return max(peaks)


def enable_compile_cache() -> str:
    """JAX's persistent cache where the program's own rule puts it:
    `JAX_COMPILATION_CACHE_DIR` if set, else `<checkout>/.jax_cache`.
    Every program is kept, however quick its compile, so that a second
    run of a cell compiles nothing."""
    from paddle_tpu.utils.compile_cache import enable_compile_cache as on
    return on(min_compile_secs=0.0)
