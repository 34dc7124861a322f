"""The chip: its peaks, the compile cache, compile counting, memory."""
from __future__ import annotations

import contextlib
from pathlib import Path

from bench.harness.spec import REFERENCE_DEVICES, ROOT

# Published peaks per chip, keyed by `device_kind` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM, 16 GB HBM). A kind missing here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

# Fixed path inside the checkout: the directory is part of what the cache
# finds again, so it never moves.
CACHE_DIR = ROOT / ".jax_cache"


class NoAccelerator(RuntimeError):
    pass


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no peak table entry for device kind {kind!r}")
    return PEAKS[kind]


def use_cache(cache_dir: Path = CACHE_DIR) -> str:
    """Persistent compilation cache for every compile, eager ops included,
    at a fixed directory of the checkout."""
    import jax
    cache_dir.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(cache_dir)


def require_accelerator(chips: int):
    """The devices of a TPU with at least `chips` chips, or NoAccelerator."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


@contextlib.contextmanager
def reference_device(dtype, where: str = "host"):
    """Where a reference runs in `dtype`. Float32 runs on the host's CPU
    (`where="host"`: exact, and quick to compile where the TPU compiler
    takes minutes and tens of GB for a float32 convolution at "highest"
    precision) or on the chip at "highest" matmul precision
    (`where="chip"`: for a family too large for the host); a
    lower-precision control runs on the chip at the default precision."""
    import jax
    import jax.numpy as jnp
    if where not in REFERENCE_DEVICES:
        raise ValueError(f"reference device {where!r}; known: "
                         f"{REFERENCE_DEVICES}")
    if jnp.dtype(dtype) != jnp.float32:
        with jax.default_device(jax.devices()[0]):
            yield
    elif where == "host":
        with jax.default_device(jax.devices("cpu")[0]):
            yield
    else:
        with jax.default_device(jax.devices()[0]), \
                jax.default_matmul_precision("highest"):
            yield


def device_info(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


class CompileMeter:
    """Backend compiles (persistent-cache loads included) and their
    seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.secs, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.secs, self.compiles, self.hits

    def since(self, snap) -> dict:
        s, c, h = snap
        return {"compile_s": self.secs - s, "compiles": self.compiles - c,
                "cache_hits": self.hits - h}
