"""Shared plumbing of a benchmark run: the compile cache, the compile
meter, device checks and small statistics."""
from __future__ import annotations

import os
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
OUT = CHECKOUT / "experiments" / "bench"
CACHE_DIR = OUT / "jax_cache"


def use_checkout_cache(jax) -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, for every program however fast it compiles, so that only a
    cell's first run in a checkout compiles. Set before anything is
    jitted; the environment variable makes the program's own
    ``enable_compile_cache`` find the same directory."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileMeter:
    """Backend compiles (count and seconds) and persistent-cache hits and
    misses, from JAX's own monitoring events; a cache hit's compile event
    times the read."""

    def __init__(self, jax):
        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"compile_s": self.secs, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


def check_traffic(traffic: dict, keys) -> None:
    """Raise unless ``traffic`` holds exactly the keys its driver reads, so
    that a traffic file cannot ask for behaviour the driver lacks."""
    extra = sorted(set(traffic) - set(keys))
    missing = sorted(set(keys) - set(traffic))
    if extra or missing:
        raise ValueError(f"traffic keys the driver does not read: {extra}; "
                         f"keys it needs: {missing}")


def memory_peak(devices) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def trace_dir(cell: str) -> Path:
    """Fixed per-cell directory for a traced run's profile; emptied first
    so that a run reads its own trace and the disk holds one."""
    d = OUT / "trace" / cell
    if d.exists():
        import shutil
        shutil.rmtree(d)
    d.mkdir(parents=True)
    return d
