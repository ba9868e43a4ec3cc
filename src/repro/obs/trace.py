"""Nested host span tracer → Chrome trace-event JSON (Perfetto-loadable).

One ``SpanTracer`` instance per run.  ``with tracer.span("gather"):``
times a host phase; spans nest naturally (a ``span`` opened inside
another span renders as its child in Perfetto, because complete-"X"
events on one track nest by time containment).  The tracer ALWAYS times
— even disabled it accumulates per-phase durations, which is how
``PopulationRunner`` keeps its ``host_s``/``round_s`` accounting and how
the telemetry round events get their ``wall.phases`` breakdown — but it
only *records* Chrome trace events when ``enabled=True``, so the
disabled tracer costs two ``perf_counter`` calls and a dict add per
span.

Span-name convention (used by every runner; see docs/observability.md):

    round        whole-round wrapper (population runner)
    sample       cohort sampling (population) / host batch draw (cohort)
    plan         StalenessTracker round plan (population)
    gather       store gather + global overlay + device_put / batch stack
    encode       codec PRNG key build (host side of the compressed uplink)
    device-step  the ONE fused compiled round dispatch (+block_until_ready)
    scatter      device→store writeback + global snapshot
    ledger       channel reports + CommLedger append
    eval         fused cohort eval dispatch
    checkpoint   round-level checkpoint save

``chrome_trace()``/``write()`` emit the standard
``{"traceEvents": [...]}`` JSON object format: load the file in
https://ui.perfetto.dev (or chrome://tracing) directly.

``jax_profile_start``/``jax_profile_stop`` bracket the run with
``jax.profiler`` for device-side traces (TensorBoard/Perfetto); a
profiler that fails to start or stop raises.
"""
from __future__ import annotations

import json
import os
import time
from contextlib import contextmanager
from typing import Dict, List, Optional


class Span:
    """Handle yielded by ``SpanTracer.span``: ``dur`` (seconds) is set
    when the ``with`` block exits."""

    __slots__ = ("name", "start", "dur")

    def __init__(self, name: str, start: float):
        self.name = name
        self.start = start
        self.dur = 0.0


class SpanTracer:
    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self._t0 = time.perf_counter()
        self._events: List[Dict] = []
        self._depth = 0
        self._round_acc: Dict[str, float] = {}   # since last pop_round()
        self._total_acc: Dict[str, float] = {}   # whole run

    @contextmanager
    def span(self, name: str, **args):
        start = time.perf_counter()
        sp = Span(name, start)
        self._depth += 1
        try:
            yield sp
        finally:
            end = time.perf_counter()
            self._depth -= 1
            sp.dur = end - start
            self._round_acc[name] = self._round_acc.get(name, 0.0) + sp.dur
            self._total_acc[name] = self._total_acc.get(name, 0.0) + sp.dur
            if self.enabled:
                ev = {"name": name, "ph": "X", "pid": os.getpid(), "tid": 1,
                      "ts": (start - self._t0) * 1e6, "dur": sp.dur * 1e6}
                if args:
                    ev["args"] = args
                self._events.append(ev)

    # ---- per-round / whole-run accounting ---------------------------------

    def pop_round(self) -> Dict[str, float]:
        """Per-span-name seconds accumulated since the last call (the
        telemetry round event's ``wall.phases``) — and reset."""
        out = {k: float(v) for k, v in self._round_acc.items()}
        self._round_acc = {}
        return out

    def totals(self) -> Dict[str, float]:
        """Whole-run per-span-name seconds (never reset)."""
        return {k: float(v) for k, v in self._total_acc.items()}

    # ---- Chrome trace-event JSON ------------------------------------------

    def chrome_trace(self) -> Dict:
        return {"traceEvents": list(self._events), "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        """Atomic write (tmp + replace) so a kill mid-dump never leaves a
        truncated trace next to a valid event stream."""
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.chrome_trace(), f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)


# ---------------------------------------------------------------------------
# optional jax.profiler bracket (device-side traces)
# ---------------------------------------------------------------------------


def jax_profile_start(out_dir: str) -> None:
    """``jax.profiler.start_trace`` into ``out_dir``.  A profiler that
    cannot start raises: a run asked to trace the device never carries on
    without the trace."""
    import jax
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)


def jax_profile_stop() -> None:
    import jax
    jax.profiler.stop_trace()
