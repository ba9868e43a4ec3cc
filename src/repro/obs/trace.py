"""Nested host span tracer → Chrome trace-event JSON (Perfetto-loadable).

One ``SpanTracer`` instance per run.  ``with tracer.span("gather"):``
times a host phase; spans nest naturally (a ``span`` opened inside
another span renders as its child in Perfetto, because complete-"X"
events on one track nest by time containment).  The tracer ALWAYS times
— even disabled it accumulates per-phase durations, which is how
``PopulationRunner`` keeps its ``host_s``/``round_s`` accounting and how
the telemetry round events get their ``wall.phases`` breakdown — but it
only *records* Chrome trace events when ``enabled=True``, so the
disabled tracer costs two ``perf_counter`` calls, a dict add and an
inactive ``TraceMe`` per span.

Every span also opens ``jax.profiler.TraceAnnotation(name)`` around its
body, under the span's exact name.  With no profiler session running
that is a no-op; whenever one is (``TelemetryConfig(jax_profile=True)``,
or any ``jax.profiler.trace``), the program's host spans land in the
same ``.xplane.pb`` as the device ops, on one clock, so Perfetto or
TensorBoard shows each idle gap under the host span that caused it.

``count(name, n)`` adds ``n`` to a named whole-run counter (bytes moved,
say), enabled or not; ``counts()`` returns them.  Counters are kept
apart from the span seconds: ``totals()`` and ``pop_round()`` (and so
``wall.phases``) hold span names only.  When enabled, each ``count``
also appends a Chrome counter event (``"ph": "C"``) carrying the
counter's running total.

Span-name convention (used by every runner; see docs/observability.md):

    round             whole-round wrapper (population runner)
    sample            cohort sampling (population) / host batch draw (cohort)
    plan              StalenessTracker round plan (population)
    gather            store gather + global overlay + device_put / batch stack
      gather.settle   the last round's pending rows of the clients sampled
                      again, written first; counter gather.settled_rows
      gather.take     one store slot's np.take into its staging buffer
                      (args: slot); counter gather.bytes
    encode            codec PRNG key build (host side of the compressed uplink)
    device-step       the ONE fused compiled round dispatch + its block
      device-step.draw   the cohort's batch draw, ghost rows and stacking
      scatter         the last round's results written into the store while
                      the device runs this round (PopulationStore.settle);
                      counter scatter.deferred_rows
        scatter.pull  the wait for one slot's started device→host copies
                      (args: slot)
        scatter.write the row writes of one slot into the store (args: slot)
      device-step.wait   block_until_ready on the round's outputs
    scatter           this round's device→host copies started and kept
                      pending + the new global's row noted; counter
                      scatter.bytes
    ledger            channel reports + CommLedger append
    eval              fused cohort eval dispatch
    checkpoint        round-level checkpoint save

The dotted children are the population runner's and its store's
(``fl/population.py``); a parent's self time (the parent less its
children) is what is left: zeroing, the global overlay, the
``device_put`` enqueues, the dispatch, the start of the result copies.

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
from typing import Dict, List

from jax.profiler import TraceAnnotation


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
        self._round_acc: Dict[str, float] = {}   # since last pop_round()
        self._total_acc: Dict[str, float] = {}   # whole run
        self._counts: Dict[str, float] = {}      # whole run, apart from spans

    @contextmanager
    def span(self, name: str, **args):
        start = time.perf_counter()
        sp = Span(name, start)
        try:
            # the name only: the .xplane.pb event is named exactly this
            with TraceAnnotation(name):
                yield sp
        finally:
            end = time.perf_counter()
            sp.dur = end - start
            self._round_acc[name] = self._round_acc.get(name, 0.0) + sp.dur
            self._total_acc[name] = self._total_acc.get(name, 0.0) + sp.dur
            if self.enabled:
                ev = {"name": name, "ph": "X", "pid": os.getpid(), "tid": 1,
                      "ts": (start - self._t0) * 1e6, "dur": sp.dur * 1e6}
                if args:
                    ev["args"] = args
                self._events.append(ev)

    def count(self, name: str, n: float) -> None:
        """Add ``n`` to the whole-run counter ``name`` (always); when
        enabled, also record a Chrome counter event of its running total."""
        total = self._counts.get(name, 0) + n
        self._counts[name] = total
        if self.enabled:
            self._events.append(
                {"name": name, "ph": "C", "pid": os.getpid(), "tid": 1,
                 "ts": (time.perf_counter() - self._t0) * 1e6,
                 "args": {"value": total}})

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

    def counts(self) -> Dict[str, float]:
        """Whole-run counter totals (never reset; no span names)."""
        return dict(self._counts)

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
