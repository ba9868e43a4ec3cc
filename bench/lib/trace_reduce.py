"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

What it reads, and from where:

- the traced window: the first host event named ``window`` (a
  ``jax.profiler.TraceAnnotation`` the harness opens around the traced
  work), on the profiler's clock;
- device work: the op events of each device plane (``/device:TPU:<n>``),
  from its ``XLA Ops`` line. The device's clock is not the host's: each
  plane is shifted onto the host clock by the smallest lag between the end
  of a program run (``XLA Modules`` events, stat ``run_id``) and the host's
  ``CompleteCallbacks`` event for the same run, since a run cannot end
  after the host has seen it complete;
- host spans: every other host event whose name the caller lists
  (harness and program ``TraceAnnotation`` names), so that an idle gap on
  the device can be named by what the host was doing in it.

Busy time is the union of a device's op intervals inside the window, and
the idle share is 1 minus busy over the window; both are averaged over the
devices. Nothing here is specific to one cell: kernels are found by a
substring of their event names.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir`` (the profiler writes
    ``plugins/profile/<time>/<host>.xplane.pb``)."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:")


def _stats(ev) -> Dict:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its HLO text
    (``%lora_matmul.1 = f32[8,768]{...} custom-call(...)``): the op's own
    name is what stands before `` = ``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _clock_offsets(host_done: Dict[Tuple[int, int], float],
                   plane) -> float:
    """Nanoseconds to add to ``plane``'s timestamps to put them on the host
    clock (see the module docstring); 0 where no run can be matched."""
    m = re.search(r"(\d+)$", plane.name)
    ordinal = int(m.group(1)) if m else 0
    lags = []
    for line in plane.lines:
        if line.name != "XLA Modules":
            continue
        for ev in line.events:
            rid = _stats(ev).get("run_id")
            done = host_done.get((ordinal, rid))
            if done is None:
                done = host_done.get((-1, rid))
            if done is not None:
                lags.append(done - (ev.start_ns + ev.duration_ns))
    return min(lags) if lags else 0.0


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds per op name, each op's time less that of the ops nested in
    it (a ``while`` op's event spans the ops of its body)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []          # [end, name, own time]

    def close(item):
        out[item[1]] += item[2] * 1e-9

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, name, e - s])
    while stack:
        close(stack.pop())
    return out


def reduce_trace(path: str, *, window: str = "bench-window",
                 host_spans: Sequence[str] = (),
                 kernels: Sequence[str] = ()) -> Dict:
    """Reduce one trace file.

    Returns seconds: ``window_s``; ``busy_s`` (mean over devices);
    ``busy_by_device``; ``ops`` (device self time per op name: less the
    ops nested in it, summed over devices and divided by their number);
    ``kernel_s`` (device time of the ops whose name holds each of
    ``kernels``, summed the same way,
    with their event counts in ``kernel_calls``); ``idle_by_span`` (idle
    device time named by the innermost listed host span open at the gap's
    midpoint, ``"(no span)"`` where none was); ``n_devices``."""
    pd = load(path)
    host_events: List[Tuple[float, float, str]] = []
    host_done: Dict[Tuple[int, int], float] = {}
    win: Optional[Interval] = None
    wanted = set(host_spans)
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window and win is None:
                        win = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in wanted:
                        host_events.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name))
                    elif ev.name == "CompleteCallbacks":
                        st = _stats(ev)
                        key = (st.get("device_ordinal", -1), st.get("run_id"))
                        host_done[key] = min(host_done.get(key, ev.start_ns),
                                             ev.start_ns)
        elif _is_device(plane.name):
            devices.append(plane)
    if win is None:
        raise ValueError(f"trace {path} has no host event named {window!r}")
    if not devices:
        raise ValueError(f"trace {path} has no device plane")
    lo, hi = win
    busy_by_device = []
    ops: Dict[str, float] = defaultdict(float)
    kernel_s: Dict[str, float] = defaultdict(float)
    kernel_calls: Dict[str, int] = defaultdict(int)
    idle_by_span: Dict[str, float] = defaultdict(float)
    nd = len(devices)
    offsets = []
    for plane in devices:
        shift = _clock_offsets(host_done, plane)
        offsets.append(shift)
        events = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s0 = ev.start_ns + shift
                iv = _clip(s0, s0 + ev.duration_ns, lo, hi)
                if iv is not None:
                    events.append((iv[0], iv[1], op_name(ev.name)))
        spans = [(s, e) for s, e, _ in events]
        for name, secs in _self_times(events).items():
            ops[name] += secs / nd
            for k in kernels:
                if k in name:
                    kernel_s[k] += secs / nd
        for _, _, name in events:
            for k in kernels:
                if k in name:
                    kernel_calls[k] += 1
        busy = _union(spans)
        busy_by_device.append(sum(e - s for s, e in busy) * 1e-9)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            inner = None
            for s, e, name in host_events:
                if s <= mid <= e and (inner is None or s >= inner[0]):
                    inner = (s, name)
            key = inner[1] if inner else "(no span)"
            idle_by_span[key] += (g1 - g0) * 1e-9 / nd
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_by_device) / nd,
        "busy_by_device": busy_by_device,
        "n_devices": nd,
        "clock_offset_ns": offsets,
        "ops": dict(ops),
        "kernel_s": {k: kernel_s.get(k, 0.0) for k in kernels},
        "kernel_calls": {k: kernel_calls.get(k, 0) for k in kernels},
        "idle_by_span": dict(idle_by_span),
    }


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    """The ``n`` largest entries of a name -> seconds map, largest first."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def describe(path: str, n_events: int = 5) -> str:
    """A plain-text listing of a trace's planes, lines and first events
    (for looking at a trace by hand before writing code against it)."""
    pd = load(path)
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:n_events]:
                stats = {}
                try:
                    stats = dict(ev.stats)
                except Exception:
                    pass
                out.append(f"    {ev.name!r} start {ev.start_ns:.0f} ns "
                           f"dur {ev.duration_ns:.0f} ns {stats}")
    return "\n".join(out)
