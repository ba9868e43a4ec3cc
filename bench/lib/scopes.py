"""Device time by ``jax.named_scope``, from a profiler trace
(``.xplane.pb``).

A named scope opened in the program lands in the ``tf_op`` stat of each
op's event metadata in the trace (``jit(serve_step)/while/body/moe/gmm/
...``), which ``jax.profiler.ProfileData`` does not expose. This module
reads the event metadata of each device plane straight from the protobuf
(message classes built here from the XPlane schema's field numbers, so
nothing beyond ``protobuf`` is imported), and joins it with the op events
that ``lib/trace_reduce.py`` reads: the same ``bench-window``, the same
device clock put on the host's, the same self times. An op lies under a
scope when the scope's components appear consecutively in its ``tf_op``
path, the components of JAX's transforms set aside (``moe/gmm`` holds
``moe/gmm/...`` and ``moe/while/body/closed_call/gmm/...``; ``moe`` holds
``moe/route/...`` and ``moe/shared/...``). A fusion carries the ``tf_op``
of its root op.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Sequence, Tuple

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

from lib import trace_reduce

_F = descriptor_pb2.FieldDescriptorProto


def _schema():
    """XSpace -> XPlane -> event_metadata / stat_metadata, as far as the
    metadata goes (field numbers of tsl's ``xplane.proto``)."""
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                            package="bench_xplane",
                                            syntax="proto3")

    def message(name, fields):
        m = fd.message_type.add(name=name)
        for fname, num, typ, ref, rep in fields:
            f = m.field.add(name=fname, number=num, type=typ,
                            label=_F.LABEL_REPEATED if rep
                            else _F.LABEL_OPTIONAL)
            if ref:
                f.type_name = ".bench_xplane." + ref
        return m

    message("XStat", [("metadata_id", 1, _F.TYPE_INT64, None, 0),
                      ("double_value", 2, _F.TYPE_DOUBLE, None, 0),
                      ("uint64_value", 3, _F.TYPE_UINT64, None, 0),
                      ("int64_value", 4, _F.TYPE_INT64, None, 0),
                      ("str_value", 5, _F.TYPE_STRING, None, 0),
                      ("bytes_value", 6, _F.TYPE_BYTES, None, 0),
                      ("ref_value", 7, _F.TYPE_UINT64, None, 0)])
    message("XEventMetadata", [("id", 1, _F.TYPE_INT64, None, 0),
                               ("name", 2, _F.TYPE_STRING, None, 0),
                               ("stats", 5, _F.TYPE_MESSAGE, "XStat", 1)])
    message("XStatMetadata", [("id", 1, _F.TYPE_INT64, None, 0),
                              ("name", 2, _F.TYPE_STRING, None, 0)])
    plane = message("XPlane", [("id", 1, _F.TYPE_INT64, None, 0),
                               ("name", 2, _F.TYPE_STRING, None, 0)])
    for fname, num, value in (("event_metadata", 4, "XEventMetadata"),
                              ("stat_metadata", 5, "XStatMetadata")):
        entry = plane.nested_type.add(
            name="".join(w.title() for w in fname.split("_")) + "Entry")
        entry.options.map_entry = True
        entry.field.add(name="key", number=1, type=_F.TYPE_INT64,
                        label=_F.LABEL_OPTIONAL)
        entry.field.add(name="value", number=2, type=_F.TYPE_MESSAGE,
                        label=_F.LABEL_OPTIONAL,
                        type_name=".bench_xplane." + value)
        plane.field.add(name=fname, number=num, type=_F.TYPE_MESSAGE,
                        label=_F.LABEL_REPEATED,
                        type_name=".bench_xplane.XPlane." + entry.name)
    message("XSpace", [("planes", 1, _F.TYPE_MESSAGE, "XPlane", 1)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


_XSPACE = _schema()


def op_metadata(path: str) -> Dict[str, Dict[str, Tuple[str, str]]]:
    """Device plane name -> {op event name: (tf_op, hlo_category)}."""
    with open(path, "rb") as fh:
        space = _XSPACE.FromString(fh.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        ops = {}
        for md in plane.event_metadata.values():
            st = {names.get(s.metadata_id): s.str_value for s in md.stats}
            if st.get("tf_op"):
                ops[md.name] = (st["tf_op"], st.get("hlo_category", ""))
        out[plane.name] = ops
    return out


# components that JAX's transforms put between a scope and its children:
# a ``lax.map``/``scan`` body traces as ``while/body/closed_call``
_TRANSFORMS = ("while", "body", "closed_call", "cond", "checkpoint",
               "remat")


def under(tf_op: str, scope: str) -> bool:
    """Whether ``tf_op``'s path holds ``scope``'s components in a row,
    once the components of JAX's transforms are set aside."""
    parts = [p.rstrip(":") for p in tf_op.split("/")]
    parts = [p for p in parts if p not in _TRANSFORMS]
    want = scope.split("/")
    return any(parts[i:i + len(want)] == want
               for i in range(len(parts) - len(want) + 1))


def scope_times(path: str, scopes: Sequence[str], *,
                window: str = "bench-window") -> Dict:
    """Device self time inside ``window`` of the ops under each of
    ``scopes``, per device and averaged over devices (``scope_s``), and of
    the custom calls (Pallas kernels) among them (``kernel_s``, with their
    event counts ``kernel_calls``, summed over devices)."""
    meta = op_metadata(path)
    pd = trace_reduce.load(path)
    host_done, win, devices = {}, None, []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window and win is None:
                        win = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name == "CompleteCallbacks":
                        st = trace_reduce._stats(ev)
                        key = (st.get("device_ordinal", -1), st.get("run_id"))
                        host_done[key] = min(host_done.get(key, ev.start_ns),
                                             ev.start_ns)
        elif plane.name in meta:
            devices.append(plane)
    if win is None or not devices:
        return {}
    lo, hi = win
    by_device = []
    kernel_s = defaultdict(float)
    kernel_calls = defaultdict(int)
    for plane in devices:
        ops = meta[plane.name]
        shift = trace_reduce._clock_offsets(host_done, plane)
        events = []
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                s0 = ev.start_ns + shift
                iv = trace_reduce._clip(s0, s0 + ev.duration_ns, lo, hi)
                if iv is not None:
                    events.append((iv[0], iv[1], ev.name))
        own = trace_reduce._self_times(events)
        counts = defaultdict(int)
        for _, _, name in events:
            counts[name] += 1
        per = {s: 0.0 for s in scopes}
        for name, secs in own.items():
            tf_op, cat = ops.get(name, ("", ""))
            for s in scopes:
                if tf_op and under(tf_op, s):
                    per[s] += secs
                    if cat == "custom-call":
                        kernel_s[s] += secs
                        kernel_calls[s] += counts[name]
        by_device.append(per)
    n = len(by_device)
    return {"scope_s": {s: sum(d[s] for d in by_device) / n for s in scopes},
            "by_device": by_device,
            "kernel_s": {s: kernel_s.get(s, 0.0) for s in scopes},
            "kernel_calls": {s: kernel_calls.get(s, 0) for s in scopes}}
