"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small plain structure: the device's operations and the host's
annotations, each as (name, start ns, duration ns, stats).  ``reduce``
then works on that structure alone, so a recorded trace checked in
with the tests exercises exactly what a chip run does:

  busy_s, window_s   union of the device's operations inside the traced
                     window, averaged over the chips; the window's length
  kernels            device time and operand bytes of each kernel's
                     events, found by the name of the instruction the
                     kernel's wrapper gives it: by the patterns the
                     caller names (``%sorted_probe_pallas`` is
                     ``hash_probe``), and every other ``%<name>_pallas``
                     instruction under ``<name>``
  scopes             device time and events inside the window of each
                     stage of a fused chain: the first ``jax.named_scope``
                     of each operation's name stack, below the ``jit``
                     and the control flow JAX names itself; empty for a
                     plan of one UDF, which sets no scope
  spans              how many of each host span have their middle inside
                     the window
  device_ops         the operations that took most time
  idle_gaps          the longest stretches with no operation on the
                     device, each named by the innermost host span that
                     covered it

Host spans come from the profiler's own host plane (the benchmark's
``TraceAnnotation``s) and from the program's spans, which run on
``time.monotonic``.  Those are put on the profiler's clock by the
benchmark's marker: an annotation named ``bench.window`` whose start the
benchmark also read on the monotonic clock.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

MARKER = "bench.window"
# a Pallas kernel's instruction, named after its wrapper ``<name>_pallas``
PALLAS = re.compile(r"^%(\w+?)_pallas(?:\.\d+)? = ")
# components of a name stack that JAX itself adds: transformations
# (``jit(apply)``), control flow and its branches, and the primitive
NOT_A_SCOPE = re.compile(r"^(\w+\(.*\)|while|body|cond|branch_\d+_fun|"
                         r"scan|remat|checkpoint|closed_call|core_call|"
                         r"custom_\w+|pallas_call)$")
# lines of a device plane that hold operations rather than their modules
# or steps
_SKIP_LINES = ("XLA Modules", "Steps", "Framework Name Scope",
               "Source code", "XLA TraceMe")


def _xspace_class():
    """A message class that reads, of an ``XSpace`` (``xplane.proto``),
    each plane's name and its event and stat metadata: the fields that
    hold what ``ProfileData`` leaves out, the stats an operation's
    metadata carries.  Built from field numbers alone; the fields not
    named here are skipped."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    f = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace.proto", package="benchxs", syntax="proto3")

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, typ, ref in fields:
            field = m.field.add(name=fname, number=number, type=typ,
                                label=f.LABEL_REPEATED if ref and ref[0] == "*"
                                else f.LABEL_OPTIONAL)
            if ref:
                field.type_name = ".benchxs." + ref.lstrip("*")

    # names and string values are read as bytes: a stray byte that is
    # not UTF-8 then costs one name, not the whole trace
    msg, i64, u64, raw = (f.TYPE_MESSAGE, f.TYPE_INT64, f.TYPE_UINT64,
                          f.TYPE_BYTES)
    message("Stat", ("metadata_id", 1, i64, None),
            ("str_value", 5, raw, None), ("ref_value", 7, u64, None))
    message("EventMeta", ("name", 2, raw, None),
            ("stats", 5, msg, "*Stat"))
    message("StatMeta", ("name", 2, raw, None))
    message("EventMetaEntry", ("key", 1, i64, None),
            ("value", 2, msg, "EventMeta"))
    message("StatMetaEntry", ("key", 1, i64, None),
            ("value", 2, msg, "StatMeta"))
    message("Plane", ("name", 2, raw, None),
            ("event_metadata", 4, msg, "*EventMetaEntry"),
            ("stat_metadata", 5, msg, "*StatMetaEntry"))
    message("Space", ("planes", 1, msg, "*Plane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchxs.Space"))


def metadata_stat(data: bytes, stat: str) -> Dict[str, Dict[str, str]]:
    """Plane name -> operation name -> the string value of ``stat`` in
    the operation's metadata, for the device planes of a serialized
    ``XSpace``.  On a TPU the name stack of an operation (``tf_op``) is
    kept there, not on its events.  A file this cannot read gives no
    values, and the metrics that need them are left out."""
    from google.protobuf.message import DecodeError
    try:
        planes = _xspace_class().FromString(data).planes
    except DecodeError:
        return {}

    def text(b: bytes) -> str:
        return b.decode("utf-8", "replace")

    out: Dict[str, Dict[str, str]] = {}
    for plane in planes:
        if not plane.name.startswith(b"/device:"):
            continue
        names = {e.key: text(e.value.name) for e in plane.stat_metadata}
        found = out.setdefault(text(plane.name), {})
        for e in plane.event_metadata:
            for st in e.value.stats:
                if names.get(st.metadata_id) == stat:
                    found[text(e.value.name)] = (
                        text(st.str_value) or names.get(st.ref_value, ""))
    return out


def load(profile_dir: str) -> dict:
    """The plain structure of the newest ``.xplane.pb`` under a
    ``jax.profiler.trace`` directory.  An operation keeps its events'
    string stats and, from its metadata, its name stack (``tf_op``)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(paths[-1])
    with open(paths[-1], "rb") as f:
        stacks = metadata_stat(f.read(), "tf_op")
    devices: Dict[str, List] = {}
    host: List = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in _SKIP_LINES:
                    continue
                for e in line.events:
                    st = {k: v for k, v in e.stats
                          if isinstance(v, str) and k in (
                              "long_name", "hlo_module", "tf_op",
                              "hlo_category")}
                    tf_op = stacks.get(plane.name, {}).get(e.name)
                    if tf_op and "tf_op" not in st:
                        st["tf_op"] = tf_op
                    ops.append([e.name, int(e.start_ns), int(e.duration_ns),
                                st, line.name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"devices": devices, "host": host}


def save(norm: dict, path: str) -> None:
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(norm, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return json.load(f)


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Disjoint sorted cover of [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window(norm: dict) -> Tuple[int, int]:
    """The traced window on the profiler's clock: the marker's extent."""
    marks = [h for h in norm["host"] if h[0] == MARKER]
    if not marks:
        raise ValueError(f"trace holds no {MARKER} annotation")
    _, s, d = marks[0]
    return s, s + d


def to_trace_clock(spans: Sequence[dict], marker_mono: float,
                   marker_ns: int) -> List[Tuple[str, int, int]]:
    """Program spans (``t0``, ``dur`` in monotonic seconds) on the
    profiler's clock."""
    out = []
    for sp in spans:
        s = marker_ns + int(round((sp["t0"] - marker_mono) * 1e9))
        out.append((sp["name"], s, s + int(round(sp["dur"] * 1e9))))
    return out


def scope_of(op_name: str) -> Optional[str]:
    """The first ``jax.named_scope`` of an operation's name stack
    (``jit(apply)/q4_nearby_monuments/while/body/add`` gives
    ``q4_nearby_monuments``); None where the stack holds none.  The last
    component is the primitive, never a scope."""
    for part in op_name.split("/")[:-1]:
        if part and not NOT_A_SCOPE.match(part):
            return part
    return None


def _covering(spans, t: int) -> Optional[str]:
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None


def reduce(norm: dict, kernels: Dict[str, str],
           program_spans: Sequence[Tuple[str, int, int]] = (),
           top: int = 10) -> dict:
    """``kernels``: kernel name -> regular expression searched in an
    operation's name and HLO text; an operation none of them matches is
    accounted by ``PALLAS`` under its kernel's own name."""
    lo, hi = window(norm)
    win = hi - lo
    host_spans = [(n, s, s + d) for n, s, d in norm["host"] if n != MARKER]
    spans = host_spans + list(program_spans)
    busy_total = 0
    op_time: Dict[str, int] = {}
    kern: Dict[str, dict] = {k: {"time_ns": 0, "events": 0, "bytes": 0}
                             for k in kernels}
    pats = {k: re.compile(p) for k, p in kernels.items()}
    scopes: Dict[str, dict] = {}
    gaps: List[Tuple[int, str]] = []
    for ops in norm["devices"].values():
        clipped = []
        for name, s, d, st, _line in ops:
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            clipped.append((a, b))
            op_time[name] = op_time.get(name, 0) + (b - a)
            text = st.get("long_name") or name
            found = [k for k, p in pats.items() if p.search(text)]
            if not found:
                m = PALLAS.search(text)
                found = [m.group(1)] if m else []
            for k in found:
                acc = kern.setdefault(k, {"time_ns": 0, "events": 0,
                                          "bytes": 0})
                acc["time_ns"] += b - a
                acc["events"] += 1
                acc["bytes"] += hlo_bytes(text)
            scope = scope_of(st.get("tf_op", ""))
            if scope:
                acc = scopes.setdefault(scope, {"time_ns": 0, "events": 0})
                acc["time_ns"] += b - a
                acc["events"] += 1
        cover = union(clipped)
        busy_total += sum(e - s for s, e in cover)
        prev = lo
        for s, e in cover + [(hi, hi)]:
            if s > prev:
                mid = (prev + s) // 2
                gaps.append((s - prev, _covering(spans, mid) or "no span"))
            prev = max(prev, e)
    n_dev = max(1, len(norm["devices"]))
    gaps.sort(key=lambda g: -g[0])
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    counts: Dict[str, int] = {}
    for name, s, e in spans:
        if lo <= (s + e) // 2 < hi:
            counts[name] = counts.get(name, 0) + 1
    return {
        "devices": len(norm["devices"]),
        "busy_s": busy_total / n_dev / 1e9,
        "window_s": win / 1e9,
        "kernels": kern,
        "scopes": scopes,
        "spans": counts,
        "device_ops": [[n, t / 1e9] for n, t in ops_top],
        "idle_gaps": [[n, g / 1e9] for g, n in gaps[:top]],
    }


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_SHAPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f(?:16|32|64))"
                    r"\[([0-9,]*)\]")


def hlo_bytes(text: str) -> int:
    """Bytes of the arrays an HLO instruction writes and reads: its
    result and its operands, at the call's shapes.  The attributes after
    the operand list (layout constraints among them) repeat shapes and
    are not counted."""
    total = 0
    head = text.split(", custom_call_target=")[0]
    for dt, dims in _SHAPE.findall(head):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total
