"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read: device-busy seconds, per-operation seconds and
the longest idle gaps.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A
device plane is named ``/device:TPU:<n>``; its line ``XLA Ops`` holds
one event per executed operation (nested where an operation such as a
``while`` contains others). Busy time is the UNION of that line's event
intervals, so nesting counts once; an operation's seconds are its SELF
time (its duration minus what its children cover), so the per-operation
table adds up to the busy time instead of counting a loop body twice.
Host spans the benchmark wrote with ``jax.profiler.TraceAnnotation``
(names starting ``bench.``) are on the same clock: each idle gap is
named after the innermost such span that covers its start.

The arithmetic works on plain ``(name, start_ns, duration_ns)`` tuples
so a test can hold it to a hand-made trace.
"""

from __future__ import annotations

import glob
import os
import re

Event = tuple[str, float, float]  # name, start_ns, duration_ns

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench."


_HLO_RE = re.compile(r"^(%[^\s=]+) = (\S+?)(?:\{\S*)? ")


def short_name(name: str) -> str:
    """An XLA op event is named by its whole HLO line; keep the
    instruction's name and result shape: ``%copy.63 s32[262144,2]``."""
    m = _HLO_RE.match(name)
    if not m:
        return name[:80]
    shape = "(tuple)" if m.group(2).startswith("(") else m.group(2)
    return f"{m.group(1)} {shape}"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_planes(path: str, lines_seen: list | None = None
                ) -> tuple[dict[str, list[Event]], list[Event]]:
    """``({device plane name: its XLA Ops events}, host annotations)``.
    ``lines_seen`` collects every ``plane/line`` name, for the log."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if lines_seen is not None:
            lines_seen.extend(f"{plane.name}/{ln.name}" for ln in plane.lines)
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(ANNOTATION_PREFIX))
    return devices, host


def busy_intervals(events: list[Event]) -> list[tuple[float, float]]:
    """Union of the events' intervals: sorted, disjoint (start, end)."""
    out: list[tuple[float, float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def self_seconds(events: list[Event]) -> dict[str, float]:
    """Seconds per operation name, each event counted for its SELF time
    (duration minus the events nested directly inside it)."""
    total: dict[str, float] = {}
    stack: list[list] = []  # [name, end_ns, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            total[name] = total.get(name, 0.0) + max(self_ns, 0.0) / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return total


def idle_gaps(busy: list[tuple[float, float]], t0: float, t1: float,
              host: list[Event]) -> dict[str, float]:
    """Idle seconds inside ``[t0, t1]`` by what the host was doing: each
    gap goes to the innermost ``bench.`` span covering its start, or to
    ``unattributed``."""
    gaps: list[tuple[float, float]] = []
    cursor = t0
    for start, end in busy:
        if start > cursor:
            gaps.append((cursor, min(start, t1)))
        cursor = max(cursor, end)
    if cursor < t1:
        gaps.append((cursor, t1))
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        cover = [(dur, name) for name, start, dur in host
                 if start <= g0 < start + dur]
        name = min(cover)[1] if cover else "unattributed"
        out[name] = out.get(name, 0.0) + (g1 - g0) / 1e9
    return out


def reduce_events(devices: dict[str, list[Event]], host: list[Event],
                  window_s: float) -> dict:
    """The summary the layer metrics read. ``busy_s`` is the average
    over the device planes; the operation and gap tables are of the
    busiest one. The traced window is taken to end at the last device
    event and to be ``window_s`` long (the host's clock timed it)."""
    if not devices:
        return {"devices": 0, "busy_s": 0.0, "window_s": window_s,
                "device_ops": [], "idle_gaps": []}
    per_dev = {}
    for name, events in devices.items():
        busy = busy_intervals(events)
        per_dev[name] = (sum(e - s for s, e in busy) / 1e9, busy, events)
    busy_s = sum(v[0] for v in per_dev.values()) / len(per_dev)
    _, busy, events = max(per_dev.values(), key=lambda v: v[0])
    t1 = busy[-1][1] if busy else 0.0
    t0 = min(t1 - window_s * 1e9, busy[0][0]) if busy else 0.0
    ops = sorted(self_seconds(events).items(), key=lambda kv: -kv[1])
    gaps = sorted(idle_gaps(busy, t0, t1, host).items(),
                  key=lambda kv: -kv[1])
    return {"devices": len(per_dev), "busy_s": busy_s, "window_s": window_s,
            "n_events": len(events),
            "device_ops": [[short_name(k), v] for k, v in ops[:10]],
            "idle_gaps": [[k, v] for k, v in gaps[:10]]}


def reduce_trace(trace_dir: str, window_s: float) -> dict:
    lines_seen: list[str] = []
    path = find_xplane(trace_dir)
    devices, host = read_planes(path, lines_seen)
    out = reduce_events(devices, host, window_s)
    out["lines_seen"] = [n for n in lines_seen if not n.startswith("/host:")
                         or "python" in n][:40]
    out["trace_bytes"] = os.path.getsize(path)
    return out
