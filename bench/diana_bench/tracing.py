"""From a JAX profiler trace to device busy time and idle gaps.

``collect`` reads the ``.xplane.pb`` the profiler wrote and keeps only
what the reduction needs, as plain lists (this is also the format of the
recorded fixture the tests use):

    {"window": [start_ns, end_ns],
     "device": {plane name: [[start_ns, dur_ns, op name], ...]},
     "host": [[annotation name, start_ns, dur_ns], ...]}

``window`` is the harness's own ``window`` annotation; device ops are the
events of each TPU plane's "XLA Ops" line; host spans are the harness's
annotations (``place_batch``, ``complete``, ``sleep``, ...).

``reduce`` then gives:

* ``busy_s``: the union of device-op intervals inside the window,
  averaged over the chips that have an op line;
* ``window_s``: the window's length;
* ``device_ops``: the ten ops with the most device time in the window;
* ``idle_gaps``: the device's idle time in the window, split by the host
  annotation that was open at each instant (the innermost one, by
  latest start), the ten largest; idle time under no annotation is
  ``unannotated``.
"""
from __future__ import annotations

import glob
import os
from typing import Iterable, Optional

__all__ = ["OP_LINE", "collect", "describe", "reduce", "union_ns"]

OP_LINE = "XLA Ops"
WINDOW = "window"


def collect(log_dir: str, annotations: Iterable[str]) -> Optional[dict]:
    """Read the newest trace under ``log_dir``; None when there is none."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        return None
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    wanted = set(annotations) | {WINDOW}
    out: dict = {"window": None, "device": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OP_LINE:
                    out["device"][plane.name] = [
                        [e.start_ns, e.duration_ns, e.name] for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name not in wanted:
                        continue
                    if e.name == WINDOW:
                        out["window"] = [e.start_ns, e.start_ns + e.duration_ns]
                    else:
                        out["host"].append([e.name, e.start_ns, e.duration_ns])
    return out if out["window"] is not None else None


def describe(trace: Optional[dict]) -> str:
    """What a collected trace holds, for the message of a traced run in
    which no device op fell inside the window."""
    if not trace:
        return "no trace, or no window annotation in it"
    lo, hi = trace["window"]
    parts = [f"window [{lo}, {hi}] ns"]
    for plane, events in trace["device"].items():
        if events:
            first = min(s for s, _, _ in events)
            last = max(s + d for s, d, _ in events)
            parts.append(f"{plane}: {len(events)} ops in [{first}, {last}] ns")
        else:
            parts.append(f"{plane}: no ops")
    if not trace["device"]:
        parts.append(f"no {OP_LINE!r} line on any TPU plane")
    return "; ".join(parts)


def union_ns(intervals: Iterable[tuple[float, float]], lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _complement(busy: list, lo: float, hi: float) -> list:
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def _labelled_segments(host: list, lo: float, hi: float) -> list:
    """[start, end, label] pieces of [lo, hi), each labelled with the
    innermost (latest-started) open annotation, or ``unannotated``."""
    bounds = []
    for k, (name, s, d) in enumerate(host):
        bounds.append((s, 1, k))
        bounds.append((s + d, 0, k))
    bounds.sort()
    open_: list[int] = []
    segs, t = [], lo
    for x, kind, k in bounds:
        x = min(max(x, lo), hi)
        if x > t:
            segs.append([t, x, host[open_[-1]][0] if open_ else "unannotated"])
            t = x
        if kind:
            open_.append(k)
        else:
            open_.remove(k)
    if t < hi:
        segs.append([t, hi, "unannotated"])
    return segs


def _split_by_annotation(gaps: list, host: list, lo: float, hi: float) -> dict[str, float]:
    """Idle nanoseconds per innermost open host annotation."""
    out: dict[str, float] = {}
    segs = _labelled_segments(host, lo, hi)
    i = 0
    for a, b in gaps:
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < b:
            s, e, label = segs[j]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                out[label] = out.get(label, 0.0) + overlap
            j += 1
    return out


def reduce(trace: dict) -> dict:
    lo, hi = trace["window"]
    per_chip_busy, ops = [], {}
    busy_all: list = []
    for events in trace["device"].values():
        busy = union_ns(((s, s + d) for s, d, _ in events), lo, hi)
        per_chip_busy.append(sum(e - s for s, e in busy))
        busy_all = busy if not busy_all else union_ns(
            [tuple(x) for x in busy_all + busy], lo, hi
        )
        for s, d, name in events:
            clipped = min(s + d, hi) - max(s, lo)
            if clipped > 0:
                ops[name] = ops.get(name, 0.0) + clipped
    busy_ns = sum(per_chip_busy) / len(per_chip_busy) if per_chip_busy else 0.0
    idle = _split_by_annotation(_complement(busy_all, lo, hi), trace["host"], lo, hi)
    top = lambda d: [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": top(ops),
        "idle_gaps": top(idle),
    }
