"""Reduce a profiler trace of the benchmark's window to device numbers.

The trace is JAX's ``.xplane.pb``.  Device planes are named
``/device:<platform>:<n>``; their ``XLA Ops`` line holds one event per
executed HLO operation (``XLA Modules`` where a plane has no op line).
Op events are named by their HLO text (``%<name> = <shape> <op>(...)``),
kept as the name alone.
Host planes hold the runtime's events and the benchmark's annotations,
``bench.<section>`` (`drive.RoundClock`), on the same clock.  From them:

* the window: the first event's start to the last event's end (the trace
  is stopped by a timer, `drive.Tracer`, so it may end inside a round);
* ``busy_s``: the union of a device's op intervals inside the window,
  averaged over the devices that ran any op; ``idle_share`` = 1 - busy
  over the window;
* ``sort_s``: device time in HLO ``sort`` operations (named ``sort`` or
  ``sort.<n>``), averaged the same way;
* ``device_ops``: the ten operations (by HLO name) that took most device
  time; a loop's time includes that of the operations inside it;
* ``idle_gaps``: the ten longest stretches with no op on any device, each
  named by the ``bench.*`` section the host was in and the host event that
  overlapped it most (``host.untraced`` where no host event was recorded:
  Python work outside any span).
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, str, str, float, float]   # plane, line, name, start, dur
OP_LINES = ("XLA Ops", "XLA Modules")


def op_name(text: str) -> str:
    """The HLO name of an op event: ``%sort.3 = s32[8] sort(...)`` gives
    ``sort.3``.  XLA names an instruction after its opcode, so a sort
    is ``sort`` or ``sort.<n>`` unless it was fused."""
    head = text[:200]
    cut = head.find(" = ")
    return (head[:cut] if cut > 0 else head).lstrip("%")


def load_events(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: List[Event] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            device_op = plane.name.startswith("/device:") and \
                line.name in OP_LINES
            for e in line.events:
                name = op_name(e.name) if device_op else e.name
                out.append((plane.name, line.name, name,
                            float(e.start_ns), float(e.duration_ns)))
    return out


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s: float, e: float, lo: float, hi: float) -> Tuple[float, float]:
    return max(s, lo), min(e, hi)


def device_op_events(events: List[Event]) -> Dict[str, List[Event]]:
    """Per device plane, the events of its op line."""
    by_plane: Dict[str, Dict[str, List[Event]]] = {}
    for ev in events:
        plane, line = ev[0], ev[1]
        if plane.startswith("/device:") and line in OP_LINES:
            by_plane.setdefault(plane, {}).setdefault(line, []).append(ev)
    out = {}
    for plane, lines in by_plane.items():
        for name in OP_LINES:
            if lines.get(name):
                out[plane] = lines[name]
                break
    return out


def reduce_events(events: List[Event]) -> Optional[dict]:
    """The window's device numbers; None when the trace holds no window
    or no device operation."""
    ops = device_op_events(events)
    if not ops or not events:
        return None
    lo = min(e[3] for e in events)
    hi = max(e[3] + e[4] for e in events)
    window = hi - lo
    busy, sort, per_op = [], [], {}
    all_busy = []
    for plane, evs in ops.items():
        spans = []
        sort_ns = 0.0
        for _, _, text, s, d in evs:
            cs, ce = _clip(s, s + d, lo, hi)
            if ce <= cs:
                continue
            spans.append((cs, ce))
            name = op_name(text)
            per_op[name] = per_op.get(name, 0.0) + (ce - cs)
            if name.split(".")[0] == "sort":
                sort_ns += ce - cs
        merged = _union(spans)
        busy.append(sum(e - s for s, e in merged))
        sort.append(sort_ns)
        all_busy.extend(spans)
    n_dev = len(busy)
    busy_ns = sum(busy) / n_dev
    if busy_ns <= 0 or hi <= lo:
        return None
    gaps = []
    prev = lo
    for s, e in _union(all_busy) + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    host = [(n, s, s + d) for p, _, n, s, d in events
            if not p.startswith("/device:") and d > 0]
    idle = []
    for gs, ge in gaps:
        idle.append([_label(host, gs, ge), (ge - gs) / 1e9])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window / 1e9,
        "idle_share": 1.0 - busy_ns / window,
        "sort_s": (sum(sort) / n_dev / 1e9) if any(sort) else None,
        "devices": n_dev,
        "device_ops": [[name, (ns / n_dev) / 1e9] for name, ns in top],
        "idle_gaps": idle,
    }


def _label(host, gs: float, ge: float) -> str:
    """``<bench section>: <host event>`` overlapping the gap the most."""
    best = {True: ("", 0.0), False: ("", 0.0)}
    for name, s, e in host:
        ov = min(e, ge) - max(s, gs)
        if ov <= 0:
            continue
        mine = name.startswith("bench.")
        if ov > best[mine][1]:
            best[mine] = (name, ov)
    parts = [p for p in (best[True][0], best[False][0]) if p]
    return ": ".join(parts) or "host.untraced"


def reduce_trace(trace_dir: str) -> Optional[dict]:
    return reduce_events(load_events(trace_dir))
