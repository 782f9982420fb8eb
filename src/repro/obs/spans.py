"""Read the stream's spans and the scheduler's device scopes from a trace.

A `jax.profiler` trace of a running stream holds, on one clock, the host's
``stream.*`` annotations (`repro.obs.profile.span`, with their counters as
arguments) and every operation the device ran, each under the op name the
program traced it with, whose path carries the ``sched.*`` and
``stream.insert_rows`` scopes (`repro.obs.profile.SCOPES`).  `load` reads
both from the newest ``.xplane.pb`` under a directory; `summarize` reduces
them over the trace's window (its first event's start to its last event's
end):

* ``spans``: per span name, its count and total and mean milliseconds;
  ``insert_ms``, the mean of ``stream.build`` + ``stream.insert`` over
  the rounds that insert;
* ``scopes``: per scope, the device seconds under it: the union of its
  ops' intervals (a scope's time includes the scopes nested in it, as a
  loop's includes its body), averaged over the devices; ``busy_s``, the
  union of every op; ``covered_s``, the union of the ops under any scope;
* ``boundary_idle_share``: the share of the window in which no op runs on
  any device while the host is inside ``stream.boundary``;
  ``unspanned_idle_share``: the share in which no op runs and the host is
  in no ``stream.*`` span;
* ``idle_gaps``: the ten longest stretches with no op on any device, each
  named by the innermost ``stream.*`` span it lies in (`innermost`;
  ``unspanned`` where none overlaps it).

On a TPU an op event names only its HLO instruction, so the scopes are
read from the compiled programs' texts, where each instruction carries
``metadata={op_name=...}``:

    python -m repro.obs.spans <trace_dir> --hlo <program text> ...
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from repro.obs.profile import SCOPES

#: a device plane's line of executed operations, and of whole programs
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"',
                    re.M)

Interval = Tuple[float, float]


class Span(NamedTuple):
    name: str
    start: float            # ns, on the trace's clock
    end: float
    args: Dict[str, int]


class Op(NamedTuple):
    device: str             # the device plane's name
    start: float
    end: float
    op_name: str            # the traced op's name path ("" if unknown)


def op_names(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """``(module, {instruction: op_name})`` of a compiled program's text
    (``jitted.lower(...).compile().as_text()``, or XLA's text dump)."""
    module = re.search(r"HloModule ([\w.\-]+)", hlo_text)
    if module is None:
        raise ValueError("not an HLO module's text: no 'HloModule' line")
    return module.group(1), dict(_INSTR.findall(hlo_text))


def hlo_name(event_name: str) -> str:
    """An op event's HLO instruction name: ``%fusion.9 = s32[2] fusion(..``
    gives ``fusion.9``."""
    head = event_name[:200]
    cut = head.find(" = ")
    return (head[:cut] if cut > 0 else head).lstrip("%")


def load(trace_dir: str, programs: Iterable[str] = ()
         ) -> Tuple[List[Span], List[Op]]:
    """The ``stream.*`` spans and the device ops of the newest
    ``.xplane.pb`` under ``trace_dir``.

    A TPU op event carries only its HLO instruction; its op name is looked
    up in ``programs``, the texts of the compiled programs, by the program
    whose run (the device's ``XLA Modules`` line) holds the op.  Ops of
    other programs keep an empty op name."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    known = dict(op_names(text) for text in programs)
    spans: List[Span] = []
    ops: List[Op] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:"):
            if OP_LINE in lines:
                ops += name_ops(
                    plane.name,
                    [(e.name, e.start_ns, e.duration_ns)
                     for e in (lines[MODULE_LINE].events
                               if MODULE_LINE in lines else ())],
                    [(e.name, e.start_ns, e.duration_ns)
                     for e in lines[OP_LINE].events], known)
            continue
        for line in lines.values():
            for e in line.events:
                if e.name.startswith("stream."):
                    start = float(e.start_ns)
                    spans.append(Span(e.name, start,
                                      start + float(e.duration_ns),
                                      {k: int(v) for k, v in e.stats
                                       if isinstance(v, int)}))
    return spans, ops


def name_ops(device: str, runs, events,
             known: Dict[str, Dict[str, str]]) -> List[Op]:
    """``Op`` s of one device from its program runs and op events, each
    ``(name, start_ns, duration_ns)``: an op takes the op name its
    instruction has in the program whose run began last before it
    (a run is named ``<module>(<id>)``)."""
    runs = sorted((float(s), name.split("(")[0]) for name, s, _ in runs)
    starts = [s for s, _ in runs]
    out = []
    for name, start, dur in events:
        start = float(start)
        at = bisect.bisect_right(starts, start) - 1
        names = known.get(runs[at][1], {}) if at >= 0 else {}
        out.append(Op(device, start, start + float(dur),
                      names.get(hlo_name(name), "")))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Total length of the intersection of two unions."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of ``[lo, hi]`` that the union ``busy`` leaves."""
    out, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            out.append((prev, min(s, hi)))
        prev = max(prev, e)
    return out


def innermost(spans: List[Span], lo: float, hi: float) -> str:
    """The span a gap ``[lo, hi]`` lies in: from the span that overlaps it
    most (the outermost on a tie), down through the nested span that
    overlaps it most, to one with no nested span over the gap."""
    def ov(sp: Span) -> float:
        return min(sp.end, hi) - max(sp.start, lo)

    over = [sp for sp in spans if ov(sp) > 0]
    if not over:
        return "unspanned"
    best = max(over, key=lambda sp: (ov(sp), sp.end - sp.start))
    while True:
        inner = [sp for sp in over if sp is not best
                 and best.start <= sp.start and sp.end <= best.end]
        if not inner:
            return best.name
        best = max(inner, key=lambda sp: (ov(sp), sp.end - sp.start))


def summarize(spans: List[Span], ops: List[Op]) -> Optional[dict]:
    """The window's span statistics, device time per scope and idle
    shares; None when the trace holds no device op."""
    if not ops:
        return None
    lo = min([o.start for o in ops] + [s.start for s in spans])
    hi = max([o.end for o in ops] + [s.end for s in spans])
    window = hi - lo
    devices = sorted({o.device for o in ops})
    n_dev = len(devices)

    path = {name: frozenset(name.split("/"))
            for name in {o.op_name for o in ops}}
    parts = [path[o.op_name] for o in ops]

    def device_time(pick) -> float:
        """Device seconds under the ops ``pick`` takes (by their op name's
        path components), union per device, mean over devices."""
        return sum(sum(e - s for s, e in union(
            (o.start, o.end) for o, p in zip(ops, parts)
            if o.device == d and pick(p)))
            for d in devices) / n_dev / 1e9

    scope_s = {sc: device_time(lambda p, sc=sc: sc in p) for sc in SCOPES}
    busy_s = device_time(lambda p: True)
    covered_s = device_time(lambda p: not p.isdisjoint(SCOPES))

    stats: Dict[str, dict] = {}
    for sp in spans:
        st = stats.setdefault(sp.name, {"count": 0, "total_ms": 0.0})
        st["count"] += 1
        st["total_ms"] += (sp.end - sp.start) / 1e6
    for st in stats.values():
        st["mean_ms"] = st["total_ms"] / st["count"]
    build = stats.get("stream.build", {"total_ms": 0.0})
    insert = stats.get("stream.insert")
    insert_ms = ((build["total_ms"] + insert["total_ms"]) / insert["count"]
                 if insert else None)

    idle = gaps(union((o.start, o.end) for o in ops), lo, hi)
    boundary = union((s.start, s.end) for s in spans
                     if s.name == "stream.boundary")
    spanned = union((s.start, s.end) for s in spans)
    idle_s = sum(e - s for s, e in idle)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": window / 1e9,
        "devices": n_dev,
        "spans": stats,
        "insert_ms": insert_ms,
        "busy_s": busy_s,
        "covered_s": covered_s,
        "scopes": scope_s,
        "boundary_idle_share": overlap(idle, boundary) / window,
        "unspanned_idle_share": (idle_s - overlap(idle, spanned)) / window,
        "idle_gaps": [[innermost(spans, s, e), (e - s) / 1e9]
                      for s, e in longest],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--hlo", nargs="*", default=(),
                    help="text files of the compiled programs the trace ran")
    args = ap.parse_args(argv)
    texts = [open(path).read() for path in args.hlo]
    print(json.dumps(summarize(*load(args.trace_dir, texts)), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
