"""Host-side timing of the streaming engine: the ``stream.*`` spans and the
section hook.

`span` is the one mechanism.  It opens a `jax.profiler.TraceAnnotation`,
which records nothing unless a profiler runs and, when one does, lands on
the host plane on the device ops' clock, with the span's counters as the
event's arguments.  `core.engine.simulate_stream` opens these spans, one
set per round, nested as indented::

    stream.round
      stream.feed          pull the due arrivals from the iterator
      stream.boundary      the host boundary (finished, inserted, deferred, live)
        stream.read_back   the blocking device_get of the table
        stream.compact     find and archive finished rows
        stream.build       the padded arrival block, slots and valid, built
                           on the host, packed, put on the device in one
                           transfer (rows, h2d_bytes)
        stream.insert      the insert_packed dispatch
      stream.segment       the segment (t0, ticks, fresh)
        stream.dispatch    the runner call
        stream.wait        where the host blocks on the segment
    stream.extract         the final merge (once per stream)

The jitted code names its device work with `jax.named_scope` (``sched.*``,
``stream.insert_rows``, listed in `SCOPES`), which lands in each op's
``op_name`` metadata; `repro.obs.spans` reads both from a trace.

The section hook is `simulate_stream`'s optional ``profile``: any object
with a ``section(name)`` context manager, such as `ProfileTimers` or the
chip benchmark's round clock.  It receives exactly three sections:
``compaction`` (the span ``stream.boundary``), ``compile`` or ``dispatch``
(the span ``stream.segment``, ending in ``block_until_ready``; ``compile``
when the segment runner was built in this call).  `ProfileTimers`
accumulates wall time per section; the scale bench
(`benchmarks.bench_sched_scale`) reports its totals.  Sections nest (each
level is charged its own wall time, so nested sections double-count by
design — they answer "how long was this section open", not "exclusive
self time").
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, Iterator

import jax

#: the device scopes of the jitted code, in the order a tick runs them
SCOPES = ("sched.queue_order", "sched.admit", "sched.place_nodes",
          "sched.plan_evictions", "sched.victim_order", "sched.node_victims",
          "sched.place_checkpoints", "sched.capture", "stream.insert_rows")


@contextmanager
def span(name: str, profile=None, section: str | None = None,
         **counters: int) -> Iterator[jax.profiler.TraceAnnotation]:
    """Open the annotation ``name`` with ``counters`` as its arguments and,
    when ``profile`` is given, the hook's ``section`` inside it.  Yields
    the annotation, so counters known only at the span's end can be set
    with ``set_metadata``."""
    with jax.profiler.TraceAnnotation(name, **counters) as note:
        if profile is None or section is None:
            yield note
        else:
            with profile.section(section):
                yield note


class ProfileTimers:
    """Accumulates ``(total_seconds, calls)`` per named section."""

    def __init__(self) -> None:
        self.total_s: Dict[str, float] = {}
        self.calls: Dict[str, int] = {}

    @contextmanager
    def section(self, name: str) -> Iterator[None]:
        start = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - start
            self.total_s[name] = self.total_s.get(name, 0.0) + dt
            self.calls[name] = self.calls.get(name, 0) + 1

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """``{section: {"total_s": ..., "calls": ...}}`` — JSON-ready."""
        return {
            name: {"total_s": self.total_s[name], "calls": self.calls[name]}
            for name in sorted(self.total_s)
        }

    def clear(self) -> None:
        self.total_s.clear()
        self.calls.clear()
