"""Drivers of the two timed entry points: `engine.simulate_stream` and
`engine.simulate_batch`.

Each driver warms up every program its window will use, at the cell's
own shapes, runs the window, and hands back what the metric readers and
the check need: the window's clock readings, the per-round sections, the
program's output and the reference's verdict on it.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from contextlib import contextmanager
from types import ModuleType
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import numpy as np

from parts import reference_of, shares_of, traffic_of
from reference.runs import (batch_draw, references, sweep_cells,
                            window_rounds)
from repro.core import engine, omfs_jax
from repro.core.crcost import MIB, CRCostModel, TieredCRCostModel
from repro.core.types import Job, JobClass, SchedulerConfig, User

#: JAX's event for one XLA (and Mosaic) compile of a top-level program
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Seconds of backend compilation JAX reports, split at a mark: what
    compiled before the window (set-up) and what compiled inside it."""

    def __init__(self):
        self.before_s = 0.0
        self.inside = 0
        self.window_open = False

    def __call__(self, event: str, duration: float, **_) -> None:
        if event != COMPILE_EVENT:
            return
        if self.window_open:
            self.inside += 1
        else:
            self.before_s += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)
        return False


class RoundClock:
    """The stream's section hook (`simulate_stream`'s ``profile``).

    ``simulate_stream`` opens ``compaction`` for each round's host
    boundary and ``compile`` or ``dispatch`` for its segment, which ends
    in ``block_until_ready``.  The clock keeps each section's start and
    end, wraps it in a profiler annotation (``bench.<section>``) so that a
    trace can tell what the host did, and calls ``on_round(r)`` as round
    ``r`` (1-based) starts."""

    def __init__(self, on_round: Callable[[int], None] = lambda r: None):
        self.on_round = on_round
        self.rounds: List[Dict[str, float]] = []

    @contextmanager
    def section(self, name: str):
        if name == "compaction":
            self.rounds.append({})
            self.on_round(len(self.rounds))
        rec = self.rounds[-1]
        start = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        finally:
            end = time.perf_counter()
            key = "boundary" if name == "compaction" else "segment"
            rec[key + "_start"], rec[key + "_end"] = start, end


def scheduler_config(config: dict) -> SchedulerConfig:
    """The program's `SchedulerConfig` for a configuration file: a flat
    cost per checkpoint, the C/R tiers where the file has them, then each
    key of its ``scheduler`` block as it stands."""
    kw = {}
    if config.get("cr_tiers"):
        tiers = config["cr_tiers"]
        kw["cr_tiers"] = TieredCRCostModel(
            tiers=tuple(CRCostModel(**{k: v for k, v in t.items()
                                       if k != "capacity_mib"})
                        for t in tiers),
            capacity_mib=tuple(int(t["capacity_mib"]) for t in tiers))
    kw.update(cpu_total=int(config["cpu_total"]),
              quantum=int(config["quantum"]),
              cr_overhead=int(config["cr_overhead"]))
    fields = {f.name for f in dataclasses.fields(SchedulerConfig)}
    for key, value in config.get("scheduler", {}).items():
        if key not in fields:
            raise ValueError(f"configuration {config.get('name')!r}: "
                             f"scheduler key {key!r} is not a field of "
                             f"SchedulerConfig")
        kw[key] = value
    return SchedulerConfig(**kw)


def users_of(config: dict) -> List[User]:
    return [User(f"u{i}", s) for i, s in enumerate(shares_of(config))]


class Parts(NamedTuple):
    """What a configuration file brings to a run (`parts`)."""
    generate: Callable
    reference: ModuleType
    cfg: SchedulerConfig
    users: List[User]


def parts_of(config: dict) -> Parts:
    return Parts(traffic_of(config), reference_of(config),
                 scheduler_config(config), users_of(config))


def to_jobs(cols: Dict[str, np.ndarray]) -> List[Job]:
    """The program's `Job` objects for generated columns (ids in order)."""
    return [Job(user=f"u{u}", cpus=int(c), work=int(w), priority=int(p),
                job_class=JobClass(int(k)), submit_time=int(s),
                state_bytes=int(m) * MIB, id=i)
            for i, (u, c, w, p, k, s, m) in enumerate(zip(
                cols["user"], cols["cpus"], cols["work"], cols["priority"],
                cols["jclass"], cols["submit"], cols["state_mib"]))]


def table_columns(tbl, compared: Sequence[str]) -> Dict[str, np.ndarray]:
    host = jax.device_get(tbl)
    return {f: np.asarray(getattr(host, f)) for f in ("jid", *compared)}


def device_peak(devices) -> Optional[int]:
    peaks = [d.memory_stats().get("peak_bytes_in_use")
             for d in devices if d.memory_stats()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Tracer:
    """Profiles ``seconds`` of the window into ``trace_dir``, from
    ``delay`` seconds after `start`, or from `begin` where that comes
    first: the drivers call it as the window's last round starts, so that
    a window shorter than ``delay`` still leaves a trace.

    A round of the large cells runs for seconds, and the device records an
    event per operation of its loops, so the trace is started and stopped
    by timers rather than at round boundaries: a longer trace overflows
    the device's event buffer and reads as idle time."""

    def __init__(self, trace_dir: Optional[str], seconds: float,
                 delay: float = 0.0):
        self.dir = trace_dir
        self.seconds = seconds
        self.delay = delay
        self.timers: List[threading.Timer] = []
        self.lock = threading.Lock()
        self.state = "idle"

    def _after(self, seconds: float, fn: Callable[[], None]) -> None:
        timer = threading.Timer(seconds, fn)
        self.timers.append(timer)
        timer.start()

    def start(self) -> None:
        with self.lock:
            if self.dir is not None and not self.timers:
                self._after(self.delay, self.begin)

    def begin(self) -> None:
        if self.state != "idle":
            # begun already: `stop` holds the lock while it writes the
            # trace, which can outlast the window, and no round waits on it
            return
        with self.lock:
            if self.dir is not None and self.state == "idle":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0     # annotations only
                jax.profiler.start_trace(self.dir, profiler_options=opts)
                self.state = "running"
                self._after(self.seconds, self.stop)

    def stop(self) -> None:
        with self.lock:
            if self.state == "running":
                jax.profiler.stop_trace()
            self.state = "done"

    def close(self) -> None:
        self.stop()
        with self.lock:
            timers = list(self.timers)
        for t in timers:
            t.cancel()
            t.join()


def round_counts(submit: np.ndarray, seg: int, rounds: int) -> np.ndarray:
    """Jobs due at each of ``rounds`` boundaries of ``seg``-tick segments."""
    return np.bincount(submit // seg, minlength=rounds)[:rounds]


def run_stream(config: dict, work: dict, seed: int, seconds: float,
               trace_dir: Optional[str], t_process: float) -> dict:
    """One `simulate_stream` call.  Round 1 inserts the standing queue and
    the next ``warm_rounds`` let the running set turn over; all of that is
    set-up, and runs every program the window runs.  The window is every
    later round."""
    generate, ref_mod, cfg, users = parts_of(config)
    seg = int(work["segment_len"])
    warm_rounds = int(work["warm_rounds"])
    rounds = window_rounds(work, seconds)
    total = 1 + warm_rounds + rounds
    horizon = seg * total
    kw = dict(capacity=int(config["capacity"]), segment_len=seg,
              pass_depth=int(config["pass_depth"]))
    policy = work["policy"]

    with CompileLog() as compiles:
        cols = generate(config, work, seed, horizon)
        jobs = to_jobs(cols)
        t_warm = time.perf_counter()
        # round 1 and the warm rounds compile the segment program and the
        # insert; here the boundary's eager table builds are warmed for
        # every arrival count a later round brings
        counts = round_counts(cols["submit"], seg, total)
        for k in sorted(set(counts[1:].tolist()) - {0}):
            block, _ = omfs_jax.table_from_jobs(jobs[:k], users,
                                                cfg.cpu_total, cfg)
            jax.block_until_ready(omfs_jax.pad_table(block, kw["capacity"]))
        t_call = time.perf_counter()
        tracer = Tracer(trace_dir, float(work["trace_seconds"]),
                        float(work["trace_start_s"]))

        def on_round(r: int) -> None:
            if r == 2 + warm_rounds:
                compiles.window_open = True
                tracer.start()
            if r == total:
                tracer.begin()

        clock = RoundClock(on_round)
        try:
            res = engine.simulate_stream(users, iter(jobs), cfg, horizon,
                                         policy, profile=clock, **kw)
        finally:
            tracer.close()
    rec = clock.rounds
    window = rec[1 + warm_rounds:]
    out = {
        "setup_s": window[0]["boundary_start"] - t_process,
        "window_s": window[-1]["segment_end"] - window[0]["boundary_start"],
        "ticks": seg * len(window),
        "round_s": [r["segment_end"] - r["boundary_start"] for r in window],
        "boundary_s": [r["boundary_end"] - r["boundary_start"]
                       for r in window],
        "segment_s": [r["segment_end"] - r["segment_start"] for r in window],
        "compile_s": compiles.before_s,
        "compiles_in_window": compiles.inside,
        "setup_parts": {
            "before_warmup_s": t_warm - t_process,
            "warmup_s": t_call - t_warm,
            "round1_s": rec[0]["segment_end"] - rec[0]["boundary_start"],
            "warm_rounds_s": [r["segment_end"] - r["boundary_start"]
                              for r in rec[1:1 + warm_rounds]]},
        "attempted": int(counts[1 + warm_rounds:].sum()),
        "failed": int(res.stream_stats["deferrals"]),
    }
    out["memory_peak_bytes"] = device_peak(jax.local_devices())
    program = table_columns(res.table, ref_mod.COMPARED)
    busy = np.asarray(res.busy)
    stats = dict(res.stream_stats)
    del res
    out["preemptions"] = int(program["n_preempt"].sum())
    out["ticks_total"] = horizon

    [(ref, ref_stats)] = references(config, work, seed, seconds)
    out["checks"] = {
        "table_mismatches": (ref_mod.mismatches(program, ref.table()), 0),
        "busy_mismatches": (int((busy != np.asarray(ref.busy)).sum())
                            + abs(busy.size - len(ref.busy)), 0),
        "stream_count_mismatches": (sum(
            abs(int(stats[k]) - ref_stats[k])
            for k in ("inserted", "deferrals", "dropped")), 0),
    }
    return out


def run_batch(config: dict, work: dict, seed: int, seconds: float,
              trace_dir: Optional[str], t_process: float) -> dict:
    """A fixed number of `simulate_batch` calls over the knob grid, each
    on its own draw of the traffic (`batch_draw`: every draw fills the
    job table, so one program serves them all); a first call on another
    draw is set-up."""
    generate, ref_mod, cfg, users = parts_of(config)
    horizon = int(work["horizon"])
    grid = sweep_cells(work)
    n_calls = window_rounds(work, seconds)

    def cells_of(cols):
        jobs = to_jobs(cols)
        return [engine.BatchCell(users=users, jobs=jobs, policy=p,
                                 quantum=q, pass_depth=d)
                for p, q, d in grid]

    calls: List[dict] = []
    outputs = []
    with CompileLog() as compiles:
        warm = engine.simulate_batch(
            cells_of(batch_draw(generate, config, work, seed, 1 << 20)),
            cfg, horizon)
        jax.block_until_ready([r.table for r in warm])
        del warm
        inputs = [cells_of(batch_draw(generate, config, work, seed, r))
                  for r in range(n_calls)]
        # the trace holds the window's last call whole
        tracer = Tracer(trace_dir, float(work["trace_seconds"]))
        compiles.window_open = True
        t0 = time.perf_counter()
        try:
            for i, cells in enumerate(inputs):
                if i == n_calls - 1:
                    tracer.begin()
                start = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.simulate_batch"):
                    results = engine.simulate_batch(cells, cfg, horizon)
                    jax.block_until_ready([x.table for x in results])
                calls.append({"start": start, "end": time.perf_counter()})
                outputs.append(results)
        finally:
            tracer.close()
    del inputs
    out = {
        "setup_s": t0 - t_process,
        "window_s": calls[-1]["end"] - t0,
        "ticks": n_calls * len(grid) * horizon,
        "round_s": [c["end"] - c["start"] for c in calls],
        "compile_s": compiles.before_s,
        "compiles_in_window": compiles.inside,
        "attempted": n_calls * len(grid),
    }
    out["memory_peak_bytes"] = device_peak(jax.local_devices())
    n_dev = len(jax.local_devices())
    spread = min(len(x.table.cpus.sharding.device_set)
                 for results in outputs for x in results)
    checked = [[(table_columns(x.table, ref_mod.COMPARED), np.asarray(x.busy))
                for x in results] for results in outputs]
    del outputs
    out["preemptions"] = int(sum(t["n_preempt"].sum() for c in checked
                                 for t, _ in c))
    out["ticks_total"] = out["ticks"]

    bad_cells, table_bad, busy_bad = 0, 0, 0
    refs = references(config, work, seed, seconds)
    for (program, busy), (ref, _) in zip(
            (c for per_cell in checked for c in per_cell), refs):
        tb = ref_mod.mismatches(program, ref.table())
        bb = int((busy != np.asarray(ref.busy)).sum())
        table_bad += tb
        busy_bad += bb
        bad_cells += (tb + bb) > 0
    out["failed"] = bad_cells
    out["checks"] = {
        "table_mismatches": (table_bad, 0),
        "busy_mismatches": (busy_bad, 0),
        "devices_unused": (n_dev - spread, 0),
    }
    return out


ENTRIES = {"stream": run_stream, "batch": run_batch}
