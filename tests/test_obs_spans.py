"""The stream's spans and the scheduler's device scopes.

`engine.simulate_stream` opens the ``stream.*`` spans of
`repro.obs.profile.span` every round, with the round's counters, and the
jitted code names its work with ``sched.*`` scopes; `repro.obs.spans`
reads both back from a trace.  Neither changes a result: the section hook
still receives its three sections, and a traced run is bit-identical to
an untraced one.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, omfs_jax
from repro.core.crcost import UNBOUNDED, CRCostModel, TieredCRCostModel
from repro.core.types import Job, JobClass, SchedulerConfig, User
from repro.core.workload import arrival_stream
from repro.obs import ProfileTimers, spans as obs_spans
from repro.obs.profile import SCOPES

CAPACITY = 12


def _conveyor():
    """Ten times more jobs than slots, so boundaries archive finished rows
    and insert arrivals, and periodic claims from A go through eviction."""
    users = [User("A", 50.0), User("B", 50.0)]
    jobs = [Job(user="B", cpus=4, work=8, priority=i % 4,
                job_class=JobClass.CHECKPOINTABLE,
                submit_time=3 * i, state_bytes=(64 + i % 5) << 20)
            for i in range(10 * CAPACITY)]
    jobs += [Job(user="A", cpus=8, work=6,
                 job_class=JobClass.CHECKPOINTABLE,
                 submit_time=25 + 30 * k, state_bytes=32 << 20)
             for k in range(10)]
    return users, jobs, 30 * CAPACITY + 60


def _tiered_cfg():
    tiers = TieredCRCostModel(
        tiers=(CRCostModel(save_mib_per_tick=256, restore_mib_per_tick=256),
               CRCostModel(save_mib_per_tick=32, restore_mib_per_tick=32,
                           save_base=1, restore_base=1)),
        capacity_mib=(64, UNBOUNDED))
    return SchedulerConfig(cpu_total=16, quantum=2, cr_overhead=1,
                           cr_tiers=tiers)


def _stream(profile=None):
    users, jobs, horizon = _conveyor()   # 420 ticks: 26 segments and 4
    cfg = SchedulerConfig(cpu_total=16, quantum=2, cr_overhead=1)
    return engine.simulate_stream(users, arrival_stream(jobs), cfg, horizon,
                                  capacity=CAPACITY, segment_len=16,
                                  profile=profile)


def _children(parent, spans):
    return [s for s in spans if s is not parent
            and parent.start <= s.start and s.end <= parent.end]


def _direct(parent, spans):
    """The spans nested in ``parent`` and in none of its other children."""
    inner = _children(parent, spans)
    return [s for s in inner
            if not any(o is not s and o.start <= s.start and s.end <= o.end
                       for o in inner)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One stream under the profiler with the section hook, and the same
    stream with neither."""
    plain = _stream()
    timers = ProfileTimers()
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(trace_dir):
        res = _stream(profile=timers)
    spans, _ = obs_spans.load(trace_dir)
    return plain, res, timers, spans


def test_stream_spans_nest_per_round_with_counters(traced):
    _, res, _, spans = traced
    stats = res.stream_stats
    rounds = [s for s in spans if s.name == "stream.round"]
    assert len(rounds) == stats["segments"] > 4
    inserted = 0
    for r in rounds:
        kids = _direct(r, spans)
        assert sorted(s.name for s in kids) == [
            "stream.boundary", "stream.feed", "stream.segment"]
        boundary = next(s for s in kids if s.name == "stream.boundary")
        segment = next(s for s in kids if s.name == "stream.segment")
        assert set(boundary.args) == {"finished", "inserted", "deferred",
                                      "live"}
        assert set(segment.args) == {"t0", "ticks", "fresh"}
        assert segment.args["ticks"] == min(16, 420 - segment.args["t0"])
        inner = sorted(s.name for s in _direct(boundary, spans))
        if boundary.args["inserted"] or boundary.args["finished"]:
            assert inner == ["stream.build", "stream.compact",
                             "stream.insert", "stream.read_back"]
        else:
            assert inner == ["stream.compact", "stream.read_back"]
        assert boundary.args["live"] <= CAPACITY
        assert sorted(s.name for s in _direct(segment, spans)) == [
            "stream.dispatch", "stream.wait"]
        inserted += boundary.args["inserted"]
    assert inserted == stats["inserted"]
    assert sorted(s.args["t0"] for s in spans
                  if s.name == "stream.segment") == list(range(0, 420, 16))
    assert [s.name for s in spans].count("stream.extract") == 1


def test_stream_build_counts_rows_and_bytes(traced):
    """Each ``stream.build`` carries the arrivals it built and the bytes it
    put on the device: the padded block, ``slots`` and ``valid``, packed."""
    _, res, _, spans = traced
    builds = [s for s in spans if s.name == "stream.build"]
    assert builds and all(set(s.args) == {"rows", "h2d_bytes"}
                          for s in builds)
    assert sum(s.args["rows"] for s in builds) == \
        res.stream_stats["inserted"]
    users, _, _ = _conveyor()
    cfg = SchedulerConfig(cpu_total=16, quantum=2, cr_overhead=1)
    block, _ = omfs_jax.table_from_jobs([], users, cfg.cpu_total, cfg,
                                        rows=CAPACITY, host=True)
    want = 4 * CAPACITY * (sum(c[0].size for c in block if c is not None)
                           + 2)
    assert {s.args["h2d_bytes"] for s in builds} == {want}


def test_stream_hook_keeps_its_three_sections(traced):
    _, res, timers, _ = traced
    snap = timers.snapshot()
    assert set(snap) <= {"compaction", "compile", "dispatch"}
    segments = res.stream_stats["segments"]
    assert snap["compaction"]["calls"] == segments
    assert sum(snap[k]["calls"] for k in ("compile", "dispatch")
               if k in snap) == segments


def test_traced_stream_is_bit_identical(traced):
    plain, res, _, _ = traced
    assert omfs_jax.tables_equal(res.table, plain.table)
    assert np.array_equal(res.busy_series(), plain.busy_series())
    assert res.stream_stats == plain.stream_stats


def _compiled_scopes(lowered):
    text = lowered.compile().as_text()
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/")}


@pytest.mark.parametrize("policy", ["omfs", "backfill_cr"])
def test_segment_program_carries_sched_scopes(policy):
    users = [User("A", 50.0), User("B", 50.0)]
    cfg = _tiered_cfg()
    empty, _ = omfs_jax.table_from_jobs([], users, cfg.cpu_total, cfg)
    tbl = omfs_jax.pad_table(empty, 8)
    ent = omfs_jax.entitlements(users, cfg.cpu_total)
    pass_fn = engine.POLICIES[policy].jax_factory(None)
    runner = engine._jitted_segment_runner(cfg, pass_fn, 4)
    found = _compiled_scopes(runner.lower(tbl, ent, jnp.int32(0)))
    assert {"sched.queue_order", "sched.admit", "sched.plan_evictions",
            "sched.victim_order", "sched.place_checkpoints"} <= found
    events = engine._jitted_segment_runner_events(cfg, pass_fn, 4, 64)
    assert "sched.capture" in _compiled_scopes(
        events.lower(tbl, ent, jnp.int32(0)))


def test_insert_rows_carries_its_scope():
    users = [User("A", 50.0)]
    cfg = SchedulerConfig(cpu_total=16, quantum=2)
    empty, _ = omfs_jax.table_from_jobs([], users, cfg.cpu_total, cfg)
    tbl = omfs_jax.pad_table(empty, 8)
    lowered = omfs_jax.insert_rows.lower(
        tbl, jnp.arange(8, dtype=jnp.int32), tbl, jnp.ones(8, bool))
    assert "stream.insert_rows" in _compiled_scopes(lowered)
    assert set(SCOPES) == {"sched.queue_order", "sched.admit",
                           "sched.place_nodes", "sched.plan_evictions",
                           "sched.victim_order", "sched.node_victims",
                           "sched.place_checkpoints", "sched.capture",
                           "stream.insert_rows"}


def test_insert_packed_carries_the_insert_scope():
    """The stream boundary's insert (`insert_packed`) runs `insert_rows`
    inside its program, so its ops keep the ``stream.insert_rows`` scope."""
    users = [User("A", 50.0)]
    cfg = SchedulerConfig(cpu_total=16, quantum=2)
    tbl, _ = omfs_jax.table_from_jobs([], users, cfg.cpu_total, cfg, rows=8)
    block, _ = omfs_jax.table_from_jobs([], users, cfg.cpu_total, cfg,
                                        rows=8, host=True)
    packed = omfs_jax.pack_insert(block, np.arange(8), np.ones(8, bool))
    lowered = omfs_jax.insert_packed.lower(tbl, jax.device_put(packed))
    assert "stream.insert_rows" in _compiled_scopes(lowered)


# -- the reader, on hand-made events (ns; one device unless named) --------

Span, Op = obs_spans.Span, obs_spans.Op
ADMIT = "jit(run)/while/body/sched.admit/while/body"


def _op(start, end, op_name="", device="/device:TPU:0"):
    return Op(device, float(start), float(end), op_name)


def _span(name, start, end, **args):
    return Span(name, float(start), float(end), args)


def test_summary_span_statistics():
    spans = [_span("stream.round", 0, 100), _span("stream.read_back", 0, 4),
             _span("stream.round", 100, 200),
             _span("stream.read_back", 100, 108),
             _span("stream.build", 110, 130), _span("stream.insert", 130, 140)]
    out = obs_spans.summarize(spans, [_op(50, 60)])
    assert out["spans"]["stream.read_back"] == {
        "count": 2, "total_ms": 12e-6, "mean_ms": 6e-6}
    assert out["spans"]["stream.round"]["count"] == 2
    # build + insert over the one round that inserts
    assert out["insert_ms"] == pytest.approx(30e-6)
    assert obs_spans.summarize([], [_op(0, 1)])["insert_ms"] is None
    assert obs_spans.summarize(spans, []) is None


def test_summary_scope_time_is_inclusive_and_averaged_over_devices():
    place = ADMIT + "/sched.plan_evictions/sched.place_checkpoints/scan"
    ops = [_op(0, 100, ADMIT[:-len("/while/body")]),   # the loop itself
           _op(10, 30, place), _op(20, 40, place),      # overlapping
           _op(100, 110, "jit(run)/sched.queue_order/sort"),
           _op(110, 120, "jit(run)/add"),
           _op(0, 50, ADMIT, device="/device:TPU:1")]
    out = obs_spans.summarize([], ops)
    sc = out["scopes"]
    # a loop's time holds its body's; the second device halves the mean
    assert sc["sched.admit"] == pytest.approx((100 + 50) / 2 / 1e9)
    assert sc["sched.plan_evictions"] == sc["sched.place_checkpoints"] \
        == pytest.approx(30 / 2 / 1e9)
    assert sc["sched.queue_order"] == pytest.approx(10 / 2 / 1e9)
    assert sc["sched.capture"] == 0.0
    assert out["busy_s"] == pytest.approx((120 + 50) / 2 / 1e9)
    assert out["covered_s"] == pytest.approx((110 + 50) / 2 / 1e9)
    # a scope is a whole component of the path, not a prefix of one
    near = obs_spans.summarize([], [_op(0, 5, "jit(run)/sched.admitted/x")])
    assert near["scopes"]["sched.admit"] == 0.0


def test_ops_take_op_names_from_the_program_that_ran_them():
    users = [User("A", 50.0)]
    cfg = SchedulerConfig(cpu_total=16, quantum=2)
    empty, _ = omfs_jax.table_from_jobs([], users, cfg.cpu_total, cfg)
    tbl = omfs_jax.pad_table(empty, 8)
    text = omfs_jax.insert_rows.lower(
        tbl, jnp.arange(8, dtype=jnp.int32), tbl, jnp.ones(8, bool)
    ).compile().as_text()
    module, names = obs_spans.op_names(text)
    assert module == "jit_insert_rows"
    instr, op_name = next((i, n) for i, n in names.items()
                          if "stream.insert_rows" in n)
    event = f"%{instr} = s32[8]{{0}} fusion(s32[8]{{0}} %p), kind=kLoop"
    assert obs_spans.hlo_name(event) == instr
    runs = [("jit_equal(11)", 0, 10), ("jit_insert_rows(42)", 20, 10)]
    ops = obs_spans.name_ops("/device:TPU:0", runs,
                             [(event, 2, 1), (event, 22, 1), (event, -5, 1)],
                             {module: names})
    # only the op that ran inside the insert program is named
    assert [o.op_name for o in ops] == ["", op_name, ""]
    with pytest.raises(ValueError):
        obs_spans.op_names("not hlo")


def test_summary_idle_shares_and_innermost_gap_label():
    spans = [_span("stream.round", 0, 1000),
             _span("stream.boundary", 0, 400),
             _span("stream.read_back", 0, 300),
             _span("stream.compact", 300, 340),
             _span("stream.build", 340, 400),
             _span("stream.segment", 400, 1000)]
    # device idle: 0-100 (read_back), 300-400 (compact, then most of it
    # in build), 900-1000 (the segment), 1100-1300 (under no span)
    ops = [_op(100, 300), _op(400, 900), _op(1000, 1100), _op(1300, 1400)]
    out = obs_spans.summarize(spans, ops)
    assert out["window_s"] == pytest.approx(1400 / 1e9)
    assert out["boundary_idle_share"] == pytest.approx(200 / 1400)
    assert out["unspanned_idle_share"] == pytest.approx(200 / 1400)
    assert out["idle_gaps"] == [
        ["unspanned", pytest.approx(200 / 1e9)],
        ["stream.read_back", pytest.approx(100 / 1e9)],
        ["stream.build", pytest.approx(100 / 1e9)],
        ["stream.segment", pytest.approx(100 / 1e9)]]
    # a gap across two rounds lies in the round holding most of it
    two = [_span("stream.round", 0, 100), _span("stream.wait", 50, 100),
           _span("stream.round", 100, 300),
           _span("stream.read_back", 110, 300)]
    assert obs_spans.innermost(two, 60, 200) == "stream.read_back"
    assert obs_spans.innermost(two, 60, 120) == "stream.wait"
