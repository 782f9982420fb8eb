"""Analyzer self-tests: every rule fires on its seeded fixture at the
exact line, stays silent on the clean fixture, and the CLI exit codes +
suppression mechanics behave.

The fixtures live in ``tests/analysis_fixtures/`` (excluded from the
default ``src/repro`` scan).  Assertions pin ``(rule, line)`` pairs, so
editing a fixture means re-pinning here — deliberate: the analyzer's
output location is part of its contract (CI step summaries link to it).
"""
from pathlib import Path

import pytest

from repro import analysis
from repro.analysis import known_failures
from repro.analysis.base import RULES, SourceFile, known_rule_ids
from repro.analysis.concurrency import analyze_concurrency

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "analysis_fixtures"


def run_file_rules(*names):
    violations, _ = analysis.collect_violations(
        REPO, targets=[FIXTURES / n for n in names],
        include_trace=False, include_project=False)
    return sorted((v.rule, v.line) for v in violations)


def test_registry_is_complete():
    assert sorted(RULES) == [
        "backend-contract", "branch-confinement", "column-dataflow",
        "cost-grid", "event-schema", "host-sync", "jaxpr-float-cast",
        "known-failures", "lock-order", "mutable-default", "retrace",
        "thread-shared-state", "tracer-leak"]
    assert "suppression" in known_rule_ids()
    for rule in RULES.values():
        assert rule.kind in ("file", "project", "trace")
        assert rule.doc


def test_tracer_leak_fixture_exact_lines():
    assert run_file_rules("tracer_leak.py") == [
        ("tracer-leak", 10),     # if on traced value
        ("tracer-leak", 17),     # int()
        ("tracer-leak", 18),     # bool()
        ("tracer-leak", 19),     # .item()
        ("tracer-leak", 20),     # int(flag) — taint flows through flag
        ("tracer-leak", 25),     # while on traced value (soft context)
    ]


def test_host_sync_fixture_exact_lines():
    assert run_file_rules("host_sync.py") == [
        ("host-sync", 10),       # np.asarray inside jit
        ("host-sync", 11),       # .block_until_ready inside jit
    ]


def test_cost_grid_fixture_exact_lines():
    assert run_file_rules("cost_grid.py") == [
        ("cost-grid", 6),        # true division assigned to cost_save
        ("cost-grid", 9),        # float literal in JobTable keyword
        ("cost-grid", 14),       # float() inside a grid cost function
    ]


def test_mutable_default_fixture_exact_lines():
    assert run_file_rules("mutable_default.py") == [
        ("mutable-default", 4),
        ("mutable-default", 9),
        ("mutable-default", 14),
    ]


def test_clean_fixture_is_silent():
    assert run_file_rules("clean.py") == []


def test_suppression_mechanics():
    got = run_file_rules("suppressed.py")
    # line 4's mutable-default is validly suppressed — absent from output
    assert ("mutable-default", 4) not in got
    assert got == [
        ("mutable-default", 12),  # missing-reason suppression doesn't count
        ("suppression", 9),       # unused suppression
        ("suppression", 12),      # missing '-- reason'
        ("suppression", 17),      # unknown rule id
    ]


def test_concurrency_fixture_exact_lines():
    sf = SourceFile(FIXTURES / "concurrency_bad.py")
    got = sorted((v.rule, v.line) for v in analyze_concurrency([sf]))
    assert got == [
        ("lock-order", 34),            # a->b here, b->a at line 39
        ("thread-shared-state", 18),   # _write runs on the pool thread
        ("thread-shared-state", 19),
        ("thread-shared-state", 22),   # snapshot races the pool thread
    ]


def test_cli_exit_codes(capsys):
    # violations -> nonzero, rule id + file:line on stdout
    rc = analysis.main([
        "--no-trace", "--no-project",
        str(FIXTURES / "mutable_default.py")])
    out = capsys.readouterr().out
    assert rc == 1
    assert "[mutable-default]" in out
    assert "mutable_default.py:4" in out
    # clean file -> zero
    rc = analysis.main([
        "--no-trace", "--no-project", str(FIXTURES / "clean.py")])
    assert rc == 0


def test_real_tree_is_analysis_clean():
    """src/repro passes every file + project rule (the CI gate, minus the
    trace layer, which compiles and is exercised by the analysis CI job)."""
    violations, _ = analysis.collect_violations(REPO, include_trace=False)
    assert violations == [], "\n".join(str(v) for v in violations)


def test_backend_contract_flags_missing_equivalence_entry(tmp_path):
    """A policy registered in the live engine but absent from a
    literal-name equivalence suite is flagged (one violation per
    uncovered policy); a registry-derived suite covers by construction."""
    from repro.analysis.contracts import check_backend_contract
    from repro.core import engine

    fake = tmp_path / "tests" / "test_policies_equivalence.py"
    fake.parent.mkdir(parents=True)
    fake.write_text('def test_one():\n    run("omfs")\n')
    got = [v for v in check_backend_contract(tmp_path)
           if "never exercised" in v.message]
    uncovered = sorted(engine.POLICIES)
    assert len(got) == len(uncovered) - 1          # every policy but "omfs"
    assert all(v.rule == "backend-contract" for v in got)

    fake.write_text("from repro.core import engine\n"
                    "NAMES = sorted(engine.POLICIES)\n")
    assert [v for v in check_backend_contract(tmp_path)
            if "never exercised" in v.message] == []


def _event_tree(tmp_path, *, events, capture="", metrics="", trace="",
                engine="", kernel=""):
    """Materialize a minimal fake tree for the event-schema rule."""
    obs = tmp_path / "src" / "repro" / "obs"
    core = tmp_path / "src" / "repro" / "core"
    obs.mkdir(parents=True)
    core.mkdir(parents=True)
    (obs / "events.py").write_text(events)
    if capture is not None:
        (obs / "jax_capture.py").write_text(capture)
    (obs / "metrics.py").write_text(metrics)
    (obs / "trace.py").write_text(trace)
    (core / "engine.py").write_text(engine)
    (core / "omfs.py").write_text(kernel)
    return tmp_path


_SCHEMA_OK = """\
class EventType:
    SUBMIT = 0
    FINISH = 1

def events_from_diff(pre, jobs, t):
    use(EventType.SUBMIT, EventType.FINISH)
"""

_CAPTURE_OK = """\
def event_flags(pre, post, t):
    use(EventType.SUBMIT, EventType.FINISH)
"""

_CONSUME_OK = "use(EventType.SUBMIT, EventType.FINISH)\n"


def test_event_schema_clean_tree_passes(tmp_path):
    from repro.analysis.event_schema import check_event_schema

    root = _event_tree(tmp_path, events=_SCHEMA_OK, capture=_CAPTURE_OK,
                       metrics=_CONSUME_OK)
    assert check_event_schema(root) == []


def test_event_schema_flags_unemitted_and_unconsumed(tmp_path):
    """A declared type the Python emitter / JAX flag matrix / consumers
    never touch is a silent telemetry hole — three distinct violations."""
    from repro.analysis.event_schema import check_event_schema

    events = ("class EventType:\n    SUBMIT = 0\n    EVICT = 1\n\n"
              "def events_from_diff(pre, jobs, t):\n"
              "    use(EventType.SUBMIT)\n")
    root = _event_tree(tmp_path, events=events,
                       capture="def event_flags(pre, post, t):\n"
                               "    use(EventType.SUBMIT)\n",
                       metrics="use(EventType.SUBMIT)\n")
    msgs = [v.message for v in check_event_schema(root)]
    assert any("events_from_diff never references" in m for m in msgs)
    assert any("event_flags" in m for m in msgs)
    assert any("nor the trace exporter consumes" in m for m in msgs)
    # the declared-but-unemitted violations pin the enum member's line
    lines = [v.line for v in check_event_schema(root)
             if "events_from_diff" in v.message]
    assert lines == [3]                            # EVICT = 1


def test_event_schema_flags_phantom_reference(tmp_path):
    from repro.analysis.event_schema import check_event_schema

    root = _event_tree(tmp_path, events=_SCHEMA_OK, capture=_CAPTURE_OK,
                       metrics=_CONSUME_OK,
                       trace="x = EventType.TELEPORT\n")
    got = [v for v in check_event_schema(root)
           if "referenced but not declared" in v.message]
    assert len(got) == 1
    assert got[0].line == 1


def test_event_schema_flags_hot_path_capture(tmp_path):
    """The uninstrumented tick path referencing the capture layer breaks
    the byte-identical guarantee; the *_events twins are exempt."""
    from repro.analysis.event_schema import check_event_schema

    engine = ("def _tick_step(cfg, tbl, t):\n"
              "    return capture_tick(tbl, tbl, t, 8)\n"
              "def _jitted_runner_events(cfg):\n"
              "    return capture_tick\n")
    root = _event_tree(tmp_path, events=_SCHEMA_OK, capture=_CAPTURE_OK,
                       metrics=_CONSUME_OK, engine=engine)
    got = [v for v in check_event_schema(root)
           if "hot-path" in v.message]
    assert len(got) == 1                           # only _tick_step, not twin
    assert "_tick_step" in got[0].message


def test_event_schema_flags_kernel_obs_import(tmp_path):
    from repro.analysis.event_schema import check_event_schema

    root = _event_tree(tmp_path, events=_SCHEMA_OK, capture=_CAPTURE_OK,
                       metrics=_CONSUME_OK,
                       kernel="from repro.obs.bus import EventBus\n")
    got = [v for v in check_event_schema(root)
           if "kernel imports repro.obs" in v.message]
    assert len(got) == 1


def test_event_schema_flags_missing_schema_files(tmp_path):
    from repro.analysis.event_schema import check_event_schema

    (tmp_path / "src" / "repro").mkdir(parents=True)
    got = check_event_schema(tmp_path)
    assert len(got) == 1 and "events.py missing" in got[0].message

    root = _event_tree(tmp_path, events=_SCHEMA_OK, metrics=_CONSUME_OK)
    (root / "src" / "repro" / "obs" / "jax_capture.py").unlink()
    msgs = [v.message for v in check_event_schema(root)]
    assert any("no in-scan emitter" in m for m in msgs)


def test_known_failures_registry_valid_and_loadable(tmp_path):
    """The repo's registry passes the rule and loads; a registry written to
    ``tmp_path`` loads when well-formed (empty included) and is flagged
    entry by entry when malformed."""
    assert known_failures.check_known_failures(REPO) == []
    for nodeid, reason in known_failures.load_known_failures(REPO).items():
        assert "::" in nodeid and reason.strip()

    reg = tmp_path / known_failures.REGISTRY
    reg.parent.mkdir(parents=True)
    (tmp_path / "tests" / "test_x.py").write_text("")
    reg.write_text("# nothing expected to fail\n")
    assert known_failures.check_known_failures(tmp_path) == []
    assert known_failures.load_known_failures(tmp_path) == {}

    reg.write_text('[[failure]]\nid = "tests/test_x.py::test_a"\n'
                   'reason = "waits on a fix"\n')
    assert known_failures.check_known_failures(tmp_path) == []
    assert known_failures.load_known_failures(tmp_path) == {
        "tests/test_x.py::test_a": "waits on a fix"}

    reg.write_text('[[failure]]\nid = "tests/test_x.py::test_a"\n'
                   'reason = "x"\n'
                   '[[failure]]\nid = "tests/test_x.py::test_a"\n'
                   'reason = ""\n'
                   '[[failure]]\nid = "tests/test_gone.py::test_b"\n'
                   'reason = "x"\nowner = "y"\n'
                   '[[failure]]\nid = "not_a_nodeid"\nreason = "x"\n')
    msgs = [v.message for v in known_failures.check_known_failures(tmp_path)]
    for part in ("duplicate id", "has no reason", "missing file",
                 "unknown key", "pytest nodeid"):
        assert any(part in m for m in msgs), (part, msgs)

    reg.write_text("[[failure]\n")
    msgs = [v.message for v in known_failures.check_known_failures(tmp_path)]
    assert len(msgs) == 1 and "does not parse" in msgs[0]


def test_github_summary_format():
    from repro.analysis import _github_summary
    from repro.analysis.base import Violation

    md = _github_summary([Violation("cost-grid", "a.py", 3, "x | y")])
    assert "| `cost-grid` | `a.py:3` |" in md
    assert "x \\| y" in md
    assert "No violations" in _github_summary([])
