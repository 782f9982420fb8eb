"""The trace reduction (bench/trace_reduce.py): on hand-made events with a
known answer, and on a small trace recorded on a TPU v5e chip."""
import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from trace_reduce import reduce_events  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "v5e_trace.json.gz"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def test_busy_idle_sort_and_gaps():
    ms = 1e6
    events = [
        (HOST, "python", "bench.compaction", 0.0, 4 * ms),
        (HOST, "python", "device_get", 0.5 * ms, 2 * ms),
        (HOST, "python", "bench.dispatch", 4 * ms, 6 * ms),
        (DEV, "XLA Ops", "%sort.1 = s32[8]{0} sort(s32[8]{0} %p)",
         4 * ms, 2 * ms),
        (DEV, "XLA Ops", "%fusion.2 = s32[8]{0} fusion(s32[8]{0} %q)",
         5 * ms, 2 * ms),                                  # overlaps sort
        (DEV, "XLA Ops", "%fusion.2 = s32[8]{0} fusion(s32[8]{0} %q)",
         8 * ms, 1 * ms),
        (DEV, "XLA Modules", "jit_run", 4 * ms, 6 * ms),  # not the op line
    ]
    r = reduce_events(events)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.004)           # [4,7] + [8,9]
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["sort_s"] == pytest.approx(0.002)
    assert r["device_ops"][0] == ["fusion.2", pytest.approx(0.003)]
    gaps = dict((round(s, 6), name) for name, s in r["idle_gaps"])
    assert gaps[0.004] == "bench.compaction: device_get"
    assert gaps[0.001] == "bench.dispatch"
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_no_device_op_gives_nothing():
    assert reduce_events([]) is None
    assert reduce_events([(HOST, "python", "bench.dispatch", 0.0, 5.0)]) is None
    assert reduce_events([(DEV, "XLA Ops", "while", 0.0, 0.0)]) is None


def test_devices_are_averaged():
    events = [(HOST, "python", "bench.simulate_batch", 0.0, 10.0),
              ("/device:TPU:0", "XLA Ops", "while", 0.0, 10.0),
              ("/device:TPU:1", "XLA Ops", "while", 0.0, 4.0)]
    r = reduce_events(events)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(7e-9)
    assert r["idle_share"] == pytest.approx(0.3)


def test_recorded_v5e_trace():
    """The first 0.12 s of a traced `hpc10k.live` run on one TPU v5 lite
    chip (its device ops with their names as `load_events` keeps them, and
    the host's events); ``expected`` is what the reduction read from it on
    that machine."""
    data = json.loads(gzip.decompress(FIXTURE.read_bytes()))
    events = [tuple(e) for e in data["events"]]
    r = reduce_events(events)
    for key, want in data["expected"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
    assert 0.0 < r["busy_s"] < r["window_s"] == pytest.approx(0.12, rel=0.01)
    # the device plane and its op line are where the reduction looks
    assert {e[0] for e in events} >= {"/device:TPU:0", "/host:CPU"}
    assert any(e[1] == "XLA Ops" for e in events)
    assert any(n.split(".")[0] == "sort" for _, line, n, _, _ in events
               if line == "XLA Ops")
    assert any(n.startswith("bench.") for _, _, n, _, _ in events)
