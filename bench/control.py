"""The control of a cell's check: the reference in the program's place with
one guarantee of the configuration broken.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 30]

The configuration states that a running job is not evicted before it has
run ``quantum`` ticks; the control ignores that (`RefSim`'s
``ignore_quantum``) and is compared with the reference exactly as a run's
output is, at the cell's own size and window.  Every seed has to come out
not correct.  The benchmark's own runs do not run this.  It needs no
accelerator: the reference and the control are host code.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from parts import reference_of  # noqa: E402


def control_readings(config: dict, work: dict, seed: int,
                     seconds: float) -> dict:
    """The numbers a run's check compares, read from the control."""
    from reference.runs import references
    mismatches = reference_of(config).mismatches
    refs = references(config, work, seed, seconds)
    ctrl = references(config, work, seed, seconds, ignore_quantum=True)
    table = busy = counts = 0
    for (r, rs), (c, cs) in zip(refs, ctrl):
        table += mismatches(c.table(), r.table())
        busy += sum(a != b for a, b in zip(c.busy, r.busy))
        if rs is not None:
            counts += sum(abs(cs[k] - rs[k]) for k in rs)
    return {"table_mismatches": table, "busy_mismatches": busy,
            "stream_count_mismatches": counts}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    import run
    manifest = run.load_manifest()
    _, config, work = run.cell_spec(manifest, args.workload)
    seconds = args.seconds or manifest["run_seconds"]
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        got = control_readings(config, work, seed, seconds)
        correct = all(v <= 0 for v in got.values())
        failed_all &= not correct
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": correct, **got}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
