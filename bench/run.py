"""The scheduler's chip benchmark: runs one cell of `BENCHMARK.json`.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a deployment (``bench/configs/<config>.json``) and a traffic
mix (``bench/workloads/<cell>.json``: the entry point, the policy, how the
client drives the scheduler, and the arrivals).  The run builds the
traffic from ``--seed``, warms up every program the window uses, runs the
window on the chip, then checks what the timed entry produced against the
plain reference (``bench/reference``).  Each metric is read by its own
reader, ``bench/metrics/<name>.py``; ``--trace 0`` reports the cell's
end-to-end metrics and ``--trace 1`` its per-layer metrics, read with the
profiler on.

The last line of standard output is one JSON object; the numbers the check
compared, each beside its limit, are the last lines of standard error and
the ``checks`` key of that object.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from process start

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: the persistent compilation cache: a fixed directory in the checkout
CACHE_DIR = ROOT / ".jax_cache" / "bench"


def load_manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_spec(manifest: dict, name: str):
    """(cell entry, configuration, workload file) for a cell."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    work = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    if (work["config"], work["traffic"]) != (cell["config"], cell["traffic"]):
        raise SystemExit(f"run.py: bench/workloads/{name}.json names "
                         f"{work['config']}/{work['traffic']}, BENCHMARK.json "
                         f"{cell['config']}/{cell['traffic']}")
    return cell, config, work


def metrics_for(manifest: dict, cell: str, trace: bool) -> list:
    """The cell's metrics of one kind: those whose ``workloads`` list it,
    or that list none."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in manifest[kind]
            if cell in m.get("workloads", [cell])]


def read_metric(name: str, ctx: dict):
    """Run ``bench/metrics/<name>.py``'s ``read(ctx)``; None = nothing."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def check_devices(chips: int):
    """The chips JAX sees; exits non-zero without a TPU or enough chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"run.py: no TPU: JAX's default backend is "
                         f"{devs[0].platform!r}, and this benchmark has no "
                         f"CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"run.py: the cell asks for {chips} chips, "
                         f"JAX sees {len(devs)}")
    return devs


def run_cell(cell: dict, config: dict, work: dict, metrics: list, seed: int,
             seconds: float, trace: bool, t_process: float,
             trace_dir: str | None = None) -> dict:
    """Drive one cell and build its result object (no device check)."""
    import jax
    from drive import ENTRIES
    from trace_reduce import reduce_trace

    own_dir = trace and trace_dir is None
    if own_dir:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        ctx = ENTRIES[work["entry"]](config, work, seed, seconds,
                                     trace_dir if trace else None, t_process)
        if trace:
            ctx["trace"] = reduce_trace(trace_dir)
    finally:
        if own_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": ctx["memory_peak_bytes"]}
    out_metrics = {}
    for m in metrics:
        value = read_metric(m["name"], ctx)
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": all(v <= lim for v, lim in ctx["checks"].values()),
              "attempted": ctx["attempted"], "failed": ctx["failed"],
              "metrics": out_metrics, "device": device}
    if trace and ctx.get("trace"):
        tr = ctx["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["info"] = {"compiles_in_window": ctx["compiles_in_window"],
                      "window_s": ctx["window_s"],
                      "round_s": ctx["round_s"],
                      "setup_parts": ctx.get("setup_parts")}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in ctx["checks"].items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = load_manifest()
    cell, config, work = cell_spec(manifest, args.workload)
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    check_devices(int(cell["chips"]))
    result = run_cell(cell, config, work,
                      metrics_for(manifest, args.workload, bool(args.trace)),
                      args.seed, args.seconds, bool(args.trace), T_PROCESS)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
