"""Jaxpr auditor: trace the jitted passes and assert what the AST can't see.

Three trace-level invariants:

* **jaxpr-float-cast** — tracing every registered policy pass (tiered
  config, so placement machinery is live) must produce NO
  ``convert_element_type`` from an integer to a floating dtype, and every
  output `JobTable` column must still be integer-typed.  A float sneaking
  into the /256 cost grid mid-pass rounds differently than the Python
  backend's integer arithmetic — schedules drift without a test failing.
* **branch-confinement** — in the incremental OMFS passes the expensive
  eviction machinery (the victim ``sort``/lexsort and the placement
  ``scan``) must stay confined under a ``lax.cond``/``switch`` branch
  inside the per-queue-position loop.  Hoisted onto the always-taken path
  it still produces identical schedules — only ~10x slower (the whole
  point of the incremental pass, ROADMAP "11k ticks/s").
* **retrace** — the compile-counter harness: a second
  ``engine.simulate`` / ``engine.simulate_matrix`` call with same-shaped
  inputs, and a tick after ``update_state_mib``, must all hit the
  compilation cache (``_cache_size() == 1``).  A retrace per tick/call
  silently turns throughput into compile time.

The audit builds one small deterministic workload (J=12, a T=3 cost
lattice with tight fast tiers so spilling actually happens, and
delta-aware recurrent-save coefficients so both lattice columns are live)
and traces the real registered passes — no fixtures, no mocks.

Every trace rule runs the passes under BOTH kernel-dispatch paths
(``SchedulerConfig.kernel_backend`` "lax" and "pallas_interpret"): the
float-cast walk descends into the ``pallas_call`` sub-jaxpr, so the fused
`kernels.sched_select` kernel is held to the same integer-grid bar, the
confinement rule additionally requires the kernel call itself to sit
behind the eviction ``cond``, and the retrace harness asserts that
toggling the flag lands on separately cached runners (each compiled
exactly once) instead of retracing one.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional

from repro.analysis.base import Violation, register

ENGINE = "src/repro/core/engine.py"
OMFS_JAX = "src/repro/core/omfs_jax.py"

#: policies whose per-queue-position loop must keep eviction machinery
#: behind a cond (backfill's once-per-tick reservation sort is by design)
CONFINED_POLICIES = ("omfs", "omfs_cheap_victim")

_FIXTURE_CACHE: Dict[str, object] = {}


def _fixture():
    """(users, jobs, cfg, tbl, ent) — small, deterministic, tiered."""
    if "fx" in _FIXTURE_CACHE:
        return _FIXTURE_CACHE["fx"]
    from repro.core import omfs_jax
    from repro.core.crcost import CRCostModel, TieredCRCostModel, UNBOUNDED
    from repro.core.types import SchedulerConfig
    from repro.core.workload import WorkloadSpec, make_jobs, make_users

    spec = WorkloadSpec(n_users=3, horizon=40, cpu_total=16, seed=7,
                        arrival_rate=0.3, mean_work=12,
                        class_mix=(0.1, 0.2, 0.7))
    users = make_users(spec)
    jobs = make_jobs(spec, users)[:12]
    tiers = TieredCRCostModel(
        tiers=(CRCostModel(save_mib_per_tick=256, restore_mib_per_tick=256,
                           delta_num=141, delta_den=256),
               CRCostModel(save_mib_per_tick=64, restore_mib_per_tick=64,
                           delta_num=182, delta_den=256),
               CRCostModel(save_mib_per_tick=32, restore_mib_per_tick=32,
                           save_base=1, restore_base=1,
                           delta_num=182, delta_den=256)),
        capacity_mib=(48, 96, UNBOUNDED))
    cfg = SchedulerConfig(cpu_total=16, quantum=2, cr_overhead=1,
                          cr_tiers=tiers)
    tbl, ent = omfs_jax.table_from_jobs(jobs, users, cfg.cpu_total, cfg)
    _FIXTURE_CACHE["fx"] = (users, jobs, cfg, tbl, ent)
    return _FIXTURE_CACHE["fx"]


#: the two kernel-dispatch paths every trace rule audits
BACKENDS = ("lax", "pallas_interpret")


def _with_backend(cfg, backend: str):
    import dataclasses
    return cfg if backend == "lax" else dataclasses.replace(
        cfg, kernel_backend=backend)


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn):
    """(param_name, jaxpr) pairs for every sub-jaxpr of an equation."""
    import jax.extend.core as jcore

    out = []
    for k, v in eqn.params.items():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for x in vals:
            if isinstance(x, jcore.ClosedJaxpr):
                out.append((k, x.jaxpr))
            elif isinstance(x, jcore.Jaxpr):
                out.append((k, x))
    return out


def _walk_eqns(jaxpr, path=()):
    """Yield (eqn, path) for every equation, path = primitive-name ancestry."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        for _, sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub, path + (eqn.primitive.name,))


def _trace_pass(name: str, backend: str = "lax"):
    """ClosedJaxpr of one registered policy pass over the fixture table,
    under the requested ``kernel_backend`` dispatch path."""
    import jax

    from repro.core import engine
    _, _, cfg, tbl, ent = _fixture()
    cfg = _with_backend(cfg, backend)
    pass_fn = engine.POLICIES[name].jax_factory(None)

    def run(tbl, t):
        return pass_fn(cfg, ent, t, tbl)

    import jax.numpy as jnp
    t0 = jnp.int32(3)
    return jax.make_jaxpr(run)(tbl, t0)


def _is_float(dtype) -> bool:
    import numpy as np
    return np.issubdtype(dtype, np.floating)


def _is_int(dtype) -> bool:
    import numpy as np
    return np.issubdtype(dtype, np.integer) or np.issubdtype(dtype, np.bool_)


@register(
    "jaxpr-float-cast", "trace",
    "no int->float convert_element_type inside any policy pass; JobTable "
    "cost/occupancy columns stay integer end-to-end")
def check_float_casts(root: Path) -> List[Violation]:
    out: List[Violation] = []
    from repro.core import engine

    for name in sorted(engine.POLICIES):
        for backend in BACKENDS:
            closed = _trace_pass(name, backend)
            for eqn, _path in _walk_eqns(closed.jaxpr):
                if eqn.primitive.name != "convert_element_type":
                    continue
                new = eqn.params.get("new_dtype")
                src = eqn.invars[0].aval.dtype if eqn.invars else None
                if new is not None and _is_float(new) and (
                        src is None or _is_int(src)):
                    out.append(Violation(
                        "jaxpr-float-cast", str(root / ENGINE), 1,
                        f"policy {name!r} ({backend}): traced pass converts "
                        f"{src} -> {new} — a float entering the integer "
                        "cost grid breaks cross-backend bit-equality"))
            for aval in closed.out_avals:
                if hasattr(aval, "dtype") and _is_float(aval.dtype):
                    out.append(Violation(
                        "jaxpr-float-cast", str(root / ENGINE), 1,
                        f"policy {name!r} ({backend}): pass output column "
                        f"has floating dtype {aval.dtype}; JobTable columns "
                        "must stay integer"))
    return out


@register(
    "branch-confinement", "trace",
    "victim sort + placement scan stay under lax.cond in the incremental "
    "OMFS passes (not hoisted onto the always-taken path)")
def check_branch_confinement(root: Path) -> List[Violation]:
    out: List[Violation] = []
    loops = {"while", "scan", "fori"}
    # the fused kernel call is the pallas path's whole eviction machinery —
    # held to the same confinement bar as the lax sort/scan
    confined = ("sort", "scan", "pallas_call")
    for name in CONFINED_POLICIES:
        for backend in BACKENDS:
            closed = _trace_pass(name, backend)
            for eqn, path in _walk_eqns(closed.jaxpr):
                if eqn.primitive.name not in confined:
                    continue
                in_loop = any(p in loops for p in path)
                if not in_loop:
                    continue    # once-per-tick (queue_order / hoisted
                    #             victim_order) sorts are the design
                if eqn.primitive.name == "scan" and "pallas_call" in path:
                    continue    # kernel-internal loops are already confined
                after_loop = path[max(i for i, p in enumerate(path)
                                      if p in loops):]
                if not any(p in ("cond", "switch") for p in after_loop):
                    out.append(Violation(
                        "branch-confinement", str(root / OMFS_JAX), 1,
                        f"policy {name!r} ({backend}): "
                        f"`{eqn.primitive.name}` runs on the always-taken "
                        "path of the per-queue-position loop (ancestry "
                        f"{'->'.join(path)}) — eviction machinery must "
                        "stay behind the lax.cond eviction branch"))
    return out


@register(
    "retrace", "trace",
    "repeat simulate / simulate_matrix and update_state_mib hit the "
    "compilation cache (compile exactly once)")
def check_retrace(root: Path) -> List[Violation]:
    out: List[Violation] = []
    from repro.core import engine, omfs_jax

    users, jobs, cfg, tbl, ent = _fixture()
    horizon = 25
    engine_path = str(root / ENGINE)

    def cache_size(jitted) -> Optional[int]:
        get = getattr(jitted, "_cache_size", None)
        return get() if get is not None else None

    # -- repeat simulate: one compile for two same-shaped calls -------------
    engine.simulate(users, jobs, cfg, horizon, policy="omfs", backend="jax")
    engine.simulate(users, jobs, cfg, horizon, policy="omfs", backend="jax")
    pass_fn = engine.POLICIES["omfs"].jax_factory(None)
    runner = engine._jitted_runner(cfg, pass_fn, horizon)
    n = cache_size(runner)
    if n is not None and n != 1:
        out.append(Violation(
            "retrace", engine_path, 1,
            f"repeat simulate(policy='omfs') compiled {n} times for "
            "same-shaped inputs — expected exactly 1 (a retrace per call "
            "destroys tick throughput)"))

    # -- update_state_mib must not invalidate the compiled scan -------------
    # (the runner donates its input; copy so the cached fixture table's
    # buffers — aliased by the untouched columns — survive)
    tbl2 = omfs_jax.update_state_mib(tbl, 0, 777, cfg)
    runner(engine._copy_table(tbl2), ent)
    n = cache_size(runner)
    if n is not None and n != 1:
        out.append(Violation(
            "retrace", str(root / OMFS_JAX), 1,
            f"update_state_mib triggered a retrace (cache size {n}) — it "
            "must be O(1) scatters with unchanged shapes/dtypes"))

    # -- kernel-backend dispatch: toggling the flag must land on separately
    # cached runners (the config IS the builder key), each compiled exactly
    # once — never a retrace of one runner
    pcfg = _with_backend(cfg, "pallas_interpret")
    engine.simulate(users, jobs, pcfg, horizon, policy="omfs", backend="jax")
    engine.simulate(users, jobs, cfg, horizon, policy="omfs", backend="jax")
    engine.simulate(users, jobs, pcfg, horizon, policy="omfs", backend="jax")
    prunner = engine._jitted_runner(pcfg, pass_fn, horizon)
    if prunner is runner:
        out.append(Violation(
            "retrace", engine_path, 1,
            "kernel_backend='pallas_interpret' resolved to the SAME cached "
            "runner as 'lax' — the flag must key separate builders"))
    for fn, label in ((runner, "lax"), (prunner, "pallas_interpret")):
        n = cache_size(fn)
        if n is not None and n != 1:
            out.append(Violation(
                "retrace", engine_path, 1,
                f"toggling kernel_backend retraced the {label} runner "
                f"(cache size {n}) — each dispatch path must keep its own "
                "compiled program"))

    # -- repeat simulate_matrix: one compile for the whole policy union -----
    names = sorted(engine.POLICIES)
    engine.simulate_matrix(users, jobs, cfg, horizon, names)
    engine.simulate_matrix(users, jobs, cfg, horizon, names)
    pass_fns = tuple(engine.POLICIES[p].jax_factory(None) for p in names)
    mrunner = engine._jitted_matrix_runner(cfg, pass_fns, horizon)
    n = cache_size(mrunner)
    if n is not None and n != 1:
        out.append(Violation(
            "retrace", engine_path, 1,
            f"repeat simulate_matrix compiled {n} times — the policy "
            "matrix must share ONE compiled lax.switch scan"))

    # -- repeat simulate_batch: one compile for the whole sweep grid --------
    cells = [engine.BatchCell(users=users, jobs=jobs, policy="omfs",
                              quantum=q, pass_depth=d)
             for q in (1, 3) for d in (4, None)]
    engine.simulate_batch(cells, cfg, horizon)
    engine.simulate_batch(list(reversed(cells)), cfg, horizon)
    brunner = engine._jitted_batch_runner(
        cfg, (engine.POLICIES["omfs"].jax_factory(None),), horizon, 1)
    n = cache_size(brunner)
    if n is not None and n != 1:
        out.append(Violation(
            "retrace", engine_path, 1,
            f"repeat simulate_batch compiled {n} times — the knobs "
            "(quantum/pass_depth) must ride the batch axis as traced "
            "scalars, ONE program for the whole grid"))

    # -- streaming: N segments, one compile (t0 is traced) ------------------
    from repro.core.workload import arrival_stream
    engine.simulate_stream(users, arrival_stream(jobs), cfg, horizon,
                           capacity=16, segment_len=5)
    srunner = engine._jitted_segment_runner(cfg, pass_fn, 5)
    n = cache_size(srunner)
    if n is not None and n != 1:
        out.append(Violation(
            "retrace", engine_path, 1,
            f"streaming segment runner compiled {n} times across segments "
            "— the segment start tick must stay traced (one program for "
            "the whole stream)"))
    ins = cache_size(omfs_jax.insert_packed)
    if ins is not None and ins > 1:
        out.append(Violation(
            "retrace", str(root / OMFS_JAX), 1,
            f"segment-boundary insert_packed compiled {ins} times — the "
            "compaction scatter must be one fixed-shape program per "
            "capacity"))

    # -- instrumented runners: event capture must not retrace either --------
    from repro.obs.events import lossless_ring_size
    engine.simulate(users, jobs, cfg, horizon, policy="omfs", backend="jax",
                    record_events=True)
    engine.simulate(users, jobs, cfg, horizon, policy="omfs", backend="jax",
                    record_events=True)
    ring = lossless_ring_size(tbl.cpus.shape[0])
    irunner = engine._jitted_runner_events(cfg, pass_fn, horizon, ring)
    n = cache_size(irunner)
    if n is not None and n != 1:
        out.append(Violation(
            "retrace", engine_path, 1,
            f"repeat instrumented simulate compiled {n} times — the event "
            "ring is fixed-shape; capture must add zero retraces"))

    engine.simulate_stream(users, arrival_stream(jobs), cfg, horizon,
                           capacity=16, segment_len=5, record_events=True)
    isrunner = engine._jitted_segment_runner_events(
        cfg, pass_fn, 5, lossless_ring_size(16))
    n = cache_size(isrunner)
    if n is not None and n != 1:
        out.append(Violation(
            "retrace", engine_path, 1,
            f"instrumented streaming segment runner compiled {n} times "
            "across segments — the ring and the traced start tick must "
            "keep it at one compile per (cfg, pass, seg_len, ring)"))

    # -- confinement: instrumentation off means the SAME plain runner -------
    # (the uninstrumented builders must not have been invalidated or
    # duplicated by the capture wiring: their caches still hold exactly one
    # entry each after the instrumented calls above)
    for fn, label in ((runner, "_jitted_runner"),
                      (srunner, "_jitted_segment_runner")):
        n = cache_size(fn)
        if n is not None and n != 1:
            out.append(Violation(
                "retrace", engine_path, 1,
                f"{label} compiled {n} times after instrumented runs — "
                "record_events=True must leave the uninstrumented program "
                "untouched"))
    return out
