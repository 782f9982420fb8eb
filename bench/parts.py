"""A configuration's parts, found by name from its own file.

A configuration file (``bench/configs/<name>.json``) may name its own
traffic model and reference, and state its tenants' shares:

* ``"traffic_model"``: the module ``bench/traffic/<name>.py``, whose
  ``generate(config, mix, seed, horizon, stream=0)`` returns the jobs'
  columns (at least `traffic.generator.COLUMNS`); ``generator`` if absent;
* ``"reference"``: the module ``bench/reference/<name>.py``, with
  ``RefSim`` (``RefSim(cols, config, policy, *, quantum, depth,
  ignore_quantum)``, ``run_stream``, ``run_all``, ``table()``, ``busy``),
  ``COMPARED`` and ``mismatches``; ``sched_ref`` if absent;
* ``"shares"``: each tenant's percent, ``tenants`` entries summing to 100;
  ``100 / tenants`` each if absent.

Nothing here imports the program: the reference's side reads these too.
The ``"scheduler"`` block, which the program's `SchedulerConfig` takes, is
read by `drive.scheduler_config`.
"""
from __future__ import annotations

import importlib
import math
from types import ModuleType
from typing import Callable, List

DEFAULT_TRAFFIC = "generator"
DEFAULT_REFERENCE = "sched_ref"


def _module(kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py`` (``bench`` is on the import path)."""
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"a configuration's {kind} module must be named "
                         f"by a Python identifier, not {name!r}")
    return importlib.import_module(f"{kind}.{name}")


def traffic_of(config: dict) -> Callable:
    """The configuration's traffic generator, ``generate``."""
    return _module("traffic", config.get("traffic_model",
                                         DEFAULT_TRAFFIC)).generate


def reference_of(config: dict) -> ModuleType:
    """The configuration's reference module."""
    return _module("reference", config.get("reference", DEFAULT_REFERENCE))


def shares_of(config: dict) -> List[float]:
    """Each tenant's percent of the machine."""
    n = int(config["tenants"])
    if "shares" not in config:
        return [100.0 / n] * n
    shares = config["shares"]
    if (not isinstance(shares, list) or len(shares) != n
            or not all(isinstance(s, (int, float)) and s > 0
                       for s in shares)):
        raise ValueError(f"shares must list {n} positive percents, one per "
                         f"tenant; got {shares!r}")
    if not math.isclose(sum(shares), 100.0):
        raise ValueError(f"shares must sum to 100; {shares!r} sums to "
                         f"{sum(shares)}")
    return [float(s) for s in shares]
