"""Compile-only checks of the `sched_select` kernel for a TPU v5e.

Nothing runs: the kernel is compiled, not interpreted, for one chip of a
described ``v5e:2x2`` topology, which raises what the chip's compiler
would raise (an op Mosaic cannot lower, more VMEM than a kernel may use).
The topology, sharding and shapes are built inside the fixtures below, so
no test-collecting process loads the TPU compiler at import.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.sched_select.ops import MAX_JOBS, plan_evictions_fused


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _args(sharding, j: int, n_tiers: int):
    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    col, flag = sds((j,)), sds((j,), jnp.bool_)
    return (col, col, col, col, flag, col, col, flag, sds((j, n_tiers)),
            sds(()), sds(()), sds((n_tiers,)), sds((n_tiers,)))


@pytest.mark.parametrize("j", [4096, MAX_JOBS])
@pytest.mark.parametrize("tiered", [False, True], ids=["flat", "tiered"])
def test_sched_select_compiles_for_v5e(one_chip, j, tiered):
    """Flat (T=1) and tiered (T=2, bounded fast tier) variants compile for
    a v5e chip up to the kernel's row limit, as a Mosaic custom call."""
    n_tiers = 2 if tiered else 1
    compiled = plan_evictions_fused.lower(
        *_args(one_chip, j, n_tiers), interpret=False, cheap=tiered,
        tiered=tiered, bounded=tiered).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sched_select_rejects_rows_above_limit(one_chip):
    with pytest.raises(ValueError, match=f"at most {MAX_JOBS} rows"):
        plan_evictions_fused.lower(*_args(one_chip, MAX_JOBS + 1, 1),
                                   interpret=False)
