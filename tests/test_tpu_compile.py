"""The serving programs compile for a TPU v5e, without the chip.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described and not attached, so these tests catch what the chip's
compiler would refuse (a Mosaic kernel it cannot lower, a program that
does not fit HBM, a lost collective) at no chip time. Nothing runs: this
says nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports this file. Keep every such compile in this one file.
"""

import numpy as np
import pytest

from repro.distributed.graph_serve import config_cell, config_grw_cell
from repro.launch.serve import deployment_config

V5E_HBM_BYTES = 16 * 10**9
# the chip deployment's widths at a smaller scale: compile time does not
# depend on it, and a test compiles in seconds
VERTICES_PER_CHIP = 2**20
BATCH_PER_CHIP = 512


@pytest.fixture(scope="module")
def topo():
    import os

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def restore():
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        restore()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    try:
        yield desc
    finally:
        restore()


def _mesh(topo, n):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices[:n]), ("shard",))


def _compile(build):
    import jax

    step, shardings, args, rt = build()
    compiled = jax.jit(step, in_shardings=shardings).lower(*args).compile()
    return compiled, rt


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _count(hlo: str, op: str) -> int:
    return sum(f" {op}(" in line or f" {op}-start(" in line
               for line in hlo.splitlines())


@pytest.mark.parametrize("n_chips", [1, 4])
def test_serving_step_compiles_for_v5e(topo, n_chips):
    cfg = deployment_config(n_chips, VERTICES_PER_CHIP * n_chips)
    compiled, rt = _compile(lambda: config_cell(
        cfg, _mesh(topo, n_chips), global_batch=BATCH_PER_CHIP * n_chips,
        blk_slack=1.25,
    ))
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    hlo = compiled.as_text()
    # the miss executor is the XLA formulation: no Mosaic kernel
    assert "tpu_custom_call" not in hlo
    # the collective-lean hop: 2 all_to_alls per hop (route out, results
    # back); a one-chip mesh has none
    n_hops = 1
    assert _count(hlo, "all-to-all") == (2 * n_hops if n_chips > 1 else 0)


def test_grw_commit_compiles_for_one_v5e(topo):
    cfg = deployment_config(1, VERTICES_PER_CHIP)
    compiled, _ = _compile(lambda: config_grw_cell(
        cfg, _mesh(topo, 1), blk_slack=1.25,
    ))
    assert _device_bytes(compiled) < V5E_HBM_BYTES
