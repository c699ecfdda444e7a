"""The chip deployment's host side: config scaling, the bulk generator,
host-side ingest and partitioning, and where the compile cache lives."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.ecommerce_graph import CHIP, FULL
from repro.distributed.graph_serve import config_espec
from repro.graphstore import StoreSpec, compact, ingest
from repro.graphstore.partition import (
    default_pspec,
    partition_store,
    partition_store_host,
)
from repro.graphstore.store import ingest_host
from repro.launch import compile_cache
from repro.launch.serve import (
    BLOCK_FILL,
    block_capacity,
    deployment_config,
    generate_graph,
)


def test_chip_config_cuts_only_scale():
    widths = ("e_per_vertex", "n_vprops", "n_eprops", "max_deg",
              "max_leaves", "recent_cap", "edge_prop", "edge_val",
              "leaf_prop", "leaf_val")
    for cfg in (CHIP, deployment_config(4), deployment_config(1, 2**12)):
        assert all(getattr(cfg, f) == getattr(FULL, f) for f in widths)
        # CHIP's ratio of vertices to cache slots is kept
        assert cfg.v_total * CHIP.cache_slots_total == (
            cfg.cache_slots_total * CHIP.v_total
        )
    assert deployment_config(4).v_total == 4 * CHIP.v_total


def test_generate_graph_is_seeded_and_in_bounds():
    cfg = deployment_config(1, 2**12)
    a, b = generate_graph(cfg, 3), generate_graph(cfg, 3)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    vlabels, vprops, esrc, edst, elabels, eprops = a
    assert len(esrc) == cfg.e_total() - cfg.recent_cap
    assert np.all(np.diff(esrc) >= 0)  # sorted by source
    assert esrc.max() < cfg.v_total and edst.max() < cfg.v_total
    assert vprops.shape == (cfg.v_total, cfg.n_vprops)
    assert eprops.shape == (len(esrc), cfg.n_eprops)
    assert np.bincount(esrc).max() <= cfg.max_deg  # no truncated rows


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_host_ingest_matches_device_compaction(seed):
    rng = np.random.default_rng(seed)
    spec = StoreSpec(v_cap=64, e_cap=512, n_vprops=2, n_eprops=1,
                     recent_cap=64)
    ne = int(rng.integers(0, 400))
    args = (rng.integers(0, 3, 40), rng.integers(0, 2, (40, 2)),
            rng.integers(0, 40, ne), rng.integers(0, 40, ne),
            rng.integers(0, 2, ne), rng.integers(0, 2, (ne, 1)))
    host = ingest_host(spec, *args)
    raw = jax.tree_util.tree_map(jnp.asarray, host)._replace(
        out_perm=jnp.zeros_like(host.out_perm),
        in_perm=jnp.zeros_like(host.in_perm),
        csr_len=jnp.int32(0),
    )
    dev = compact(spec, raw)
    for f in host._fields:
        a, b = np.asarray(getattr(host, f)), np.asarray(getattr(dev, f))
        assert a.dtype == b.dtype and np.array_equal(a, b), f
    assert all(isinstance(x, (np.ndarray, np.generic)) for x in host)


@pytest.mark.parametrize("n_shards", [1, 4])
def test_partition_is_host_side_and_fits_block_capacity(n_shards):
    cfg = deployment_config(n_shards, 2**10)
    spec = config_espec(cfg).store
    host = ingest_host(spec, *generate_graph(cfg, 0))
    cap = block_capacity(host, n_shards)
    pspec = default_pspec(spec, n_shards)._replace(
        e_blk_cap=cap, recent_blk_cap=cfg.recent_cap
    )
    ps = partition_store_host(pspec, host)
    assert all(isinstance(x, np.ndarray) for x in jax.tree_util.tree_leaves(ps))
    fill = max(ps.out.blk_len.max(), ps.inc.blk_len.max()) / cap
    assert 0.9 * BLOCK_FILL <= fill <= BLOCK_FILL
    dev = partition_store(pspec, ingest(spec, *generate_graph(cfg, 0)))
    for x, y in zip(jax.tree_util.tree_leaves(ps), jax.tree_util.tree_leaves(dev)):
        assert np.array_equal(x, np.asarray(y))


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    files = jax.config.jax_hlo_source_file_canonicalization_regex
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", files)


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
    # stage names are metadata: a renamed stage must not load an old entry,
    # and the checkout's location must not change the key
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    assert re.match(jax.config.jax_hlo_source_file_canonicalization_regex,
                    str(compile_cache.CHECKOUT / "src" / "repro" / "x.py"))


def test_compile_cache_follows_the_variable(monkeypatch, tmp_path,
                                            cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir is None


def test_chip_smoke_refuses_cpu(tmp_path):
    import subprocess
    import sys

    from repro.launch.compile_cache import CHECKOUT

    out = subprocess.run(
        [sys.executable, str(CHECKOUT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
    )
    assert out.returncode != 0
    assert out.stdout.strip() == "" or '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_phases_run_on_cpu():
    """Every phase of the smoke at a tiny scale on the CPU: the oracle
    agrees before and after the commit (``run`` raises otherwise)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", compile_cache.CHECKOUT / "chip_smoke.py"
    )
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    chip_smoke.run(jax.devices(), 1, seed=0, batch=64, n_sample=32,
                   vertices=2**12)
