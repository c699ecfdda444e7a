"""Collective-count and overlap pins for the one-exchange hop protocol.

The sharded gR step's collective budget is part of its contract: ONE packed
all_to_all each direction per hop (route out, results home) and ONE
all-reduce for the whole step (the deferred metrics/phase psum) — see the
``distributed.graph_serve`` module docstring. These tests lower the actual
compiled serving program and count collectives in the optimized HLO with
``launch.hlo_analysis``, so a regression that sneaks an extra exchange into
the hop loop (e.g. un-deferring a psum, or splitting the query frame back
into per-field routes) fails loudly rather than silently tripling latency.

Also pins that ``overlap=True`` (double-buffered frontier streams) returns
row-identical results to the default schedule: the overlap knob may change
wall-clock and program shape, never bytes.

Runs in subprocesses so XLA_FLAGS can create the host devices before jax
initializes (same pattern as test_graph_serve_multishard).
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp
    from conftest import (
        build_world, enabled_ttable, fig1_plan, common_watchlist_plan,
    )
    from repro.core import CacheSpec, EngineSpec
    from repro.distributed import flat_mesh
    from repro.distributed.graph_serve import ShardedTxnRuntime
    from repro.launch.hlo_analysis import analyze

    spec, store = build_world()
    cspec = CacheSpec(capacity=1024, probes=8, max_leaves=16, max_chunks=2)
    espec = EngineSpec(store=spec, cache=cspec, max_deg=32, frontier=32)
    ttable, _, _ = enabled_ttable()
    mesh = flat_mesh(8)
    """
)


def _run(script: str, token: str) -> None:
    env = dict(
        os.environ,
        PYTHONPATH=os.pathsep.join(
            [os.path.join(REPO, "src"), os.path.join(REPO, "tests")]
        ),
    )
    out = subprocess.run(
        [sys.executable, "-c", PRELUDE + textwrap.dedent(script)],
        capture_output=True, text=True, env=env, cwd=REPO,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert token in out.stdout, out.stdout


def test_gr_step_collective_counts():
    """Exactly 2 all_to_alls per hop + 1 all-reduce per step, on both a
    1-hop and a 2-hop plan — and nothing else (no all-gathers, no
    collective-permutes smuggled in by the compiler)."""
    _run(
        """
        rt = ShardedTxnRuntime(espec, mesh)
        pstore = rt.partition_store(store)
        cache = rt.empty_cache()
        for plan in (fig1_plan(), common_watchlist_plan()):
            step = rt.serve_step(plan, 64)
            # roots and valid flags ride one packed [bucket, 2] input
            hlo = step.jitted.lower(
                pstore, cache, ttable, jnp.ones((64, 2), jnp.int32),
                rt._down_none(), rt._rtable_none(),
            ).compile().as_text()
            c = analyze(hlo)["counts"]
            h = len(plan.hops)
            assert c["all-to-all"] == 2 * h, (h, c)
            assert c["all-reduce"] == 1, (h, c)
            assert c["all-gather"] == 0 and c["collective-permute"] == 0, c
        print("COLLECTIVE_COUNTS_OK")
        """,
        "COLLECTIVE_COUNTS_OK",
    )


def test_routing_table_keeps_collective_budget_and_program():
    """The replicated routing table is a traced INPUT of the serving step,
    not a closure constant: a table with live exceptions must (a) reuse the
    exact compiled program the identity table compiled (zero recompiles —
    ``_cache_size() == 1``), and (b) add ZERO collectives — still 2
    all_to_alls per hop + 1 all-reduce, no all-gather / collective-permute
    smuggled in by the locality routing or the defer mask."""
    _run(
        """
        from repro.distributed.routing import RoutingTableHost

        rt = ShardedTxnRuntime(espec, mesh)
        pstore = rt.partition_store(store)
        cache = rt.empty_cache()
        rhost = RoutingTableHost(rt.n)
        rhost.set_cache_owner(5, 0)   # split root (native owner is 5)
        rhost.apply_moves([(9, 2)])   # migrated vertex (native owner is 1)
        for plan in (fig1_plan(), common_watchlist_plan()):
            h = len(plan.hops)
            step = rt.serve_step(plan, 64)
            roots = jnp.zeros(64, jnp.int32)
            bv = jnp.ones(64, bool)
            step(pstore, cache, ttable, roots, bv)
            step(pstore, cache, ttable, roots, bv,
                 rtable=rhost.device_table())
            assert step.jitted._cache_size() == 1, step.jitted._cache_size()
            hlo = step.jitted.lower(
                pstore, cache, ttable, jnp.stack([roots, bv], axis=1),
                rt._down_none(), rhost.device_table(),
            ).compile().as_text()
            c = analyze(hlo)["counts"]
            assert c["all-to-all"] == 2 * h, (h, c)
            assert c["all-reduce"] == 1, (h, c)
            assert c["all-gather"] == 0 and c["collective-permute"] == 0, c
        print("RTABLE_BUDGET_OK")
        """,
        "RTABLE_BUDGET_OK",
    )


def test_overlap_schedule_is_row_identical():
    """Double-buffered frontier streams (overlap=True) must return the
    same results, miss-record sets, and metrics as the default schedule
    for multi-hop plans over a mixed local/remote Zipf-ish batch."""
    _run(
        """
        rng = np.random.default_rng(7)
        roots = rng.integers(0, spec.v_cap, size=64).astype(np.int32)
        mkey = lambda ms: sorted(
            (m.tpl_idx, m.root, tuple(m.params.tolist()), m.read_version)
            for m in ms
        )
        base = ShardedTxnRuntime(espec, mesh)
        ov = ShardedTxnRuntime(
            espec, mesh, overlap=True, e_blk_cap=base.pspec.e_blk_cap
        )
        ps_b = base.partition_store(store)
        ps_o = ov.partition_store(store)
        for plan in (fig1_plan(), common_watchlist_plan()):
            ra, msa, ma = base.run_gr_tx_batch(
                ps_b, base.empty_cache(), ttable, plan, roots
            )
            rb, msb, mb = ov.run_gr_tx_batch(
                ps_o, ov.empty_cache(), ttable, plan, roots
            )
            assert np.array_equal(ra, rb)
            assert mkey(msa) == mkey(msb)
            for k in ma:
                assert ma[k] == mb[k], (k, ma[k], mb[k])
        print("OVERLAP_IDENTITY_OK")
        """,
        "OVERLAP_IDENTITY_OK",
    )


def test_telemetry_keeps_collective_budget_and_bytes():
    """The observability tier's per-owner stage block rides the step's
    existing stacked all-reduce: telemetry on vs off must compile to the
    SAME collective counts (2 all_to_alls per hop, 1 all-reduce, nothing
    else), return byte-identical results/misses/metrics, and the
    attributed owner_stage columns must sum exactly to the global
    metrics they decompose."""
    _run(
        """
        rng = np.random.default_rng(11)
        roots = rng.integers(0, spec.v_cap, size=64).astype(np.int32)
        mkey = lambda ms: sorted(
            (m.tpl_idx, m.root, tuple(m.params.tolist()), m.read_version)
            for m in ms
        )
        rt_t = ShardedTxnRuntime(espec, mesh)  # telemetry defaults on
        rt_p = ShardedTxnRuntime(
            espec, mesh, telemetry=False, e_blk_cap=rt_t.pspec.e_blk_cap
        )
        ps_t = rt_t.partition_store(store)
        ps_p = rt_p.partition_store(store)
        for plan in (fig1_plan(), common_watchlist_plan()):
            h = len(plan.hops)
            for rt, ps in ((rt_t, ps_t), (rt_p, ps_p)):
                step = rt.serve_step(plan, 64)
                hlo = step.jitted.lower(
                    ps, rt.empty_cache(), ttable, jnp.ones((64, 2), jnp.int32),
                    rt._down_none(), rt._rtable_none(),
                ).compile().as_text()
                c = analyze(hlo)["counts"]
                assert c["all-to-all"] == 2 * h, (h, c)
                assert c["all-reduce"] == 1, (h, c)
                assert c["all-gather"] == 0 and c["collective-permute"] == 0, c
            ra, msa, ma = rt_t.run_gr_tx_batch(
                ps_t, rt_t.empty_cache(), ttable, plan, roots
            )
            rb, msb, mb = rt_p.run_gr_tx_batch(
                ps_p, rt_p.empty_cache(), ttable, plan, roots
            )
            assert np.array_equal(ra, rb)
            assert mkey(msa) == mkey(msb)
            for k in ma:
                assert ma[k] == mb[k], (k, ma[k], mb[k])
            # attribution is a decomposition, not an estimate: per-owner
            # columns sum exactly to the step's global metrics
            stage = rt_t.last_owner_stage
            assert stage is not None and stage.shape[0] == 8
            assert rt_p.last_owner_stage is None
            from repro.obs.metrics import OWNER_STAGE_FIELDS
            col = {f: int(stage[:, i].sum())
                   for i, f in enumerate(OWNER_STAGE_FIELDS)}
            assert col["probe_hits"] == ma["hits"]
            assert col["miss_rows"] == ma["misses"]
            assert col["edges_scanned"] == ma["edges_scanned"]
            assert col["leaf_fetches"] == ma["leaf_fetches"]
            assert col["route_overflow"] == ma["route_overflow"]
            assert rt_t.last_step_owner_seconds.shape == (8,)
        print("TELEMETRY_BUDGET_OK")
        """,
        "TELEMETRY_BUDGET_OK",
    )
