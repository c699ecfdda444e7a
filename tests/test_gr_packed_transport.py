"""The gR step's host<->device boundary: one packed buffer each way.

``ShardedTxnRuntime`` sends a gR batch to the device as one int32
``[bucket, 2]`` buffer and brings every output back packed into one int32
buffer, sliced on the host by the layout recorded when the program was
traced (``graph_serve._GrStep``). Two things are pinned here:

- byte identity: ``run_gr_tx_batch`` and ``serve_step`` give the same host
  outputs (results, deferred flags, miss records, metrics, the per-owner
  stage block, the read version), dtype and bytes, as the same hop driver
  with the unpacked transport (its six outputs as a pytree, fetched leaf by
  leaf). Every plan of the benchmark's traffic, buckets 8 and 32, cache on
  and off, 1 and 4 virtual devices, cold and after a CP drain; plus a
  forced ``route_cap_factor="auto"`` overflow retry and a locality retry
  through a split vertex;
- the transfer count: a healthy call makes one ``jax.device_put`` and one
  ``jax.device_get`` of one array and nothing implicit, and
  ``gr_host_transfers`` rises by 2 per dispatch (3 with the overflow retry,
  which reuses the input on the device; 4 with the locality retry).

The identity cases run in subprocesses with 4 virtual CPU devices
(XLA_FLAGS must be set before JAX starts); a 1-device case meshes the
first of them.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from bench import graphgen, system
    from bench.tests.test_bench_generator import tiny
    from repro.distributed import graph_serve
    from repro.distributed.graph_serve import ShardedMissDrain, _GrStep
    from repro.distributed.routing import RoutingTableHost
    from repro.graphstore.store import ingest_host

    SEED = 2_147_483_659

    cfg, traffic = tiny(config=CONFIG, v_total=1024)
    if cfg["use_cache"]:
        cfg["cache_slots_total"] = 256
    graph = graphgen.make_graph(cfg, SEED)
    sched = graphgen.make_schedule(cfg, traffic, graph, SEED, 400.0, 1.0)
    espec = system.engine_spec(cfg)
    host = ingest_host(espec.store, graph.vlabel, graph.vprops, graph.esrc,
                       graph.edst, graph.elabel, graph.eprops)
    e_blk = system.block_capacity(cfg, N, host)


    class Unpacked(_GrStep):
        # the same hop driver with the unpacked transport: the six outputs
        # as a pytree, fetched leaf by leaf by jax.device_get
        def __init__(self, rt, plan, bucket, worst_case):
            fused = rt._gr_plan_fn(plan, bucket, worst_case=worst_case)
            a = rt.axes

            def body(store, cache, ttable, qin, down, rtable):
                return fused(store, cache, ttable, qin[:, 0], qin[:, 1] != 0,
                             down, rtable)

            super().__init__(rt, jax.jit(shard_map(
                body, mesh=rt.mesh,
                in_specs=(rt._store_specs(), rt._cache_specs(), P(), P(a),
                          P(), P()),
                out_specs=(P(a),) * 4 + (P(), P()), check_vma=False,
            )), None)

        def unpack(self, out):
            return out


    def unpacked_runtime(sysm):
        rt, steps = sysm.rt, {}

        def gr(plan, bucket, *, pspec=None, worst_case=False):
            caps = tuple(rt._hop_route_caps(plan, bucket // rt.n,
                                            worst_case=worst_case))
            key = (graph_serve._plan_key(plan), bucket, caps)
            if key not in steps:
                steps[key] = Unpacked(rt, plan, bucket, worst_case)
            return steps[key]

        rt._gr = gr
        return sysm


    packed = system.build(cfg, traffic, espec, N, e_blk)
    ref = unpacked_runtime(system.build(cfg, traffic, espec, N, e_blk))
    store = packed.rt.partition_store(host)
    caches = {"packed": packed.rt.empty_cache(), "ref": ref.rt.empty_cache()}


    def same(a, b, tag):
        la, ta = jax.tree_util.tree_flatten(a)
        lb, tb = jax.tree_util.tree_flatten(b)
        assert ta == tb, (tag, ta, tb)
        for x, y in zip(la, lb):
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype and x.shape == y.shape, (tag, x, y)
            assert x.tobytes() == y.tobytes(), (tag, x, y)


    def records(ms):
        return [(m.tpl_idx, m.root, m.params.tobytes(), m.read_version)
                for m in ms]


    def serve(p, roots, tag):
        # run_gr_tx_batch on both transports; returns the packed side's
        # metrics and misses after checking every host output
        out = {}
        for side, sysm in (("packed", packed), ("ref", ref)):
            res, misses, met, dfr = sysm.rt.run_gr_tx_batch(
                store, caches[side], sysm.ttable, sysm.plans[p], roots,
                return_deferred=True)
            out[side] = (res, records(misses), met, dfr,
                         sysm.rt.last_owner_stage, misses)
        a, b = out["packed"], out["ref"]
        same(a[0], b[0], (tag, "result"))
        assert a[1] == b[1], (tag, "misses")
        assert a[2] == b[2] and list(a[2]) == list(b[2]), (tag, a[2], b[2])
        same(a[3], b[3], (tag, "deferred"))
        same(a[4], b[4], (tag, "owner_stage"))
        return a[2], out


    def step_outputs(p, roots, bucket, tag):
        # serve_step's six outputs, raw miss arrays and version included
        bvalid = np.arange(bucket) < len(roots)
        proots = np.resize(roots, bucket) * bvalid
        got = [sysm.rt.serve_step(sysm.plans[p], bucket)(
                   store, caches[side], sysm.ttable, proots, bvalid)
               for side, sysm in (("packed", packed), ("ref", ref))]
        assert len(got[0]) == 6
        same(got[0], jax.device_get(got[1]), (tag, "serve_step"))
    """
)

PLANS = textwrap.dedent(
    """
    drains = {side: ShardedMissDrain(s.rt, s.tpl_meta)
              for side, s in (("packed", packed), ("ref", ref))}
    hits = {"cold": 0, "warm": 0}
    for phase in hits:
        for p in range(len(packed.plans)):
            pick = np.flatnonzero(sched.plan == p)
            for bucket, B in ((8, 6), (32, 20)):
                roots = sched.root[pick[:B]].astype(np.int32)
                tag = (phase, traffic["plans"][p]["name"], bucket)
                met, out = serve(p, roots, tag)
                assert met["host_syncs"] == 1, (tag, met)
                hits[phase] += met["hits"]
                step_outputs(p, roots, bucket, tag)
                for side in drains:
                    drains[side].push(out[side][5])
        if phase == "cold" and packed.rt.use_cache:
            # both caches populated from the same miss records, so the
            # warm pass serves hits
            for side, sysm in (("packed", packed), ("ref", ref)):
                caches[side] = drains[side].drain(
                    store, store, caches[side], sysm.ttable, 1024)
            assert drains["packed"].committed == drains["ref"].committed > 0
    assert hits["cold"] == 0 and (hits["warm"] > 0) == packed.rt.use_cache
    print("PACKED_IDENTITY_OK")
    """
)

OVERFLOW = textwrap.dedent(
    """
    # buckets of one row per peer: two rows of one shard routed to one
    # owner overflow, and "auto" re-dispatches on the worst-case variant
    graph_serve.DEFAULT_ROUTE_CAP_FACTOR = (1, 1)
    for s in (packed, ref):
        s.rt.route_cap_factor = "auto"
    p = [q["name"] for q in traffic["plans"]].index("q_fig1")
    pick = sched.root[np.flatnonzero(sched.plan == p)]
    r0 = int(pick[0])
    same_owner = [int(r) for r in pick if r % N == r0 % N]
    roots = np.asarray(same_owner[:6], np.int32)
    assert len(roots) == 6
    t0, g0 = packed.rt.gr_host_transfers, packed.rt.route_cap_retries
    met, _ = serve(p, roots, "overflow")
    assert met["route_cap_retries"] == 1 and met["host_syncs"] == 2, met
    assert packed.rt.route_cap_retries == g0 + 1
    assert packed.rt.gr_host_transfers - t0 == 3
    print("PACKED_IDENTITY_OK")
    """
)

LOCALITY = textwrap.dedent(
    """
    for s in (packed, ref):
        s.rt.attach_routing(RoutingTableHost(N))
    p = [q["name"] for q in traffic["plans"]].index("q_fig1")
    roots = np.unique(sched.root[sched.plan == p])[:6].astype(np.int32)
    for s in (packed, ref):
        for r in roots[:2]:
            s.rt.rhost.set_cache_owner(int(r), (int(r) + 1) % N)
    t0 = packed.rt.gr_host_transfers
    met, _ = serve(p, roots, "locality")
    assert met["locality_retry_rows"] == 2 and met["host_syncs"] == 2, met
    assert packed.rt.locality_retries == 1
    assert packed.rt.gr_host_transfers - t0 == 4
    print("PACKED_IDENTITY_OK")
    """
)

CASES = {"plans": PLANS, "overflow": OVERFLOW, "locality": LOCALITY}


@pytest.mark.parametrize("n,config,case", [
    (1, "ecom-cache", "plans"),
    (1, "ecom-nocache", "plans"),
    (4, "ecom-cache", "plans"),
    (4, "ecom-nocache", "plans"),
    (4, "ecom-cache", "overflow"),
    (4, "ecom-cache", "locality"),
])
def test_packed_step_outputs_are_byte_identical(n, config, case):
    script = (f"N = {n}\nCONFIG = {config!r}\n" + PRELUDE + CASES[case])
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [REPO, os.path.join(REPO, "src")]))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=REPO)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "PACKED_IDENTITY_OK" in out.stdout, out.stdout


def test_one_transfer_each_way_per_dispatch(world, monkeypatch):
    import jax

    from conftest import common_watchlist_plan
    from repro.distributed import flat_mesh
    from repro.distributed.graph_serve import ShardedTxnRuntime

    rt = ShardedTxnRuntime(world["espec"], flat_mesh(1))
    ps = rt.partition_store(world["store"])
    cache = rt.empty_cache()
    plan = common_watchlist_plan()
    roots = np.array([4, 5, 7, 9, 11], np.int32)
    rt.run_gr_tx_batch(ps, cache, world["ttable"], plan, roots)  # compile

    gets, puts = [], []
    real_get, real_put = jax.device_get, jax.device_put
    monkeypatch.setattr(jax, "device_get",
                        lambda x: gets.append(x) or real_get(x))
    monkeypatch.setattr(jax, "device_put",
                        lambda x, *a, **k: puts.append(x) or real_put(x, *a, **k))
    t0 = rt.gr_host_transfers
    with jax.transfer_guard("disallow"):
        for i in range(3):
            res, misses, met = rt.run_gr_tx_batch(
                ps, cache, world["ttable"], plan, roots)
            assert rt.gr_host_transfers - t0 == 2 * (i + 1)
    assert len(gets) == 3 and len(puts) == 3, (len(gets), len(puts))
    assert all(isinstance(g, jax.Array) for g in gets)
    assert all(p.shape == (8, 2) and p.dtype == np.int32 for p in puts)
    assert met["host_syncs"] == 1

    # serve_step keeps its six outputs and runs the same compiled program
    step = rt.serve_step(plan, 8)
    out = step(ps, cache, world["ttable"], np.resize(roots, 8),
               np.arange(8) < len(roots))
    assert len(out) == 6 and rt.gr_host_transfers - t0 == 8
    result, deferred, miss_roots, miss_counts, m, version = out
    assert np.array_equal(result[:len(roots)], res)
    assert deferred.dtype == bool and not deferred.any()
    assert len(miss_roots) == len(miss_counts) == 2
    assert int(version) == 0 and m["requests"] == met["requests"]
    assert step.jitted._cache_size() == 1, step.jitted._cache_size()
    # a profiler trace names the program jit_fused, as the benchmark's
    # device readers expect
    assert step.jitted.__name__ == "fused"
