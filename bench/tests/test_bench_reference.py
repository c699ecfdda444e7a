"""The plain reference against the program: per template against
``core/oracle.onehop_oracle``, and per plan against the partitioned
runtime on a small world, cold (misses), after a CP drain (hits) and after
a commit that changes cached answers."""

from types import SimpleNamespace

import numpy as np
import pytest

from bench import graphgen, harness
from bench.reference import Reference, hop_specs
from bench.tests.test_bench_generator import tiny

SEED = 2_147_483_659  # just past 2**31


def _store_view(ref, graph):
    """The host arrays ``onehop_oracle`` scans, with the reference's writes."""
    ne = len(ref.new_src)
    props = np.asarray(ref.new_props, np.int32).reshape(ne, graph.eprops.shape[1])
    return SimpleNamespace(
        vlabel=graph.vlabel, valive=np.ones(len(graph.vlabel), bool),
        vprops=ref.vprops,
        esrc=np.concatenate([graph.esrc, np.asarray(ref.new_src, np.int32)]),
        edst=np.concatenate([graph.edst, np.asarray(ref.new_dst, np.int32)]),
        elabel=np.concatenate([graph.elabel, np.asarray(ref.new_label, np.int32)]),
        ealive=np.concatenate([ref.alive, np.asarray(ref.new_alive, bool)]),
        eprops=np.concatenate([graph.eprops, props]),
        v_len=len(graph.vlabel), e_len=graph.n_edges + ne,
    )


def test_reference_matches_the_oracle_per_template():
    from repro.core.oracle import HostStore, onehop_oracle

    from bench import system

    cfg, traffic = tiny(v_total=1024)
    graph = graphgen.make_graph(cfg, SEED)
    sched = graphgen.make_schedule(cfg, traffic, graph, SEED, 400.0, 5.0)
    ref = Reference(cfg, graph)
    for w in sched.writes:
        ref.apply(w)
    hs = HostStore(_store_view(ref, graph))
    rng = np.random.default_rng(0)
    checked = 0
    for p in traffic["plans"]:
        qplan = system.query_plan(cfg, p)
        for k, (hop, spec) in enumerate(zip(qplan.hops, hop_specs(cfg, p))):
            lo, hi = graph.ranges[p["root_label"]] if k == 0 else (0, len(graph.vlabel))
            for r in rng.integers(lo, hi, 12):
                want = onehop_oracle(hs, hop.direction, hop.edge_label, hop.pr,
                                     hop.pe, hop.pl, int(r), hop.params)
                got = set(ref.onehop(spec, int(r)).tolist())
                assert got == want, (p["name"], int(r))
                checked += len(want)
    assert checked > 50


@pytest.mark.parametrize("config", ["ecom-cache", "ecom-nocache"])
def test_reference_agrees_with_the_partitioned_runtime(config):
    import jax

    from repro.graphstore import make_mutation_batch
    from repro.graphstore.store import ingest_host

    from bench import system

    cfg, traffic = tiny(config=config, v_total=1024)
    if cfg["use_cache"]:
        cfg["cache_slots_total"] = 256
    traffic["batch_cap"] = 32
    graph = graphgen.make_graph(cfg, SEED)
    sched = graphgen.make_schedule(cfg, traffic, graph, SEED, 400.0, 1.0)
    espec = system.engine_spec(cfg)
    host = ingest_host(espec.store, graph.vlabel, graph.vprops, graph.esrc,
                       graph.edst, graph.elabel, graph.eprops)
    sysm = system.build(cfg, traffic, espec, 1, system.block_capacity(cfg, 1, host))
    loop = harness.Loop(sysm, sysm.rt.partition_store(host), sysm.rt.empty_cache(),
                        sched, traffic, graph)
    ref = Reference(cfg, graph)
    hops = [hop_specs(cfg, p) for p in traffic["plans"]]
    picks = [np.flatnonzero(sched.plan == p)[:32] for p in range(len(traffic["plans"]))]

    def serve_and_compare(tag):
        hits = misses = 0
        for p, idxs in enumerate(picks):
            before = dict(loop.out.gr_sums)
            loop.recording = True
            got_misses = loop.serve(p, idxs)
            loop.recording = False
            loop.drain_step(got_misses)
            hits += loop.out.gr_sums["hits"] - before.get("hits", 0)
            misses += loop.out.gr_sums["misses"] - before.get("misses", 0)
            for i in idxs:
                want, _ = ref.plan(hops[p], traffic["plans"][p], int(sched.root[i]))
                got = harness.answer(traffic["plans"][p], loop.out.answers[int(i)],
                                     sysm.final_ids_from_values[p], cfg)
                assert got == want, (tag, traffic["plans"][p]["name"], int(i))
        return hits, misses

    h0, m0 = serve_and_compare("cold")
    assert m0 > 0
    h1, _ = serve_and_compare("after the CP drain")
    assert (h1 > 0) == bool(cfg["use_cache"])
    # a commit that changes cached answers: a qualifying listing joins a
    # served watch-list, and one of its edges goes
    w = int(sched.root[picks[0][0]])
    out_l = {int(x) for x in ref.edges(w, "out")[0]}
    lo, hi = graph.ranges["listing"]
    listing = next(v for v in range(lo, hi)
                   if v not in out_l and ref.degree(v, "in") < 4)
    eid = next(e for e in range(*graph.includes) if int(graph.esrc[e]) == w)
    VP, EL = cfg["schema"]["vertex_props"], cfg["schema"]["edge_labels"]
    changes = {"set_vprops": [(listing, VP["Status"], 0)],
               "new_edges": [(w, listing, EL["includes"], [1])],
               "del_edges": [eid]}
    store, cache, m = loop.rt.run_grw_tx(
        loop.store, loop.cache, sysm.ttable,
        make_mutation_batch(sysm.espec.store, **changes))
    jax.block_until_ready((store, cache))
    assert m["op_overflow"] == 0 and m["store_append_overflow"] == 0
    if cfg["use_cache"]:
        assert m["impacted_keys"] > 0
    loop.store, loop.cache = store, cache
    ref.apply(changes)
    serve_and_compare("after the commit")
