"""The generator: repeatable from a seed, within every cap the
configuration states at every point of the schedule, and true to the
traffic file's shares."""

import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import graphgen
from bench.graphgen import READ, WRITE
from bench.reference import Reference, hop_specs

BENCH = Path(__file__).resolve().parents[1]
SEED = 3_000_000_019  # past 31 bits, as the benchmark's seeds are


def tiny(config="ecom-cache", v_total=4096):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg["v_total"] = v_total
    cfg["edge_capacity"] = int(1.6 * v_total) + 256  # 1.56 edges per vertex
    traffic = json.loads((BENCH / "traffic" / "rhat.json").read_text())
    traffic["warmup_requests"] = 300
    return cfg, traffic


def world(seed=SEED, rate=400.0, seconds=5.0, **kw):
    cfg, traffic = tiny(**kw)
    graph = graphgen.make_graph(cfg, seed)
    sched = graphgen.make_schedule(cfg, traffic, graph, seed, rate, seconds)
    return cfg, traffic, graph, sched


def test_generator_repeats_from_seed():
    a, b = world(), world()
    for f in ("vlabel", "vprops", "esrc", "edst", "elabel", "eprops"):
        assert np.array_equal(getattr(a[2], f), getattr(b[2], f)), f
    for f in ("due", "kind", "plan", "root", "write"):
        assert np.array_equal(getattr(a[3], f), getattr(b[3], f)), f
    assert a[3].writes == b[3].writes
    c = world(seed=SEED + 1)
    assert not np.array_equal(a[2].edst, c[2].edst)
    assert not np.array_equal(a[3].root, c[3].root)
    # another seed: the same counts of every kind, in another order
    assert Counter(a[3].plan.tolist()) == Counter(c[3].plan.tolist())
    assert len(a[3].writes) == len(c[3].writes)


@pytest.mark.parametrize("seed", [SEED, 7, -5])
def test_no_vertex_or_frontier_exceeds_its_cap_at_any_point(seed):
    """Every plan from every root of its label, before the first write and
    after each write of the schedule, stays within ``max_deg`` per
    direction and ``frontier`` per hop."""
    cfg, traffic, graph, sched = world(seed=seed, rate=2000.0, seconds=5.0)
    ref = Reference(cfg, graph)
    F, D = int(cfg["frontier"]), int(cfg["max_deg"])
    plans = traffic["plans"]
    hops = [hop_specs(cfg, p) for p in plans]
    g = cfg["graph"]
    w_lo, w_hi = graph.ranges["watchlist"]
    l_lo, l_hi = graph.ranges["listing"]

    def check_all(roots_per_plan):
        for p, roots in roots_per_plan:
            for r in roots:
                _, trail = ref.plan(hops[p], plans[p], int(r))
                for frontier, counts in trail:
                    assert len(frontier) <= F and max(counts, default=0) <= F

    every = [(p, range(*graph.ranges[pl["root_label"]])) for p, pl in enumerate(plans)]
    check_all(every)
    writes = [sched.writes[sched.write[i]] for i in np.flatnonzero(sched.kind == WRITE)]
    assert sum(bool(w.get("new_edges")) for w in writes) > 20
    for w in writes:
        ref.apply(w)
    size = np.array([ref.degree(v, "out") for v in range(w_lo, w_hi)])
    watchers = np.array([ref.degree(v, "in") for v in range(l_lo, l_hi)])
    assert size.max() <= g["list_size_max"]
    assert watchers.max() <= g["watchers_per_listing_max"]
    for d in ("out", "in"):
        assert max(ref.degree(v, d) for v in range(len(graph.vlabel))) <= D
    check_all(every)


def test_plan_write_and_root_shares_match_the_traffic_file():
    cfg, traffic, graph, sched = world(rate=20000.0, seconds=5.0)
    win = np.arange(sched.n_warmup, len(sched))
    n = len(win)
    kinds = sched.kind[win]
    assert n == 100000
    assert int(np.sum(kinds == WRITE)) == round(n * (1 - traffic["read_share"]))
    reads = win[kinds == READ]
    w = np.array([p["weight"] for p in traffic["plans"]])
    want = w / w.sum() * len(reads)
    got = np.bincount(sched.plan[reads], minlength=len(w))
    assert np.all(np.abs(got - want) <= 1)
    types = Counter(tuple(sorted(sched.writes[sched.write[i]]))
                    for i in win[kinds == WRITE])
    wts = {x["type"]: x["weight"] for x in traffic["writes"]}
    total = sum(wts.values())
    n_w = int(np.sum(kinds == WRITE))
    assert abs(types[("del_edges",)] - n_w * wts["del_edges"] / total) <= 1
    assert abs(types[("new_edges", "set_vprops")] + types[()]
               - n_w * wts["upsert"] / total) <= 1
    assert abs(types[()] - round(n_w * wts["upsert"] / total * 0.3)) <= 1
    # roots: bounded Zipf(a) over the label through a permutation; the
    # most drawn watch-list of q_fig1 takes rank 1's share
    a = traffic["roots"]["a"]
    p0 = np.flatnonzero(sched.plan[reads] == 0)
    roots = sched.root[reads][p0]
    N = graph.ranges["watchlist"][1] - graph.ranges["watchlist"][0]
    top = 1.0 / np.sum(np.arange(1, N + 1, dtype=np.float64) ** -a)
    share = Counter(roots.tolist()).most_common(1)[0][1] / len(roots)
    sigma = np.sqrt(top * (1 - top) / len(roots))
    assert abs(share - top) < 5 * sigma
    # the hot set is scattered, not one id range
    hot = [v for v, _ in Counter(roots.tolist()).most_common(20)]
    assert np.ptp(hot) > N // 4


def test_a_shape_seed_gives_every_seed_the_same_work_on_other_data():
    """With ``shape_seed`` the arrivals, kinds, plans and root ranks are the
    same for every seed; the graph and the roots themselves are not."""
    cfg, traffic = tiny()
    assert "shape_seed" in traffic
    scheds = {}
    for shaped in (True, False):
        t = dict(traffic) if shaped else {k: v for k, v in traffic.items()
                                          if k != "shape_seed"}
        for seed in (SEED, SEED + 1):
            graph = graphgen.make_graph(cfg, seed)
            scheds[shaped, seed] = (graph, graphgen.make_schedule(
                cfg, t, graph, seed, 400.0, 5.0))
    (ga, a), (gc, c) = scheds[True, SEED], scheds[True, SEED + 1]
    for f in ("due", "kind", "plan"):
        assert np.array_equal(getattr(a, f), getattr(c, f)), f
    assert not np.array_equal(ga.edst, gc.edst)
    assert not np.array_equal(a.root, c.root)
    for p in range(len(traffic["plans"])):  # one rank sequence, relabelled
        ra, rc = a.root[a.plan == p], c.root[c.plan == p]
        first = {}
        pattern_a = [first.setdefault(r, len(first)) for r in ra.tolist()]
        first = {}
        pattern_c = [first.setdefault(r, len(first)) for r in rc.tolist()]
        assert pattern_a == pattern_c
    assert not np.array_equal(scheds[False, SEED][1].due, scheds[False, SEED + 1][1].due)


def test_sweep_steps_are_separated_by_gaps_with_no_arrivals():
    rng = graphgen.rng_for(SEED, 9)
    arr = {"process": "steps", "gap_s": 5.0, "rates_x": [1.0, 2.0]}
    t = graphgen.arrival_times(rng, 10.0, 30.0, arr)
    assert graphgen.step_phases(30.0, [1.0, 2.0], 5.0) == [(0.0, 10.0, 1.0),
                                                          (15.0, 25.0, 2.0)]
    assert np.sum(t < 10.0) == 100 and np.sum((t >= 15.0) & (t < 25.0)) == 200
    assert len(t) == 300
