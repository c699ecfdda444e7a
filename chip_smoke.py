"""On-chip smoke of the partitioned serving path at deployment size.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # the sharded tier on flat_mesh(4)

Runs the paper's whole consistency cycle through ``ShardedTxnRuntime``
on the eCommerce deployment (``configs.ecommerce_graph.CHIP``: FULL widths,
2^23 vertices and 2^22 cache slots per chip, data generated from
``--seed``): ``partition_store``, a cold gR-Tx batch (misses), a
``ShardedMissDrain`` CP drain, a warm batch (hits), one gRW commit that
invalidates cached entries, and a read after the commit. A sample of every
batch is compared with ``core.oracle.onehop_oracle`` on the host view of
the store (the post-commit view for the last read).

Exits nonzero when JAX finds no TPU, on any mismatch and on any failed
phase. The last line of stdout is the JSON result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The compile cache lives where ``repro.launch.compile_cache`` says.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


def fail(msg: str):
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(cond, msg: str):
    if not cond:
        fail(msg)


class CompileClock:
    """Sums JAX's own compile events (trace, lowering, backend compile or
    persistent-cache load) over the run."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_bytes(*trees) -> dict:
    """Bytes each device holds of the given arrays, from shard shapes."""
    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(trees):
        for sh in leaf.addressable_shards:
            out[sh.device] = out.get(sh.device, 0) + sh.data.nbytes
    return out


def oracle_view(h, roots, *, vprops=None, dead=(), new_edges=()):
    """The host view ``onehop_oracle`` scans, cut to the edges that touch
    ``roots`` (one vectorized ``np.isin`` per endpoint). ``vprops``,
    ``dead`` (edge slots) and ``new_edges`` ((src, dst, label, props))
    apply a commit to the view."""
    e = int(h.e_len)
    idx = np.flatnonzero(np.isin(h.esrc[:e], roots) | np.isin(h.edst[:e], roots))
    alive = h.ealive[idx] & ~np.isin(idx, np.asarray(dead, np.int64))
    ne = list(new_edges)
    nep = h.eprops.shape[1]
    return SimpleNamespace(
        vlabel=h.vlabel, valive=h.valive,
        vprops=h.vprops if vprops is None else vprops,
        esrc=np.concatenate([h.esrc[idx], [x[0] for x in ne]]).astype(np.int32),
        edst=np.concatenate([h.edst[idx], [x[1] for x in ne]]).astype(np.int32),
        elabel=np.concatenate([h.elabel[idx], [x[2] for x in ne]]).astype(np.int32),
        ealive=np.concatenate([alive, np.ones(len(ne), bool)]),
        eprops=np.concatenate(
            [h.eprops[idx], np.asarray([x[3] for x in ne], np.int32).reshape(-1, nep)]
        ),
        v_len=h.v_len, e_len=len(idx) + len(ne),
    )


def mismatches(view, hop, roots, results) -> int:
    """Rows whose leaf set differs from the oracle's (or repeats a leaf)."""
    from repro.core.oracle import HostStore, onehop_oracle

    hs = HostStore(view)
    bad = 0
    for r, row in zip(roots, results):
        got = row[row >= 0]
        want = onehop_oracle(hs, hop.direction, hop.edge_label, hop.pr,
                             hop.pe, hop.pl, int(r), hop.params)
        bad += int(set(got.tolist()) != want or len(got) != len(want))
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)")
    print(f"compile cache: {cache_dir}", flush=True)
    run(devs, args.chips, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips,
    }}))
    return 0


def run(devs, n: int, seed: int, batch: int = 512, n_sample: int = 256,
        vertices: int | None = None):
    """Every phase on the first ``n`` of ``devs``; raises ``SystemExit``
    on any failure. ``batch`` is gR-Tx roots per chip per batch, of which
    ``n_sample`` are compared with the oracle. ``vertices`` cuts the scale
    below CHIP's (tests and CPU rehearsals call ``run`` directly)."""
    import jax

    d0 = devs[0]
    check(len(devs) >= n, f"{n} chips needed, found {len(devs)} devices")
    clock = CompileClock()

    from repro.configs.ecommerce_graph import CHIP_REDUCED
    from repro.core.cache import empty_cache
    from repro.distributed.graph_serve import (
        ShardedMissDrain, ShardedTxnRuntime, config_espec,
        config_plan_and_ttable,
    )
    from repro.distributed.sharding import flat_mesh
    from repro.graphstore import make_mutation_batch
    from repro.graphstore.partition import abstract_partitioned_store, tree_nbytes
    from repro.graphstore.store import ingest_host
    from repro.launch.serve import block_capacity, deployment_config, generate_graph

    print(f"device: {d0.platform} {d0.device_kind} x{len(devs)} "
          f"(using {n})", flush=True)
    cfg = deployment_config(n, vertices)
    for cut in CHIP_REDUCED:
        print(f"reduced: {cut}", flush=True)
    print(f"deployment: v_total={cfg.v_total} e_cap={cfg.e_total()} "
          f"cache_slots={cfg.cache_slots_total} max_deg={cfg.max_deg} "
          f"max_leaves={cfg.max_leaves} n_vprops={cfg.n_vprops} "
          f"n_eprops={cfg.n_eprops} recent_cap={cfg.recent_cap}", flush=True)
    espec = config_espec(cfg)
    plan, ttable = config_plan_and_ttable(cfg)
    hop = plan.hops[0]

    t = time.perf_counter()
    host = ingest_host(espec.store, *generate_graph(cfg, seed))
    ingest_s = time.perf_counter() - t
    print(f"ingest: {int(host.e_len)} edges on the host in {ingest_s:.3f} s",
          flush=True)

    rt = ShardedTxnRuntime(espec, flat_mesh(n),
                           e_blk_cap=block_capacity(host, n))
    cache_shapes = jax.eval_shape(lambda: empty_cache(espec.cache))
    want_bytes = (tree_nbytes(abstract_partitioned_store(rt.pspec)),
                  tree_nbytes(cache_shapes))
    print(f"reckoned from shapes: store {want_bytes[0]} B + cache "
          f"{want_bytes[1]} B = {sum(want_bytes)} B over {n} chip(s) "
          f"(e_blk_cap={rt.pspec.e_blk_cap})", flush=True)
    t = time.perf_counter()
    pstore = rt.partition_store(host)
    cache = rt.empty_cache()
    jax.block_until_ready((pstore, cache))
    partition_s = time.perf_counter() - t
    per_dev = device_bytes(pstore, cache)
    print(f"partition: {partition_s:.3f} s; bytes on device: "
          + ", ".join(f"{d.id}:{b}" for d, b in sorted(
              per_dev.items(), key=lambda kv: kv[0].id)), flush=True)
    check(min(per_dev.values()) >= 4 * 10**9 or vertices is not None,
          f"store + cache below 4 GB on a chip: {per_dev}")

    rng = np.random.default_rng(seed + 1)
    B = batch * n
    roots = rng.choice(cfg.v_total, B, replace=False).astype(np.int32)
    sample = roots[:n_sample]
    pre = oracle_view(host, sample)
    total_bad = 0

    def gr(tag, store, cache, view):
        nonlocal total_bad
        t = time.perf_counter()
        res, misses, m = rt.run_gr_tx_batch(store, cache, ttable, plan, roots)
        dt = time.perf_counter() - t
        check(m["route_overflow"] == 0 and m["deferred"] == 0,
              f"{tag}: route_overflow={m['route_overflow']} "
              f"deferred={m['deferred']}")
        bad = mismatches(view, hop, sample, res[:n_sample])
        total_bad += bad
        print(f"{tag}: hits={m['hits']} misses={m['misses']} "
              f"truncated={m['truncated']} mismatches={bad}/{len(sample)} "
              f"in {dt:.3f} s", flush=True)
        return res, misses, m

    res_cold, misses, m = gr("cold", pstore, cache, pre)
    check(m["misses"] > 0, "cold batch had no misses")

    t = time.perf_counter()
    tpl_meta = {hop.tpl_idx: (hop.direction, hop.edge_label)}
    drain = ShardedMissDrain(rt, tpl_meta)
    drain.push(misses)
    cache = drain.drain(pstore, pstore, cache, ttable, len(misses))
    jax.block_until_ready(cache)
    print(f"cp_drain: committed={drain.committed} aborted={drain.aborted} "
          f"pending={drain.pending()} in {time.perf_counter() - t:.3f} s",
          flush=True)
    check(drain.committed > 0 and drain.pending() == 0, "CP drain stalled")

    res_warm, _, m = gr("warm", pstore, cache, pre)
    check(m["hits"] > 0, "warm batch had no hits")
    check(np.array_equal(res_warm, res_cold), "warm results differ from cold")

    # the commit: delete one edge and add one qualifying edge under
    # different cached sample roots, and flip the leaf property of leaves
    # that cached results hold
    e = int(host.e_len)
    lo = np.searchsorted(host.esrc[:e], sample)  # esrc is sorted by src
    has = np.flatnonzero(host.esrc[np.minimum(lo, e - 1)] == sample)
    dead = [int(lo[i]) for i in has[:8]]
    new_edges = [(int(r), int(rng.integers(cfg.v_total)), 0, [cfg.edge_val])
                 for r in sample[-8:]]
    leaves = np.unique(res_warm[:n_sample][res_warm[:n_sample] >= 0])
    flips = [(int(v), cfg.leaf_prop, 1 - int(host.vprops[v, cfg.leaf_prop]))
             for v in leaves[:8]]
    mb = make_mutation_batch(espec.store, new_edges=new_edges, del_edges=dead,
                             set_vprops=flips)
    t = time.perf_counter()
    pstore2, cache2, wm = rt.run_grw_tx(pstore, cache, ttable, mb)
    jax.block_until_ready((pstore2, cache2))
    print(f"grw: impacted={wm['impacted_keys']} "
          f"op_overflow={wm['op_overflow']} "
          f"append_overflow={wm['store_append_overflow']} "
          f"in {time.perf_counter() - t:.3f} s", flush=True)
    check(wm["impacted_keys"] > 0, "commit invalidated nothing")
    check(wm["op_overflow"] == 0 and wm["store_append_overflow"] == 0,
          "commit overflowed")
    del pstore, cache  # the pre-commit state leaves the device

    vprops = host.vprops.copy()
    for v, pid, val in flips:
        vprops[v, pid] = val
    post = oracle_view(host, sample, vprops=vprops, dead=dead,
                       new_edges=new_edges)
    gr("post_commit", pstore2, cache2, post)

    stats = d0.memory_stats() or {}
    print(f"compile: {clock.seconds:.3f} s (persistent cache hits "
          f"{clock.cache_hits}); peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    print(f"mismatches: {total_bad}", flush=True)
    check(total_bad == 0, f"{total_bad} results differ from the oracle")


if __name__ == "__main__":
    sys.exit(main())
