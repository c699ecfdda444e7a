"""Serving driver for the paper's architecture: run the sharded transaction
runtime — owner-routed gR-Txs over the partitioned dual-CSR storage tier
with the co-partitioned cache — on a local debug mesh with real data and
report hit/overflow statistics plus the storage-tier memory profile.

  PYTHONPATH=src python -m repro.launch.serve --shards 4 --batches 10

The loop exercises the full serving life-cycle on a host:

- gR-Tx batches through ``ShardedTxnRuntime.serve_step``, each pinning its
  read epoch in the journal's ``EpochRegistry`` (the liveness fence that
  makes tombstone purge safe to enable);
- the **sharded MissQueue drain**: ``serve_step``'s per-shard miss records
  land in per-owner CP queues (``ShardedMissDrain``) and each CP batch
  executes + inserts at a single owner shard — no host-side global-FIFO
  round-trip;
- interleaved gRW-Tx commits (``--write-every``) with the **on-device
  maintenance gate**: the commit step itself compacts over-threshold
  blocks inside ``lax.cond`` (no per-batch host round-trip), with purge
  enabled per commit only when ``EpochRegistry.safe_to_purge`` allows;
- **write-behind durability**: every commit is appended to the
  ``WriteBehindJournal`` (async coalescing flusher runs behind the loop)
  and checkpointed every ``--checkpoint-every`` commits, so a crashed
  run restarts via ``journal.replay`` instead of losing the store;
- **hitless capacity growth**: when commit metrics cross the occupancy
  high-water, the next tier's gR/gRW/CP steps compile on a background
  thread (``precompile_next_tier``) while serving continues on the current
  tier; the store hot-swaps at a batch boundary (``swap_to_next_tier``)
  once they are ready — the growth pause is one device pad, not a
  recompile.

The deployment is the paper's eCommerce graph at ``configs.ecommerce_graph``
``CHIP`` widths and scale per device (``deployment_config``), generated
in bulk from ``--seed`` (``generate_graph``) and built on the host, so the
same code loads a chip to its real size; ``--vertices`` scales it down for
a CPU run. The mesh is ``jax.devices()``' first ``--shards`` devices: for
virtual CPU devices set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before starting.
"""

from __future__ import annotations

import argparse
import tempfile
import time

import numpy as np

# edge-block fill at ingest: below MaintenancePolicy's 0.85 grow high-water,
# so a fresh deployment does not grow on its first commit
BLOCK_FILL = 0.8


def deployment_config(n_shards: int = 1, vertices: int | None = None):
    """The eCommerce deployment for ``n_shards`` devices: ``CHIP``'s
    per-device scale times the device count, at FULL widths. ``vertices``
    (a power of two) cuts the scale further for CPU runs, keeping CHIP's
    ratio of vertices to cache slots."""
    import dataclasses

    from repro.configs.ecommerce_graph import CHIP

    v_total = CHIP.v_total * n_shards if vertices is None else int(vertices)
    slots = max(CHIP.cache_slots_total * v_total // CHIP.v_total, 8 * n_shards)
    return dataclasses.replace(CHIP, v_total=v_total, cache_slots_total=slots)


def generate_graph(cfg, seed: int):
    """A random graph at ``cfg``'s scale and widths, made in bulk from
    ``seed``: ``e_per_vertex`` out-edges per vertex on average (uniform
    sources, so degrees are Poisson and stay far below ``max_deg``),
    uniform destinations, 0/1 properties. One ``recent_cap`` of the edge
    capacity stays free for appends. Returns the ``ingest`` arguments
    after the spec: ``(vlabels, vprops, esrc, edst, elabels, eprops)``."""
    rng = np.random.default_rng(seed)
    V = cfg.v_total
    E = cfg.e_total() - cfg.recent_cap
    deg = np.bincount(rng.integers(0, V, E, dtype=np.int32), minlength=V)
    esrc = np.repeat(np.arange(V, dtype=np.int32), deg)  # sorted by src
    edst = rng.integers(0, V, E, dtype=np.int32)
    eprops = rng.integers(0, 2, (E, cfg.n_eprops), dtype=np.int32)
    vprops = rng.integers(0, 2, (V, cfg.n_vprops), dtype=np.int32)
    return (np.zeros(V, np.int32), vprops, esrc, edst,
            np.zeros(E, np.int32), eprops)


def block_capacity(store, n_shards: int) -> int:
    """Per-shard edge-block capacity for a host store: the fullest
    orientation's largest owner share at ``BLOCK_FILL``."""
    from repro.distributed.routing import base_owner

    e_len = int(store.e_len)
    need = max(
        int(np.bincount(base_owner(store.esrc[:e_len], n_shards),
                        minlength=n_shards).max()),
        int(np.bincount(base_owner(store.edst[:e_len], n_shards),
                        minlength=n_shards).max()),
    )
    return int(np.ceil(need / BLOCK_FILL))


def main(argv=None):
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--batches", type=int, default=10)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--vertices", type=int, default=None,
                    help="total vertices, a power of two (default: the "
                         "CHIP deployment's 2^23 per shard)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--store-tier", default="partitioned",
                    choices=("partitioned", "replicated"))
    ap.add_argument("--write-every", type=int, default=2,
                    help="apply a small gRW commit every N batches "
                         "(0 disables writes; partitioned tier only)")
    ap.add_argument("--no-maintenance", action="store_true",
                    help="disable the on-device maintenance gate and "
                         "hitless growth")
    ap.add_argument("--journal-dir", default=None,
                    help="write-behind journal root (default: a tempdir; "
                         "pass a persistent path to make restarts real)")
    ap.add_argument("--no-journal", action="store_true",
                    help="disable write-behind durability")
    ap.add_argument("--checkpoint-every", type=int, default=8,
                    help="checkpoint the store every N commits")
    ap.add_argument("--purge", action="store_true",
                    help="reclaim tombstones at gated compactions when the "
                         "liveness epoch allows")
    ap.add_argument("--inject-crash", default=None, metavar="SHARD:BATCH",
                    help="chaos: lose shard SHARD's storage from batch "
                         "BATCH (serving degrades, writes queue, recovery "
                         "replays — requires the journal)")
    ap.add_argument("--recover-after", type=int, default=4,
                    help="batches of degraded serving before recovery-as-"
                         "migration runs for the crashed shard")
    ap.add_argument("--hedge-after", type=float, default=0.05,
                    help="straggler hedge deadline in seconds for the gR "
                         "read path")
    ap.add_argument("--io-timeout", type=float, default=None,
                    help="wall-clock bound per journal flush / checkpoint "
                         "write attempt (CallTimeout + retry past it)")
    ap.add_argument("--full-checkpoints", action="store_true",
                    help="periodic checkpoints snapshot the whole store "
                         "(default: incremental — dirty owners only)")
    ap.add_argument("--migrate", action="store_true",
                    help="attach the routing table and run the hot-vertex "
                         "migration policy loop at batch boundaries "
                         "(partitioned tier only)")
    ap.add_argument("--hot-frac", type=float, default=0.0,
                    help="fraction of each batch's roots drawn from a hot "
                         "set colliding on one owner (the skew --migrate "
                         "exists to fix; 0 = uniform)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write structured telemetry (span / snapshot / "
                         "report events) as JSONL to PATH; validate with "
                         "`python -m repro.obs.validate PATH`")
    ap.add_argument("--snapshot-every", type=int, default=5,
                    help="emit a telemetry snapshot event every N batches "
                         "(0 disables periodic snapshots; the end-of-run "
                         "report is always emitted)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from repro.distributed.fault import (
        HedgedCalls, NodeFailure, ShardFaultPlan,
    )
    from repro.distributed.failover import FailoverController
    from repro.distributed.graph_serve import (
        ShardedMissDrain, ShardedTxnRuntime, config_espec,
        config_plan_and_ttable,
    )
    from repro.distributed.sharding import flat_mesh
    from repro.graphstore import (
        DeviceGate, MaintenancePolicy, WriteBehindJournal, make_mutation_batch,
    )
    from repro.distributed.routing import RoutingTableHost
    from repro.graphstore.migration import HotSetTracker, MigrationEngine
    from repro.graphstore.store import ingest_host
    from repro.obs.metrics import OWNER_STAGE_FIELDS
    from repro.obs.telemetry import ServeTelemetry

    cfg = deployment_config(args.shards, args.vertices)
    espec = config_espec(cfg)
    plan, ttable = config_plan_and_ttable(cfg)
    rng = np.random.default_rng(args.seed)
    V = cfg.v_total
    store = ingest_host(espec.store, *generate_graph(cfg, args.seed))

    # telemetry: per-owner stage attribution rides the runtime's existing
    # stacked all-reduce; the tracer times the host-side phases. JSONL
    # export only happens under --trace; the histograms + end-of-run
    # report are always on.
    telemetry = ServeTelemetry(args.shards, trace_path=args.trace)
    mesh = flat_mesh(args.shards)
    partitioned = args.store_tier == "partitioned"
    rt = ShardedTxnRuntime(
        espec, mesh, store_tier=args.store_tier, tracer=telemetry.tracer,
        e_blk_cap=block_capacity(store, args.shards) if partitioned else None,
    )
    if partitioned:
        sstate = rt.partition_store(store, elastic=True)
        rep = rt.store_bytes()
        print(
            f"store tier: {rep['per_shard_bytes']/2**20:.2f} MiB/shard "
            f"partitioned vs {rep['replicated_per_shard_bytes']/2**20:.2f} "
            f"MiB/shard replicated (ratio {rep['ratio']:.3f}, "
            f"ideal 1/n = {rep['ideal_ratio']:.3f})"
        )
    else:
        sstate = jax.device_put(store, rt.store_sharding())
    cache = rt.empty_cache()
    tpl_meta = {0: (plan.hops[0].direction, plan.hops[0].edge_label)}
    # per-owner CP queues: each shard's miss records drain at that shard
    drain = ShardedMissDrain(rt, tpl_meta)
    policy = MaintenancePolicy(recent_fill_frac=0.5, grow_occupancy_frac=0.85)
    maintain = partitioned and not args.no_maintenance
    gate_base = DeviceGate(recent_fill_frac=policy.recent_fill_frac)

    journal = None
    if partitioned and not args.no_journal:
        root = args.journal_dir or os.path.join(
            tempfile.mkdtemp(prefix="serve-journal-"), "journal"
        )
        journal = WriteBehindJournal(root, rt.n, io_timeout=args.io_timeout,
                                     tracer=telemetry.tracer)
        journal.checkpoint(
            sstate, e_blk_cap=rt.pspec.e_blk_cap,
            recent_blk_cap=rt.pspec.recent_blk_cap,
            store_version=int(jax.device_get(sstate.version)),
        )
        journal.start()  # async coalescing flusher behind the loop
        print(f"journal: {root} (checkpoint every "
              f"{args.checkpoint_every} commits)")

    failover = None
    crash_shard = crash_batch = None
    if args.inject_crash is not None:
        if journal is None:
            ap.error("--inject-crash requires the journal (degraded-mode "
                     "writes queue there)")
        crash_shard, crash_batch = (int(x) for x in args.inject_crash.split(":"))
        fault_plan = ShardFaultPlan(crash={crash_shard: crash_batch})
        failover = FailoverController(
            rt, journal, ttable, plan=fault_plan, hedge=HedgedCalls(),
            hedge_after=args.hedge_after,
        )
        print(f"chaos: shard {crash_shard} crashes at batch {crash_batch}, "
              f"recovery after {args.recover_after} degraded batches")

    engine = None
    hot = None
    if args.migrate:
        if not partitioned:
            ap.error("--migrate requires the partitioned store tier")
        # the routing table is a traced input to the already-compiled serve
        # step: attaching it (and every later epoch bump) never recompiles
        rhost = RoutingTableHost(rt.n)
        rt.attach_routing(rhost)
        engine = MigrationEngine(
            rt.pspec, rhost, tracker=HotSetTracker(), journal=journal,
            detector=failover.detector if failover is not None else None,
        )
        print("routing: table attached (epoch 0), migration policy loop on")
    if args.hot_frac > 0:
        # hot roots all land on one owner (shard 1, or 0 alone) under the
        # modulo layout
        hot_owner = min(1, args.shards - 1)
        hot = np.arange(hot_owner, V, args.shards, dtype=np.int64)[:16]
    FR = OWNER_STAGE_FIELDS.index("frontier_rows")

    total = dict(requests=0, hits=0, misses=0, route_overflow=0, deferred=0,
                 locality_routed=0, locality_retry_rows=0)
    avail = dict(unavailable_batches=0, degraded_batches=0, deferred_rows=0,
                 queued_commits=0, recovery_seconds=0.0)
    maint = dict(device_compactions=0, growths=0, commits=0,
                 append_overflow=0, purges=0)
    t0 = time.time()
    for b in range(args.batches):
        # hot-swap at the batch boundary once the background pre-compile
        # of the next capacity tier is ready
        if maintain and rt._next_tier is not None and rt._next_tier.ready.is_set():
            sstate, swap = rt.swap_to_next_tier(sstate)
            if journal is not None:
                journal.append_grow(
                    rt.pspec.e_blk_cap, rt.pspec.recent_blk_cap
                )
            maint["growths"] += 1
            print(f"batch {b}: hot-swapped to e_blk_cap="
                  f"{swap['e_blk_cap']} in {swap['swap_seconds']*1e3:.1f} ms "
                  f"(precompiled {swap['compiled_steps']} steps in "
                  f"{swap['precompile_seconds']:.1f} s off-loop)")
        roots = rng.integers(0, V, args.batch).astype(np.int32)
        if hot is not None:
            pick = rng.random(args.batch) < args.hot_frac
            zipf = np.minimum(rng.zipf(1.2, args.batch) - 1, len(hot) - 1)
            roots = np.where(pick, hot[zipf], roots).astype(np.int32)
        if failover is not None:
            failover.probe(b)
            try:
                res, _deferred, misses, m = failover.run_gr(
                    sstate, cache, plan, roots, b
                )
            except NodeFailure:
                # detection gap: the dead owner is needed but not yet
                # marked down — this batch IS the unavailability window
                avail["unavailable_batches"] += 1
                continue
            avail["deferred_rows"] += m["deferred_rows"]
            avail["degraded_batches"] += int(bool(failover.detector.down()))
        elif journal is not None:
            # pin the gR snapshot's epoch: purge may not reclaim under us;
            # the scope releases on every exit path (no leaked pins)
            with journal.epochs.pin_scope():
                res, misses, m = rt.run_gr_tx_batch(
                    sstate, cache, ttable, plan, roots
                )
        else:
            res, misses, m = rt.run_gr_tx_batch(
                sstate, cache, ttable, plan, roots
            )
        for k in total:
            total[k] += int(m.get(k, 0))
        # fold the batch into the latency histograms + owner attribution
        telemetry.record_gr(rt.last_step_seconds, m,
                            owner_stage=rt.last_owner_stage)
        # CP-per-shard: misses route to their owner's queue and drain there
        tcp = time.perf_counter()
        with telemetry.tracer.span("cp_drain"):
            drain.push(misses)
            cache = drain.drain(sstate, sstate, cache, ttable, 512)
        telemetry.record_cp_drain(time.perf_counter() - tcp)
        if (failover is not None and crash_shard in failover.detector.down()
                and b >= crash_batch + args.recover_after):
            sstate, cache, rinfo = failover.recover(sstate, cache, crash_shard)
            avail["queued_commits"] = rinfo["drained_commits"]
            avail["recovery_seconds"] = round(rinfo["recovery_seconds"], 3)
            print(f"batch {b}: recovered shard {crash_shard} — replayed "
                  f"{rinfo['replayed_commits']} commits to seq "
                  f"{rinfo['replayed_to_seq']}, drained "
                  f"{rinfo['drained_commits']} queued, "
                  f"{rinfo['recovery_seconds']*1e3:.0f} ms")
        if engine is not None:
            # batch boundary: observe root heat, maybe run one journal-first
            # migration round, and install the spliced store + bumped table
            # together so no in-flight batch sees a torn layout
            engine.observe(roots)
            ps2, moves = engine.step(sstate, rt.last_owner_stage[:, FR])
            if moves:
                sstate = jax.device_put(ps2, rt.store_sharding())
                print(f"batch {b}: migrated {moves} "
                      f"(table epoch -> {engine.rhost.epoch})")
        wm = None
        if partitioned and args.write_every and (b + 1) % args.write_every == 0:
            # a small upsert burst lands in the block recent regions
            ne = [
                (int(rng.integers(0, V)), int(rng.integers(0, V)), 0,
                 [int(rng.integers(0, 2))])
                for _ in range(8)
            ]
            mb = make_mutation_batch(espec.store, new_edges=ne)
            gate = None
            if maintain:
                # purge only behind the liveness epoch + journal checkpoint
                purge_ok = args.purge and journal is not None and (
                    journal.epochs.safe_to_purge(
                        journal.epochs.current, journal
                    )
                )
                gate = gate_base._replace(purge=purge_ok)
                maint["purges"] += int(purge_ok)
            tw = time.perf_counter()
            if failover is not None:
                # degraded mode queues the commit durably instead of
                # applying (order-dependent ids; see distributed.failover)
                sstate, cache, wm = failover.run_grw(
                    sstate, cache, mb, gate=gate
                )
            else:
                sstate, cache, wm = rt.run_grw_tx(
                    sstate, cache, ttable, mb, gate=gate, journal=journal
                )
            telemetry.record_grw(time.perf_counter() - tw)
            # under --no-maintenance this is the degradation signal the
            # flag exists to demonstrate — report it, don't crash on it
            maint["append_overflow"] += wm.get("store_append_overflow", 0)
            maint["device_compactions"] += wm.get("device_compactions", 0)
            maint["commits"] += 1
            if (journal is not None and not wm.get("queued", 0)
                    and maint["commits"] % args.checkpoint_every == 0):
                ckpt = (journal.checkpoint if args.full_checkpoints
                        else journal.checkpoint_incremental)
                ckpt(
                    sstate, e_blk_cap=rt.pspec.e_blk_cap,
                    recent_blk_cap=rt.pspec.recent_blk_cap,
                    store_version=int(jax.device_get(sstate.version)),
                )
        if (
            maintain and wm is not None and rt._next_tier is None
            and wm.get("store_occupancy_max", 0) >= policy.grow_occupancy_frac
        ):
            # occupancy high-water: compile the next tier in the background
            # while this tier keeps serving; the swap happens at a later
            # batch boundary
            rt.precompile_next_tier(
                int(np.ceil(rt.pspec.e_blk_cap * policy.growth_factor)),
                ttable,
                gr_plans=[(plan, max(args.batch, rt.n))],
                grw_policies=[("write-around", gate_base),
                              ("write-around",
                               gate_base._replace(purge=True))]
                if args.purge else [("write-around", gate_base)],
                compact_purges=(False,),
                pop_steps=[(tpl_meta, 0, bkt) for bkt in (8, 16, 32)],
            )
            print(f"batch {b}: occupancy "
                  f"{wm['store_occupancy_max']:.2f} crossed high-water — "
                  f"precompiling next tier in the background")
        if args.snapshot_every and (b + 1) % args.snapshot_every == 0:
            telemetry.snapshot(b)
    dt = time.time() - t0
    assert res.shape == (args.batch, espec.result_width)
    print(
        f"{args.batches} batches x {args.batch} gR-Txs on {args.shards} "
        f"shards [{args.store_tier}]: requests={total['requests']} "
        f"hits={total['hits']} misses={total['misses']} "
        f"populated={drain.committed} route_overflow={total['route_overflow']} "
        f"({dt/args.batches*1e3:.1f} ms/batch after compile)"
    )
    if partitioned:
        occ = rt.store_occupancy(sstate)
        print(
            f"maintenance: {maint['commits']} gRW commits, "
            f"{maint['device_compactions']} device compactions "
            f"({maint['purges']} purge-enabled), {maint['growths']} "
            f"hot-swaps, {maint['append_overflow']} appends dropped; "
            f"occupancy max {occ['max_occupancy']:.3f}, recent fill max "
            f"{occ['max_recent_fill']}/{occ['recent_blk_cap']}"
        )
    if journal is not None:
        journal.stop(final_flush=True)
        jm = journal.metrics()
        total.update({k: jm[k] for k in (
            "journal_lag_batches", "flush_queue_depth", "pinned_epoch_min",
            "open_pins", "leaked_pin_releases",
        )})
        total["swap_events"] = rt.swap_events
        print(
            f"durability: journal_lag_batches={jm['journal_lag_batches']} "
            f"flush_queue_depth={jm['flush_queue_depth']} "
            f"flushes={jm['flushes']} flushed_records={jm['flushed_records']} "
            f"checkpoint_seq={jm['checkpoint_seq']} "
            f"pinned_epoch_min={jm['pinned_epoch_min']} "
            f"open_pins={jm['open_pins']} "
            f"leaked_pin_releases={jm['leaked_pin_releases']} "
            f"swap_events={rt.swap_events}"
        )
    if failover is not None:
        fm = failover.metrics()
        total.update(avail)
        total.update({k: fm[k] for k in (
            "detections", "recoveries", "hedge_rate",
        ) if k in fm})
        print(
            f"failover: unavailable_batches={avail['unavailable_batches']} "
            f"degraded_batches={avail['degraded_batches']} "
            f"deferred_rows={avail['deferred_rows']} "
            f"queued_commits_drained={avail['queued_commits']} "
            f"recovery_seconds={avail['recovery_seconds']} "
            f"detections={fm['detections']} recoveries={fm['recoveries']} "
            f"hedge_rate={fm.get('hedge_rate', 0.0)}"
        )
    if engine is not None:
        mm = engine.metrics()
        total.update({k: mm[k] for k in (
            "migration_rounds", "migrated_vertices", "migrated_rows",
            "migration_deferred_rounds", "table_epoch",
        )})
        total["route_cap_retries"] = rt.route_cap_retries
        print(
            f"routing: migration_rounds={mm['migration_rounds']} "
            f"migrated_vertices={mm['migrated_vertices']} "
            f"migrated_rows={mm['migrated_rows']} "
            f"deferred_rounds={mm['migration_deferred_rounds']} "
            f"table_epoch={mm['table_epoch']} "
            f"storage_exceptions={mm['storage_exceptions']} "
            f"cache_exceptions={mm['cache_exceptions']} "
            f"locality_routed={total['locality_routed']} "
            f"locality_retry_rows={total['locality_retry_rows']} "
            f"route_cap_retries={rt.route_cap_retries}"
        )
    # end-of-run telemetry report (emitted after journal.stop so the final
    # flush's span is counted)
    report = telemetry.report()

    def _ms(v):
        return "n/a" if v is None else f"{v * 1e3:.2f}ms"

    for cls in ("gr_cached", "gr_uncached", "grw", "cp_drain"):
        p = report["latency"][cls]
        print(
            f"latency[{cls}]: p50={_ms(p['p50'])} p95={_ms(p['p95'])} "
            f"p99={_ms(p['p99'])} p99.9={_ms(p['p999'])} (n={p['count']})"
        )
    print("hit_locality per shard: "
          + " ".join(f"{v:.2f}" for v in report["hit_locality"]))
    total["trace_events"] = (telemetry.writer.events_written
                             if telemetry.writer is not None else 0)
    if args.trace:
        print(f"trace: {args.trace} ({total['trace_events']} events)")
    telemetry.close()
    return total


if __name__ == "__main__":
    main()
