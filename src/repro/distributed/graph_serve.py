"""Distributed graph-query serving — the paper's production architecture
mapped onto a TPU mesh with shard_map.

``ShardedTxnRuntime`` is the sharded instantiation of the shared transaction
runtime (``repro.core.runtime``). Vertex *ownership* is interleaved over the
mesh (shard ``v % n`` owns vertex ``v`` — round-robin striping; see
``partition.owner_of`` for why range partitioning forces worst-case routing
buckets) and the one-hop result cache is **co-partitioned with it**: the
cache shard for a key lives on the shard owning the key's root vertex, so a
probe is always local to the owner.

Two storage tiers back it:

- ``store_tier="partitioned"`` (default) — the ``PartitionedGraphStore``
  dual-CSR tier: each shard holds only the out-CSR block of the edges it
  src-owns and the in-CSR block of the edges it dst-owns (plus the small
  replicated vertex-attribute tier), so per-shard store bytes are O(E/n)
  instead of O(E). A hop's miss execution — in *either* direction — reads
  purely owner-local arrays after root routing.
- ``store_tier="replicated"`` — the PR 3 design: a full read-snapshot
  ``GraphStore`` replica per shard. Kept as the memory/throughput baseline
  the partitioned tier is benchmarked against.

- gR-Txs (``serve_step`` / ``run_gr_tx_batch``): arbitrary multi-hop
  ``QueryPlan``s execute the fused probe→miss-exec→frontier-merge pipeline
  *inside* ``shard_map`` via the shared hop driver (``runtime.make_plan_fn``)
  with a mesh tier. The hop protocol is **collective-lean**: exactly ONE
  all_to_all each direction per hop, with everything a hop needs packed
  into one contiguous int32 frame (``runtime.pack_query_frame`` /
  ``pack_result_frame``):

  * **route** — each frontier root ships as ``[root | flags | params]``
    (``WIRE_QUERY_LANES`` lanes; bit 0 of flags = VALID, padding slots are
    zero-filled so they can never decode valid) into per-peer buckets of
    ``cap = ceil(route_cap_factor[hop] * rows / n)`` rows, then one
    tiled all_to_all scatters every peer's bucket to its owner.
  * **exec** — the owner probes its co-partitioned cache block and runs the
    fused ``kernels/block_gather`` executor (one pass: CSR window + recent
    region + liveness + statically specialized predicates, sort-based
    set-dedup) over its owner-local blocks. ``fused_gather=False`` keeps
    the legacy multi-op ``onehop_exec_view`` path for A/B.
  * **unroute** — results return as ``[vals x RW | cnt]`` frames (the cnt
    lane doubles as the hit/miss/deferred flag, -1 = deferred) in the
    mirror all_to_all; the querying shard unpacks into the on-device
    ``segmented_dedup_merge``.

  Per-hop metric/phase globalization is DEFERRED into a single concatenated
  psum after the hop loop (commutative sums, so totals are unchanged), so a
  whole gR step costs ``2 * n_hops`` all_to_alls + 1 all-reduce — pinned by
  the HLO collective-count test in ``tests/test_sharded_collectives.py``.
  With ``overlap=True`` the batch splits into two row streams software-
  pipelined one hop apart (stream B's route exchange issues while stream
  A's owner-local exec runs), overlapping communication with compute under
  async collectives; off by default (row-identical results, but it changes
  the program shape and per-stream route caps halve). The batch goes in as
  one packed int32 buffer, and results, per-hop miss arrays, the reduced
  metrics and the read version come back packed into one int32 buffer (a
  row per shard): one device→host transfer, unpacked on the host
  byte-identical to the single-host fused engine.

- gRW-Txs (``run_grw_tx``): two phases inside one jitted step. On the
  partitioned tier, phase A applies the commit to owner-local storage
  (``apply_mutations_partitioned``) and runs the mutation listener
  (Algorithms 1–9) as *ownership-masked op derivation*: reverse traversals
  happen at the leaf's owner against its local blocks, edge-change emissions
  at the root side's owner, sweeps at the swept root's owner — the union
  over shards is exactly the single-host emission set. Phase B compacts the
  op stream (only real ops survive) and routes each op to the shard owning
  its root, which applies it against the local cache block — batched for
  write-around (deletes commute), key-segmented vectorized for
  write-through (``apply_op_stream_segmented``; same-key runs stay ordered,
  distinct keys apply in parallel rounds). On the replicated tier, phase A
  round-robins the batch rows instead (every shard can traverse the full
  replica). Store and cache post-states are byte-/logically identical to
  the single-host commit.

- CP population: ``populator()`` returns the standard ``CachePopulator``
  wired with a shard_map step that executes each miss at its owner shard
  (against owner-local blocks on the partitioned tier) and inserts at the
  owner's cache block.

Every routing round reports an **overflow count** (valid items dropped
because a peer bucket or op-stream capacity filled up) in the step metrics;
an overflow means silently degraded results/maintenance and should alarm.
``DEFAULT_ROUTE_CAP_FACTOR`` holds the measured production default (see
``benchmarks/workload.measure_route_skew``); pass ``route_cap_factor=None``
for worst-case no-drop buckets (the byte-identity tests do).

``GraphServeConfig`` (bottom) is the capacity-planning description of the
production deployment; ``config_cell`` lowers it onto the runtime for the
roofline/dry-run tooling. The legacy fixed-template ``build_serve_step``
serving cell was retired in favour of ``ShardedTxnRuntime.serve_step``.

Observability
-------------

With ``telemetry=True`` (the default) the serving step additionally
assembles a per-owner/per-stage counter block
(``repro.obs.metrics.OWNER_STAGE_FIELDS``: frontier occupancy, probe hits,
miss rows, edges scanned, leaf fetches, route overflow, deferred rows)
that rides the SAME stacked metrics all-reduce: each shard one-hot
scatters its *pre-reduction local* stage counters at its own row of an
``[n, S]`` int32 block, the block flattens onto the existing concatenated
psum vector, and the sum across shards assembles the full matrix on every
shard — the per-step collective budget (2 all_to_alls per hop + 1
all-reduce) is unchanged, pinned by ``tests/test_sharded_collectives.py``.
The host wrapper pops the block into ``last_owner_stage`` before building
the metrics dict, so host-visible results and metrics are byte-identical
to ``telemetry=False``, and work-attributes the measured step wall-clock
into ``last_step_owner_seconds`` (``obs.metrics.attribute_step_seconds``)
— the per-owner heartbeat ``FailureDetector.observe_step`` consumes so one
straggler no longer marks every owner straggling. Host-side phases
(gr_dispatch / gr_sync / gr_unpack, grw_step, journal_flush, checkpoint,
compaction_tick, hot_swap_pause) are wrapped in ``tracer.span(...)``
(``repro.obs.trace``; the zero-cost ``NULL_TRACER`` unless a tracer is
injected), and ``launch/serve.py`` aggregates everything into streaming
latency histograms and a schema-validated JSONL trace — format and
reading guide in ``docs/OBSERVABILITY.md``.
"""

from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.cache import CacheState, empty_cache
from repro.core.invalidation import (
    CacheOpStream,
    SweepStream,
    apply_op_stream_batched,
    apply_op_stream_segmented,
    apply_sweeps,
    derive_cache_ops,
    derive_cache_ops_views,
)
from repro.core.runtime import (
    WIRE_FLAG_VALID,
    bucket_for,
    bucketize,
    compact_rows,
    decode_miss_records,
    make_plan_fn,
    onehop_exec_view,
    pack_query_frame,
    pack_result_frame,
    route_plan,
    route_scatter,
    unpack_query_frame,
    unpack_result_frame,
)
from repro.kernels.block_gather.ops import block_onehop_exec
from repro.graphstore.maintenance import (
    DeviceGate,
    MaintenancePolicy,
    block_occupancy,
    compact_block,
    decide_maintenance,
    grow_block_local,
)
from repro.graphstore.mutations import (
    apply_mutations,
    make_mutation_batch,
    shard_mutation_rows,
)
from repro.graphstore.partition import (
    BlockCapacityError,
    BlockStoreView,
    EdgeBlock,
    PartitionedGraphStore,
    abstract_partitioned_store,
    apply_mutations_partitioned,
    default_pspec,
    owner_of,
    partition_store_host,
    store_bytes_report,
)
from repro.distributed.routing import (
    RoutingTable,
    RoutingTableHost,
    base_owner,
    cache_owner_of,
    identity_table,
    storage_owner_of,
)
from repro.obs.metrics import OWNER_STAGE_FIELDS, attribute_step_seconds
from repro.obs.trace import NULL_TRACER
from repro.utils import NULL_ID

_STAT_FIELDS = ("n_hit", "n_miss", "n_insert", "n_evict", "n_delete", "n_oversize")
_ADDITIVE_METRICS = (
    "requests", "hits", "misses", "truncated", "leaf_fetches",
    "edges_scanned", "cache_reads", "route_overflow", "deferred",
    "locality_routed",
)

# Measured default per-peer routing capacity multipliers, per hop: sized
# from the Zipfian (a=1.3) eCommerce workload's owner skew on an 8-shard
# mesh (benchmarks/workload.measure_route_skew; recorded in
# BENCH_partitioned_store.json, per_hop_recommended = [3, 3]). Hop 1 routes
# the raw Zipfian query roots and keeps 4x headroom over the uniform share
# (p99.9 root skew ≈ 3.4x); hops ≥ 2 route leaf-derived frontier roots whose
# measured skew is flatter, so 3x suffices. Both make the measured overflow
# rate 0 on the production mix while bounding bucket memory at factor/n of
# the worst case.
DEFAULT_ROUTE_CAP_FACTOR = (4, 3)


def _plan_key(plan):
    """Structural hash key for a QueryPlan: equal-but-distinct plan objects
    (hops hold numpy params, so plans aren't hashable) share one compiled
    serve step instead of re-tracing per object identity."""
    def pred(p):
        return tuple(np.asarray(getattr(p, f)).tobytes() for f in p._fields)

    hops = tuple(
        (h.direction, h.edge_label, h.tpl_idx,
         np.asarray(h.params, np.int32).tobytes(),
         pred(h.pr), pred(h.pe), pred(h.pl))
        for h in plan.hops
    )
    return (hops, plan.final, plan.final_prop, plan.post_filter, plan.extra_phases)


def _replicate_stats(before: CacheState, after: CacheState, axes):
    """Rebuild the cache's 0-d stats counters as replicated global values:
    input stats are replicated, so each shard adds the psum of all local
    deltas — every shard then stores the same global counter."""
    reps = {}
    for f in _STAT_FIELDS:
        b, a = getattr(before, f), getattr(after, f)
        reps[f] = b + jax.lax.psum(a - b, axes)
    return after._replace(**reps)


# The gR step's host<->device boundary is one int32 buffer each way. In:
# the padded batch as [bucket, 2] (root, valid). Out: every output leaf of
# a shard flattened into one row of int32 words (bool widened, other 4-byte
# dtypes bitcast), the rows stacked [n * L] by shard_map. Of the step's six
# outputs the first four (result, deferred, miss_roots, miss_counts) are
# per-shard blocks concatenated along axis 0; metrics and version are
# replicated and read from row 0.
_GR_SHARDED = (True, True, True, True, False, False)


def pack_roots(roots, bucket: int) -> np.ndarray:
    """A host root batch padded to ``bucket`` as the gR step's one input:
    int32 ``[bucket, 2]``, column 0 the roots, column 1 the valid flags."""
    qin = np.zeros((bucket, 2), np.int32)
    qin[:len(roots), 0] = roots
    qin[:len(roots), 1] = 1
    return qin


def _to_words(x):
    if x.dtype == jnp.bool_:
        return x.astype(jnp.int32).reshape(-1)
    if x.dtype.itemsize != 4:
        raise TypeError(f"a gR step output of dtype {x.dtype} has no "
                        "int32 word form")
    return jax.lax.bitcast_convert_type(x, jnp.int32).reshape(-1)


def _pack_words(outputs, layout: list):
    """Flatten the step's outputs (traced, per shard) into one int32
    vector. ``layout`` receives, per output, its treedef and each leaf's
    ``(offset, size, shape, dtype)`` — all the host needs to slice it
    back."""
    layout.clear()
    words, off = [], 0
    for out in outputs:
        leaves, treedef = jax.tree_util.tree_flatten(out)
        slots = []
        for x in map(jnp.asarray, leaves):
            slots.append((off, x.size, x.shape, np.dtype(x.dtype)))
            words.append(_to_words(x))
            off += x.size
        layout.append((treedef, slots))
    return jnp.concatenate(words)


def _unpack_words(words: np.ndarray, layout: list, n: int):
    """The host copy of a packed step output back as the step's six
    outputs, with the dtypes and shapes the unpacked program returned."""
    rows = words.reshape(n, -1)
    outs = []
    for (treedef, slots), sharded in zip(layout, _GR_SHARDED):
        leaves = []
        for off, size, shape, dtype in slots:
            if sharded:
                w = rows[:, off:off + size].reshape((n * shape[0],) + shape[1:])
            else:
                w = rows[0, off:off + size].reshape(shape)
            leaves.append(w != 0 if dtype == bool else w.view(dtype))
        outs.append(treedef.unflatten(leaves))
    return tuple(outs)


class _MeshTier:
    """The sharded instantiation of the shared hop driver's hooks: per-hop
    owner routing over ``all_to_all``, psum'd batch-global gates, and (on
    the partitioned store tier) owner-local block execution."""

    routed = True
    # stateful serving inputs: the plan fn takes TWO extra traced inputs —
    # the ``down: bool[n]`` owner mask and the replicated ``RoutingTable``
    # (both fixed-shape). All-False / identity-table are the healthy fast
    # path and trace byte-identically, so flipping an owner down or moving
    # a vertex (migration, locality override) is an *input* change, not a
    # recompile.
    extra_inputs = 2

    def __init__(self, rt: "ShardedTxnRuntime", caps, pspec):
        # pspec is captured at BUILD time (not read off rt at trace time):
        # a background pre-compile builds next-tier programs while the
        # runtime still serves the current tier
        self.rt = rt
        self.caps = caps
        self.pspec = pspec
        self.axes, self.n = rt.axes, rt.n
        self.fused_gather = rt.fused_gather
        # telemetry: the hop driver accumulates owner-side frontier
        # occupancy (stage_rows) and reduce_metrics folds the per-owner
        # stage block into the existing stacked all-reduce
        self.telemetry = rt.telemetry
        self.stage_rows = rt.telemetry
        self._down = None
        self._rtable = None
        self._locality = None

    def bind(self, down, rtable):
        self._down = down
        self._rtable = rtable
        # per-trace accumulator: rows routed away from their static-modulo
        # home by the table (folded into the metrics psum)
        self._locality = jnp.int32(0)

    def defer_fn(self):
        if self.pspec is None:
            # the replicated tier keeps a full snapshot per shard: losing
            # an owner's storage loses nothing, and every shard can execute
            # any miss, so nothing ever defers
            return None

        def defer(roots_flat):
            # a miss defers where this shard cannot execute it: the owner's
            # storage blocks are down, or the row was routed here for its
            # *cache* home (locality routing) while its dual-CSR rows live
            # at another shard — the host re-dispatches those through the
            # storage view of the same table (same compiled program).
            # Cache hits still serve either way.
            me = jax.lax.axis_index(self.axes)
            split = storage_owner_of(self._rtable, roots_flat, self.n) != me
            return self._down[me] | split

        return defer

    def exec_fn(self, hop):
        if self.pspec is None:
            return None  # replicated snapshot: the default full-store exec
        pspec, espec, axes = self.pspec, self.rt.lspec, self.axes

        if self.fused_gather:
            def exec_fn(store, roots_f, params, miss_m, hop=hop):
                me = jax.lax.axis_index(axes)
                view = BlockStoreView(pspec, store, me, rtable=self._rtable)
                return block_onehop_exec(
                    espec, view, hop.direction, hop.edge_label,
                    hop.pr, hop.pe, hop.pl, roots_f, params, miss_m,
                )
        else:
            def exec_fn(store, roots_f, params, miss_m, hop=hop):
                me = jax.lax.axis_index(axes)
                view = BlockStoreView(pspec, store, me, rtable=self._rtable)
                return onehop_exec_view(
                    espec, view, hop.direction, hop.edge_label,
                    hop.pr, hop.pe, hop.pl, roots_f, params, miss_m,
                )

        return exec_fn

    def route(self, hop_idx, A, roots_flat, rmask_flat, params_row):
        # interleaved ownership maps any id (even past v_cap) to exactly
        # one shard, where an out-of-range root is processed and comes back
        # empty exactly like on the single host; negative ids are
        # indistinguishable from frontier padding.
        #
        # ONE exchange: root id + valid flag + bound predicate params
        # travel together as a packed query frame (runtime wire format)
        # instead of separate per-field collectives. Bucket padding is
        # zero-filled, so padded rows decode as flags=0 (invalid) — their
        # root lane 0 is never observed (every owner-side output is gated
        # by the decoded row mask, and home-side gathers are kept-masked).
        n, cap = self.n, self.caps[hop_idx]
        rvals = jnp.where(rmask_flat, roots_flat, NULL_ID)
        ok = rmask_flat & (roots_flat >= 0)
        # gR routes by the *cache* owner (Smart Query Routing): a hit is
        # served entirely at the caching shard; a locality-split miss comes
        # back deferred and the host retries at the storage owner. The
        # identity table reduces this to exactly owner_of.
        dest = cache_owner_of(self._rtable, roots_flat, n)
        owner = jnp.where(ok, dest, -1)
        self._locality = self._locality + jnp.sum(
            (ok & (dest != owner_of(roots_flat, n))).astype(jnp.int32)
        )
        flags = rmask_flat.astype(jnp.int32) * WIRE_FLAG_VALID
        params = jnp.broadcast_to(
            params_row[None, :], (roots_flat.shape[0], params_row.shape[0])
        )
        frame = pack_query_frame(rvals, flags, params)
        send, slot, kept, ovf = bucketize(frame, owner, n, cap, fill=0)
        recv = jax.lax.all_to_all(
            send, self.axes, split_axis=0, concat_axis=0, tiled=True
        )
        q, qflags, qparams = unpack_query_frame(recv.reshape(n * cap, -1))
        qmask = (qflags & WIRE_FLAG_VALID) == WIRE_FLAG_VALID
        return q, qmask, qparams, (slot, kept, cap), ovf

    def unroute(self, ctx, vals, cnt):
        # ONE exchange home: the RW leaf lanes and the count lane (which
        # doubles as the hit/deferred flag, cnt = -1 deferred) ride one
        # packed result frame
        slot, kept, cap = ctx
        n, axes = self.n, self.axes
        RW = vals.shape[-1]
        frame = pack_result_frame(vals, cnt)
        back = jax.lax.all_to_all(
            frame.reshape(n, cap, RW + 1), axes,
            split_axis=0, concat_axis=0, tiled=True,
        ).reshape(n * cap, RW + 1)
        back_v, back_c = unpack_result_frame(back)
        sl = jnp.clip(slot, 0, n * cap - 1)
        return (
            jnp.where(kept[:, None], back_v[sl], NULL_ID),
            jnp.where(kept, back_c[sl], 0),
        )

    def psum(self, x):
        return jax.lax.psum(x, self.axes)

    def pack_count(self, nrec):
        return nrec[None]  # one independently-counted miss segment per shard

    def reduce_metrics(self, m):
        # ONE all-reduce for the whole plan: the additive scalars and the
        # per-hop miss-count vector (the deferred phase gate) globalize as
        # a single concatenated psum instead of one psum per metric per plan
        # plus one gate psum per hop
        m["locality_routed"] = self._locality
        keys = [k for k in _ADDITIVE_METRICS if k in m]
        hop_k = m["_hop_k"]
        parts = [jnp.stack([m[k] for k in keys]).astype(jnp.int32), hop_k]
        S = len(OWNER_STAGE_FIELDS)
        if self.telemetry:
            # per-owner stage attribution rides the SAME psum: before the
            # reduction every metric value is this shard's local count, so
            # one-hot scattering the locals at our own row of an [n, S]
            # block and summing across shards assembles the full matrix on
            # every shard — zero extra collectives. Field order is the
            # OWNER_STAGE_FIELDS contract (repro.obs.metrics). hits/misses/
            # edges/leaves and frontier occupancy accumulate owner-side
            # (post-route); route_overflow and deferred accumulate at the
            # origin shard.
            local_src = {
                "frontier_rows": m.pop("_frontier_rows"),
                "probe_hits": m["hits"],
                "miss_rows": m["misses"],
                "edges_scanned": m["edges_scanned"],
                "leaf_fetches": m["leaf_fetches"],
                "route_overflow": m["route_overflow"],
                "deferred_rows": m["deferred"],
            }
            local = jnp.stack(
                [local_src[f] for f in OWNER_STAGE_FIELDS]
            ).astype(jnp.int32)
            block = jnp.zeros((self.n, S), jnp.int32).at[
                jax.lax.axis_index(self.axes)
            ].set(local)
            parts.append(block.reshape(-1))
        g = jax.lax.psum(jnp.concatenate(parts), self.axes)
        for i, k in enumerate(keys):
            m[k] = g[i]
        nk, nh = len(keys), hop_k.shape[0]
        m["_hop_k"] = g[nk:nk + nh]
        if self.telemetry:
            m["owner_stage"] = g[nk + nh:].reshape(self.n, S)
        return m


class _NextTier:
    """Handle for a background capacity pre-compile: the double-buffered
    next-tier spec plus completion state. ``ready`` fires when every
    requested step (and the grow-pad swap program) is compiled; ``error``
    carries a worker failure to surface at swap time."""

    def __init__(self, pspec):
        self.pspec = pspec
        self.ready = threading.Event()
        self.error: Exception | None = None
        self.compiled = 0
        self.seconds = 0.0


class _GrStep:
    """One compiled gR program and its host boundary. ``jitted`` takes the
    packed ``[bucket, 2]`` input and returns every output in one packed
    buffer; ``layout`` is filled when it is traced. ``dispatch`` runs it,
    ``fetch`` is the one blocking device→host transfer and ``unpack``
    slices the host copy into ``(results, deferred, miss_roots,
    miss_counts, metrics, read_version)``. Calling the step does all three
    — the ``serve_step`` contract."""

    def __init__(self, rt: "ShardedTxnRuntime", jitted, layout: list):
        self.rt = rt
        self.jitted = jitted
        self.layout = layout

    def dispatch(self, store, cache, ttable, qin, down=None, rtable=None):
        rt = self.rt
        if down is None:
            down = rt._down_none()
        elif not isinstance(down, jax.Array):
            rt.gr_host_transfers += 1
            down = jnp.asarray(down)
        return self.jitted(store, cache, ttable, qin, down,
                           rt._resolve_rtable(rtable))

    def fetch(self, words) -> np.ndarray:
        self.rt.gr_host_transfers += 1
        return jax.device_get(words)

    def unpack(self, words: np.ndarray):
        return _unpack_words(words, self.layout, self.rt.n)

    def __call__(self, store, cache, ttable, roots, bvalid, down=None,
                 rtable=None):
        qin = np.stack([np.asarray(roots, np.int32),
                        np.asarray(bvalid, np.int32)], axis=1)
        words = self.dispatch(store, cache, ttable,
                              self.rt._put_gr_input(qin), down, rtable)
        return self.unpack(self.fetch(words))


class ShardedTxnRuntime:
    """One transaction runtime spread over a device mesh.

    ``espec`` is the *global* spec: ``espec.cache.capacity`` is the fleet
    cache capacity, sharded into ``n`` co-partitioned blocks of
    ``capacity // n`` slots (each a power of two); vertex ownership is
    interleaved (``partition.owner_of``). On a 1-device mesh every
    collective degenerates and the runtime is the single-host engine.

    ``store_tier`` selects the storage layout: ``"partitioned"`` (default)
    keeps only owner-local dual-CSR edge blocks per shard (O(E/n) bytes;
    build state with ``partition_store``); ``"replicated"`` keeps a full
    ``GraphStore`` snapshot per shard (the PR 3 baseline).

    ``route_cap_factor`` / ``ops_route_cap`` bound per-peer routing buckets;
    the default is the measured-skew production cap
    (``DEFAULT_ROUTE_CAP_FACTOR``) — ``None`` sizes them for the worst case
    (no overflow possible, byte-identity-test configuration). Smaller
    values trade memory/traffic for a nonzero ``route_overflow`` risk,
    which the step metrics surface. A tuple gives **per-hop** factors (hop
    ``i`` uses entry ``min(i, last)``): hop ≥ 2 routes *leaf-derived*
    frontier roots whose skew is measured separately from root skew
    (``workload.measure_route_skew``), so a mix whose frontiers are flatter
    than its Zipfian roots can run tighter buckets on the inner hops.
    ``"auto"`` sizes buckets from the telemetry tier's *measured* per-owner
    frontier skew (starting at the production default), ratcheting up as
    skew is observed; a batch that still overflows re-dispatches once on
    the worst-case-caps program variant instead of dropping rows
    (``route_cap_retries`` in the step metrics) — this retires hand-tuned
    CI cap factors.

    ``attach_routing(rhost)`` threads a live ``RoutingTableHost`` through
    every step (serving, commits, CP population, miss-drain queueing) as a
    replicated traced input: table updates — hot-vertex migrations, cache
    locality overrides — are input changes at batch boundaries, never
    recompiles. See ``repro.distributed.routing`` and ``docs/ROUTING.md``.

    ``maintenance_tick`` (between transaction batches) keeps the
    partitioned tier healthy under sustained gRW traffic: owner-local block
    compaction once recent regions fill and capacity growth instead of
    append overflow — see ``repro.graphstore.maintenance``.
    """

    def __init__(self, espec, mesh: Mesh, *, use_cache: bool = True,
                 store_tier: str = "partitioned",
                 route_cap_factor: int | tuple | None = DEFAULT_ROUTE_CAP_FACTOR,
                 ops_cap: int = 4096, sweep_cap: int = 512,
                 ops_route_cap: int | None = None,
                 blk_slack: float = 2.0, e_blk_cap: int | None = None,
                 recent_blk_cap: int | None = None,
                 fused_gather: bool = True, overlap: bool = False,
                 telemetry: bool = True, tracer=None):
        assert store_tier in ("partitioned", "replicated"), store_tier
        self.axes = tuple(mesh.axis_names)
        # spec spelling for device_put shardings: a single mesh axis must
        # be the bare name, not a 1-tuple. P(("shard",)) and P("shard")
        # compare equal, but the jit fastpath keys on the concrete layout
        # string and shard_map outputs normalize to the bare-name form —
        # mixing the spellings makes a second executable-cache entry for
        # the same program (pinned by the zero-recompile tests)
        self._ax = self.axes[0] if len(self.axes) == 1 else self.axes
        self.n = int(np.prod([mesh.shape[a] for a in self.axes]))
        n = self.n
        assert n & (n - 1) == 0, "shard count must be a power of two"
        C = espec.cache.capacity
        Cloc = C // n
        assert C % n == 0 and Cloc & (Cloc - 1) == 0, (
            "global cache capacity must shard into power-of-two blocks"
        )
        assert espec.store.v_cap % n == 0, "v_cap must divide over shards"
        self.mesh = mesh
        self.espec = espec
        self.lspec = espec._replace(cache=espec.cache._replace(capacity=Cloc))
        self.use_cache = use_cache
        self.store_tier = store_tier
        if store_tier == "partitioned":
            pspec = default_pspec(
                espec.store, n, slack=blk_slack, recent_blk_cap=recent_blk_cap
            )
            if e_blk_cap is not None:
                pspec = pspec._replace(
                    e_blk_cap=e_blk_cap,
                    recent_blk_cap=min(pspec.recent_blk_cap, e_blk_cap),
                )
            self.pspec = pspec
        else:
            self.pspec = None
        if isinstance(route_cap_factor, (list, tuple)):
            route_cap_factor = tuple(route_cap_factor)
            assert route_cap_factor and all(
                isinstance(f, int) for f in route_cap_factor
            ), "per-hop route_cap_factor entries must be ints"
        elif isinstance(route_cap_factor, str):
            assert route_cap_factor == "auto", route_cap_factor
        self.route_cap_factor = route_cap_factor
        # fused_gather selects the kernels/block_gather owner-local miss
        # executor (sort-based dedup + static-specialized predicates) on
        # the partitioned tier; False keeps the PR 4 multi-op
        # gather_block + onehop_exec_view path for A/B comparison.
        self.fused_gather = fused_gather
        # overlap double-buffers the hop-loop frontier (two row streams,
        # one-stage pipeline skew) so exchanges overlap owner-local exec
        # under async collectives — see runtime.make_plan_fn(overlap=...)
        self.overlap = overlap
        # telemetry: when on (default), serving steps assemble the
        # per-owner stage block on-device (riding the existing stacked
        # all-reduce — see the module docstring's Observability section)
        # and host wrappers wrap their phases in tracer spans. ``tracer``
        # defaults to the zero-cost NULL_TRACER.
        self.telemetry = bool(telemetry)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # wall-clock of the latest executed serving step (blocking sync
        # included) — the unscripted FailoverController probe's heartbeat
        self.last_step_seconds = 0.0
        # the latest step's per-owner stage counters ([n, S] int64, field
        # order OWNER_STAGE_FIELDS) and work-attributed per-owner step
        # seconds — None until a telemetry-on step runs
        self.last_owner_stage = None
        self.last_step_owner_seconds = None
        self.ops_cap = ops_cap
        self.sweep_cap = sweep_cap
        self.ops_route_cap = ops_route_cap if ops_route_cap is not None else ops_cap
        # compiled-step caches, every key TIER-SCOPED (leading element is the
        # pspec the program closed over) so a capacity swap invalidates only
        # the tiers it retires — see _set_pspec
        self._gr_fns: dict = {}
        self._grw_fns: dict = {}
        self._pop_fns: dict = {}
        self._maint_fns: dict = {}
        self._grow_fns: dict = {}
        # applied mutation rows since the last compaction tick (one input to
        # MaintenancePolicy's latency-amortization bound)
        self.mutation_rows_since_compact = 0
        # hitless elasticity: the in-flight background pre-compile handle and
        # the count of completed hot-swaps (serve-loop metric)
        self._next_tier: _NextTier | None = None
        self.swap_events = 0
        # stateful routing: the attached host routing table (None = the
        # compiled-in modulo layout — identity-table input, byte-identical),
        # the peak measured owner frontier skew (feeds "auto" route caps),
        # and the host-side retry counters the serve loop reports
        self.rhost: RoutingTableHost | None = None
        self._route_skew_seen: float | None = None
        self.route_cap_retries = 0
        self.locality_retries = 0
        # buffers gR steps moved across the host<->device boundary, both
        # directions: one in and one out per healthy dispatch
        self.gr_host_transfers = 0
        self._rows_sharding = NamedSharding(mesh, P(self._ax))

    # ------------------------------------------------------------ sharding
    def cache_sharding(self):
        """NamedShardings laying the cache over the mesh — built from the
        same specs the steps' shard_map outputs carry, so a device_put
        cache and a step-returned cache are one executable-cache key."""
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), self._cache_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )

    def _cache_specs(self):
        # one entry per array dimension (vals is 2D: P(ax, None)), the
        # spelling a jitted step's outputs carry. P(ax) places vals the
        # same, but the jit fastpath keys on the spelling: a device_put
        # cache under one and a step-returned cache under the other would
        # be a second executable of the serve step (see the _ax note in
        # __init__; pinned by the zero-recompile tests)
        a = self._ax
        return CacheState(
            tpl=P(a), root=P(a), fp=P(a), chunk=P(a), total_len=P(a),
            vals=P(a, None), version=P(a), valid=P(a),
            n_hit=P(), n_miss=P(), n_insert=P(), n_evict=P(), n_delete=P(),
            n_oversize=P(),
        )

    def _store_specs(self):
        """shard_map PartitionSpecs for the storage tier."""
        if self.pspec is None:
            return P()  # replicated snapshot
        # one entry per array dimension, like the cache specs: the
        # spelling a step's returned store carries
        a = self._ax
        blk = EdgeBlock(
            key=P(a), other=P(a), label=P(a), alive=P(a), props=P(a, None),
            geid=P(a), gperm=P(a), indptr=P(a), blk_len=P(a), csr_len=P(a),
        )
        return PartitionedGraphStore(
            vlabel=P(None), valive=P(None), vprops=P(None, None),
            vversion=P(None), out=blk, inc=blk, v_len=P(), e_len=P(),
            version=P(),
        )

    def store_sharding(self):
        """NamedShardings laying the storage tier over the mesh."""
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), self._store_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )

    def partition_store(self, store, *, elastic: bool = False) -> PartitionedGraphStore:
        """Partition a full ``GraphStore`` into this runtime's owner-local
        blocks and lay it over the mesh (partitioned tier only). The blocks
        are built on the host and each shard's slice goes straight to its
        device, so no device stages the whole store.

        With ``elastic=True`` an over-capacity orientation grows
        ``e_blk_cap`` (25% headroom over the reported need) and retries
        instead of raising ``BlockCapacityError`` — the ingest-time half of
        capacity elasticity; ``maintenance_tick`` handles the online half.
        """
        assert self.pspec is not None, "replicated tier keeps full snapshots"
        while True:
            try:
                ps = partition_store_host(self.pspec, store)
                break
            except BlockCapacityError as e:
                if not elastic:
                    raise
                self._set_pspec(self.pspec._replace(
                    e_blk_cap=max(
                        int(np.ceil(e.needed * 1.25)), self.pspec.e_blk_cap + 1
                    ),
                ))
        return jax.device_put(ps, self.store_sharding())

    def store_bytes(self, pstore=None) -> dict:
        """Per-shard bytes vs the replicated snapshot (partitioned tier)."""
        assert self.pspec is not None
        return store_bytes_report(self.pspec, pstore)

    # ---------------------------------------------------- block maintenance
    def _set_pspec(self, pspec):
        """Swap the block layout spec. Invalidation is **tier-scoped**:
        every compiled-step cache key leads with the pspec the program
        closed over, so programs of the incoming tier (a background
        pre-compile populated them) and the outgoing tier (in-flight
        batches may still reference it) survive the swap — only strictly
        older tiers are pruned. Unaffected plans keep their compiled steps
        across a swap instead of recompiling from scratch."""
        keep = {self.pspec, pspec}
        self.pspec = pspec
        for cache in (self._gr_fns, self._grw_fns, self._pop_fns,
                      self._maint_fns, self._grow_fns):
            for k in [k for k in cache if k[0] not in keep]:
                del cache[k]

    def set_block_capacity(self, e_blk_cap: int, *,
                           recent_blk_cap: int | None = None):
        """Adopt a block-layout spec without a store in hand — the recovery
        path: ``journal.replay`` restores a checkpoint whose blocks were
        snapshotted under a recorded capacity, so the runtime must speak
        that layout before the restore."""
        assert self.pspec is not None
        rb = (self.pspec.recent_blk_cap if recent_blk_cap is None
              else int(recent_blk_cap))
        self._set_pspec(self.pspec._replace(
            e_blk_cap=int(e_blk_cap), recent_blk_cap=min(rb, int(e_blk_cap)),
        ))

    def store_occupancy(self, pstore) -> dict:
        """Per-shard/per-block occupancy + recent fill (partitioned tier)."""
        assert self.pspec is not None
        return block_occupancy(self.pspec, pstore)

    def compact_step(self, purge: bool = False, *, pspec=None):
        """The jitted owner-local compaction pass: every shard merges its
        block recent regions into the sorted CSR bodies and rebuilds its
        geid→slot indexes, with no collectives (cached per tier + ``purge``)."""
        assert self.pspec is not None
        pspec = self.pspec if pspec is None else pspec
        key = (pspec, purge)
        if key not in self._maint_fns:
            def local_compact(ps):
                return ps._replace(
                    out=compact_block(pspec, ps.out, purge=purge),
                    inc=compact_block(pspec, ps.inc, purge=purge),
                )

            sm = shard_map(
                local_compact, mesh=self.mesh,
                in_specs=(self._store_specs(),),
                out_specs=self._store_specs(), check_vma=False,
            )
            self._maint_fns[key] = jax.jit(sm)
        return self._maint_fns[key]

    def _grow_step(self, new_pspec, *, pspec=None):
        """The jitted device-resident capacity-grow program (cached per
        tier pair): each shard pads its own blocks from ``pspec`` to
        ``new_pspec`` shapes in place on device — owner-local, no
        collectives, no host round-trip. With the target tier's serving
        steps precompiled (``precompile_next_tier``), one run of this pad
        is the entire hot-swap pause."""
        pspec = self.pspec if pspec is None else pspec
        key = (pspec, new_pspec)
        if key not in self._grow_fns:
            def local_grow(ps):
                return ps._replace(
                    out=grow_block_local(pspec, new_pspec, ps.out),
                    inc=grow_block_local(pspec, new_pspec, ps.inc),
                )

            sm = shard_map(
                local_grow, mesh=self.mesh,
                in_specs=(self._store_specs(),),
                out_specs=self._store_specs(), check_vma=False,
            )
            self._grow_fns[key] = jax.jit(sm)
        return self._grow_fns[key]

    def grow_blocks(self, pstore, e_blk_cap: int, *,
                    recent_blk_cap: int | None = None):
        """Grow every block to ``e_blk_cap`` (device-resident pad, byte-
        identical to the host ``grow_store``) and swap the spec.
        Invalidation is tier-scoped (``_set_pspec``): old-tier steps are
        retained for the previous tier only, and steps for the NEW tier
        compile lazily on first use unless ``precompile_next_tier`` built
        them in the background first — the hitless path is
        ``precompile_next_tier`` + ``swap_to_next_tier``. Step handles
        fetched *directly* (``serve_step`` / ``grw_step`` /
        ``compact_step``) before a growth are stale and must be
        re-acquired; the ``run_*`` wrappers re-resolve per call."""
        assert self.pspec is not None
        rb = (self.pspec.recent_blk_cap if recent_blk_cap is None
              else int(recent_blk_cap))
        new_pspec = self.pspec._replace(
            e_blk_cap=int(e_blk_cap), recent_blk_cap=min(rb, int(e_blk_cap)),
        )
        assert new_pspec.e_blk_cap >= self.pspec.e_blk_cap
        grown = self._grow_step(new_pspec)(pstore)
        self._set_pspec(new_pspec)
        return grown

    # ------------------------------------------------- hitless elasticity
    def precompile_next_tier(self, e_blk_cap: int, ttable, *,
                             recent_blk_cap: int | None = None,
                             gr_plans=(), grw_policies=(),
                             grw_caps: tuple = (8, 32, 32, 8, 32, 32),
                             compact_purges=(), pop_steps=(),
                             background: bool = True):
        """Compile the NEXT capacity tier's serving programs off the serve
        critical path (the background half of hitless elasticity).

        A worker thread warm-calls each requested step on owner-sharded
        dummy inputs at the next tier's shapes — warm calls, because they
        populate the jit dispatch caches under the new tier's key
        (``.lower().compile()`` would not) — plus the device grow-pad
        program that performs the swap itself. The serve loop keeps running
        on the current tier the whole time (compiled-step caches are
        tier-scoped, nothing it uses is touched); when the returned
        handle's ``ready`` event fires, ``swap_to_next_tier`` flips the
        store at a batch boundary with every post-swap step already
        compiled. The dummy next-tier store transiently costs one extra
        store's worth of device memory.

        - ``gr_plans`` — ``(plan, global_batch_bucket)`` pairs to warm.
        - ``grw_policies`` — ``(policy, gate)`` pairs (``gate`` a
          ``DeviceGate`` or None) at mutation caps ``grw_caps``.
        - ``compact_purges`` — purge flags to warm ``compact_step`` for.
        - ``pop_steps`` — ``(templates_meta, tpl_idx, bucket)`` CP steps.
        """
        assert self.pspec is not None
        cur = self.pspec
        rb = cur.recent_blk_cap if recent_blk_cap is None else int(recent_blk_cap)
        nxt = cur._replace(
            e_blk_cap=int(e_blk_cap), recent_blk_cap=min(rb, int(e_blk_cap)),
        )
        assert nxt.e_blk_cap > cur.e_blk_cap, (nxt.e_blk_cap, cur.e_blk_cap)
        handle = _NextTier(nxt)
        self._next_tier = handle

        def work():
            t0 = time.perf_counter()
            try:
                def zeros_for(pspec):
                    tmpl = abstract_partitioned_store(pspec)
                    z = jax.tree_util.tree_map(
                        lambda s: jnp.zeros(s.shape, s.dtype), tmpl
                    )
                    return jax.device_put(z, self.store_sharding())

                store0 = zeros_for(nxt)
                cache0 = self.empty_cache()
                # the swap pad itself (current -> next tier)
                out = self._grow_step(nxt, pspec=cur)(zeros_for(cur))
                jax.block_until_ready(out)
                handle.compiled += 1
                for plan, bucket in gr_plans:
                    fn = self._gr(plan, bucket, pspec=nxt)
                    fn(store0, cache0, ttable, np.zeros(bucket, np.int32),
                       np.zeros(bucket, bool))
                    handle.compiled += 1
                for pol, gate in grw_policies:
                    fn = self._grw(pol, gate, pspec=nxt)
                    mb = make_mutation_batch(self.espec.store, caps=grw_caps)
                    jax.block_until_ready(fn(store0, cache0, ttable, mb))
                    handle.compiled += 1
                for purge in compact_purges:
                    jax.block_until_ready(
                        self.compact_step(purge, pspec=nxt)(store0)
                    )
                    handle.compiled += 1
                for templates_meta, tpl_idx, bucket in pop_steps:
                    from repro.core.keys import PARAM_LEN

                    fn = self._pop_compiled(
                        templates_meta, tpl_idx, bucket, pspec=nxt
                    )
                    jax.block_until_ready(fn(
                        store0, store0, cache0, ttable,
                        jnp.full((bucket,), -1, jnp.int32),
                        jnp.zeros((bucket, PARAM_LEN), jnp.int32),
                        jnp.zeros((bucket,), jnp.bool_),
                        jnp.zeros((bucket,), jnp.int32),
                        self._rtable_none(),
                    ))
                    handle.compiled += 1
            except Exception as e:  # noqa: BLE001 — surfaced at swap time
                handle.error = e
            finally:
                handle.seconds = time.perf_counter() - t0
                handle.ready.set()

        if background:
            threading.Thread(
                target=work, name="tier-precompile", daemon=True
            ).start()
        else:
            work()
        return handle

    def swap_to_next_tier(self, pstore):
        """Hot-swap the store and compiled steps to the precompiled next
        tier at a batch boundary: run the (pre-warmed) device grow-pad,
        flip the spec, prune strictly-older tiers. Blocks until the
        background pre-compile finishes if it has not (callers wanting a
        pause-free swap check ``handle.ready`` first). Returns
        ``(pstore', info)``."""
        h = self._next_tier
        assert h is not None, "no next tier: call precompile_next_tier first"
        h.ready.wait()
        if h.error is not None:
            self._next_tier = None
            raise RuntimeError("next-tier precompile failed") from h.error
        t0 = time.perf_counter()
        with self.tracer.span("hot_swap_pause"):
            grown = self._grow_step(h.pspec)(pstore)
            jax.block_until_ready(grown)
        swap_s = time.perf_counter() - t0
        self._set_pspec(h.pspec)
        self.swap_events += 1
        self._next_tier = None
        return grown, dict(
            swap_seconds=swap_s, e_blk_cap=h.pspec.e_blk_cap,
            recent_blk_cap=h.pspec.recent_blk_cap,
            precompile_seconds=h.seconds, compiled_steps=h.compiled,
        )

    def maintenance_tick(self, pstore, policy: MaintenancePolicy | None = None,
                         *, occupancy: dict | None = None, journal=None):
        """Run due maintenance between transaction batches.

        Reads only the tiny block-length scalars, then (per the policy)
        grows capacity and/or runs the owner-local compaction pass. Returns
        ``(pstore', info)`` where ``info`` reports what ran and the
        occupancy/recent-fill signals that drove it.

        ``occupancy`` lets a caller that just committed reuse the report its
        ``run_grw_tx`` metrics were derived from (any dict carrying
        ``max_occupancy`` / ``max_recent_fill`` for *this* ``pstore``)
        instead of re-reading the block scalars inside a timed loop.

        ``journal`` (a ``graphstore.journal.WriteBehindJournal``) records
        every maintenance event that runs (GROW / COMPACT), so recovery
        replays layout changes at the same point in the commit order.
        Host-scheduled ticks are the fallback path — the gated gRW step
        (``grw_step(gate=...)``) compacts on-device without any of this.
        """
        assert self.pspec is not None, "maintenance targets the partitioned tier"
        with self.tracer.span("compaction_tick"):
            policy = MaintenancePolicy() if policy is None else policy
            occ = (self.store_occupancy(pstore) if occupancy is None
                   else occupancy)
            dec = decide_maintenance(
                self.pspec, occ, policy, self.mutation_rows_since_compact
            )
            info = dict(
                compacted=False, grown_to=None, reason=dec.reason,
                max_occupancy=occ["max_occupancy"],
                max_recent_fill=occ["max_recent_fill"],
            )
            if dec.grow_to is not None:
                pstore = self.grow_blocks(pstore, dec.grow_to)
                if journal is not None:
                    journal.append_grow(
                        self.pspec.e_blk_cap, self.pspec.recent_blk_cap
                    )
                info["grown_to"] = dec.grow_to
            if dec.compact:
                pstore = self.compact_step(policy.purge)(pstore)
                if journal is not None:
                    journal.append_compact(purge=policy.purge)
                self.mutation_rows_since_compact = 0
                info["compacted"] = True
        return pstore, info

    def empty_cache(self) -> CacheState:
        """Global-capacity empty cache, device_put over the mesh: block s of
        every slot array is shard s's local cache (all blocks empty)."""
        return jax.device_put(empty_cache(self.espec.cache), self.cache_sharding())

    def shard_cache(self, cache: CacheState) -> CacheState:
        """Lay an existing global CacheState out over the mesh. Note the
        slot *layout* is reinterpreted (each block probes with the local
        capacity), so only caches whose entries were inserted through this
        runtime probe correctly — use ``empty_cache`` + population for new
        state."""
        return jax.device_put(cache, self.cache_sharding())

    # ---------------------------------------------------- stateful routing
    def attach_routing(self, rhost: RoutingTableHost | None):
        """Attach the host routing table. Once attached, every serving /
        commit / CP step resolves ``rhost.device_table()`` at dispatch time
        (cached per epoch, so an unchanged table costs a dict hit), and
        ``ShardedMissDrain`` queues misses at each root's *cache* owner.
        ``None`` detaches — back to the compiled-in modulo layout."""
        if rhost is not None:
            assert rhost.n == self.n, (rhost.n, self.n)
        self.rhost = rhost
        return rhost

    def _rtable_none(self) -> RoutingTable:
        """The identity table (routes exactly like ``owner_of``) — the
        serve step's default ``rtable`` input, cached so steady-state
        batches reuse one device constant instead of re-transferring."""
        if getattr(self, "_rtable_id", None) is None:
            self._rtable_id = identity_table(self.n)
        return self._rtable_id

    def _resolve_rtable(self, rtable) -> RoutingTable:
        """Resolve a step's table input: an explicit device ``RoutingTable``
        passes through, a ``RoutingTableHost`` stamps its current device
        table, ``None`` falls back to the attached ``rhost`` (or the
        identity table)."""
        if rtable is None:
            return (self.rhost.device_table() if self.rhost is not None
                    else self._rtable_none())
        if isinstance(rtable, RoutingTableHost):
            return rtable.device_table()
        return rtable

    # --------------------------------------------------------- gR-Tx path
    def _effective_cap_factor(self, worst_case: bool = False):
        """The cap factor a program variant compiles with. ``"auto"``
        derives the factor from measured telemetry (the peak per-owner
        frontier-row share, 25% headroom, floor 2) and starts at the
        measured production default before any step has run; the factor
        only ever grows (monotone max), so adaptation recompiles a bounded
        number of times. ``worst_case=True`` is the no-drop fallback
        variant the overflow retry dispatches."""
        if worst_case:
            return None
        rcf = self.route_cap_factor
        if rcf == "auto":
            if self._route_skew_seen is None:
                return DEFAULT_ROUTE_CAP_FACTOR
            f = max(2, int(np.ceil(self._route_skew_seen * 1.25)))
            return (max(f, DEFAULT_ROUTE_CAP_FACTOR[0]),
                    max(f, DEFAULT_ROUTE_CAP_FACTOR[1]))
        return rcf

    def _hop_route_caps(self, plan, Bloc, *, worst_case: bool = False):
        """Per-hop per-peer routing capacity (worst case unless bounded).

        A scalar ``route_cap_factor`` applies to every hop; a tuple supplies
        per-hop factors (hop 1 routes query roots, hops ≥ 2 route
        leaf-derived frontier roots with separately measured skew);
        ``"auto"`` derives them from the telemetry tier's measured owner
        skew (``_effective_cap_factor``)."""
        caps, A = [], 1
        F, RW = self.espec.frontier, self.espec.result_width
        rcf = self._effective_cap_factor(worst_case)
        for i, _ in enumerate(plan.hops):
            rows = Bloc * A
            f = rcf[min(i, len(rcf) - 1)] if isinstance(rcf, tuple) else rcf
            if f is None:
                caps.append(max(1, rows))
            else:
                caps.append(max(1, -(-f * rows // self.n)))
            A = min(F, A * RW)
        return caps

    def _down_none(self):
        """The healthy owner mask (all-False) — the serve step's default
        ``down`` input, cached so steady-state batches reuse one device
        constant instead of re-transferring per call."""
        if getattr(self, "_down_zeros", None) is None:
            self._down_zeros = jnp.zeros((self.n,), jnp.bool_)
        return self._down_zeros

    def _gr_fn(self, plan, bucket: int, *, pspec=None,
               worst_case: bool = False, layout: list | None = None):
        """The un-jitted shard_map serving program (AOT lowering hook):
        ``(store, cache, ttable, qin, down, rtable) -> words``. ``qin`` is
        the padded batch as one int32 ``[bucket, 2]`` buffer (roots,
        valid flags); ``words`` is every output packed into one int32
        buffer, one row of ``L`` words per shard (``_pack_words``), whose
        layout is written into ``layout`` when the program is traced.
        ``pspec`` defaults to the current tier; the background pre-compiler
        passes the next tier's spec to build double-buffered programs.
        ``worst_case`` sizes route buckets for no-drop (the overflow-retry
        fallback variant)."""
        plan_fn = self._gr_plan_fn(plan, bucket, pspec=pspec,
                                   worst_case=worst_case)
        layout = [] if layout is None else layout

        # named ``fused`` so the compiled program stays ``jit_fused`` on a
        # profiler trace, the name the benchmark's device readers look for
        def fused(store, cache, ttable, qin, down, rtable):
            out = plan_fn(store, cache, ttable, qin[:, 0], qin[:, 1] != 0,
                          down, rtable)
            return _pack_words(out, layout)

        return shard_map(
            fused,
            mesh=self.mesh,
            in_specs=(
                self._store_specs(), self._cache_specs(), P(),
                P(self.axes), P(), P(),
            ),
            out_specs=P(self.axes),
            check_vma=False,
        )

    def _gr_plan_fn(self, plan, bucket: int, *, pspec=None,
                    worst_case: bool = False):
        """The per-shard hop driver of ``_gr_fn``, before its outputs are
        packed: ``(result, deferred, miss_roots, miss_counts, metrics,
        version)`` as the shard sees them."""
        n = self.n
        assert bucket % n == 0, "global batch bucket must divide over shards"
        pspec = self.pspec if pspec is None else pspec
        Bloc = bucket // n
        # double-buffering needs an even per-shard batch to split into two
        # row streams; route caps are sized for the half-batch each stream
        # actually routes
        overlap = self.overlap and Bloc % 2 == 0 and Bloc >= 2
        caps = self._hop_route_caps(
            plan, Bloc // 2 if overlap else Bloc, worst_case=worst_case
        )
        return make_plan_fn(
            self.lspec, plan, self.use_cache, _MeshTier(self, caps, pspec),
            overlap=overlap,
        )

    def _put_gr_input(self, qin: np.ndarray):
        """The step's one host→device copy: the ``[bucket, 2]`` int32
        input (``pack_roots``), laid over the mesh by rows."""
        self.gr_host_transfers += 1
        return jax.device_put(qin, self._rows_sharding)

    def _gr(self, plan, bucket: int, *, pspec=None, worst_case: bool = False):
        pspec = self.pspec if pspec is None else pspec
        # the caps are part of the key: "auto" mode re-derives the factor
        # from telemetry, and a grown factor is a new program variant (the
        # worst-case retry variant keys the same way)
        Bloc = bucket // self.n
        overlap = self.overlap and Bloc % 2 == 0 and Bloc >= 2
        caps = tuple(self._hop_route_caps(
            plan, Bloc // 2 if overlap else Bloc, worst_case=worst_case
        ))
        key = (pspec, _plan_key(plan), bucket, caps)
        if key not in self._gr_fns:
            layout = []
            jitted = jax.jit(self._gr_fn(
                plan, bucket, pspec=pspec, worst_case=worst_case,
                layout=layout,
            ))
            self._gr_fns[key] = _GrStep(self, jitted, layout)
        return self._gr_fns[key]

    def serve_step(self, plan, global_batch: int):
        """The jitted serving step for one ``QueryPlan`` (any hop count) —
        ``step(store, cache, ttable, roots [global_batch], bvalid,
        down=None, rtable=None) -> (results, deferred, miss_roots,
        miss_counts, metrics, read_version)``. ``down`` is the
        degraded-mode owner mask (bool[n], default all-healthy);
        ``rtable`` the replicated routing table (``RoutingTable`` /
        ``RoutingTableHost``; default: the attached ``rhost`` or the
        identity table — byte-identical to the static modulo layout);
        ``deferred`` flags rows whose miss segments were masked at a down
        owner (bounded-stale) or locality-routed away from their storage
        owner (retry through ``RoutingTableHost.storage_table()``). The
        six come back as host arrays: the step sends ``roots`` and
        ``bvalid`` as one packed buffer and fetches its outputs as one
        (see ``_GrStep``); ``step.jitted`` is the compiled program."""
        return self._gr(plan, global_batch)

    def run_gr_tx_batch(self, store, cache, ttable, plan, roots, *,
                        down=None, rtable=None,
                        return_deferred: bool = False):
        """Host wrapper: pad, execute, decode misses. Same contract as
        ``GraphEngine.run``. On the healthy path a call moves two buffers
        across the host↔device boundary: one host→device copy of the
        padded batch and one blocking device→host transfer of every
        output, packed (``gr_host_transfers`` counts them).

        ``down`` (bool[n]) masks the named owners' miss segments
        (degraded-mode serving); ``rtable`` threads the routing table (see
        ``serve_step``). Two host-side retry loops wrap the step, both
        re-dispatching through compiled program variants (never a
        recompile on the serving path):

        - **locality retry** — rows deferred because they hit a *split*
          vertex's cache home (cache owner ≠ storage owner) re-dispatch
          once through the table's storage view
          (``RoutingTableHost.storage_table()`` — the same compiled
          program, a different table input). Needs a host table (a
          ``RoutingTableHost`` argument or the attached ``rhost``).
        - **overflow retry** (``route_cap_factor="auto"`` only) — a batch
          that overflowed the telemetry-derived buckets re-dispatches on
          the worst-case-caps variant, and the measured skew ratchets up
          so future plans compile with wider buckets
          (``route_cap_retries`` counts the fallbacks). It reuses the
          input already on the device: one more transfer, back.

        With ``return_deferred=True`` the per-query deferred flags come
        back as a fourth element."""
        tr = self.tracer
        B = len(roots)
        with tr.span("gr_prepare"):
            bucket = max(bucket_for(B), self.n)
            qin = self._put_gr_input(pack_roots(roots, bucket))
        rhost = rtable if isinstance(rtable, RoutingTableHost) else (
            self.rhost if rtable is None else None
        )
        t0 = time.perf_counter()
        with tr.span("gr_dispatch"):
            step = self._gr(plan, bucket)
            words = step.dispatch(store, cache, ttable, qin, down, rtable)
        with tr.span("gr_sync"):
            words = step.fetch(words)
        # measured per-step wall-clock (the fetch above is the blocking
        # sync): the live heartbeat FailoverController feeds the
        # FailureDetector when no scripted ShardFaultPlan is driving it
        self.last_step_seconds = time.perf_counter() - t0
        with tr.span("gr_unpack"):
            result, deferred, miss_roots, miss_counts, m, version = (
                step.unpack(words)
            )
            # pop the per-owner stage block BEFORE building the host
            # metrics dict, keeping it byte-identical to telemetry=False
            owner_stage = m.pop("owner_stage", None)
            metrics = {k: int(v) for k, v in m.items()}
            metrics["host_syncs"] = 1
            misses = decode_miss_records(
                plan, self.use_cache, miss_roots, miss_counts, int(version)
            )
        if owner_stage is not None:
            self.last_owner_stage = np.asarray(owner_stage, dtype=np.int64)
            self.last_step_owner_seconds = attribute_step_seconds(
                self.last_step_seconds, self.last_owner_stage
            )
            # feed the auto-cap sizer: peak owner share of routed frontier
            # rows this step (ratcheted max, so factors only ever grow)
            fr = self.last_owner_stage[
                :, OWNER_STAGE_FIELDS.index("frontier_rows")
            ].astype(np.float64)
            if fr.sum() > 0:
                skew = float(fr.max() * self.n / fr.sum())
                self._route_skew_seen = (
                    skew if self._route_skew_seen is None
                    else max(self._route_skew_seen, skew)
                )
        else:
            self.last_owner_stage = None
            self.last_step_owner_seconds = None
        metrics["route_cap_retries"] = 0
        if self.route_cap_factor == "auto" and metrics["route_overflow"] > 0:
            with tr.span("gr_dispatch"):
                step = self._gr(plan, bucket, worst_case=True)
                words = step.dispatch(store, cache, ttable, qin, down, rtable)
            with tr.span("gr_sync"):
                words = step.fetch(words)
            result, deferred, miss_roots, miss_counts, m2, version = (
                step.unpack(words)
            )
            m2.pop("owner_stage", None)
            syncs = metrics["host_syncs"] + 1
            metrics = {k: int(v) for k, v in m2.items()}
            metrics["host_syncs"] = syncs
            metrics["route_cap_retries"] = 1
            self.route_cap_retries += 1
            misses = decode_miss_records(
                plan, self.use_cache, miss_roots, miss_counts, int(version)
            )
        metrics["locality_retry_rows"] = 0
        if rhost is not None and rhost.cache_exceptions and deferred[:B].any():
            split = np.asarray(rhost.is_split(np.asarray(roots, np.int64)))
            idx = np.flatnonzero(deferred[:B] & split)
            if idx.size:
                r2, mis2, m2, d2 = self.run_gr_tx_batch(
                    store, cache, ttable, plan,
                    np.asarray(roots, np.int32)[idx],
                    down=down, rtable=rhost.storage_table(),
                    return_deferred=True,
                )
                # unpacked outputs may be read-only views of the fetched
                # buffer; copy to merge into
                result, deferred = result.copy(), deferred.copy()
                result[idx] = r2
                deferred[idx] = d2
                misses = list(misses) + list(mis2)
                for k, v in m2.items():
                    if k in metrics:
                        metrics[k] += int(v)
                metrics["locality_retry_rows"] = int(idx.size)
                self.locality_retries += 1
        if return_deferred:
            return result[:B], misses, metrics, deferred[:B]
        return result[:B], misses, metrics

    # -------------------------------------------------------- gRW-Tx path
    def _route_and_apply_ops(self, cache, ops, sweeps, through, local_sweeps,
                             rtable=None):
        """Shared phase B: compact the derived op stream, route each op to
        the shard holding its root's *cache* entries (``cache_owner_of``
        under ``rtable``; the identity table is exactly ``owner_of``), and
        apply against the local cache block. ``local_sweeps`` marks sweeps
        as already owner-local; otherwise they are all_gathered and every
        shard applies the full stream (non-matching sweeps no-op, so this
        is correct wherever a root's entries live — the partitioned tier
        uses it because a migrated/split root's cache home may differ from
        the storage shard that derived the sweep).

        Returns (cache', occupancy_delta, overflow)."""
        lcspec = self.lspec.cache
        n, axes = self.n, self.axes
        ops_cap, sweep_cap = self.ops_cap, self.sweep_cap
        ops_route_cap = self.ops_route_cap

        # compact: only real ops are routed/applied — the pre-compaction
        # path instead probed every masked lane of the stream
        (okind, otpl, oroot, oparams, ovid, oorder), _, ovf_c = compact_rows(
            ops.ok, ops_cap,
            (ops.kind, ops.tpl, ops.root, ops.params, ops.vid, ops.order),
            (0, -1, NULL_ID, 0, NULL_ID, 0),
        )
        # route each op to the shard whose local cache block holds the
        # impacted entry (the root's cache home under the routing table)
        dest = jnp.where(
            oroot != NULL_ID, cache_owner_of(rtable, oroot, n), -1
        )
        slot, kept, ovf_r = route_plan(dest, n, ops_route_cap)

        def a2a(x, fill):
            return jax.lax.all_to_all(
                route_scatter(x, slot, n, ops_route_cap, fill), axes,
                split_axis=0, concat_axis=0, tiled=True,
            ).reshape((n * ops_route_cap,) + x.shape[1:])

        rroot = a2a(oroot, NULL_ID)
        rops = CacheOpStream(
            kind=a2a(okind, 0), tpl=a2a(otpl, -1), root=rroot,
            params=a2a(oparams, 0), vid=a2a(ovid, NULL_ID),
            order=a2a(oorder, 0), ok=rroot != NULL_ID,
        )
        (stpl, sroot), _, ovf_s = compact_rows(
            sweeps.ok, sweep_cap, (sweeps.tpl, sweeps.root), (-1, NULL_ID)
        )
        if local_sweeps:
            # ownership-masked phase A already emitted each sweep at the
            # shard whose cache block holds the swept root's entries
            gsw = SweepStream(tpl=stpl, root=sroot, ok=sroot != NULL_ID)
        else:
            g = jax.lax.all_gather(
                jnp.stack([stpl, sroot], axis=1), axes, axis=0, tiled=True
            )
            gsw = SweepStream(tpl=g[:, 0], root=g[:, 1], ok=g[:, 1] != NULL_ID)

        # impacted counts *distinct logical keys removed*: chunk-0
        # occupancy delta. Counting raw ops would over-count a key hit by
        # several routed ops, and counting all slots would over-count
        # multi-chunk chains.
        head = lambda c: jnp.sum((c.valid & (c.chunk == 0)).astype(jnp.int32))
        occ0 = head(cache)
        cache2 = apply_sweeps(lcspec, cache, gsw)
        if through:
            # value edits are order-sensitive per key; distinct keys
            # commute — the segmented apply vectorizes across them
            cache2 = apply_op_stream_segmented(lcspec, cache2, rops)
        else:
            # deletes commute: one batched pass
            cache2 = apply_op_stream_batched(lcspec, cache2, rops)
        occ_delta = occ0 - head(cache2)
        cache2 = cache2._replace(n_delete=cache.n_delete + occ_delta)
        return cache2, occ_delta, ovf_c + ovf_r + ovf_s

    def _grw_fn(self, policy: str, gate: DeviceGate | None = None, *,
                pspec=None):
        """The un-jitted shard_map gRW commit (AOT lowering hook).

        With ``gate`` (a ``DeviceGate``) the step carries the maintenance
        decision **on-device**: after the owner-local apply + listener,
        each shard checks its own blocks' recent fill against the gate
        threshold and compacts them inside a ``lax.cond`` — no per-batch
        host round-trip of block scalars, no separate compaction dispatch.
        The post-maintenance capacity signals (max block occupancy /
        recent fill, pmax-reduced) and the number of shard-blocks compacted
        come back as step outputs, so the host reads them from the commit's
        one transfer instead of a follow-up occupancy read.

        The commit's stages run under named scopes, so a profiler trace
        gives each its device time: phase A is ``grw/apply`` (the
        mutations) and ``grw/derive`` (the listener), then ``grw/gate``
        (the on-device compaction), phase B ``grw/invalidate`` (the routed
        op stream applied to the cache) and ``grw/signals`` (the reduced
        stats and capacity signals)."""
        espec = self.espec
        lspec = self.lspec
        pspec = self.pspec if pspec is None else pspec
        n, axes = self.n, self.axes
        through = policy != "write-around"

        if pspec is not None:
            # static per-block threshold: gate decisions are a pure function
            # of (store, batch, gate), which journal replay relies on
            thresh = (
                max(int(np.ceil(gate.recent_fill_frac * pspec.recent_blk_cap)), 0)
                if gate is not None else 0
            )

            def local_grw(store, cache, ttable, batch, rtable):
                me = jax.lax.axis_index(axes)
                # phase A: commit to owner-local storage; the listener
                # derives ops where the storage lives (ownership masks,
                # table-aware: a migrated vertex's rows commit and derive
                # at its table owner)
                with jax.named_scope("grw/apply"):
                    store2, applied, store_ovf = apply_mutations_partitioned(
                        pspec, store, batch, me, axes, rtable=rtable
                    )
                with jax.named_scope("grw/derive"):
                    ops, sweeps = derive_cache_ops_views(
                        lspec, BlockStoreView(pspec, store, me, rtable=rtable),
                        BlockStoreView(pspec, store2, me, rtable=rtable),
                        ttable, applied, through=through,
                    )
                if gate is not None:
                    # on-device maintenance gate — ops were derived above,
                    # so the layout change cannot perturb this commit's
                    # invalidation; compact_block is collective-free, so a
                    # per-shard lax.cond is legal under check_vma=False
                    def maybe_compact(blk):
                        rec = blk.blk_len[0] - blk.csr_len[0]
                        hit = rec >= thresh
                        return jax.lax.cond(
                            hit,
                            lambda b: compact_block(
                                pspec, b, purge=gate.purge, me=me
                            ),
                            lambda b: b,
                            blk,
                        ), hit
                    with jax.named_scope("grw/gate"):
                        out_b, hit_o = maybe_compact(store2.out)
                        inc_b, hit_i = maybe_compact(store2.inc)
                        store2 = store2._replace(out=out_b, inc=inc_b)
                        ncomp = jax.lax.psum(
                            hit_o.astype(jnp.int32) + hit_i.astype(jnp.int32),
                            axes,
                        )
                else:
                    ncomp = jnp.int32(0)
                # sweeps gather (local_sweeps=False): the listener derives
                # each sweep at the swept root's STORAGE shard, but under a
                # routing table the root's cache entries may live elsewhere
                # — every shard applies the full gathered stream, and
                # non-matching sweeps no-op (byte-identical to the old
                # owner-local apply when the table is the identity)
                with jax.named_scope("grw/invalidate"):
                    cache2, occ_delta, ovf = self._route_and_apply_ops(
                        cache, ops, sweeps, through, local_sweeps=False,
                        rtable=rtable,
                    )
                    impacted = jax.lax.psum(occ_delta, axes)
                with jax.named_scope("grw/signals"):
                    cache2 = _replicate_stats(cache, cache2, axes)
                    overflow = jax.lax.psum(ovf, axes)
                    # post-maintenance capacity signals, reduced on-device
                    blk_max = jax.lax.pmax(jnp.maximum(
                        store2.out.blk_len[0], store2.inc.blk_len[0]
                    ), axes)
                    rec_max = jax.lax.pmax(jnp.maximum(
                        store2.out.blk_len[0] - store2.out.csr_len[0],
                        store2.inc.blk_len[0] - store2.inc.csr_len[0],
                    ), axes)
                return (store2, cache2, impacted, overflow, store_ovf,
                        blk_max, rec_max, ncomp)
        else:
            assert gate is None, "the device gate targets the partitioned tier"

            def local_grw(store, cache, ttable, batch, rtable):
                me = jax.lax.axis_index(axes)
                # every replica applies the same commit (deterministic)
                with jax.named_scope("grw/apply"):
                    store2, applied = apply_mutations(espec.store, store, batch)
                # phase A: derive impacted keys from this shard's slice
                # of the mutation batch (round-robin rows)
                with jax.named_scope("grw/derive"):
                    part = shard_mutation_rows(applied, n, me)
                    ops, sweeps = derive_cache_ops(
                        espec, store, store2, ttable, part, through=through,
                        row_offset=me, row_stride=n,
                    )
                with jax.named_scope("grw/invalidate"):
                    cache2, occ_delta, ovf = self._route_and_apply_ops(
                        cache, ops, sweeps, through, local_sweeps=False,
                        rtable=rtable,
                    )
                    impacted = jax.lax.psum(occ_delta, axes)
                with jax.named_scope("grw/signals"):
                    cache2 = _replicate_stats(cache, cache2, axes)
                    overflow = jax.lax.psum(ovf, axes)
                z = jnp.int32(0)
                return store2, cache2, impacted, overflow, z, z, z, z

        return shard_map(
            local_grw,
            mesh=self.mesh,
            in_specs=(self._store_specs(), self._cache_specs(), P(), P(),
                      P()),
            out_specs=(
                self._store_specs(), self._cache_specs(), P(), P(), P(),
                P(), P(), P(),
            ),
            check_vma=False,
        )

    def _grw(self, policy: str, gate: DeviceGate | None = None, *,
             pspec=None):
        pspec = self.pspec if pspec is None else pspec
        key = (pspec, policy, gate)
        if key not in self._grw_fns:
            jitted = jax.jit(self._grw_fn(policy, gate, pspec=pspec))

            def step(store, cache, ttable, batch, rtable=None, _fn=jitted):
                return _fn(
                    store, cache, ttable, batch,
                    self._resolve_rtable(rtable),
                )

            step.jitted = jitted
            self._grw_fns[key] = step
        return self._grw_fns[key]

    def grw_step(self, policy: str = "write-around",
                 gate: DeviceGate | None = None):
        """The jitted sharded gRW-Tx commit (cached per tier + policy +
        gate): ``step(store, cache, ttable, batch, rtable=None) ->
        (store', cache', impacted, route_overflow, store_overflow,
        max_blk_len, max_recent_fill, device_compactions)``. With ``gate``
        the step compacts over-threshold blocks on-device (see
        ``_grw_fn``); ``rtable`` resolves like ``serve_step``'s."""
        return self._grw(policy, gate)

    def run_grw_tx(self, store, cache, ttable, batch, policy: str = "write-around",
                   *, gate: DeviceGate | None = None,
                   occupancy_metrics: bool = True, journal=None,
                   rtable=None):
        """Host wrapper mirroring ``repro.core.engine.run_grw_tx``.

        ``rtable`` threads the routing table through the commit (resolved
        like ``serve_step``'s: explicit table > ``RoutingTableHost`` >
        attached ``rhost`` > identity); when a host table is available its
        ``storage_owner`` lookup also routes the journal's dirty-owner
        bookkeeping, so incremental checkpoints stay consistent with
        migrated placements.

        On the partitioned tier the metrics also surface the post-commit
        capacity signals (max block occupancy / recent fill) that drive
        growth decisions — computed **inside the step** and pmax-reduced
        on-device, so they ride the commit's own transfer (the pre-gate
        runtime re-read block scalars from the host per batch). With
        ``gate`` the step additionally compacts over-threshold blocks
        on-device and reports ``device_compactions``.

        ``journal`` (a ``WriteBehindJournal``) makes the commit durable
        write-behind: the batch is appended with its effective step config
        (policy + gate) and the journal's lag/queue metrics are folded into
        the returned metrics."""
        rhost = rtable if isinstance(rtable, RoutingTableHost) else (
            self.rhost if rtable is None else None
        )
        with self.tracer.span("grw_step"):
            out = self._grw(policy, gate)(
                store, cache, ttable, batch, rtable
            )
            (store2, cache2, impacted, overflow, store_ovf,
             blk_max, rec_max, ncomp) = out
            metrics = {
                "impacted_keys": int(impacted), "op_overflow": int(overflow),
                "store_append_overflow": int(store_ovf),
            }
        if self.pspec is not None:
            b = batch
            self.mutation_rows_since_compact += sum(
                int(x) for x in (b.nv_n, b.ne_n, b.de_n, b.dv_n, b.sv_n, b.se_n)
            )
            if gate is not None:
                ncomp = int(ncomp)
                metrics["device_compactions"] = ncomp
                if ncomp:
                    self.mutation_rows_since_compact = 0
            if occupancy_metrics:
                EB = self.pspec.e_blk_cap
                metrics["store_occupancy_max"] = round(int(blk_max) / EB, 4)
                metrics["store_recent_fill_max"] = int(rec_max)
        if journal is not None:
            journal.append_commit(
                batch, policy=policy, gate=gate,
                commit_version=int(jax.device_get(store2.version)),
                device_compactions=(
                    int(ncomp) if (gate is not None and self.pspec is not None)
                    else 0
                ),
                route=(rhost.storage_owner if rhost is not None else None),
            )
            metrics.update(journal.metrics())
        return store2, cache2, metrics

    # ------------------------------------------------------ CP population
    def populator(self, templates_meta, max_retries: int = 3):
        """A ``CachePopulator`` whose CP transactions execute each miss at
        its owner shard (against owner-local storage on the partitioned
        tier) and insert at the owner's cache block, draining the same
        MissQueue."""
        from repro.core.population import CachePopulator

        return CachePopulator(
            self.espec, templates_meta, max_retries=max_retries,
            step_builder=functools.partial(self._pop, templates_meta),
        )

    def _pop(self, templates_meta, tpl_idx: int, bucket: int):
        # the returned step resolves the compiled program at CALL time:
        # populators cache this thin adapter in their own _jitted dicts, and
        # _pop_fns is keyed by the CURRENT pspec — so the next drain after a
        # capacity swap resolves the new tier's program (precompiled in the
        # background, or compiled lazily) instead of silently reusing a
        # closure over the pre-growth pspec (whose gathers clamp slots to
        # the old e_blk_cap). The adapter also bridges CachePopulator's
        # keyword calls to shard_map's positional-only wrapper.
        def step(store_exec, store_commit, cache, ttable, roots, params,
                 mask, read_versions):
            return self._pop_compiled(templates_meta, tpl_idx, bucket)(
                store_exec, store_commit, cache, ttable, roots, params,
                mask, read_versions, self._resolve_rtable(None),
            )

        return step

    def _pop_compiled(self, templates_meta, tpl_idx: int, bucket: int, *,
                      pspec=None):
        pspec = self.pspec if pspec is None else pspec
        key = (pspec, tpl_idx, bucket)
        if key not in self._pop_fns:
            from repro.core.population import populate_step

            lspec, n, axes = self.lspec, self.n, self.axes
            direction, edge_label = templates_meta[tpl_idx]

            def local_pop(store_exec, store_commit, cache, ttable, roots,
                          params, mask, read_versions, rtable):
                me = jax.lax.axis_index(axes)
                valid = mask & (roots >= 0)
                if pspec is not None:
                    # CP split under the routing table: the miss executes
                    # at the root's STORAGE owner (where its dual-CSR rows
                    # live) and the entry inserts at its CACHE owner; the
                    # computed bundle crosses via a zero-masked psum inside
                    # populate_step. Identity table → exec == commit shard,
                    # byte-identical to the fused path.
                    owned_exec = valid & (
                        storage_owner_of(rtable, roots, n) == me
                    )
                    owned_commit = valid & (
                        cache_owner_of(rtable, roots, n) == me
                    )
                    view = BlockStoreView(
                        pspec, store_exec, me, rtable=rtable
                    )
                    cache2, ok, ab = populate_step(
                        lspec, store_exec, store_commit, cache, ttable,
                        tpl_idx, direction, edge_label, roots, params,
                        owned_exec, read_versions, exec_view=view,
                        commit_mask=owned_commit,
                        allreduce=lambda x: jax.lax.psum(x, axes),
                    )
                else:
                    # replicated snapshot: every shard can execute any
                    # miss, so CP runs whole at the root's cache owner
                    owned = valid & (cache_owner_of(rtable, roots, n) == me)
                    cache2, ok, ab = populate_step(
                        lspec, store_exec, store_commit, cache, ttable,
                        tpl_idx, direction, edge_label, roots, params,
                        owned, read_versions, exec_view=None,
                    )
                ok = jax.lax.psum(ok.astype(jnp.int32), axes) > 0
                ab = jax.lax.psum(ab.astype(jnp.int32), axes) > 0
                cache2 = _replicate_stats(cache, cache2, axes)
                return cache2, ok, ab

            sm = shard_map(
                local_pop,
                mesh=self.mesh,
                in_specs=(
                    self._store_specs(), self._store_specs(),
                    self._cache_specs(), P(), P(), P(), P(), P(), P(),
                ),
                out_specs=(self._cache_specs(), P(), P()),
                check_vma=False,
            )
            self._pop_fns[key] = jax.jit(sm)
        return self._pop_fns[key]


class ShardedMissDrain:
    """Per-shard CP drain loops over ``serve_step``'s per-shard miss records.

    ``serve_step`` already returns one independently-counted miss segment
    per shard; the single host-side ``CachePopulator`` round-trip merged
    them back into one global FIFO, re-deriving ownership at insert time.
    This keeps one ``MissQueue`` + populator per shard instead — each miss
    record lands in its root's owner queue (the shard whose blocks execute
    it and whose cache block receives the insert), and ``drain`` walks the
    shards round-robin so every CP batch is single-owner (the CP-per-shard
    layout of §4's population threads). All populators share the runtime's
    compiled CP steps, so the fan-out costs no extra compilation.
    """

    def __init__(self, rt: ShardedTxnRuntime, templates_meta,
                 max_retries: int = 3):
        self.n = rt.n
        self.rt = rt
        self.pops = [
            rt.populator(templates_meta, max_retries) for _ in range(rt.n)
        ]

    def push(self, misses):
        rhost = self.rt.rhost
        with self.rt.tracer.span("cp_push"):
            for m in misses:
                # each miss lands at its root's CACHE owner queue — under
                # the routing table that is where the insert commits (and,
                # for an unsplit vertex, where its rows execute)
                owner = (int(rhost.cache_owner(int(m.root)))
                         if rhost is not None
                         else int(base_owner(m.root, self.n)))
                self.pops[owner].queue.push([m])

    def drain(self, store_exec, store_commit, cache, ttable, k: int = 128):
        """Drain up to ``k`` misses per shard queue; returns the new cache.
        The runtime's tracer times each populator's host phases."""
        for pop in self.pops:
            cache = pop.drain(store_exec, store_commit, cache, ttable, k,
                              tracer=self.rt.tracer)
        return cache

    def queue_counts(self) -> dict:
        """``MissQueue.counts`` summed over the shard queues
        (``max_depth`` is the deepest single queue)."""
        per = [p.queue.counts() for p in self.pops]
        out = {k: sum(c[k] for c in per) for k in per[0]}
        out["max_depth"] = max(c["max_depth"] for c in per)
        return out

    @property
    def committed(self) -> int:
        return sum(p.committed for p in self.pops)

    @property
    def aborted(self) -> int:
        return sum(p.aborted for p in self.pops)

    def pending(self) -> int:
        return sum(len(p.queue) for p in self.pops)


# ======================================================================
# Capacity planning: the paper's production deployment described as a
# config, lowered onto the runtime for the roofline/dry-run tooling.
# ======================================================================


@dataclass(frozen=True)
class GraphServeConfig:
    name: str = "ecommerce-graph"
    v_total: int = 2**30  # ~1.1B vertices (tens of billions of edges)
    e_per_vertex: int = 8  # average degree for capacity planning
    n_vprops: int = 2
    n_eprops: int = 1
    max_deg: int = 64  # per-hop gather window
    max_leaves: int = 64  # cache value width
    cache_slots_total: int = 2**26  # cache capacity across the fleet
    route_cap_factor: int | tuple | None = DEFAULT_ROUTE_CAP_FACTOR
    recent_cap: int = 1024  # append-region scan window
    # the served template instance (Figure 1): edge prop0 == 1, leaf prop0 == 0
    edge_prop: int = 0
    edge_val: int = 1
    leaf_prop: int = 0
    leaf_val: int = 0

    def e_total(self) -> int:
        return self.v_total * self.e_per_vertex


def config_espec(cfg: GraphServeConfig):
    """Lower a capacity config to an ``EngineSpec`` for the runtime."""
    from repro.core.cache import CacheSpec
    from repro.core.engine import EngineSpec
    from repro.graphstore.store import StoreSpec

    spec = StoreSpec(
        v_cap=cfg.v_total, e_cap=cfg.e_total(), n_vprops=cfg.n_vprops,
        n_eprops=cfg.n_eprops, recent_cap=cfg.recent_cap,
    )
    cspec = CacheSpec(
        capacity=cfg.cache_slots_total, probes=8,
        max_leaves=cfg.max_leaves, max_chunks=1,
    )
    return EngineSpec(
        store=spec, cache=cspec, max_deg=cfg.max_deg, frontier=cfg.max_leaves
    )


def config_plan_and_ttable(cfg: GraphServeConfig):
    """The served SQ1-shape template instance (Figure 1) as a runtime
    ``QueryPlan`` plus its enabled ``TemplateTable``."""
    from repro.core.engine import Hop, QueryPlan
    from repro.core.keys import PARAM_LEN
    from repro.core.lifecycle import GraphQP, ServiceCoordinator
    from repro.core.templates import (
        ANY_LABEL, DIR_OUT, MAX_CONDS, OP_EQ, WILDCARD, Template, make_pred,
        make_template_table,
    )
    from repro.utils import PROP_MISSING

    econd = [(cfg.edge_prop, OP_EQ, WILDCARD)]
    lcond = [(cfg.leaf_prop, OP_EQ, WILDCARD)]
    tpl = Template(
        "SQ1", DIR_OUT, (ANY_LABEL, []), (ANY_LABEL, econd), (ANY_LABEL, lcond)
    )
    ttable = make_template_table([tpl])
    qp = GraphQP("qp0")
    sc = ServiceCoordinator([qp])
    sc.register(0)
    sc.enable(0)
    ttable = qp.ttable_masks(ttable, 1)
    params = np.full(PARAM_LEN, int(PROP_MISSING), np.int32)
    params[0] = cfg.edge_val
    params[MAX_CONDS] = cfg.leaf_val
    hop = Hop(
        DIR_OUT, ANY_LABEL, make_pred(ANY_LABEL, []),
        make_pred(ANY_LABEL, econd), make_pred(ANY_LABEL, lcond), 0, params,
    )
    return QueryPlan(hops=(hop,)), ttable


def config_cell(cfg: GraphServeConfig, mesh: Mesh, *, use_cache: bool = True,
                global_batch: int = 8192, blk_slack: float = 1.0):
    """Build the dry-run cell for a capacity config on the partitioned
    runtime: ``(step_fn, in_shardings, abstract_args, runtime)`` with the
    first three ready for
    ``jax.jit(step_fn, in_shardings=...).lower(*abstract_args)``."""
    espec = config_espec(cfg)
    plan, ttable = config_plan_and_ttable(cfg)
    rt = ShardedTxnRuntime(
        espec, mesh, use_cache=use_cache, store_tier="partitioned",
        route_cap_factor=cfg.route_cap_factor, blk_slack=blk_slack,
    )
    step = rt._gr_fn(plan, global_batch)
    sds = jax.ShapeDtypeStruct
    pstore = abstract_partitioned_store(rt.pspec)
    cache = jax.eval_shape(lambda: empty_cache(espec.cache))
    qin = sds((global_batch, 2), jnp.int32)
    down = sds((rt.n,), jnp.bool_)
    rtab = jax.eval_shape(lambda: identity_table(rt.n))
    repl = NamedSharding(mesh, P())
    rshard = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    in_shardings = (
        rt.store_sharding(),
        rt.cache_sharding(),
        jax.tree_util.tree_map(lambda _: repl, ttable),
        rshard, repl,
        jax.tree_util.tree_map(lambda _: repl, rtab),
    )
    return step, in_shardings, (pstore, cache, ttable, qin, down, rtab), rt


def config_grw_cell(cfg: GraphServeConfig, mesh: Mesh, *,
                    policy: str = "write-around", blk_slack: float = 1.0,
                    caps: tuple = (8, 32, 32, 8, 32, 32)):
    """Build the dry-run cell for the sharded gRW commit at capacity-config
    scale: ``(step_fn, in_shardings, abstract_args, runtime)``.

    This is the lowering check for the indexed edge-copy location: the
    former O(K × e_blk_cap) broadcast-compare materialized [K, 2^30]
    intermediates at the FULL config's per-shard block capacity, a compile
    cliff the geid→slot ``searchsorted`` probes remove. The cell lowers the
    whole commit — owner-local apply, ownership-masked listener, and the
    routed cache-maintenance phase — at dry-run block capacity.
    """
    espec = config_espec(cfg)
    _, ttable = config_plan_and_ttable(cfg)
    rt = ShardedTxnRuntime(
        espec, mesh, store_tier="partitioned",
        route_cap_factor=cfg.route_cap_factor, blk_slack=blk_slack,
    )
    step = rt._grw_fn(policy)
    batch = jax.eval_shape(
        lambda: make_mutation_batch(espec.store, caps=caps)
    )
    pstore = abstract_partitioned_store(rt.pspec)
    cache = jax.eval_shape(lambda: empty_cache(espec.cache))
    rtab = jax.eval_shape(lambda: identity_table(rt.n))
    repl = NamedSharding(mesh, P())
    in_shardings = (
        rt.store_sharding(),
        rt.cache_sharding(),
        jax.tree_util.tree_map(lambda _: repl, ttable),
        jax.tree_util.tree_map(lambda _: repl, batch),
        jax.tree_util.tree_map(lambda _: repl, rtab),
    )
    return step, in_shardings, (pstore, cache, ttable, batch, rtab), rt
