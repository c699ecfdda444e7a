"""The shared transaction-runtime substrate.

Both entry points of the system compile against this module: the
single-host ``GraphEngine`` (core/engine.py) and the sharded serve tier
(distributed/graph_serve.py). It owns everything that used to be duplicated
between them:

- ``onehop_exec``          — one one-hop sub-query instance per root (the
                             cache-miss path; Definition 2.1 semantics).
- ``make_hop_kernel``      — one hop of the fused gR-Tx pipeline: lean cache
                             probe + ``lax.cond``-gated masked miss
                             execution over a flat root frontier. The
                             sharded runtime runs this same kernel at the
                             *owner* shard after routing; the single-host
                             engine runs it in place.
- ``make_fused_plan_fn``   — the whole-plan fused pipeline (PR 2): all hops,
                             on-device frontier merges, final clause, device
                             metrics. The single-host engine jits this
                             directly; it is byte-identical to running the
                             hop kernels inside ``shard_map`` on a 1-shard
                             mesh.
- bucketing / padding      — ``BUCKETS`` / ``bucket_for`` / ``pad_roots``
                             (previously copied between ``GraphEngine`` and
                             ``CachePopulator``) and the MoE-style routing
                             primitives ``route_plan`` / ``route_scatter`` /
                             ``bucketize`` (previously private to
                             ``graph_serve``). ``bucketize`` surfaces an
                             *overflow count* — valid items dropped because
                             a peer bucket filled up — so serving tiers can
                             alert on silent truncation.
- ``get_grw_step``         — the jitted gRW-Tx commit (apply mutations +
                             cache maintenance in one functional state
                             transition), cached by ``(espec, policy)`` so
                             repeated ``run_grw_tx`` calls never re-trace.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import cache_lookup_lean
from repro.core.keys import PARAM_LEN
from repro.core.templates import (
    DIR_BOTH,
    DIR_IN,
    DIR_OUT,
    MAX_CONDS,
    evaluate_pred,
)
from repro.graphstore.store import GlobalStoreView
from repro.utils import (
    NULL_ID,
    compact_masked,
    dedup_masked,
    segmented_dedup_merge,
    take_along0,
)

# final-clause codes of a QueryPlan
FINAL_IDS, FINAL_COUNT, FINAL_VALUES = 0, 1, 2

# ------------------------------------------------------- packed wire format
# One hop exchange each direction moves ONE contiguous int32 buffer (one
# all_to_all), instead of the former separate root / value / count phases.
#
# Query frame (querier -> owner), int32 lanes per routed row:
#     [0]              root vertex id (>= 0 for delivered rows)
#     [1]              flags — bit 0 (WIRE_FLAG_VALID) marks a live row;
#                      bucket padding is zero-filled, so its flags are 0
#     [2 : 2+PARAM_LEN] the hop's bound predicate params (wildcard values)
#
# Result frame (owner -> querier), int32 lanes per row:
#     [0 : RW]         left-packed leaf ids (cache hit or miss exec)
#     [RW]             count lane, doubling as the hit/miss/deferred flag:
#                      >= 0 is the leaf count (hit or executed miss),
#                      -1 marks a row deferred at a down owner
WIRE_FLAG_VALID = 1
WIRE_QUERY_LANES = 2 + PARAM_LEN


def pack_query_frame(roots, flags, params):
    """Pack routed query rows into the contiguous wire layout above.

    ``roots`` int32 [M], ``flags`` int32 [M], ``params`` int32
    [M, PARAM_LEN] -> int32 [M, WIRE_QUERY_LANES].
    """
    return jnp.concatenate(
        [roots[:, None], flags[:, None], params], axis=1
    ).astype(jnp.int32)


def unpack_query_frame(frame):
    """Inverse of ``pack_query_frame``: (roots, flags, params)."""
    return frame[..., 0], frame[..., 1], frame[..., 2:]


def pack_result_frame(vals, cnt):
    """Pack per-row results + count/flag lane: [M, RW] + [M] -> [M, RW+1]."""
    return jnp.concatenate(
        [vals, cnt[..., None].astype(vals.dtype)], axis=-1
    )


def unpack_result_frame(frame):
    """Inverse of ``pack_result_frame``: (vals [M, RW], cnt [M])."""
    return frame[..., :-1], frame[..., -1]

# batch buckets: gR-Tx batches are padded to the next bucket so the jit
# cache stays small. ``CachePopulator`` uses the prefix ``BUCKETS[:4]``.
BUCKETS = (8, 32, 128, 512, 2048, 8192)


def bucket_for(k: int, buckets=BUCKETS, clamp: bool = False) -> int:
    """Smallest bucket >= k; next power of two (or, clamped, the largest
    bucket — the caller then chunks) beyond the table."""
    for b in buckets:
        if b >= k:
            return b
    if clamp:
        return buckets[-1]
    return 1 << int(np.ceil(np.log2(max(k, 1))))


def pad_roots(roots: np.ndarray, bucket: int):
    """Pad a host root batch to ``bucket``: (roots [bucket], valid [bucket])."""
    B = len(roots)
    proots = np.zeros(bucket, np.int32)
    proots[:B] = roots
    bvalid = np.zeros(bucket, bool)
    bvalid[:B] = True
    return proots, bvalid


# ------------------------------------------------------------------ routing
def route_plan(dest: jax.Array, n: int, cap: int):
    """Slot assignment for routing M items into [n, cap] peer buckets.

    Returns (slot [M] — each input's peer*cap+rank, or OOB when dropped,
    kept [M], overflow — the count of *valid* (0 <= dest < n) items dropped
    because their peer bucket overflowed ``cap``). Items with a dest outside
    [0, n) are dropped silently (padding), not counted as overflow.
    """
    M = dest.shape[0]
    order = jnp.argsort(dest)
    sd = dest[order]
    offs = jnp.searchsorted(sd, jnp.arange(n, dtype=dest.dtype), side="left")
    rank = jnp.arange(M) - offs[jnp.clip(sd, 0, n - 1)]
    keep_sorted = (rank < cap) & (sd >= 0) & (sd < n)
    slot_sorted = jnp.where(keep_sorted, sd * cap + rank, n * cap)
    slot = jnp.full((M,), n * cap, jnp.int32)
    slot = slot.at[order].set(slot_sorted.astype(jnp.int32), mode="drop")
    kept = slot < n * cap
    overflow = jnp.sum(((dest >= 0) & (dest < n) & ~kept).astype(jnp.int32))
    return slot, kept, overflow


def route_scatter(vals: jax.Array, slot: jax.Array, n: int, cap: int, fill=NULL_ID):
    """Place ``vals`` into the [n, cap] send buckets of a ``route_plan``."""
    buckets = jnp.full((n * cap,) + vals.shape[1:], fill, vals.dtype)
    return buckets.at[slot].set(vals, mode="drop").reshape((n, cap) + vals.shape[1:])


def bucketize(vals, dest, n, cap, fill=NULL_ID):
    """Route ``vals`` into [n, cap] peer buckets (MoE-dispatch style).

    Returns (buckets [n, cap], slot, kept, overflow); see ``route_plan``.
    """
    slot, kept, overflow = route_plan(dest, n, cap)
    return route_scatter(vals, slot, n, cap, fill), slot, kept, overflow


def compact_rows(mask: jax.Array, cap: int, arrays, fills):
    """Order-preserving row compaction of parallel arrays to ``cap`` rows.

    Returns (compacted arrays, n kept, overflow — masked rows dropped past
    ``cap``). Used to shrink the mostly-masked cache-maintenance op stream
    before it is routed between shards. One index scatter over the M-row
    stream builds a gather map, so each of the k columns costs only a
    ``cap``-row gather instead of its own M-row scatter.
    """
    mask = mask.astype(bool)
    M = mask.shape[0]
    idx = jnp.cumsum(mask.astype(jnp.int32)) - 1
    dest = jnp.where(mask, idx, cap)
    sel = jnp.full((cap,), M, jnp.int32).at[dest].set(
        jnp.arange(M, dtype=jnp.int32), mode="drop"
    )
    live = sel < M
    selc = jnp.clip(sel, 0, M - 1)
    outs = []
    for a, fill in zip(arrays, fills):
        got = a[selc]
        m = live.reshape((cap,) + (1,) * (a.ndim - 1))
        outs.append(jnp.where(m, got, jnp.asarray(fill, a.dtype)))
    total = jnp.sum(mask.astype(jnp.int32))
    n = jnp.minimum(total, cap)
    return outs, n, total - n


# --------------------------------------------------------------- miss exec
def onehop_exec_view(
    espec,
    view,
    direction: int,
    edge_label: int,
    pr,
    pe,
    pl,
    roots: jax.Array,  # int32 [B]
    params: jax.Array,  # int32 [B, PARAM_LEN]
    rmask: jax.Array,  # bool [B]
):
    """Execute one one-hop sub-query instance per root (the cache-miss path)
    against a storage ``view`` — the full replicated store
    (``GlobalStoreView``) or one shard's owner-local blocks
    (``partition.BlockStoreView``). Both views yield identical values for
    the same logical store, so this one function *is* both engines' miss
    path.

    Returns (leaves [B, RW], lmask, n_true [B], truncated [B], stats) where
    RW = espec.result_width. ``n_true`` is the un-truncated cardinality and
    ``truncated`` flags supernode rows whose adjacency exceeded the gather
    window — neither is cacheable when truncated.
    """
    pe_bound = params[:, :MAX_CONDS]
    pl_bound = params[:, MAX_CONDS:]

    rlab = take_along0(view.vlabel, roots)
    rprops = take_along0(view.vprops, roots)
    r_ok = evaluate_pred(pr, rlab, rprops) & rmask

    leaf_parts, mask_parts, el_parts, ep_parts = [], [], [], []
    trunc = jnp.zeros_like(r_ok)
    if direction in (DIR_OUT, DIR_BOTH):
        o, m, t, el, epr = view.adjacency(roots, espec.max_deg, incoming=False)
        leaf_parts.append(o), mask_parts.append(m)
        el_parts.append(el), ep_parts.append(epr)
        trunc |= t
    if direction in (DIR_IN, DIR_BOTH):
        o, m, t, el, epr = view.adjacency(roots, espec.max_deg, incoming=True)
        leaf_parts.append(o), mask_parts.append(m)
        el_parts.append(el), ep_parts.append(epr)
        trunc |= t
    leaf = jnp.concatenate(leaf_parts, axis=1)
    # gate the observed-edge mask by rmask so per-row stats only count rows
    # this call was actually asked to execute (padded / hit-short-circuited
    # rows must not contribute phantom scans)
    scanned_mask = jnp.concatenate(mask_parts, axis=1) & rmask[:, None]
    mask = scanned_mask
    n_edges_scanned = jnp.sum(mask.astype(jnp.int32))

    elab = jnp.concatenate(el_parts, axis=1)
    ep = jnp.concatenate(ep_parts, axis=1)
    e_ok = (edge_label < 0) | (elab == edge_label)
    e_ok &= evaluate_pred(pe, elab, ep, bound_vals=pe_bound[:, None, :])
    mask &= e_ok
    n_leaf_fetches = jnp.sum(mask.astype(jnp.int32))  # the paper's "n"

    llab = take_along0(view.vlabel, leaf)
    lp = take_along0(view.vprops, leaf)
    l_ok = evaluate_pred(pl, llab, lp, bound_vals=pl_bound[:, None, :])
    mask &= l_ok & r_ok[:, None]

    mask = dedup_masked(leaf, mask)  # set semantics (Definition 2.1)
    n_true = jnp.sum(mask.astype(jnp.int32), axis=1)
    leaves, lmask = compact_masked(leaf, mask, espec.result_width)
    stats = {
        "edges_scanned": n_edges_scanned,
        "leaf_fetches": n_leaf_fetches,
        # full read-conflict set for OCC population commits: every vertex
        # whose state this execution *observed*, including filtered-out
        # leaves (their property writes can change the result too)
        "scanned": leaf,
        "scanned_mask": scanned_mask,
    }
    return leaves, lmask, n_true, trunc & rmask, stats


def onehop_exec(
    espec,
    store,
    direction: int,
    edge_label: int,
    pr,
    pe,
    pl,
    roots: jax.Array,
    params: jax.Array,
    rmask: jax.Array,
):
    """``onehop_exec_view`` against a full ``GraphStore`` (single-host)."""
    return onehop_exec_view(
        espec, GlobalStoreView(espec.store, store), direction, edge_label,
        pr, pe, pl, roots, params, rmask,
    )


class MissRecord(NamedTuple):
    """Host-side record of one cache miss awaiting async population."""

    tpl_idx: int
    root: int
    params: np.ndarray  # int32 [PARAM_LEN]
    read_version: int


# ----------------------------------------------------------- fused pipeline
def make_hop_kernel(espec, hop, use_cache: bool, exec_fn=None, defer_fn=None,
                    name: str = "hop"):
    """One hop of the fused pipeline over a flat root frontier.

    Returns ``kernel(store, cache, ttable, roots_flat, rmask_flat,
    params_flat=None) ->
    (vals [BF, RW], cnt [BF], miss_roots [BF], n_miss_records, stats)``.
    ``params_flat`` is the per-row bound predicate params ([BF, PARAM_LEN]);
    the sharded tier unpacks it from the routed query frame, the single
    host leaves it None and the hop's own params broadcast in place.
    ``(vals, cnt)`` are the hop's per-row results left-packed; everything
    the miss path touches — the storage gathers, hit/miss select, and
    miss-record compaction — lives behind a ``lax.cond``, so an all-hit
    frontier pays none of it. The sharded serve tier calls this kernel at
    the root's *owner* shard against the local cache shard; the single-host
    engine calls it in place. ``stats`` carries the device-side metric
    deltas (k = misses, n_read, hits, trunc, edges, leaves).

    ``exec_fn(store, roots, params, rmask)`` is the storage hook for the
    miss path (default: ``onehop_exec`` over a full ``GraphStore``; the
    partitioned tier supplies an owner-local block executor).

    ``defer_fn(roots_flat) -> bool[BF]`` is the degraded-mode hook: a
    traced per-row mask that is True where this shard cannot execute the
    row's miss — its storage is marked down, or (under cache-locality
    routing) the row was routed here for its *cache* home while its rows
    live at another shard. Misses there then **defer** instead of
    executing — cache hits still serve (the cache tier survives an
    owner's storage loss, and a locality-routed hit is the whole point),
    no storage gather runs, no miss record is emitted (CP must not
    populate from a lost block), and the deferred rows are encoded as
    ``cnt = -1`` so the home shard can flag them after unrouting. With
    the hook absent (single host) or the mask all-False (healthy mesh,
    no locality splits) the program is byte-identical to the
    non-degraded trace — degrading is an *input* change, not a recompile.

    The ``lax.cond`` and both its branches run under the named scope
    ``<name>/miss_exec``, so a profiler trace gives the miss path's device
    time by name (the hit select runs only in the miss branch and counts
    there).
    """
    RW = espec.result_width
    cacheable = hop.tpl_idx >= 0 and use_cache
    if exec_fn is None:
        def exec_fn(store, roots_f, params, miss_m, hop=hop):
            return onehop_exec(
                espec, store, hop.direction, hop.edge_label,
                hop.pr, hop.pe, hop.pl, roots_f, params, miss_m,
            )

    def kernel(store, cache, ttable, roots_flat, rmask_flat, params_flat=None):
        BF = roots_flat.shape[0]
        if params_flat is None:
            params = jnp.broadcast_to(
                jnp.asarray(hop.params, jnp.int32), (BF, PARAM_LEN)
            )
        else:
            params = params_flat
        if cacheable:
            # lean probe: raw cached rows + O(BF) validity counts
            # (no per-element mask/select on the hit path)
            hit, leaves_c, cnt_c, _ = cache_lookup_lean(
                espec.cache, cache, hop.tpl_idx, roots_flat, params
            )
            hit = hit & rmask_flat & ttable.read_enabled[hop.tpl_idx]
            cnt_c = jnp.where(hit, cnt_c, 0)
            n_read = jnp.sum(rmask_flat.astype(jnp.int32))
            n_hit = jnp.sum(hit.astype(jnp.int32))
        else:
            hit = jnp.zeros((BF,), bool)
            leaves_c = cnt_c = None
            n_read = n_hit = jnp.int32(0)
        miss_mask = rmask_flat & ~hit
        if defer_fn is not None:
            deferred = miss_mask & defer_fn(roots_flat)
            miss_mask = miss_mask & ~deferred
        else:
            deferred = jnp.zeros((BF,), bool)
        k = jnp.sum(miss_mask.astype(jnp.int32))

        def run_exec(args, hop=hop):
            roots_f, miss_m = args
            leaves_e, lmask_e, n_true, trunc, stats = exec_fn(
                store, roots_f, params, miss_m,
            )
            cnt_e = jnp.where(miss_m, jnp.minimum(n_true, RW), 0)
            if cacheable:
                vals = jnp.where(hit[:, None], leaves_c, leaves_e)
                cnt = jnp.where(hit, cnt_c, cnt_e)
                rec = miss_m & ~trunc & (n_true <= RW)
                mr, _ = compact_masked(roots_f, rec, BF)
                nrec = jnp.sum(rec.astype(jnp.int32))
            else:
                vals, cnt = leaves_e, cnt_e
                mr = jnp.full((BF,), NULL_ID, jnp.int32)
                nrec = jnp.int32(0)
            return (vals, cnt, mr, nrec,
                    jnp.sum(trunc.astype(jnp.int32)),
                    stats["edges_scanned"], stats["leaf_fetches"])

        def skip_exec(args):
            # the all-hit short circuit: no storage gathers at all
            if cacheable:
                vals, cnt = leaves_c, cnt_c
            else:
                vals = jnp.full((BF, RW), NULL_ID, jnp.int32)
                cnt = jnp.zeros((BF,), jnp.int32)
            return (vals, cnt,
                    jnp.full((BF,), NULL_ID, jnp.int32),
                    jnp.int32(0), jnp.int32(0),
                    jnp.int32(0), jnp.int32(0))

        with jax.named_scope(f"{name}/miss_exec"):
            vals, cnt, mr, nrec, trunc_n, es, lf = jax.lax.cond(
                k > 0, run_exec, skip_exec, (roots_flat, miss_mask)
            )
        # deferred rows ride the count channel home as -1 (their cnt is 0
        # on both cond branches, so the encoding is unambiguous)
        cnt = jnp.where(deferred, jnp.int32(-1), cnt)
        stats = {
            "k": k, "n_read": n_read, "hits": n_hit,
            "trunc": trunc_n, "edges": es, "leaves": lf,
        }
        return vals, cnt, mr, nrec, stats

    return kernel


def finalize_frontier(plan, store, q_roots, leaves, lmask):
    """Apply a plan's post filter + final clause to the final frontier."""
    if plan.post_filter is not None:
        kind = plan.post_filter[0]
        if kind == "id_neq":
            lmask = lmask & (leaves != q_roots[:, None])
        elif kind == "prop_neq_root":
            pid = plan.post_filter[1]
            lp = take_along0(store.vprops, leaves)[..., pid]
            rp = take_along0(store.vprops, q_roots)[..., pid]
            lmask = lmask & (lp != rp[:, None])
    if plan.final == FINAL_COUNT:
        return jnp.sum(lmask.astype(jnp.int32), axis=1)
    if plan.final == FINAL_VALUES:
        vals = take_along0(store.vprops, leaves)[..., plan.final_prop]
        return jnp.where(lmask, vals, NULL_ID)
    return jnp.where(lmask, leaves, NULL_ID)


class LocalPlanTier:
    """The single-host instantiation of the shared hop driver: no routing,
    no collectives, storage is the full ``GraphStore``. Every hook is the
    identity, so ``make_plan_fn(..., LocalPlanTier())`` traces exactly the
    program the pre-driver fused pipeline traced."""

    routed = False
    # degraded-mode hooks: a single host has no owner to lose, so the plan
    # fn takes no extra inputs and nothing ever defers
    extra_inputs = 0

    def bind(self, *extra):
        pass

    def defer_fn(self):
        return None

    def exec_fn(self, hop):
        return None  # default: onehop_exec over the full store

    def route(self, hop_idx, A, roots_flat, rmask_flat, params_row):
        # no routing: rows stay home, per-row params stay implicit (None ->
        # the hop kernel broadcasts its own params)
        return roots_flat, rmask_flat, None, None, jnp.int32(0)

    def unroute(self, ctx, vals, cnt):
        return vals, cnt

    def psum(self, x):
        return x

    def pack_count(self, nrec):
        return nrec

    def reduce_metrics(self, m):
        return m


def _hop_scope(stage: str):
    """Run a hop-driver stage ``fn(s, hop_idx)`` under the named scope
    ``hop<hop_idx>/<stage>``."""
    def wrap(fn):
        def run(s, hop_idx):
            with jax.named_scope(f"hop{hop_idx}/{stage}"):
                return fn(s, hop_idx)
        return run
    return wrap


def make_plan_fn(espec, plan, use_cache: bool, tier, *, overlap: bool = False):
    """The ROADMAP's shared hop driver: the whole-plan device program —
    every hop's probe + masked miss-exec + frontier merge, the final clause,
    per-hop compact miss arrays, and device metrics — parameterized by a
    ``tier`` of route/storage hooks so the single-host engine and the
    sharded serve tier are structurally one function instead of
    hand-mirrored loops.

    Tier hooks: ``exec_fn(hop)`` supplies the miss-path storage executor
    (None → full-store ``onehop_exec``); ``route``/``unroute`` move frontier
    roots to their owners and results home (identity on a single host,
    all_to_all on a mesh); ``psum`` reduces batch-global quantities (the
    miss-phase gate must fire on *any* shard's miss); ``pack_count`` shapes
    per-hop miss counts (the sharded tier emits one segment per shard);
    ``reduce_metrics`` globalizes additive metrics. ``extra_inputs`` /
    ``bind`` / ``defer_fn`` are the degraded-mode hooks: a tier may declare
    extra traced inputs (the sharded tier takes a ``down: bool[n]`` owner
    mask), bind them at the top of the trace, and defer owner-down misses
    in the hop kernel — deferred slots come home as ``cnt = -1`` and are
    surfaced per row in the ``deferred`` output. Shape-polymorphic over
    the batch dimension (the caller pads to a ``BUCKETS`` bucket and jits).

    The per-hop collective profile is lean: ``route`` and ``unroute`` are
    each ONE exchange of a packed frame (see the wire-format constants at
    the top of this module), and the former per-hop ``psum`` miss gate is
    deferred — per-hop local miss counts are stacked under the ``_hop_k``
    metrics key and globalized together with the additive metrics in one
    ``reduce_metrics`` call after the hop loop, which on a mesh is a single
    all-reduce per plan instead of one per hop plus one per metric.

    ``overlap=True`` double-buffers the frontier: the batch is split into
    two row streams pipelined through the hop loop with a one-stage skew,
    so one stream's exchange is issued adjacent to the other stream's
    owner-local exec and the two can overlap under async collectives.
    The caller must guarantee an even per-shard batch (and size route caps
    for the half-batch); results are row-identical to the unoverlapped
    schedule when route caps don't drop (e.g. ``route_cap_factor=None``).

    Each stage runs under a named scope, so a profiler trace gives its
    device time by a name that survives edits: ``hop<h>/route``,
    ``hop<h>/probe`` (the cache probe and the hop's metrics),
    ``hop<h>/miss_exec`` (the miss path's ``lax.cond``), ``hop<h>/unroute``,
    ``hop<h>/merge`` (the frontier merge), then ``finalize`` (post filter
    and final clause) and ``reduce_metrics``. Scopes are metadata: the
    compiled program is the same without them.
    """
    F, RW = espec.frontier, espec.result_width
    kernels = [
        make_hop_kernel(
            espec, hop, use_cache, tier.exec_fn(hop), tier.defer_fn(),
            name=f"hop{h}",
        )
        for h, hop in enumerate(plan.hops)
    ]
    n_extra = getattr(tier, "extra_inputs", 0)

    H = len(plan.hops)

    def fused(store, cache, ttable, roots, bvalid, *extra):
        assert len(extra) == n_extra, (len(extra), n_extra)
        if n_extra:
            tier.bind(*extra)
        Bb = roots.shape[0]
        n_streams = 2 if overlap else 1
        assert Bb % n_streams == 0, (Bb, n_streams)
        Bs = Bb // n_streams
        z = jnp.int32(0)
        m = {
            "phases": jnp.int32(1),  # root index lookup (request 1)
            "requests": jnp.sum(bvalid.astype(jnp.int32)),
            "hits": z, "misses": z, "truncated": z,
            "leaf_fetches": z, "edges_scanned": z, "cache_reads": z,
            "deferred": z,
        }
        if tier.routed:
            m["route_overflow"] = z
        # telemetry tier: owner-side frontier occupancy (live routed rows
        # this shard probed/executed, summed over hops). Local-only until
        # ``reduce_metrics`` folds it into the per-owner stage block — the
        # key is popped there, so host-visible metrics are unchanged.
        stage_rows = getattr(tier, "stage_rows", False)
        if stage_rows:
            m["_frontier_rows"] = z
        # per-hop miss segments and local miss counts, in stream order
        miss_roots = [[] for _ in range(H)]
        miss_counts = [[] for _ in range(H)]
        hop_k = [z for _ in range(H)]

        def new_stream(r, bv):
            return {
                "frontier": jnp.full(
                    (r.shape[0], F), NULL_ID, jnp.int32
                ).at[:, 0].set(r),
                "fmask": jnp.zeros((r.shape[0], F), bool).at[:, 0].set(bv),
                "row_def": jnp.zeros((r.shape[0],), bool),
                # the occupied frontier is always a left-packed prefix, so
                # each hop only probes/executes the A slots that can be live
                # (1 for the root hop, then min(F, A*RW)) instead of the
                # full F-wide frontier
                "A": 1,
            }

        streams = [
            new_stream(roots[i * Bs:(i + 1) * Bs], bvalid[i * Bs:(i + 1) * Bs])
            for i in range(n_streams)
        ]

        @_hop_scope("route")
        def stage_route(s, hop_idx):
            # ---- one packed exchange: frontier roots + flags + bound
            # params travel to their owner shards in a single frame
            # (identity on a single host) ----
            hop, A = plan.hops[hop_idx], s["A"]
            roots_flat = s["frontier"][:, :A].reshape(-1)
            rmask_flat = s["fmask"][:, :A].reshape(-1)
            q, qmask, qparams, ctx, ovf = tier.route(
                hop_idx, A, roots_flat, rmask_flat,
                jnp.asarray(hop.params, jnp.int32),
            )
            if tier.routed:
                m["route_overflow"] = m["route_overflow"] + ovf
            s["q"], s["qmask"], s["qparams"], s["ctx"] = q, qmask, qparams, ctx

        @_hop_scope("probe")
        def stage_exec(s, hop_idx):
            # ---- owner-local probe + cond-gated miss execution ----
            hop, kernel = plan.hops[hop_idx], kernels[hop_idx]
            if stage_rows:
                m["_frontier_rows"] = m["_frontier_rows"] + jnp.sum(
                    s["qmask"].astype(jnp.int32))
            vals, cnt, mr, nrec, hs = kernel(
                store, cache, ttable, s["q"], s["qmask"], s["qparams"]
            )
            if hop.tpl_idx >= 0 and use_cache:
                m["requests"] = m["requests"] + hs["n_read"]
                m["cache_reads"] = m["cache_reads"] + hs["n_read"]
                m["hits"] = m["hits"] + hs["hits"]
                miss_roots[hop_idx].append(mr)
                miss_counts[hop_idx].append(tier.pack_count(nrec))
            # the miss-phase gate is structural (fires on *any* shard's
            # miss) — stash the local count; it globalizes with the other
            # metrics in the single deferred reduction below
            hop_k[hop_idx] = hop_k[hop_idx] + hs["k"]
            m["requests"] = m["requests"] + hs["k"] + hs["leaves"]
            m["leaf_fetches"] = m["leaf_fetches"] + hs["leaves"]
            m["edges_scanned"] = m["edges_scanned"] + hs["edges"]
            m["misses"] = m["misses"] + hs["k"]
            m["truncated"] = m["truncated"] + hs["trunc"]
            s["vals"], s["cnt"] = vals, cnt

        def stage_finish(s, hop_idx):
            # ---- one packed exchange home, then the home-shard on-device
            # dedup/compact merge (cost tracks occupancy) ----
            A, Br = s["A"], s["frontier"].shape[0]
            with jax.named_scope(f"hop{hop_idx}/unroute"):
                vals, cnt = tier.unroute(s["ctx"], s["vals"], s["cnt"])
            with jax.named_scope(f"hop{hop_idx}/merge"):
                cnt = cnt.reshape(Br, A)
                # decode the deferred channel: any owner-down slot
                # (cnt = -1) marks the whole query row bounded-stale
                s["row_def"] = s["row_def"] | jnp.any(cnt < 0, axis=1)
                cnt = jnp.maximum(cnt, 0)
                s["frontier"], s["fmask"] = segmented_dedup_merge(
                    vals.reshape(Br, A, RW), cnt, F
                )
            s["A"] = min(F, A * RW)

        if n_streams == 1:
            (s,) = streams
            for h in range(H):
                stage_route(s, h)
                stage_exec(s, h)
                stage_finish(s, h)
        else:
            # double-buffered schedule, one-stage skew: each exchange
            # (route/unroute) is issued adjacent to the *other* stream's
            # owner-local exec, so async collectives overlap miss work
            sa, sb = streams
            stage_route(sa, 0)
            for h in range(H):
                stage_exec(sa, h)
                stage_route(sb, h)       # b's hop-h exchange vs a's exec
                stage_finish(sa, h)
                if h + 1 < H:
                    stage_route(sa, h + 1)
                stage_exec(sb, h)        # b's exec vs a's hop-(h+1) exchange
                stage_finish(sb, h)

        with jax.named_scope("finalize"):
            for hop in plan.hops:
                if hop.tpl_idx >= 0 and use_cache:
                    m["phases"] = m["phases"] + 1  # one cache get round-trip

            row_def = jnp.concatenate([s["row_def"] for s in streams])
            frontier = jnp.concatenate([s["frontier"] for s in streams])
            fmask = jnp.concatenate([s["fmask"] for s in streams])
            m["deferred"] = jnp.sum(row_def.astype(jnp.int32))
            result = finalize_frontier(plan, store, roots, frontier, fmask)
            if plan.post_filter is not None and plan.post_filter[0] != "id_neq":
                m["phases"] = m["phases"] + 1  # un-rewritten property fetch
                m["requests"] = m["requests"] + jnp.sum(fmask.astype(jnp.int32))
            if plan.final == FINAL_VALUES:
                m["phases"] = m["phases"] + 1  # valueMap fetch
                m["requests"] = m["requests"] + jnp.sum(fmask.astype(jnp.int32))
            m["phases"] = m["phases"] + plan.extra_phases
        with jax.named_scope("reduce_metrics"):
            # single deferred reduction: per-hop miss counts ride the
            # metrics dict through ``reduce_metrics`` (one all-reduce on a
            # mesh), then gate the per-hop edge-read + leaf-fetch phases on
            # the global count
            m["_hop_k"] = jnp.stack(hop_k) if H else jnp.zeros((0,), jnp.int32)
            m = tier.reduce_metrics(m)
            k_g = m.pop("_hop_k")
            for h in range(H):
                m["phases"] = m["phases"] + 2 * (k_g[h] > 0)  # edge read + leaf fetch
            mr_out = tuple(
                seg[0] if len(seg) == 1 else jnp.concatenate(seg)
                for seg in miss_roots if seg
            )
            mc_out = tuple(
                c[0] if len(c) == 1 else
                jnp.concatenate([jnp.atleast_1d(x) for x in c])
                for c in miss_counts if c
            )
        return (result, row_def, mr_out, mc_out, m, store.version)

    return fused


def make_fused_plan_fn(espec, plan, use_cache: bool):
    """The single-host whole-plan fused device program (PR 2), now an
    instantiation of the shared hop driver with identity hooks."""
    return make_plan_fn(espec, plan, use_cache, LocalPlanTier())


def decode_miss_records(plan, use_cache, miss_roots, miss_counts, read_version):
    """Turn per-hop compact device miss arrays into host ``MissRecord``s.

    Each hop entry may hold several independently-counted segments (one per
    shard on the sharded runtime; a single segment on the single-host path):
    ``miss_roots[i]`` reshapes to [segments, L] with ``miss_counts[i]`` of
    shape [segments].
    """
    misses: list[MissRecord] = []
    ci = 0
    for hop in plan.hops:
        if hop.tpl_idx >= 0 and use_cache:
            counts = np.asarray(miss_counts[ci]).reshape(-1)
            segs = np.asarray(miss_roots[ci]).reshape(len(counts), -1)
            ci += 1
            params = np.asarray(hop.params, np.int32)
            for seg, cnt in zip(segs, counts):
                for r in seg[: int(cnt)]:
                    misses.append(
                        MissRecord(hop.tpl_idx, int(r), params, read_version)
                    )
    return misses


def host_compact_dedup(vals: np.ndarray, mask: np.ndarray, width: int):
    """Host-side per-row dedup + compaction (frontier merge between hops)."""
    B = vals.shape[0]
    out = np.full((B, width), NULL_ID, np.int32)
    omask = np.zeros((B, width), bool)
    for b in range(B):
        row = vals[b][mask[b]]
        if row.size:
            _, first = np.unique(row, return_index=True)
            row = row[np.sort(first)][:width]
            out[b, : len(row)] = row
            omask[b, : len(row)] = True
    return out, omask


# ---------------------------------------------------------------- gRW step
_GRW_STEPS: dict = {}


def get_grw_step(espec, policy: str = "write-around", *, ops_cap: int = 4096,
                 sweep_cap: int = 512):
    """The jitted gRW-Tx commit: apply mutations + maintain the cache.

    Both the graph writes and the cache maintenance happen in one functional
    state transition — the tensor analogue of FDB buffering both in one
    transaction commit (§4). The step is cached by ``(espec, policy,
    caps)`` so repeated ``run_grw_tx`` calls reuse one compiled program.

    The maintenance phase uses the sharded write path's *op-stream
    compaction* (backported): the mutation listener derives the impacted
    keys as tensor streams, the mostly-masked stream is compacted to
    ``ops_cap`` real ops, and only those are applied against the cache —
    the pre-compaction path instead probed the hash table for every masked
    lane of every emission (O(mutations x templates x gather-width) probes;
    the old gRW benchmark baseline). Sweeps and exact-key ops commute as
    applied (sweeps first, ops in emission order per key), reproducing the
    sequential listener semantics; ``repro.core.invalidation``'s sink-based
    appliers remain the behavioural reference the equivalence tests pin.

    Returns ``(store', cache', impacted, op_overflow)``; ``impacted``
    counts distinct logical cache entries removed (chunk-0 occupancy delta)
    and a nonzero ``op_overflow`` means real maintenance ops were dropped
    by the compaction caps — raise ``ops_cap``/``sweep_cap``.
    """
    key = (espec, policy, ops_cap, sweep_cap)
    if key not in _GRW_STEPS:
        from repro.core.invalidation import (
            CacheOpStream,
            SweepStream,
            apply_op_stream_batched,
            apply_op_stream_segmented,
            apply_sweeps,
            derive_cache_ops,
        )
        from repro.graphstore.mutations import apply_mutations

        through = policy != "write-around"
        cspec = espec.cache

        @jax.jit
        def step(store, cache, ttable, batch):
            store2, applied = apply_mutations(espec.store, store, batch)
            ops, sweeps = derive_cache_ops(
                espec, store, store2, ttable, applied, through=through
            )
            (okind, otpl, oroot, oparams, ovid, oorder), n_ops, ovf_o = compact_rows(
                ops.ok, ops_cap,
                (ops.kind, ops.tpl, ops.root, ops.params, ops.vid, ops.order),
                (0, -1, NULL_ID, 0, NULL_ID, 0),
            )
            cops = CacheOpStream(
                kind=okind, tpl=otpl, root=oroot, params=oparams, vid=ovid,
                order=oorder, ok=jnp.arange(ops_cap) < n_ops,
            )
            (stpl, sroot), n_sw, ovf_s = compact_rows(
                sweeps.ok, sweep_cap, (sweeps.tpl, sweeps.root), (-1, NULL_ID)
            )
            gsw = SweepStream(
                tpl=stpl, root=sroot, ok=jnp.arange(sweep_cap) < n_sw
            )
            head = lambda c: jnp.sum((c.valid & (c.chunk == 0)).astype(jnp.int32))
            occ0 = head(cache)
            cache2 = apply_sweeps(cspec, cache, gsw)
            if through:
                # value edits are order-sensitive per key; distinct keys
                # commute — the segmented apply vectorizes across them
                cache2 = apply_op_stream_segmented(cspec, cache2, cops)
            else:
                cache2 = apply_op_stream_batched(cspec, cache2, cops)
            impacted = occ0 - head(cache2)
            cache2 = cache2._replace(n_delete=cache.n_delete + impacted)
            return store2, cache2, impacted, ovf_o + ovf_s

        _GRW_STEPS[key] = step
    return _GRW_STEPS[key]
