"""The partitioned dual-CSR storage tier: owner-local edge blocks.

``PartitionedGraphStore`` is the sharded layout of a ``GraphStore``: edge
storage is split into *owner-local blocks* so that a one-hop scan reads only
arrays resident at the shard that owns the hop's root vertex:

- the **out block** of shard ``s`` holds (a copy of) every edge whose *src*
  vertex is owned by ``s``, CSR-ordered by src — a ``DIR_OUT`` hop routed to
  the root's owner scans purely local arrays;
- the **in block** of shard ``s`` holds every edge whose *dst* vertex is
  owned by ``s``, CSR-ordered by dst — a ``DIR_IN`` hop routes to dst-owners
  instead of scanning a replicated snapshot.

This is the dual-orientation analogue of LiveGraph's sequential adjacency
blocks (Zhu et al.) combined with the decoupled routing of *On Smart Query
Routing* (Khan et al.): route the sub-query to the shard owning the
adjacency list, then scan sequentially. Each edge is stored exactly twice
fleet-wide (once per orientation) instead of once *per shard*, so per-shard
edge bytes drop from O(E) to O(E/n).

Blocks are stored *physically CSR-sorted* (no permutation index): the CSR
region of a block is its edges sorted by (owner-side key, global edge id),
and appends land in the block's *recent region* tail — the same
write-buffer-in-front-of-index design as the single-host store, but per
block. Gathers therefore reproduce the single-host ``_gather`` lane order
exactly (CSR lanes ascend by global edge id within a root, recent lanes
ascend by id), which is what makes the partitioned engine byte-identical to
the single-host engine.

The vertex **attribute** tier (labels, liveness, properties, versions) stays
replicated across shards, like an FDB storage replica: it is a few percent
of store bytes (edge records + CSR indexes dominate), every shard needs leaf
attributes of arbitrary vertices during miss execution, and the OCC conflict
check needs arbitrary vertex versions at commit. Partitioning vertex
attributes behind denormalized adjacency records is a recorded follow-on
(it trades ~60%% more edge-block bytes for the O(V) residual).

Scalars ``v_len`` / ``e_len`` / ``version`` are replicated: every shard
applies the (replicated) mutation batch's section counts identically, so
global id assignment needs no coordination.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.routing import base_owner, storage_owner_of
from repro.graphstore.store import INT32_MAX, GraphStore, StoreSpec
from repro.utils import PROP_MISSING, take_along0


class PartitionedStoreSpec(NamedTuple):
    """Static layout of a partitioned store (hashable; safe as a closure).

    ``e_blk_cap`` bounds edges per block (per orientation, per shard);
    ``recent_blk_cap`` is the per-block append-scan window (the analogue of
    ``StoreSpec.recent_cap``). Skewed ownership needs headroom over the
    uniform ``e_cap / n`` — size it from measured skew, not worst case.
    """

    base: StoreSpec
    n_shards: int
    e_blk_cap: int
    recent_blk_cap: int

    @property
    def v_loc(self) -> int:
        return self.base.v_cap // self.n_shards


def owner_of(vids, n: int):
    """Vertex ownership is *interleaved* (round-robin): shard ``v % n``
    owns vertex ``v``, whose local index is ``v // n``. Interleaving
    stripes label-clustered id ranges across the whole mesh — with range
    partitioning, a workload whose roots share a label (the common case:
    every SQ1 root is a watch-list) routes its entire frontier to the one
    or two shards owning that label's id block, forcing worst-case routing
    buckets; measured on the eCommerce mix the max per-owner share was the
    full batch. Modulo ownership leaves only genuine hot-key (Zipf) skew,
    which measured caps can bound. Any int (including out-of-range ids)
    maps to exactly one shard; callers mask negatives where they mean
    padding."""
    return jnp.mod(jnp.asarray(vids, jnp.int32), n)


def local_of(vids, n: int):
    """Owner-local vertex index under interleaved ownership."""
    return jnp.asarray(vids, jnp.int32) // n


def default_pspec(spec: StoreSpec, n_shards: int, *, slack: float = 2.0,
                  recent_blk_cap: int | None = None) -> PartitionedStoreSpec:
    """Block capacities for a given shard count: ``slack``x the uniform
    share (ownership skew headroom), recent window defaulting to the base
    store's (appends are not sharded-down in the worst case)."""
    assert spec.v_cap % n_shards == 0, "v_cap must divide over shards"
    eb = int(np.ceil(spec.e_cap * slack / n_shards))
    rb = min(spec.recent_cap if recent_blk_cap is None else recent_blk_cap, eb)
    return PartitionedStoreSpec(spec, n_shards, eb, rb)


class BlockCapacityError(ValueError):
    """A shard's owner-local block cannot hold the edges it owns.

    ``needed`` carries the max per-shard edge count of the failing
    orientation, so elastic callers can grow ``e_blk_cap`` and retry
    (``ShardedTxnRuntime.partition_store(..., elastic=True)``) instead of
    dying on a shape assert deep inside block packing.
    """

    def __init__(self, msg: str, needed: int):
        super().__init__(msg)
        self.needed = needed


class EdgeBlock(NamedTuple):
    """One orientation's owner-local edge copies, all shards stacked.

    Arrays carry the global layout ``[n * e_blk_cap, ...]`` (shard ``s``
    owns rows ``[s*e_blk_cap, (s+1)*e_blk_cap)``); inside ``shard_map`` each
    shard sees its ``[e_blk_cap, ...]`` slice. ``key`` is the owner-side
    endpoint (src for the out block, dst for the in block), ``other`` the
    opposite endpoint, ``geid`` the immutable global edge id (the handle
    mutation sections use to find their local copies). The CSR region
    ``[0, csr_len)`` is physically sorted by (key, geid); ``[csr_len, len)``
    is the recent append region.

    ``gperm`` is the block's **sorted geid→slot index**: the geid column is
    CSR-ordered by key (not monotone), so a permutation array keeps
    ``geid[gperm[:blk_len]]`` ascending and the tail ``gperm[blk_len:]`` the
    unallocated slots in ascending order. Edge-copy location
    (``geid_slot_lookup``) is then an O(log e_blk_cap) ``searchsorted``
    probe instead of the former O(K × e_blk_cap) broadcast-compare — the
    compile cliff before billion-edge blocks. Appends keep it incrementally
    correct for free (new geids exceed all existing ones, so the sorted
    position of an appended slot is the slot itself); compaction and growth
    rebuild it (``maintenance.compact_block`` / ``rebuild_geid_index``).
    """

    key: jax.Array  # int32 [n*EB]
    other: jax.Array  # int32 [n*EB]
    label: jax.Array  # int32 [n*EB]
    alive: jax.Array  # bool  [n*EB]
    props: jax.Array  # int32 [n*EB, n_eprops]
    geid: jax.Array  # int32 [n*EB]
    gperm: jax.Array  # int32 [n*EB] sorted-geid rank -> block slot
    indptr: jax.Array  # int32 [n*(v_loc+1)] CSR row offsets (local vertex)
    blk_len: jax.Array  # int32 [n] edges in the block
    csr_len: jax.Array  # int32 [n] CSR region length


class PartitionedGraphStore(NamedTuple):
    """Pytree of the sharded storage tier. See module docstring."""

    # replicated vertex-attribute tier (identical on every shard)
    vlabel: jax.Array  # int32 [v_cap]
    valive: jax.Array  # bool  [v_cap]
    vprops: jax.Array  # int32 [v_cap, n_vprops]
    vversion: jax.Array  # int32 [v_cap]
    # owner-local dual-CSR edge blocks
    out: EdgeBlock
    inc: EdgeBlock
    # replicated scalars
    v_len: jax.Array
    e_len: jax.Array
    version: jax.Array


# ------------------------------------------------------------------ build
def _build_block(pspec: PartitionedStoreSpec, keyside, otherside, elabel,
                 ealive, eprops, perm, e_len: int, csr_len: int):
    """Host-side construction of one orientation's blocks (numpy).

    ``perm`` is the source store's CSR permutation for this orientation:
    ``perm[:csr_len]`` lists the CSR slots stably sorted by key, so each
    shard's CSR region is that order filtered to the shard's keys — the
    single-host lane order, with no second sort."""
    spec, n = pspec.base, pspec.n_shards
    EB, Vloc = pspec.e_blk_cap, pspec.v_loc
    nep = spec.n_eprops
    key = np.full((n * EB,), INT32_MAX, np.int32)
    other = np.full((n * EB,), -1, np.int32)
    label = np.full((n * EB,), -1, np.int32)
    alive = np.zeros((n * EB,), bool)
    props = np.full((n * EB, nep), np.int32(-(2**31) + 1), np.int32)
    geid = np.full((n * EB,), -1, np.int32)
    gperm = np.zeros((n * EB,), np.int32)
    indptr = np.zeros((n * (Vloc + 1),), np.int32)
    blk_len = np.zeros((n,), np.int32)
    csr_blk = np.zeros((n,), np.int32)

    owner = base_owner(keyside[:e_len], n)
    counts = np.bincount(owner, minlength=n) if e_len else np.zeros(n, np.int64)
    if counts.max(initial=0) > EB:
        worst = int(counts.argmax())
        raise BlockCapacityError(
            f"shard {worst} owns {int(counts.max())} edges of this "
            f"orientation > e_blk_cap={EB}. Raise e_blk_cap / blk_slack, or "
            f"partition with ShardedTxnRuntime.partition_store(..., "
            f"elastic=True) to grow block capacity automatically.",
            needed=int(counts.max()),
        )
    csr_order = np.asarray(perm[:csr_len])
    csr_owner = owner[csr_order]

    def fill(s):
        # CSR region in key order, ties in global-slot order (the stable
        # sort's), then the recent region in slot order
        csr_sorted = csr_order[csr_owner == s]
        mine = owner == s
        rec_mine = np.flatnonzero(mine[csr_len:]) + csr_len
        local = np.concatenate([csr_sorted, rec_mine])
        k, m = len(csr_sorted), len(local)
        base = s * EB
        key[base : base + m] = keyside[local]
        other[base : base + m] = otherside[local]
        label[base : base + m] = elabel[local]
        alive[base : base + m] = ealive[local]
        props[base : base + m] = eprops[local]
        geid[base : base + m] = local
        blk_len[s] = m
        csr_blk[s] = k
        # sorted geid->slot index: allocated slots by ascending geid, then
        # the unallocated tail in slot order. Recent geids exceed every CSR
        # geid and ascend already; the CSR part inverts each slot's rank
        # among this shard's CSR slots
        rank = (np.cumsum(mine[:csr_len]) - 1)[csr_sorted]
        gperm[base + rank] = np.arange(k, dtype=np.int32)
        gperm[base + k : base + EB] = np.arange(k, EB, dtype=np.int32)
        lk = keyside[csr_sorted] // n  # interleaved: local index = v // n
        indptr[s * (Vloc + 1) : (s + 1) * (Vloc + 1)] = np.searchsorted(
            lk, np.arange(Vloc + 1), side="left"
        )

    # shards write disjoint slices, and numpy's gathers release the GIL
    with ThreadPoolExecutor(max_workers=n) as pool:
        list(pool.map(fill, range(n)))
    return EdgeBlock(
        key=key, other=other, label=label, alive=alive, props=props,
        geid=geid, gperm=gperm, indptr=indptr, blk_len=blk_len,
        csr_len=csr_blk,
    )


def partition_store_host(pspec: PartitionedStoreSpec,
                         store: GraphStore) -> PartitionedGraphStore:
    """Partition a (host or device) ``GraphStore`` into owner-local blocks
    held as numpy arrays — the form ``jax.device_put`` scatters over a
    mesh shard by shard, so no device ever stages the whole store.

    Pure layout change: the partitioned store serves byte-identical reads.
    Dead-but-allocated edges keep their CSR lanes (they are masked at read
    time, exactly like the single-host store), so per-root CSR degrees — and
    therefore truncation flags and scan metrics — match the source store.
    """
    e_len, csr_len = int(store.e_len), int(store.csr_len)
    h = {f: np.asarray(getattr(store, f)) for f in (
        "esrc", "edst", "elabel", "ealive", "eprops", "out_perm", "in_perm",
    )}
    edges = (h["elabel"], h["ealive"], h["eprops"])
    with ThreadPoolExecutor(max_workers=2) as pool:
        out = pool.submit(_build_block, pspec, h["esrc"], h["edst"], *edges,
                          h["out_perm"], e_len, csr_len)
        inc = pool.submit(_build_block, pspec, h["edst"], h["esrc"], *edges,
                          h["in_perm"], e_len, csr_len)
        out, inc = out.result(), inc.result()
    return PartitionedGraphStore(
        vlabel=np.asarray(store.vlabel), valive=np.asarray(store.valive),
        vprops=np.asarray(store.vprops), vversion=np.asarray(store.vversion),
        out=out, inc=inc, v_len=np.asarray(store.v_len),
        e_len=np.asarray(store.e_len), version=np.asarray(store.version),
    )


def partition_store(pspec: PartitionedStoreSpec, store: GraphStore) -> PartitionedGraphStore:
    """``partition_store_host`` on the default device."""
    return jax.tree_util.tree_map(jnp.asarray, partition_store_host(pspec, store))


def abstract_partitioned_store(pspec: PartitionedStoreSpec):
    """ShapeDtypeStructs of a partitioned store (dry-run / AOT inputs)."""
    spec, n = pspec.base, pspec.n_shards
    EB, Vloc = pspec.e_blk_cap, pspec.v_loc
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32

    def blk():
        return EdgeBlock(
            key=sds((n * EB,), i32), other=sds((n * EB,), i32),
            label=sds((n * EB,), i32), alive=sds((n * EB,), jnp.bool_),
            props=sds((n * EB, spec.n_eprops), i32), geid=sds((n * EB,), i32),
            gperm=sds((n * EB,), i32),
            indptr=sds((n * (Vloc + 1),), i32), blk_len=sds((n,), i32),
            csr_len=sds((n,), i32),
        )

    return PartitionedGraphStore(
        vlabel=sds((spec.v_cap,), i32), valive=sds((spec.v_cap,), jnp.bool_),
        vprops=sds((spec.v_cap, spec.n_vprops), i32),
        vversion=sds((spec.v_cap,), i32), out=blk(), inc=blk(),
        v_len=sds((), i32), e_len=sds((), i32), version=sds((), i32),
    )


# ------------------------------------------------------------------ bytes
def tree_nbytes(tree) -> int:
    """Total array bytes of a pytree (ShapeDtypeStructs count too)."""
    return int(sum(
        int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(tree)
    ))


def store_bytes_report(pspec: PartitionedStoreSpec, pstore=None) -> dict:
    """Per-shard bytes of the partitioned tier vs the replicated snapshot.

    ``per_shard`` counts one shard's edge blocks + its copy of the
    replicated vertex/scalar tier; ``replicated_per_shard`` is the full
    single-host ``GraphStore`` every shard used to carry. ``ratio`` is their
    quotient (ideal ``1/n`` for the sharded part; each edge appears at two
    owners, so the edge term floors at ``~2/n`` of the replicated edge+CSR
    bytes — measured, not hidden).
    """
    from repro.graphstore.store import empty_store

    n = pspec.n_shards
    pstore = pstore if pstore is not None else abstract_partitioned_store(pspec)
    blocks = tree_nbytes((pstore.out, pstore.inc))
    repl = tree_nbytes(
        (pstore.vlabel, pstore.valive, pstore.vprops, pstore.vversion,
         pstore.v_len, pstore.e_len, pstore.version)
    )
    per_shard = blocks // n + repl
    baseline = tree_nbytes(jax.eval_shape(lambda: empty_store(pspec.base)))
    return dict(
        n_shards=n,
        per_shard_bytes=per_shard,
        per_shard_block_bytes=blocks // n,
        per_shard_replicated_bytes=repl,
        replicated_per_shard_bytes=baseline,
        ratio=per_shard / baseline,
        ideal_ratio=1.0 / n,
    )


# ------------------------------------------------------------------ reads
def gather_block(pspec: PartitionedStoreSpec, ps: PartitionedGraphStore,
                 roots: jax.Array, max_deg: int, *, incoming: bool, me,
                 rtable=None):
    """Owner-local padded adjacency gather (one shard's view).

    Shard-local mirror of ``store._gather``: CSR lanes from the physically
    sorted block region plus a bounded recent-region scan. Returns
    ``(slots [B, W], other [B, W], mask [B, W], truncated [B])`` with
    ``W = max_deg + recent_blk_cap``; ``slots`` index the *local block*
    arrays (label/props reads), ``other`` carries global leaf ids. Roots not
    owned by this shard (or out of range) come back fully masked — the same
    observable as the single-host gather for an invalid root.

    ``rtable`` (a ``distributed.routing.RoutingTable``) makes ownership
    table-driven: a migrated-in root is valid here even though ``v % n``
    says otherwise. Its rows live in the *recent region* (migration appends
    them there) and match by global key; the CSR window is native-only —
    a foreign root's local index ``v // n`` would alias a native vertex's
    CSR rows — so both the CSR mask and the truncation flag gate on
    nativeness when a table is in play. ``rtable=None`` is byte-identical
    to the historical modulo-only gather.
    """
    spec, n = pspec.base, pspec.n_shards
    EB, Vloc, R = pspec.e_blk_cap, pspec.v_loc, pspec.recent_blk_cap
    blk = ps.inc if incoming else ps.out

    roots = roots.astype(jnp.int32)
    me = jnp.asarray(me, jnp.int32)
    local = local_of(roots, n)
    rvalid = (storage_owner_of(rtable, roots, n) == me) & (roots >= 0) \
        & (roots < spec.v_cap)
    if rtable is None:
        cvalid = rvalid
    else:
        native = owner_of(roots, n) == me
        cvalid = rvalid & native
    lc = jnp.clip(local, 0, Vloc - 1)
    start = blk.indptr[lc]
    deg = blk.indptr[lc + 1] - start
    truncated = deg > max_deg
    if rtable is not None:
        truncated &= native
    pos = start[:, None] + jnp.arange(max_deg, dtype=jnp.int32)[None, :]
    csr_mask = (jnp.arange(max_deg)[None, :] < deg[:, None]) & cvalid[:, None]
    slot_csr = jnp.clip(pos, 0, EB - 1)

    # recent region of this block: [csr_len, blk_len) within a bounded window
    clb = blk.csr_len[0]
    lb = blk.blk_len[0]
    roff = jnp.clip(clb, 0, EB - R)
    key_r = jax.lax.dynamic_slice(blk.key, (roff,), (R,))
    sid = roff + jnp.arange(R, dtype=jnp.int32)
    in_region = (sid >= clb) & (sid < lb)
    rec_mask = (key_r[None, :] == roots[:, None]) & in_region[None, :]
    rec_mask &= rvalid[:, None]
    slot_rec = jnp.broadcast_to(sid[None, :], (roots.shape[0], R))

    slots = jnp.concatenate([slot_csr, slot_rec], axis=1)
    mask = jnp.concatenate([csr_mask, rec_mask], axis=1)
    # liveness chain identical to the single-host gather: edge alive, both
    # endpoints alive (leaf via the replicated vertex tier)
    mask &= take_along0(blk.alive, slots)
    other = take_along0(blk.other, slots)
    mask &= take_along0(ps.valive, other)
    mask &= take_along0(ps.valive, jnp.broadcast_to(roots[:, None], slots.shape))
    return slots, other, mask, truncated


class BlockStoreView:
    """One shard's storage view over its owner-local blocks.

    Same interface as ``store.GlobalStoreView`` — vertex attributes come
    from the replicated tier, adjacency from the local dual-CSR blocks, and
    ``own`` reports which vertices route here (clamped like the serve tier's
    owner routing, so out-of-range ids resolve to exactly one shard).
    Intended to be constructed *inside* ``shard_map`` (or a vmap with a
    named axis) where ``ps`` holds the local block slices. ``rtable`` makes
    ownership table-driven (``None`` = the compiled-in modulo, exactly).
    """

    def __init__(self, pspec: PartitionedStoreSpec, ps: PartitionedGraphStore,
                 me, rtable=None):
        self.pspec = pspec
        self.ps = ps
        self.me = jnp.asarray(me, jnp.int32)
        self.rtable = rtable

    @property
    def vlabel(self):
        return self.ps.vlabel

    @property
    def vprops(self):
        return self.ps.vprops

    @property
    def valive(self):
        return self.ps.valive

    def own(self, vids):
        return storage_owner_of(self.rtable, vids, self.pspec.n_shards) == self.me

    def adjacency(self, roots: jax.Array, max_deg: int, *, incoming: bool):
        slots, other, mask, trunc = gather_block(
            self.pspec, self.ps, roots, max_deg, incoming=incoming, me=self.me,
            rtable=self.rtable,
        )
        blk = self.ps.inc if incoming else self.ps.out
        elab = take_along0(blk.label, slots)
        ep = take_along0(blk.props, slots)
        return other, mask, trunc, elab, ep

    def kernel_operands(self, *, incoming: bool) -> "BlockGatherOperands":
        """Flat per-orientation operand bundle for ``kernels/block_gather``
        (the fused scan+filter executor): the local block arrays, the
        replicated vertex-attribute tier, and the block fill scalars —
        exactly the arrays the kernel streams, in its argument order."""
        blk = self.ps.inc if incoming else self.ps.out
        return BlockGatherOperands(
            indptr=blk.indptr, key=blk.key, other=blk.other, label=blk.label,
            alive=blk.alive, props=blk.props,
            vlabel=self.ps.vlabel, valive=self.ps.valive,
            vprops=self.ps.vprops,
            csr_len=blk.csr_len[0], blk_len=blk.blk_len[0],
        )


class BlockGatherOperands(NamedTuple):
    """Kernel-friendly view of one orientation's owner-local block: the
    positional operands of ``kernels/block_gather`` (see that package for
    the layout contract). Built inside ``shard_map`` from the local slices
    via ``BlockStoreView.kernel_operands``."""

    indptr: jax.Array   # int32 [v_loc + 1] CSR row index (local vertex ids)
    key: jax.Array      # int32 [e_blk_cap] owner-side key per edge record
    other: jax.Array    # int32 [e_blk_cap] global leaf id per edge record
    label: jax.Array    # int32 [e_blk_cap] edge label
    alive: jax.Array    # bool  [e_blk_cap] edge liveness
    props: jax.Array    # int32 [e_blk_cap, NEP] edge properties
    vlabel: jax.Array   # int32 [v_cap] replicated vertex labels
    valive: jax.Array   # bool  [v_cap] replicated vertex liveness
    vprops: jax.Array   # int32 [v_cap, NVP] replicated vertex properties
    csr_len: jax.Array  # int32 [] sorted-region length of this block
    blk_len: jax.Array  # int32 [] allocated length (recent = [csr, blk))


# ------------------------------------------------------------- geid index
def rebuild_geid_index(blk_len, geid) -> jax.Array:
    """Recompute one block's sorted geid→slot permutation from scratch.

    Allocated slots (``< blk_len``) sort by ascending geid; the unallocated
    tail keeps ascending slot order (stable ties on the sentinel), matching
    the host-side ``_build_block`` construction byte-for-byte. Used at
    compaction / growth; appends maintain the index incrementally instead.
    """
    lanes = jnp.arange(geid.shape[0], dtype=jnp.int32)
    masked = jnp.where(lanes < blk_len, geid, INT32_MAX)
    return jnp.argsort(masked, stable=True).astype(jnp.int32)


def sorted_geid_view(EB: int, geid, gperm, blk_len):
    """The index's ascending geid view: one O(EB) gather, shareable across
    every probe batch against the same block state."""
    lanes = jnp.arange(EB, dtype=jnp.int32)
    return jnp.where(lanes < blk_len, take_along0(geid, gperm), INT32_MAX)


def geid_slot_lookup(EB: int, geid, gperm, blk_len, eids, skey=None):
    """Locate global edge ids in one block via the sorted geid→slot index.

    ``searchsorted`` over the index's ascending geid view: O(log EB) per
    probe plus one linear gather to materialize the view (pass a shared
    ``sorted_geid_view`` as ``skey`` to amortize it across probe batches;
    the gather is the same order as the functional scatter updates the
    apply already pays). The former [K, e_blk_cap] broadcast-compare was
    O(K × EB) — the compile cliff before billion-edge blocks. Returns
    ``(slot [K], found [K])``; ``slot`` is only meaningful where ``found``
    (callers scatter with OOB-drop otherwise).
    """
    if skey is None:
        skey = sorted_geid_view(EB, geid, gperm, blk_len)
    eids = jnp.asarray(eids, jnp.int32)
    pos = jnp.searchsorted(skey, eids, side="left").astype(jnp.int32)
    posc = jnp.clip(pos, 0, EB - 1)
    slot = take_along0(gperm, posc)
    found = (pos < blk_len) & (skey[posc] == eids) & (eids >= 0)
    return slot, found


# ----------------------------------------------------------------- writes
def _lookup_block(pspec: PartitionedStoreSpec, blk: EdgeBlock, eids, psum,
                  skey=None):
    """Locate global edge ids in one shard's block and psum-replicate their
    records. Exactly one shard holds an edge's copy per orientation, so the
    sum over shards *is* that owner's contribution. Returns ``(found, key,
    other, label, props)`` replicated across the mesh. The per-block match
    is an indexed ``geid_slot_lookup`` probe (``skey`` shares the sorted
    view across lookups against the same block state)."""
    EB = pspec.e_blk_cap
    sl, found_l = geid_slot_lookup(
        EB, blk.geid, blk.gperm, blk.blk_len[0], eids, skey=skey
    )
    contrib = lambda a: jnp.where(found_l, a[sl], 0)
    found = psum(found_l.astype(jnp.int32)) > 0
    key = psum(contrib(blk.key))
    other = psum(contrib(blk.other))
    label = psum(contrib(blk.label))
    props = psum(jnp.where(found_l[:, None], blk.props[sl], 0))
    return found, key, other, label, props


def apply_mutations_partitioned(pspec: PartitionedStoreSpec,
                                ps: PartitionedGraphStore, batch, me, axes,
                                rtable=None):
    """Apply one gRW commit to the partitioned tier (per shard, inside
    ``shard_map`` — or a vmap with a named axis for host testing).

    Each mutation section lands only at the partitions it touches: new /
    deleted / re-propertied edges at their src-owner's out block and
    dst-owner's in block (located by global edge id; new edges append to
    the block recent regions), vertex sections on the replicated attribute
    tier (every shard applies them identically — no coordination, the batch
    is replicated). Deleted-edge and edge-prop pre-images — which the
    single host reads from its slot arrays — are psum-gathered from the
    src-owners, so the returned ``AppliedMutations`` snapshot is replicated
    and byte-identical to the single-host listener input.

    Returns ``(store', applied, append_overflow)``; a nonzero overflow
    means a block's capacity dropped new edges (raise ``e_blk_cap``).

    ``rtable`` routes new-edge appends to their *table* owner: edges of a
    migrated vertex land in the block that now serves it (the recent
    region matches by key, so they are readable there immediately). The
    de/se sections locate their copies by geid, which is
    placement-agnostic. ``rtable=None`` is the historical modulo routing.
    """
    from repro.graphstore.mutations import AppliedMutations, _sec_mask

    spec, n = pspec.base, pspec.n_shards
    Vloc, EB = pspec.v_loc, pspec.e_blk_cap
    nvp, nep = spec.n_vprops, spec.n_eprops
    b = batch
    me = jnp.asarray(me, jnp.int32)
    psum = lambda x: jax.lax.psum(x, axes)
    owner = lambda v: storage_owner_of(rtable, v, n)
    new_version = ps.version + 1

    nv_mask = _sec_mask(b.nv_label, b.nv_n)
    ne_mask = _sec_mask(b.ne_src, b.ne_n)
    de_mask = _sec_mask(b.de_eid, b.de_n)
    dv_mask = _sec_mask(b.dv_vid, b.dv_n)
    sv_mask = _sec_mask(b.sv_vid, b.sv_n)
    se_mask = _sec_mask(b.se_eid, b.se_n)

    # ---- pre-images (pre-state blocks; defaults mirror empty slot arrays;
    # the de/se lookups share one sorted view of the pre-state out block)
    skey_pre = sorted_geid_view(EB, ps.out.geid, ps.out.gperm, ps.out.blk_len[0])
    f_de, de_src_g, de_dst_g, de_lab_g, de_props_g = _lookup_block(
        pspec, ps.out, b.de_eid, psum, skey=skey_pre
    )
    de_src = jnp.where(de_mask, jnp.where(f_de, de_src_g, INT32_MAX), -1)
    de_dst = jnp.where(de_mask, jnp.where(f_de, de_dst_g, -1), -1)
    de_label = jnp.where(de_mask, jnp.where(f_de, de_lab_g, -1), -1)
    de_props = jnp.where(
        de_mask[:, None],
        jnp.where(f_de[:, None], de_props_g, PROP_MISSING), PROP_MISSING,
    )
    f_se, se_src_g, se_dst_g, se_lab_g, se_props_g = _lookup_block(
        pspec, ps.out, b.se_eid, psum, skey=skey_pre
    )
    se_src = jnp.where(se_mask, jnp.where(f_se, se_src_g, INT32_MAX), -1)
    se_dst = jnp.where(se_mask, jnp.where(f_se, se_dst_g, -1), -1)
    se_label = jnp.where(se_mask, jnp.where(f_se, se_lab_g, -1), -1)
    se_pre_rows = jnp.where(f_se[:, None], se_props_g, PROP_MISSING)
    se_old = jnp.where(
        se_mask,
        jnp.take_along_axis(
            se_pre_rows, jnp.clip(b.se_pid, 0, nep - 1)[:, None], axis=1
        )[:, 0],
        PROP_MISSING,
    )
    sv_rows = take_along0(ps.vprops, b.sv_vid)
    sv_old = jnp.where(
        sv_mask,
        jnp.take_along_axis(
            sv_rows, jnp.clip(b.sv_pid, 0, nvp - 1)[:, None], axis=1
        )[:, 0],
        PROP_MISSING,
    )

    # ---- id assignment from the replicated scalars (no coordination)
    knv, kne = b.nv_label.shape[0], b.ne_src.shape[0]
    nv_vid = jnp.where(nv_mask, ps.v_len + jnp.arange(knv, dtype=jnp.int32), -1)
    ne_eid = jnp.where(ne_mask, ps.e_len + jnp.arange(kne, dtype=jnp.int32), -1)

    # ---- replicated vertex-attribute tier (identical scatter on all shards)
    nv_idx = jnp.where(nv_mask, nv_vid, spec.v_cap)
    vlabel = ps.vlabel.at[nv_idx].set(b.nv_label, mode="drop")
    valive = ps.valive.at[nv_idx].set(True, mode="drop")
    vprops = ps.vprops.at[nv_idx].set(b.nv_props, mode="drop")
    sv_idx = jnp.where(sv_mask, b.sv_vid, spec.v_cap)
    vprops = vprops.at[sv_idx, jnp.clip(b.sv_pid, 0, nvp - 1)].set(
        b.sv_val, mode="drop"
    )
    dv_idx = jnp.where(dv_mask, b.dv_vid, spec.v_cap)
    valive = valive.at[dv_idx].set(False, mode="drop")
    vversion = ps.vversion
    for vid, m in (
        (b.ne_src, ne_mask),
        (b.ne_dst, ne_mask),
        (de_src, de_mask),
        (de_dst, de_mask),
        (b.sv_vid, sv_mask),
        (se_src, se_mask),
        (se_dst, se_mask),
        (b.dv_vid, dv_mask),
        (nv_vid, nv_mask),
    ):
        vversion = vversion.at[jnp.where(m, vid, spec.v_cap)].set(
            new_version, mode="drop"
        )

    # ---- owner-local edge blocks
    def apply_block(blk: EdgeBlock, keysel, othersel):
        own_ne = ne_mask & (owner(keysel) == me)
        rank = jnp.cumsum(own_ne.astype(jnp.int32)) - 1
        pos = jnp.where(own_ne, blk.blk_len[0] + rank, EB)
        ovf = jnp.sum((own_ne & (pos >= EB)).astype(jnp.int32))
        blk = blk._replace(
            key=blk.key.at[pos].set(keysel, mode="drop"),
            other=blk.other.at[pos].set(othersel, mode="drop"),
            label=blk.label.at[pos].set(b.ne_label, mode="drop"),
            alive=blk.alive.at[pos].set(True, mode="drop"),
            props=blk.props.at[pos].set(b.ne_props, mode="drop"),
            geid=blk.geid.at[pos].set(ne_eid, mode="drop"),
            # sorted geid->slot index, maintained incrementally: appended
            # geids exceed every existing geid (e_len only grows), so an
            # appended slot's sorted rank *is* the slot index
            gperm=blk.gperm.at[pos].set(pos.astype(jnp.int32), mode="drop"),
        )
        new_len = blk.blk_len[0] + jnp.sum(
            (own_ne & (pos < EB)).astype(jnp.int32)
        )
        # edge-prop edits / deletes locate their local copy through the
        # index (post-append, so same-batch new edges are editable); both
        # probe batches share one sorted view of the post-append state
        skey = sorted_geid_view(EB, blk.geid, blk.gperm, new_len)
        sl_se, f_se = geid_slot_lookup(
            EB, blk.geid, blk.gperm, new_len, b.se_eid, skey=skey
        )
        tgt = jnp.where(f_se & se_mask, sl_se, EB)
        props = blk.props.at[tgt, jnp.clip(b.se_pid, 0, nep - 1)].set(
            b.se_val, mode="drop"
        )
        sl_de, f_de = geid_slot_lookup(
            EB, blk.geid, blk.gperm, new_len, b.de_eid, skey=skey
        )
        kt = jnp.where(f_de & de_mask, sl_de, EB)
        alive = blk.alive.at[kt].set(False, mode="drop")
        return blk._replace(
            props=props, alive=alive, blk_len=jnp.reshape(new_len, (1,))
        ), ovf

    out2, ovf_o = apply_block(ps.out, b.ne_src, b.ne_dst)
    inc2, ovf_i = apply_block(ps.inc, b.ne_dst, b.ne_src)

    ps2 = ps._replace(
        vlabel=vlabel, valive=valive, vprops=vprops, vversion=vversion,
        out=out2, inc=inc2,
        v_len=ps.v_len + b.nv_n, e_len=ps.e_len + b.ne_n,
        version=new_version,
    )
    # post-change edge-prop rows (for key calc), from the post-state blocks
    f_sp, _, _, _, se_post_rows = _lookup_block(pspec, ps2.out, b.se_eid, psum)
    se_props_new = jnp.where(
        se_mask[:, None],
        jnp.where(f_sp[:, None], se_post_rows, PROP_MISSING), PROP_MISSING,
    )
    applied = AppliedMutations(
        batch=batch, ne_eid=ne_eid, nv_vid=nv_vid,
        de_src=de_src, de_dst=de_dst, de_label=de_label, de_props=de_props,
        sv_old=sv_old, se_old=se_old, se_src=se_src, se_dst=se_dst,
        se_label=se_label, se_props=se_props_new,
        commit_version=new_version,
    )
    return ps2, applied, psum(ovf_o + ovf_i)


def local_shard(pspec: PartitionedStoreSpec, ps: PartitionedGraphStore, s: int):
    """Slice shard ``s``'s local view out of a global partitioned store
    (host-side; inside ``shard_map`` the runtime sees this shape directly)."""
    EB, Vloc, n = pspec.e_blk_cap, pspec.v_loc, pspec.n_shards

    def blk(b: EdgeBlock) -> EdgeBlock:
        return EdgeBlock(
            key=b.key[s * EB : (s + 1) * EB],
            other=b.other[s * EB : (s + 1) * EB],
            label=b.label[s * EB : (s + 1) * EB],
            alive=b.alive[s * EB : (s + 1) * EB],
            props=b.props[s * EB : (s + 1) * EB],
            geid=b.geid[s * EB : (s + 1) * EB],
            gperm=b.gperm[s * EB : (s + 1) * EB],
            indptr=b.indptr[s * (Vloc + 1) : (s + 1) * (Vloc + 1)],
            blk_len=b.blk_len[s : s + 1],
            csr_len=b.csr_len[s : s + 1],
        )

    return ps._replace(out=blk(ps.out), inc=blk(ps.inc))


def stack_blocks(pspec: PartitionedStoreSpec, ps: PartitionedGraphStore):
    """Reshape a global-layout store's blocks to a leading shard axis
    ``[n, ...]`` — the per-shard view a named-axis vmap (or host-side
    per-shard pass) consumes. Inverse of ``unstack_blocks``; the replicated
    vertex tier and scalars pass through unchanged."""
    n, EB, Vloc = pspec.n_shards, pspec.e_blk_cap, pspec.v_loc

    def blk(b: EdgeBlock) -> EdgeBlock:
        return EdgeBlock(
            key=b.key.reshape(n, EB), other=b.other.reshape(n, EB),
            label=b.label.reshape(n, EB), alive=b.alive.reshape(n, EB),
            props=b.props.reshape(n, EB, -1), geid=b.geid.reshape(n, EB),
            gperm=b.gperm.reshape(n, EB), indptr=b.indptr.reshape(n, Vloc + 1),
            blk_len=b.blk_len.reshape(n, 1), csr_len=b.csr_len.reshape(n, 1),
        )

    return ps._replace(out=blk(ps.out), inc=blk(ps.inc))


def splice_owner_blocks(pspec: PartitionedStoreSpec,
                        dst: PartitionedGraphStore,
                        src: PartitionedGraphStore,
                        owner: int) -> PartitionedGraphStore:
    """Graft owner ``owner``'s out/inc block rows from ``src`` into ``dst``
    (host-side, numpy). This is the recovery-as-migration transport: ``src``
    is the dead shard's reconstructed store (incremental checkpoint +
    journal replay), ``dst`` the live store that kept serving in degraded
    mode — only the lost owner's block region moves, everything else stays
    the live tier's bytes. The replicated vertex tier and global scalars are
    taken from ``src`` as well: during the outage every gRW commit queued in
    the journal unapplied, so the replayed store *is* the durable global
    state (``v_len``/``e_len``/``version`` included) and the live store's
    copy is identical by construction.

    The geid index makes the splice sufficient: ``gperm`` (the sorted
    geid→slot probe permutation) lives inside the block rows and travels
    with them, so the spliced store is immediately servable — no host
    re-sort, no re-index pass."""
    EB, Vloc, s = pspec.e_blk_cap, pspec.v_loc, int(owner)

    def blk(d: EdgeBlock, r: EdgeBlock) -> EdgeBlock:
        def row(dv, rv):
            out = np.asarray(dv).copy()
            out[s * EB:(s + 1) * EB] = np.asarray(rv)[s * EB:(s + 1) * EB]
            return out

        indptr = np.asarray(d.indptr).copy()
        indptr[s * (Vloc + 1):(s + 1) * (Vloc + 1)] = (
            np.asarray(r.indptr)[s * (Vloc + 1):(s + 1) * (Vloc + 1)]
        )
        blk_len = np.asarray(d.blk_len).copy()
        blk_len[s] = np.asarray(r.blk_len)[s]
        csr_len = np.asarray(d.csr_len).copy()
        csr_len[s] = np.asarray(r.csr_len)[s]
        return EdgeBlock(
            key=row(d.key, r.key), other=row(d.other, r.other),
            label=row(d.label, r.label), alive=row(d.alive, r.alive),
            props=row(d.props, r.props), geid=row(d.geid, r.geid),
            gperm=row(d.gperm, r.gperm), indptr=indptr,
            blk_len=blk_len, csr_len=csr_len,
        )

    return src._replace(
        out=blk(dst.out, src.out), inc=blk(dst.inc, src.inc),
    )


def unstack_blocks(pspec: PartitionedStoreSpec, ps: PartitionedGraphStore):
    """Flatten shard-stacked blocks back to the global layout."""
    n, EB = pspec.n_shards, pspec.e_blk_cap

    def blk(b: EdgeBlock) -> EdgeBlock:
        return EdgeBlock(
            key=b.key.reshape(-1), other=b.other.reshape(-1),
            label=b.label.reshape(-1), alive=b.alive.reshape(-1),
            props=b.props.reshape(n * EB, -1), geid=b.geid.reshape(-1),
            gperm=b.gperm.reshape(-1), indptr=b.indptr.reshape(-1),
            blk_len=b.blk_len.reshape(-1), csr_len=b.csr_len.reshape(-1),
        )

    return ps._replace(out=blk(ps.out), inc=blk(ps.inc))
