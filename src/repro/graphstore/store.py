"""The tensor property-graph store.

Vertices and edges live in fixed-capacity *slot arrays*; the slot index is
the immutable id (the paper requires immutable vertex ids for cache keys).
Out-/in-edge adjacency is served by CSR permutation indexes built at
*compaction* time over slots ``[0, csr_len)``; edges appended after the last
compaction sit in the *recent region* ``[csr_len, e_len)`` and are found by a
bounded linear scan (capacity ``recent_cap``), mirroring FDB's in-memory
write buffer in front of its on-disk B-tree.

All reads are masked by liveness (``ealive`` and both endpoint ``valive``),
so deletes are O(1) scatter writes and never require index maintenance.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import PROP_MISSING, take_along0

INT32_MAX = np.int32(2**31 - 1)


class StoreSpec(NamedTuple):
    """Static shape/capacity configuration (hashable; safe as a closure)."""

    v_cap: int = 1024
    e_cap: int = 8192
    n_vprops: int = 4
    n_eprops: int = 2
    recent_cap: int = 256


class GraphStore(NamedTuple):
    """Pytree of device arrays. See module docstring for the layout."""

    # vertex slots
    vlabel: jax.Array  # int32 [v_cap]
    valive: jax.Array  # bool  [v_cap]
    vprops: jax.Array  # int32 [v_cap, n_vprops]
    vversion: jax.Array  # int32 [v_cap]  (FDB-style conflict ranges)
    # edge slots
    esrc: jax.Array  # int32 [e_cap]
    edst: jax.Array  # int32 [e_cap]
    elabel: jax.Array  # int32 [e_cap]
    ealive: jax.Array  # bool  [e_cap]
    eprops: jax.Array  # int32 [e_cap, n_eprops]
    # CSR indexes over [0, csr_len)
    out_indptr: jax.Array  # int32 [v_cap + 1]
    out_perm: jax.Array  # int32 [e_cap]  (CSR position -> edge slot)
    in_indptr: jax.Array  # int32 [v_cap + 1]
    in_perm: jax.Array  # int32 [e_cap]
    # scalars (0-d int32 arrays)
    v_len: jax.Array
    e_len: jax.Array
    csr_len: jax.Array
    version: jax.Array  # global commit version


def empty_store(spec: StoreSpec) -> GraphStore:
    i32 = jnp.int32
    return GraphStore(
        vlabel=jnp.full((spec.v_cap,), -1, i32),
        valive=jnp.zeros((spec.v_cap,), bool),
        vprops=jnp.full((spec.v_cap, spec.n_vprops), PROP_MISSING, i32),
        vversion=jnp.zeros((spec.v_cap,), i32),
        esrc=jnp.full((spec.e_cap,), INT32_MAX, i32),
        edst=jnp.full((spec.e_cap,), -1, i32),
        elabel=jnp.full((spec.e_cap,), -1, i32),
        ealive=jnp.zeros((spec.e_cap,), bool),
        eprops=jnp.full((spec.e_cap, spec.n_eprops), PROP_MISSING, i32),
        out_indptr=jnp.zeros((spec.v_cap + 1,), i32),
        out_perm=jnp.zeros((spec.e_cap,), i32),
        in_indptr=jnp.zeros((spec.v_cap + 1,), i32),
        in_perm=jnp.zeros((spec.e_cap,), i32),
        v_len=jnp.int32(0),
        e_len=jnp.int32(0),
        csr_len=jnp.int32(0),
        version=jnp.int32(0),
    )


def ingest(
    spec: StoreSpec,
    vlabels: np.ndarray,
    vprops: np.ndarray,
    esrc: np.ndarray,
    edst: np.ndarray,
    elabels: np.ndarray,
    eprops: np.ndarray,
) -> GraphStore:
    """Bulk-load a graph (used by data generators) and compact: the
    ``ingest_host`` store on the default device."""
    return jax.tree_util.tree_map(
        jnp.asarray, ingest_host(spec, vlabels, vprops, esrc, edst, elabels, eprops)
    )


def _host_csr(keys: np.ndarray, e_len: int, v_cap: int):
    """numpy twin of ``compact``'s per-orientation index: the stable
    argsort is unique, so the permutation is byte-identical. It sorts
    (key, slot) packed into one int64, several times faster than numpy's
    stable argsort at 10^8 edges."""
    slot = np.arange(keys.shape[0], dtype=np.int64)
    okey = np.where(slot < e_len, keys, INT32_MAX)
    packed = (okey.astype(np.int64) << 32) | slot
    packed.sort()
    perm = (packed & 0xFFFFFFFF).astype(np.int32)
    indptr = np.searchsorted(
        okey[perm], np.arange(v_cap + 1), side="left"
    ).astype(np.int32)
    return indptr, perm


def ingest_host(
    spec: StoreSpec,
    vlabels: np.ndarray,
    vprops: np.ndarray,
    esrc: np.ndarray,
    edst: np.ndarray,
    elabels: np.ndarray,
    eprops: np.ndarray,
) -> GraphStore:
    """Bulk-load and compact on the host: a ``GraphStore`` of numpy arrays.
    Nothing touches a device, so a store larger than one chip's memory can
    be built and then partitioned straight onto the mesh
    (``ShardedTxnRuntime.partition_store``)."""
    nv, ne = len(vlabels), len(esrc)
    assert nv <= spec.v_cap and ne <= spec.e_cap
    i32 = np.int32

    def filled(shape, fill, dtype, head):
        a = np.full(shape, fill, dtype)
        a[: len(head)] = head
        return a

    vlabel = filled((spec.v_cap,), -1, i32, np.asarray(vlabels, i32))
    valive = filled((spec.v_cap,), False, bool, np.ones(nv, bool))
    missing = int(PROP_MISSING)
    vprop = filled((spec.v_cap, spec.n_vprops), missing, i32,
                   np.asarray(vprops).astype(i32).reshape(-1, spec.n_vprops))
    src = filled((spec.e_cap,), INT32_MAX, i32, np.asarray(esrc, i32))
    dst = filled((spec.e_cap,), -1, i32, np.asarray(edst, i32))
    with ThreadPoolExecutor(max_workers=2) as pool:  # sorts release the GIL
        out_csr = pool.submit(_host_csr, src, ne, spec.v_cap)
        in_csr = pool.submit(_host_csr, dst, ne, spec.v_cap)
        (out_indptr, out_perm), (in_indptr, in_perm) = (
            out_csr.result(), in_csr.result()
        )
    return GraphStore(
        vlabel=vlabel, valive=valive, vprops=vprop,
        vversion=np.zeros((spec.v_cap,), i32),
        esrc=src, edst=dst,
        elabel=filled((spec.e_cap,), -1, i32, np.asarray(elabels, i32)),
        ealive=filled((spec.e_cap,), False, bool, np.ones(ne, bool)),
        eprops=filled((spec.e_cap, spec.n_eprops), missing, i32,
                      np.asarray(eprops).astype(i32).reshape(-1, spec.n_eprops)),
        out_indptr=out_indptr, out_perm=out_perm,
        in_indptr=in_indptr, in_perm=in_perm,
        v_len=i32(nv), e_len=i32(ne), csr_len=i32(ne), version=i32(0),
    )


def compact(spec: StoreSpec, store: GraphStore) -> GraphStore:
    """Rebuild both CSR indexes over all allocated edge slots.

    Sort-based (O(E log E) on device); dead edges keep their slots but are
    masked at read time. The analogue of an LSM compaction: afterwards the
    recent region is empty and every edge is range-readable.
    """
    idx = jnp.arange(spec.e_cap, dtype=jnp.int32)
    allocated = idx < store.e_len
    # unallocated slots sort to the end; dead-but-allocated stay indexed
    okey = jnp.where(allocated, store.esrc, INT32_MAX)
    operm = jnp.argsort(okey, stable=True).astype(jnp.int32)
    osorted = okey[operm]
    out_indptr = jnp.searchsorted(
        osorted, jnp.arange(spec.v_cap + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    ikey = jnp.where(allocated, store.edst, INT32_MAX)
    iperm = jnp.argsort(ikey, stable=True).astype(jnp.int32)
    isorted = ikey[iperm]
    in_indptr = jnp.searchsorted(
        isorted, jnp.arange(spec.v_cap + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    return store._replace(
        out_indptr=out_indptr,
        out_perm=operm,
        in_indptr=in_indptr,
        in_perm=iperm,
        csr_len=store.e_len,
    )


def _gather(
    spec: StoreSpec,
    store: GraphStore,
    roots: jax.Array,
    max_deg: int,
    *,
    incoming: bool,
):
    """Padded adjacency gather: CSR rows + recent-region scan.

    Returns (eids [B, W], other [B, W], mask [B, W], truncated [B]) where
    W = max_deg + recent_cap and ``other`` is the opposite endpoint.
    ``truncated`` flags supernode rows whose CSR degree exceeded max_deg —
    the paper's supernode-detection hook (§4, timeout/retry discussion).
    """
    indptr = store.in_indptr if incoming else store.out_indptr
    perm = store.in_perm if incoming else store.out_perm
    key_side = store.edst if incoming else store.esrc
    other_side = store.esrc if incoming else store.edst

    roots = roots.astype(jnp.int32)
    rvalid = (roots >= 0) & (roots < spec.v_cap)
    rc = jnp.clip(roots, 0, spec.v_cap - 1)
    start = indptr[rc]
    deg = indptr[rc + 1] - start
    truncated = deg > max_deg
    pos = start[:, None] + jnp.arange(max_deg, dtype=jnp.int32)[None, :]
    csr_mask = (jnp.arange(max_deg)[None, :] < deg[:, None]) & rvalid[:, None]
    eid_csr = take_along0(perm, pos)

    # recent region: dynamic slice [csr_len, csr_len + recent_cap)
    roff = jnp.clip(store.csr_len, 0, spec.e_cap - spec.recent_cap)
    key_r = jax.lax.dynamic_slice(key_side, (roff,), (spec.recent_cap,))
    eid_r = roff + jnp.arange(spec.recent_cap, dtype=jnp.int32)
    in_region = (eid_r >= store.csr_len) & (eid_r < store.e_len)
    rec_mask = (key_r[None, :] == roots[:, None]) & in_region[None, :]
    rec_mask &= rvalid[:, None]
    eid_rec = jnp.broadcast_to(eid_r[None, :], (roots.shape[0], spec.recent_cap))

    eids = jnp.concatenate([eid_csr, eid_rec], axis=1)
    mask = jnp.concatenate([csr_mask, rec_mask], axis=1)
    # liveness: edge alive, both endpoints alive, key side really matches
    # (CSR may be stale only in that dead edges remain; src never mutates)
    mask &= take_along0(store.ealive, eids)
    other = take_along0(other_side, eids)
    mask &= take_along0(store.valive, other)
    mask &= take_along0(store.valive, jnp.broadcast_to(roots[:, None], eids.shape))
    return eids, other, mask, truncated


def gather_out(spec: StoreSpec, store: GraphStore, roots: jax.Array, max_deg: int):
    """Outgoing edges of each root. See ``_gather``."""
    return _gather(spec, store, roots, max_deg, incoming=False)


def gather_in(spec: StoreSpec, store: GraphStore, roots: jax.Array, max_deg: int):
    """Incoming edges of each root. See ``_gather``."""
    return _gather(spec, store, roots, max_deg, incoming=True)


class GlobalStoreView:
    """Storage view of a full (replicated) ``GraphStore``.

    The storage hook consumed by the shared hop driver and the mutation
    listener (``repro.core.runtime`` / ``repro.core.invalidation``): vertex
    attribute arrays plus a padded adjacency gather that also resolves each
    scanned edge's label/properties. The partitioned tier provides the same
    interface over owner-local blocks (``partition.BlockStoreView``); both
    views return identical values for identical logical stores, which is the
    structural basis of the engines' byte-identity.

    ``own`` is ``None``: a single host owns every vertex, and the listener
    skips ownership gating entirely (keeping its traced graph unchanged).
    """

    own = None

    def __init__(self, spec: StoreSpec, store: GraphStore):
        self.spec = spec
        self.store = store

    @property
    def vlabel(self):
        return self.store.vlabel

    @property
    def vprops(self):
        return self.store.vprops

    @property
    def valive(self):
        return self.store.valive

    def adjacency(self, roots: jax.Array, max_deg: int, *, incoming: bool):
        """Returns ``(other [B, W], mask, truncated [B], elabel, eprops)``."""
        eids, other, mask, trunc = _gather(
            self.spec, self.store, roots, max_deg, incoming=incoming
        )
        elab = take_along0(self.store.elabel, eids)
        ep = take_along0(self.store.eprops, eids)
        return other, mask, trunc, elab, ep
