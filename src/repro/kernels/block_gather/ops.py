"""Public wrappers for block_gather: the raw fused scan+filter and the
drop-in owner-local miss executor for the sharded serve tier.

``block_gather`` runs the fully vectorized XLA formulation
(``ref.block_gather_filter_ref``) on every platform, so the chip runs the
same program the tier-1 tests pin. ``use_pallas=True`` dispatches to the
Pallas kernel instead (padding the batch to whole kernel blocks), which is
kept for its interpret-mode parity tests: it loads whole blocks with
vector-indexed ref reads, which Mosaic refuses to compile for a TPU.

``block_onehop_exec`` is the fused replacement for
``runtime.onehop_exec_view`` over a ``partition.BlockStoreView``: same
(leaves, lmask, n_true, truncated, stats) contract, but the per-direction
scan + filter run in one fused pass and the Definition 2.1 set-dedup is the
O(W log W) sort-based first-occurrence keep instead of the O(W^2) pairwise
compare — the dominant cost at production widths (W = max_deg +
recent_blk_cap lanes per orientation). The two are byte-identical on
well-formed stores: a qualifying lane's leaf id is never NULL_ID (alive
edges carry real endpoints), which is the only value where the two dedup
styles could diverge.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.keys import PARAM_LEN
from repro.core.templates import DIR_BOTH, DIR_IN, DIR_OUT, MAX_CONDS, evaluate_pred
from repro.distributed.routing import storage_owner_of
from repro.graphstore.partition import local_of, owner_of
from repro.kernels.block_gather.kernel import block_gather_pallas
from repro.kernels.block_gather.ref import block_gather_filter_ref, pred_static
from repro.utils import NULL_ID, compact_masked, take_along0


def block_gather(
    indptr, key, other, label, alive, props, vlabel, valive, vprops,
    csr_len, blk_len, roots, lroot, rvalid, cvalid, rmask, r_ok,
    pe_bound, pl_bound,
    *, max_deg, recent_cap, e_blk_cap, edge_label, pe, pl,
    block_b=128, use_pallas=False, interpret=None,
):
    """One orientation's fused scan + filter (see ``ref`` for the operand
    and output contract). The Pallas path handles arbitrary batch sizes by
    padding B up to whole kernel blocks (padded rows are invalid and fully
    masked)."""
    statics = dict(
        max_deg=max_deg, recent_cap=recent_cap, e_blk_cap=e_blk_cap,
        edge_label=edge_label, pe=pe, pl=pl,
    )
    if not use_pallas:
        return block_gather_filter_ref(
            indptr, key, other, label, alive, props, vlabel, valive, vprops,
            csr_len, blk_len, roots, lroot, rvalid, cvalid, rmask, r_ok,
            pe_bound, pl_bound, **statics,
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B = roots.shape[0]
    if B <= block_b:
        Bp, blk = B, B
    else:
        Bp = -(-B // block_b) * block_b
        blk = block_b
    if Bp != B:
        pad = Bp - B
        pad_i = lambda x: jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
        pad_b = lambda x: jnp.concatenate([x, jnp.zeros((pad,), bool)])
        pad_2 = lambda x: jnp.concatenate(
            [x, jnp.zeros((pad, x.shape[1]), x.dtype)]
        )
        roots, lroot = pad_i(roots), pad_i(lroot)
        rvalid, cvalid = pad_b(rvalid), pad_b(cvalid)
        rmask, r_ok = pad_b(rmask), pad_b(r_ok)
        pe_bound, pl_bound = pad_2(pe_bound), pad_2(pl_bound)
    leaf, scan, emask, qual, trunc = block_gather_pallas(
        indptr, key, other, label, alive, props, vlabel, valive, vprops,
        csr_len, blk_len, roots, lroot, rvalid, cvalid, rmask, r_ok,
        pe_bound, pl_bound, block_b=blk, interpret=interpret, **statics,
    )
    return leaf[:B], scan[:B], emask[:B], qual[:B], trunc[:B]


def first_occurrence_mask(vals, mask):
    """Per-row first-occurrence keep over masked lanes — the O(W log W)
    equivalent of ``utils.dedup_masked`` (stable sort + adjacent compare,
    permutation inverted back to original order). Identical for any row
    where no masked lane carries NULL_ID (guaranteed for liveness-masked
    block lanes)."""
    mask = mask.astype(bool)
    big = jnp.int32(2**31 - 1)  # sorts after every valid id
    keyed = jnp.where(mask, vals, big)
    order = jnp.argsort(keyed, axis=-1, stable=True)
    sv = jnp.take_along_axis(keyed, order, axis=-1)
    first = jnp.concatenate(
        [jnp.ones(sv.shape[:-1] + (1,), bool), sv[..., 1:] != sv[..., :-1]],
        axis=-1,
    )
    keep_sorted = first & (sv != big)
    inv = jnp.argsort(order, axis=-1)  # invert the permutation
    return jnp.take_along_axis(keep_sorted, inv, axis=-1)


def block_onehop_exec(
    espec, view, direction: int, edge_label: int, pr, pe, pl,
    roots, params, rmask, *, use_pallas=False,
):
    """Fused owner-local miss executor over a ``BlockStoreView`` — the
    partitioned tier's ``exec_fn`` hook. Same contract as
    ``runtime.onehop_exec_view`` (leaves [B, RW], lmask, n_true, truncated,
    stats), byte-identical outputs."""
    pspec = view.pspec
    n, v_cap = pspec.n_shards, pspec.base.v_cap
    pe_bound = params[:, :MAX_CONDS]
    pl_bound = params[:, MAX_CONDS:]

    # root-side gates, shared by both orientations (cheap [B] work)
    roots = roots.astype(jnp.int32)
    rlab = take_along0(view.vlabel, roots)
    rprops = take_along0(view.vprops, roots)
    r_ok = evaluate_pred(pr, rlab, rprops) & rmask
    local = local_of(roots, n)
    rtable = getattr(view, "rtable", None)
    in_range = (roots >= 0) & (roots < v_cap)
    rvalid = (storage_owner_of(rtable, roots, n) == view.me) & in_range
    if rtable is None:
        native = None
        cvalid = rvalid
    else:
        # a migrated-in root's local index v//n aliases a *native* vertex's
        # CSR rows — only native roots may open the CSR window (their rows,
        # once migrated in, live in the recent region and match by key)
        native = owner_of(roots, n) == view.me
        cvalid = rvalid & native
    lroot = jnp.clip(local, 0, pspec.v_loc - 1)

    pe_s, pl_s = pred_static(pe), pred_static(pl)
    incs = {DIR_OUT: (False,), DIR_IN: (True,), DIR_BOTH: (False, True)}
    leaf_p, scan_p, em_p, qual_p, trunc = [], [], [], [], jnp.zeros_like(rmask)
    for incoming in incs[direction]:
        o = view.kernel_operands(incoming=incoming)
        leaf, scan, emask, qual, t = block_gather(
            *o, roots, lroot, rvalid, cvalid, rmask, r_ok, pe_bound, pl_bound,
            max_deg=espec.max_deg, recent_cap=pspec.recent_blk_cap,
            e_blk_cap=pspec.e_blk_cap, edge_label=edge_label,
            pe=pe_s, pl=pl_s, use_pallas=use_pallas,
        )
        leaf_p.append(leaf), scan_p.append(scan)
        em_p.append(emask), qual_p.append(qual)
        # a foreign root's CSR deg is an aliased native vertex's — its
        # truncation flag is meaningless (its real rows, in the recent
        # region, are never truncated: migration policy bounds degree)
        trunc |= (t & native) if native is not None else t

    leaf = jnp.concatenate(leaf_p, axis=1)
    scanned_mask = jnp.concatenate(scan_p, axis=1)
    n_edges_scanned = jnp.sum(scanned_mask.astype(jnp.int32))
    emask = jnp.concatenate(em_p, axis=1)
    n_leaf_fetches = jnp.sum(emask.astype(jnp.int32))  # the paper's "n"
    qual = jnp.concatenate(qual_p, axis=1)

    keep = first_occurrence_mask(leaf, qual)  # set semantics (Definition 2.1)
    n_true = jnp.sum(keep.astype(jnp.int32), axis=1)
    leaves, lmask = compact_masked(leaf, keep, espec.result_width)
    stats = {
        "edges_scanned": n_edges_scanned,
        "leaf_fetches": n_leaf_fetches,
        # full read-conflict set for OCC population commits (see
        # onehop_exec_view): every vertex this execution observed
        "scanned": leaf,
        "scanned_mask": scanned_mask,
    }
    return leaves, lmask, n_true, trunc & rmask, stats
