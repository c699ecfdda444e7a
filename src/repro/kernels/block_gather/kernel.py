"""Owner-local block gather + predicate filter, Pallas TPU.

The partitioned tier's miss-execution hot path, fused into one kernel per
orientation: for a block of routed roots, scan the owner-local CSR window
AND the block's recent append region, chain edge/endpoint liveness, and
apply the hop's edge-label + edge-predicate + leaf-predicate filters — one
pass over VMEM-resident block arrays instead of the former multi-op
gather/take/select chain (see ``ref.block_gather_filter_ref`` for the exact
math and the operand contract; ``partition.BlockGatherOperands`` bundles the
arrays).

Grid: (B / block_b,). Per program the root block's per-row inputs live in
VMEM; the block arrays (indptr, key/other/label/alive/props) and the
replicated vertex tier are streamed as whole-array blocks — like
``onehop_gather`` this validation variant assumes the block partition fits
VMEM (the production variant would DMA each root's CSR window via
scalar-prefetched indptr, same math). Predicates arrive statically frozen
(``ref.pred_static``), so each condition unrolls to its exact comparison
with wildcard lanes read from the per-row bound params.

This variant runs only in interpret mode: its vector-indexed ref reads
(``indptr_ref[lroot]`` and the window gathers below) are refused by Mosaic
("Cannot do int indexing on TPU"), so the serving path runs the XLA
formulation in ``ref`` on every platform (``ops.block_gather``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
# not the usual ``as pl`` alias: the hop's leaf predicate arrives as a
# parameter named ``pl`` (mirroring ``QueryPlan`` field names) and would
# shadow it inside ``block_gather_pallas``
from jax.experimental import pallas

from repro.kernels.block_gather.ref import eval_pred_static


def _block_gather_kernel(
    indptr_ref, key_ref, other_ref, label_ref, alive_ref, props_ref,
    vlabel_ref, valive_ref, vprops_ref, csr_len_ref, blk_len_ref,
    roots_ref, lroot_ref, rvalid_ref, cvalid_ref, rmask_ref, r_ok_ref,
    pe_bound_ref, pl_bound_ref,
    leaf_ref, scan_ref, emask_ref, qual_ref, trunc_ref,
    *, max_deg, recent_cap, e_blk_cap, edge_label, pe, pl,
):
    EB, R = e_blk_cap, recent_cap
    roots = roots_ref[...]          # [bb] global ids
    lroot = lroot_ref[...]          # [bb] clipped local ids
    rvalid = rvalid_ref[...]        # recent-region gate (table owner == me)
    cvalid = cvalid_ref[...]        # CSR-window gate (native roots only)
    rmask = rmask_ref[...]
    r_ok = r_ok_ref[...]
    bb = roots.shape[0]
    csr_len = csr_len_ref[0]
    blk_len = blk_len_ref[0]

    # ---- CSR window ----
    start = indptr_ref[lroot]
    deg = indptr_ref[lroot + 1] - start
    trunc = deg > max_deg
    lane = jax.lax.broadcasted_iota(jnp.int32, (bb, max_deg), 1)
    pos = start[:, None] + lane
    csr_mask = (lane < deg[:, None]) & cvalid[:, None]
    slot_csr = jnp.clip(pos, 0, EB - 1)

    # ---- recent region: [csr_len, blk_len) within a bounded window ----
    roff = jnp.clip(csr_len, 0, EB - R)
    key_r = jax.lax.dynamic_slice(key_ref[...], (roff,), (R,))
    sid = roff + jax.lax.broadcasted_iota(jnp.int32, (R,), 0)
    in_region = (sid >= csr_len) & (sid < blk_len)
    rec_mask = (key_r[None, :] == roots[:, None]) & in_region[None, :]
    rec_mask &= rvalid[:, None]
    slot_rec = jnp.broadcast_to(sid[None, :], (bb, R))

    slots = jnp.concatenate([slot_csr, slot_rec], axis=1)  # [bb, W]
    mask = jnp.concatenate([csr_mask, rec_mask], axis=1)
    mask &= alive_ref[...][slots]
    leaf = other_ref[...][slots]
    v_cap = valive_ref.shape[0]
    leaf_c = jnp.clip(leaf, 0, v_cap - 1)
    valive = valive_ref[...]
    mask &= valive[leaf_c]
    root_c = jnp.clip(roots, 0, v_cap - 1)
    mask &= valive[root_c][:, None]

    # ---- statically specialized filter chain ----
    scan = mask & rmask[:, None]
    elab = label_ref[...][slots]
    epv = props_ref[...][slots]
    if edge_label < 0:
        e_ok = jnp.ones_like(scan)
    else:
        e_ok = elab == edge_label
    e_ok &= eval_pred_static(pe, elab, epv, pe_bound_ref[...][:, None, :])
    emask = scan & e_ok
    llab = vlabel_ref[...][leaf_c]
    lpv = vprops_ref[...][leaf_c]
    l_ok = eval_pred_static(pl, llab, lpv, pl_bound_ref[...][:, None, :])
    qual = emask & l_ok & r_ok[:, None]

    leaf_ref[...] = leaf
    scan_ref[...] = scan
    emask_ref[...] = emask
    qual_ref[...] = qual
    trunc_ref[...] = trunc


def block_gather_pallas(
    indptr, key, other, label, alive, props, vlabel, valive, vprops,
    csr_len, blk_len, roots, lroot, rvalid, cvalid, rmask, r_ok,
    pe_bound, pl_bound,
    *, max_deg, recent_cap, e_blk_cap, edge_label, pe, pl,
    block_b=128, interpret=False,
):
    """Pallas dispatch of ``ref.block_gather_filter_ref`` (same signature,
    same outputs; B must divide into ``block_b`` row blocks — the ops
    wrapper pads)."""
    B = roots.shape[0]
    W = max_deg + recent_cap
    Vp = indptr.shape[0]
    EB = e_blk_cap
    v_cap = vlabel.shape[0]
    NEP, NVP = props.shape[1], vprops.shape[1]
    block_b = min(block_b, B)
    assert B % block_b == 0, (B, block_b)
    grid = (B // block_b,)
    kernel = functools.partial(
        _block_gather_kernel, max_deg=max_deg, recent_cap=recent_cap,
        e_blk_cap=e_blk_cap, edge_label=edge_label, pe=pe, pl=pl,
    )
    full1 = lambda n: pallas.BlockSpec((n,), lambda i: (0,))
    full2 = lambda n, k: pallas.BlockSpec((n, k), lambda i: (0, 0))
    row1 = pallas.BlockSpec((block_b,), lambda i: (i,))
    row2 = lambda k: pallas.BlockSpec((block_b, k), lambda i: (i, 0))
    leaf, scan, emask, qual, trunc = pallas.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            full1(Vp),        # indptr
            full1(EB),        # key
            full1(EB),        # other
            full1(EB),        # label
            full1(EB),        # alive
            full2(EB, NEP),   # props
            full1(v_cap),     # vlabel
            full1(v_cap),     # valive
            full2(v_cap, NVP),  # vprops
            full1(1),         # csr_len
            full1(1),         # blk_len
            row1,             # roots
            row1,             # lroot
            row1,             # rvalid
            row1,             # cvalid
            row1,             # rmask
            row1,             # r_ok
            row2(pe_bound.shape[1]),  # pe_bound
            row2(pl_bound.shape[1]),  # pl_bound
        ],
        out_specs=[
            row2(W), row2(W), row2(W), row2(W), row1,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, W), jnp.int32),
            jax.ShapeDtypeStruct((B, W), jnp.bool_),
            jax.ShapeDtypeStruct((B, W), jnp.bool_),
            jax.ShapeDtypeStruct((B, W), jnp.bool_),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
        ],
        interpret=interpret,
    )(
        indptr, key, other, label, alive, props, vlabel, valive, vprops,
        jnp.reshape(csr_len, (1,)), jnp.reshape(blk_len, (1,)),
        roots, lroot, rvalid, cvalid, rmask, r_ok, pe_bound, pl_bound,
    )
    return leaf, scan, emask, qual, trunc
