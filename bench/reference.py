"""The plain reference: what a gR-Tx must return, computed on the host
from the generated graph and the writes committed before it.

A one-hop sub-query from root ``r`` (Definition 2.1 of the paper, as
``core/oracle.onehop_oracle`` states it) is the set of leaves ``l`` such
that ``r`` satisfies the root predicate, an alive edge joins ``r`` and
``l`` in the hop's direction with the hop's edge label and satisfies the
edge predicate, and ``l`` satisfies the leaf predicate. A wildcard
condition bound to a value is an equality with it; a property that is
absent fails every condition on it. A plan composes its hops over the
union of each hop's leaves, then applies its post filter and its final
clause (ids, values of one property, or the count).

This module imports nothing of the system under test; it reads the
configuration and traffic files and the arrays ``graphgen`` made.
"""

from __future__ import annotations

import numpy as np

from bench.graphgen import MISSING

OPS = {
    "eq": np.equal, "neq": np.not_equal, "lt": np.less, "le": np.less_equal,
    "gt": np.greater, "ge": np.greater_equal,
}


def hop_specs(cfg, plan) -> list:
    """Each hop of a traffic plan as plain data: direction, edge label and
    three predicates ``(label or None, [(prop, op, value or None)])`` with
    the plan's bound values filled into the wildcards."""
    sch = cfg["schema"]
    VL, EL = sch["vertex_labels"], sch["edge_labels"]
    props = {**sch["vertex_props"], **{"e." + k: v for k, v in
                                        sch["edge_props"].items()}}
    tpls = {t["name"]: t for t in cfg["templates"]}
    out = []
    for h in plan["hops"]:
        shape = tpls[h["template"]] if h.get("template") else h
        bound = h.get("params", {})

        def pred(p, edge=False):
            label = p.get("label")
            conds = []
            for prop, op, val in p.get("conds", []):
                pid = props[("e." if edge else "") + prop]
                if val == "*":
                    val = bound.get(prop)  # None: presence only
                conds.append((pid, op, val))
            if edge:
                return (None if label is None else EL[label], conds)
            return (None if label is None else VL[label], conds)

        out.append({
            "dir": shape["direction"],
            "elabel": EL[shape["edge_label"]] if shape.get("edge_label") else None,
            "root": pred(shape["root"]),
            "edge": pred(shape["edge"], edge=True),
            "leaf": pred(shape["leaf"]),
        })
    return out


def _holds(pred, labels, props) -> np.ndarray:
    """Vectorized predicate over rows: ``labels`` [k], ``props`` [k, P]."""
    label, conds = pred
    ok = np.ones(len(labels), bool)
    if label is not None:
        ok &= labels == label
    for pid, op, val in conds:
        pv = props[:, pid]
        ok &= pv != MISSING
        if val is not None:
            ok &= OPS[op](pv, val)
    return ok


class Reference:
    """The graph as the reference sees it, with writes applied in order.

    Adjacency is a CSR per direction over the generated edges, built here
    with numpy; committed new edges go to a per-vertex overlay and deleted
    edges are marked dead.
    """

    def __init__(self, cfg, graph):
        self.cfg = cfg
        self.vlabel = graph.vlabel
        self.vprops = graph.vprops.copy()
        E = graph.n_edges
        self.n_base = E
        self.src = graph.esrc
        self.dst = graph.edst
        self.elabel = graph.elabel
        self.eprops = graph.eprops
        self.alive = np.ones(E, bool)
        V = len(graph.vlabel)
        self.csr = {}
        slot = np.arange(E, dtype=np.int64)
        for d, key in (("out", graph.esrc), ("in", graph.edst)):
            packed = (key.astype(np.int64) << 32) | slot  # sort by (key, eid)
            packed.sort()
            order = packed & 0xFFFFFFFF
            ptr = np.concatenate([[0], np.cumsum(np.bincount(key, minlength=V))])
            self.csr[d] = (ptr, order)
        # committed new edges: eid -> (src, dst, label, props)
        self.new_src, self.new_dst, self.new_label, self.new_props = [], [], [], []
        self.new_alive: list = []
        self.overlay = {"out": {}, "in": {}}

    # ------------------------------------------------------------ writes
    def apply(self, changes: dict) -> None:
        for v, pid, val in changes.get("set_vprops", ()):
            self.vprops[v, pid] = val
        for e in changes.get("del_edges", ()):
            if e < self.n_base:
                self.alive[e] = False
            else:
                self.new_alive[e - self.n_base] = False
        for s, d, lab, props in changes.get("new_edges", ()):
            i = len(self.new_src)
            self.new_src.append(s)
            self.new_dst.append(d)
            self.new_label.append(lab)
            self.new_props.append(list(props))
            self.new_alive.append(True)
            self.overlay["out"].setdefault(s, []).append(i)
            self.overlay["in"].setdefault(d, []).append(i)

    # ------------------------------------------------------------- reads
    def edges(self, v: int, direction: str):
        """Alive edges at ``v``: (other endpoint, label, props) arrays."""
        ptr, order = self.csr[direction]
        eids = order[ptr[v]:ptr[v + 1]]
        eids = eids[self.alive[eids]]
        other = (self.dst if direction == "out" else self.src)[eids]
        lab = self.elabel[eids]
        props = self.eprops[eids]
        extra = [i for i in self.overlay[direction].get(v, ()) if self.new_alive[i]]
        if extra:
            o = self.new_dst if direction == "out" else self.new_src
            other = np.concatenate([other, [o[i] for i in extra]]).astype(np.int64)
            lab = np.concatenate([lab, [self.new_label[i] for i in extra]])
            props = np.concatenate(
                [props, np.asarray([self.new_props[i] for i in extra],
                                   props.dtype).reshape(len(extra), -1)])
        return other, lab, props

    def degree(self, v: int, direction: str) -> int:
        return len(self.edges(v, direction)[0])

    def onehop(self, hop, root: int) -> np.ndarray:
        """Leaf ids of one hop from ``root``, ascending."""
        V = len(self.vlabel)
        if not (0 <= root < V):
            return np.zeros(0, np.int64)
        if not _holds(hop["root"], self.vlabel[root:root + 1],
                      self.vprops[root:root + 1])[0]:
            return np.zeros(0, np.int64)
        parts = []
        for d in (("out",) if hop["dir"] == "out" else ("in",) if hop["dir"] == "in"
                  else ("out", "in")):
            other, lab, props = self.edges(root, d)
            ok = np.ones(len(other), bool)
            if hop["elabel"] is not None:
                ok &= lab == hop["elabel"]
            ok &= _holds(hop["edge"], lab, props)
            other = other[ok]
            ok = _holds(hop["leaf"], self.vlabel[other], self.vprops[other])
            parts.append(other[ok])
        return np.unique(np.concatenate(parts)) if parts else np.zeros(0, np.int64)

    def plan(self, hops, plan, root: int):
        """The plan's answer from ``root`` and the frontier of every hop:
        ``(answer, [(hop roots, per-root leaf counts)] + [(final frontier,
        [])])``. ``answer`` is a sorted tuple of ids or values, or an int
        for a count."""
        frontier = np.asarray([root], np.int64)
        trail = []
        for hop in hops:
            got = [self.onehop(hop, int(v)) for v in frontier]
            trail.append((frontier, [len(g) for g in got]))
            frontier = (np.unique(np.concatenate(got)) if got
                        else np.zeros(0, np.int64))
        trail.append((frontier, []))
        post = plan.get("post_filter")
        if post:
            if post[0] == "prop_neq_root":
                pid = self.cfg["schema"]["vertex_props"][post[1]]
                frontier = frontier[self.vprops[frontier, pid]
                                    != self.vprops[root, pid]]
            elif post[0] == "id_neq":
                frontier = frontier[frontier != root]
            else:
                raise ValueError(post)
        final = plan.get("final", "ids")
        if final == "count":
            return len(frontier), trail
        if final == "values":
            pid = self.cfg["schema"]["vertex_props"][plan["final_prop"]]
            return tuple(sorted(int(x) for x in self.vprops[frontier, pid])), trail
        return tuple(int(x) for x in frontier), trail
