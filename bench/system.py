"""The system under test, built from a configuration file: the store
spec, the registered templates, the query plans of a traffic mix and the
partitioned ``ShardedTxnRuntime`` on a ``flat_mesh`` of the cell's chips.

"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np
from repro.core.cache import CacheSpec
from repro.core.engine import EngineSpec, Hop, QueryPlan
from repro.core.keys import PARAM_LEN
from repro.core.lifecycle import GraphQP, ServiceCoordinator
from repro.core.rewrite import rewrite_plan
from repro.core.templates import (
    ANY_LABEL, MAX_CONDS, WILDCARD, Template, make_pred, make_template_table,
)
from repro.distributed.graph_serve import ShardedTxnRuntime
from repro.distributed.sharding import flat_mesh
from repro.graphstore.store import StoreSpec
from repro.launch.serve import BLOCK_FILL, block_capacity as needed_capacity
from repro.utils import PROP_MISSING

DIRS = {"out": 0, "in": 1, "both": 2}
OPS = {"eq": 0, "neq": 1, "lt": 2, "le": 3, "gt": 4, "ge": 5}
FINALS = {"ids": 0, "count": 1, "values": 2}


def _pred_args(cfg, p, wildcard, edge=False):
    """``(label, conds)`` for ``make_pred``/``Template``."""
    sch = cfg["schema"]
    labels = sch["edge_labels" if edge else "vertex_labels"]
    props = sch["edge_props" if edge else "vertex_props"]
    label = ANY_LABEL if p.get("label") is None else labels[p["label"]]
    conds = [(props[prop], OPS[op], wildcard if val == "*" else int(val))
             for prop, op, val in p.get("conds", [])]
    return label, conds


def templates(cfg):
    """The configuration's registered one-hop templates, in order."""
    out = []
    for t in cfg["templates"]:
        el = cfg["schema"]["edge_labels"]
        out.append(Template(
            t["name"], DIRS[t["direction"]],
            _pred_args(cfg, t["root"], WILDCARD),
            _pred_args(cfg, t["edge"], WILDCARD, edge=True),
            _pred_args(cfg, t["leaf"], WILDCARD),
            edge_label=el[t["edge_label"]] if t.get("edge_label") else ANY_LABEL,
        ))
    return out


def query_plan(cfg, plan):
    """A traffic plan as the engine's ``QueryPlan`` (before any rewrite)."""
    sch = cfg["schema"]
    names = [t["name"] for t in cfg["templates"]]
    tpls = dict(zip(names, cfg["templates"]))
    hops = []
    for h in plan["hops"]:
        shape = tpls[h["template"]] if h.get("template") else h
        bound = h.get("params", {})
        params = np.full(PARAM_LEN, int(PROP_MISSING), np.int32)
        for base, part in ((0, "edge"), (MAX_CONDS, "leaf")):
            for i, (prop, _, val) in enumerate(shape[part].get("conds", [])):
                if val == "*" and prop in bound:
                    params[base + i] = int(bound[prop])
        el = shape.get("edge_label")
        hops.append(Hop(
            DIRS[shape["direction"]],
            sch["edge_labels"][el] if el else ANY_LABEL,
            make_pred(*_pred_args(cfg, shape["root"], WILDCARD)),
            make_pred(*_pred_args(cfg, shape["edge"], WILDCARD, edge=True)),
            make_pred(*_pred_args(cfg, shape["leaf"], WILDCARD)),
            names.index(h["template"]) if h.get("template") else -1,
            params,
        ))
    post = plan.get("post_filter")
    if post and post[0] == "prop_neq_root":
        post = ("prop_neq_root", sch["vertex_props"][post[1]])
    elif post:
        post = tuple(post)
    final_prop = plan.get("final_prop")
    return QueryPlan(
        tuple(hops), FINALS[plan.get("final", "ids")],
        -1 if final_prop is None else sch["vertex_props"][final_prop],
        post, int(plan.get("extra_phases", 0)),
    )


@dataclass
class System:
    rt: object  # ShardedTxnRuntime
    espec: object
    ttable: object
    plans: list  # QueryPlan per traffic plan, as served
    tpl_meta: dict  # template index -> (direction, edge label)
    final_ids_from_values: list  # per plan: a values clause served as ids
    tpl_root_label: list  # per template: its root label's name


def engine_spec(cfg):
    store = StoreSpec(
        v_cap=int(cfg["v_total"]),
        e_cap=int(cfg["edge_capacity"]) + int(cfg["recent_cap"]),
        n_vprops=int(cfg["n_vprops"]), n_eprops=int(cfg["n_eprops"]),
        recent_cap=int(cfg["recent_cap"]),
    )
    cache = CacheSpec(
        capacity=int(cfg["cache_slots_total"]), probes=int(cfg["cache_probes"]),
        max_leaves=int(cfg["max_leaves"]), max_chunks=1,
    )
    return EngineSpec(store=store, cache=cache, max_deg=int(cfg["max_deg"]),
                      frontier=int(cfg["frontier"]))


def block_capacity(cfg, chips: int, host) -> int:
    """Per-shard edge-block capacity from the configuration's edge
    capacity, so the programs' shapes do not depend on the seed; raises
    when the generated graph does not fit it."""
    cap = int(np.ceil(int(cfg["edge_capacity"]) / chips / BLOCK_FILL))
    if needed_capacity(host, chips) > cap:
        raise SystemExit(f"the graph needs edge blocks of "
                         f"{needed_capacity(host, chips)}, "
                         f"the configuration's edge capacity gives {cap}")
    return cap


def build(cfg, traffic, espec, chips: int, e_blk_cap: int) -> System:
    tpls = templates(cfg)
    ttable = make_template_table(tpls)
    qp = GraphQP("qp0")
    sc = ServiceCoordinator([qp])
    for i in range(len(tpls)):
        sc.register(i)
        sc.enable(i)
    ttable = qp.ttable_masks(ttable, len(tpls))
    unique = frozenset(cfg["schema"]["vertex_props"][p]
                       for p in cfg.get("unique_props", []))
    plans, as_ids = [], []
    for p in traffic["plans"]:
        qplan = query_plan(cfg, p)
        if cfg["rewrite"]:
            qplan = rewrite_plan(qplan, unique)
        plans.append(qplan)
        as_ids.append(p.get("final") == "values" and qplan.final == FINALS["ids"])
    rt = ShardedTxnRuntime(espec, flat_mesh(chips),
                           use_cache=bool(cfg["use_cache"]), e_blk_cap=e_blk_cap)
    meta = {i: (t.direction, t.edge_label) for i, t in enumerate(tpls)}
    roots = [t["root"].get("label") for t in cfg["templates"]]
    return System(rt, espec, ttable, plans, meta, as_ids, roots)
