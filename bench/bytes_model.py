"""The least bytes a gR-Tx's one-hop work must read, and the table of
chip peaks it is compared with.

Counted from the requests served and the graph as the reference holds it,
so the count is the same whatever the program implements. Per hop root:

- a hop the configuration caches (a registered template, cache on) reads
  at least one cache entry: its key (template, root, parameter
  fingerprint: 12 B) and its value (4 B per leaf);
- any other hop reads the root's CSR window (8 B), the root's label and
  properties (4 B each), and per edge of the root in that direction the
  opposite endpoint, the label, the alive flag and the edge properties
  (4 + 4 + 1 + 4 B per property), and per leaf its label and properties;
- a values clause reads 4 B per answer leaf, a ``prop_neq_root`` filter
  4 B per frontier leaf and 4 B for the root.

Degrees are read at the end of the run, after every commit.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, required: bool = True) -> dict | None:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        if required:
            raise SystemExit(f"no peaks for device kind {device_kind!r} in {PEAKS}")
        return None
    return table[device_kind]


def gr_bytes(cell, chk, sched) -> int:
    """Bytes over every read the check compared: every read served for the
    window."""
    cfg, plans = cell.config, cell.traffic["plans"]
    ref = chk["ref"]
    nvp, nep = int(cfg["n_vprops"]), int(cfg["n_eprops"])
    vbytes = 4 + 4 * nvp
    ebytes = 4 + 4 + 1 + 4 * nep
    total = 0
    for i, trail in chk["trails"].items():
        plan = plans[int(sched.plan[i])]
        for hop, (roots, leaves) in zip(plan["hops"], trail):
            cached = bool(cfg["use_cache"]) and bool(hop.get("template"))
            d = (cfg["templates"][[t["name"] for t in cfg["templates"]]
                                  .index(hop["template"])]["direction"]
                 if hop.get("template") else hop["direction"])
            for v, n in zip(roots, leaves):
                if cached:
                    total += 12 + 4 * n
                else:
                    deg = sum(ref.degree(int(v), x) for x in
                              (("out", "in") if d == "both" else (d,)))
                    total += 8 + vbytes + deg * ebytes + n * vbytes
        n_final = len(trail[-1][0])  # the final frontier
        if plan.get("final") == "values" and not cfg["rewrite"]:
            total += 4 * n_final
        if plan.get("post_filter") and not cfg["rewrite"]:
            total += 4 * n_final + 4
    return total
