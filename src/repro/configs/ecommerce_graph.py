"""ecommerce-graph: the paper's own architecture — production-scale
transactional graph serving with the one-hop sub-query result cache.

~1.1B vertices / ~8.6B edges (the paper's deployment is "tens of billions
of vertices and edges"), vertex-partitioned over the full mesh with the
cache co-partitioned; peak 8k concurrent one-hop gR-Txs per step."""

import dataclasses

from repro.distributed.graph_serve import GraphServeConfig

FAMILY = "graph"

FULL = GraphServeConfig(
    name="ecommerce-graph",
    v_total=2**30,
    e_per_vertex=8,
    max_deg=64,
    max_leaves=64,
    cache_slots_total=2**26,
)

# One TPU v5e chip's share of FULL (16 GB of HBM). Only the scale is cut:
# widths, degree, template and cache value width are FULL's. The store plus
# cache take ~5.6 GB of the chip, so a gRW commit's input and output
# stores (the commit step does not donate them) fit together.
CHIP = dataclasses.replace(
    FULL, name="ecommerce-graph-chip", v_total=2**23, cache_slots_total=2**22,
)
CHIP_REDUCED = (
    "v_total 2^30 -> 2^23 per chip (67M edges): a commit's input + output "
    "store must fit one v5e's 16 GB",
    "cache_slots_total 2^26 -> 2^22 per chip (1.2 GB): one slot per 2 "
    "vertices, where FULL has one per 16",
)

SMOKE = GraphServeConfig(
    name="ecommerce-graph-smoke",
    v_total=256,
    e_per_vertex=4,
    max_deg=8,
    max_leaves=8,
    cache_slots_total=256,
)

SHAPES = {
    "serve_peak": dict(kind="graph_serve", batch=8192, use_cache=True),
    "serve_low": dict(kind="graph_serve", batch=1024, use_cache=True),
    "serve_nocache": dict(kind="graph_serve", batch=8192, use_cache=False),
}
SKIPS = {}
