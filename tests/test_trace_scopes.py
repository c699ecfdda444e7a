"""The program's tracing: stage names on the device, and the CP queue's
counters and wait.

- Each device program names its stages with ``jax.named_scope``; the
  names are pinned here in the lowered text of the runtime's gR step,
  gRW commit and CP step, so a refactor cannot drop one unnoticed (the
  benchmark's ``bench/trace_scopes.py`` reads device time by them).
- ``MissQueue`` counts what enters and leaves it, releases the key of a
  record a full queue drops, and ``CachePopulator.drain`` records each
  committed insert's wait from push to commit as ``cp_wait``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cache import empty_cache
from repro.core.engine import MissRecord
from repro.core.keys import PARAM_LEN
from repro.core.population import CachePopulator, MissQueue
from repro.distributed.graph_serve import (
    config_cell,
    config_grw_cell,
    config_plan_and_ttable,
)
from repro.distributed.routing import identity_table
from repro.distributed.sharding import flat_mesh
from repro.graphstore.partition import abstract_partitioned_store
from repro.launch.serve import deployment_config
from repro.obs.trace import Tracer

VERTICES = 2**12
GR_SCOPES = ("hop0/route", "hop0/probe", "hop0/miss_exec", "hop0/unroute",
             "hop0/merge", "finalize", "reduce_metrics")
GRW_SCOPES = ("grw/apply", "grw/derive", "grw/invalidate", "grw/signals")
CP_SCOPES = ("cp/exec", "cp/occ", "cp/insert")


def _lowered(step, shardings, args) -> str:
    return jax.jit(step, in_shardings=shardings).lower(*args).as_text(
        debug_info=True)


def _gr_text():
    step, shardings, args, _ = config_cell(
        deployment_config(1, VERTICES), flat_mesh(1), global_batch=8)
    return _lowered(step, shardings, args)


def _grw_text():
    step, shardings, args, _ = config_grw_cell(
        deployment_config(1, VERTICES), flat_mesh(1))
    return _lowered(step, shardings, args)


def _cp_text():
    cfg = deployment_config(1, VERTICES)
    _, _, _, rt = config_cell(cfg, flat_mesh(1), global_batch=8)
    _, ttable = config_plan_and_ttable(cfg)
    hop = config_plan_and_ttable(cfg)[0].hops[0]
    fn = rt._pop_compiled({0: (hop.direction, hop.edge_label)}, 0, 8)
    sds = jax.ShapeDtypeStruct
    pstore = abstract_partitioned_store(rt.pspec)
    cache = jax.eval_shape(lambda: empty_cache(rt.espec.cache))
    rtab = jax.eval_shape(lambda: identity_table(rt.n))
    return fn.lower(
        pstore, pstore, cache, ttable, sds((8,), jnp.int32),
        sds((8, PARAM_LEN), jnp.int32), sds((8,), jnp.bool_),
        sds((8,), jnp.int32), rtab,
    ).as_text(debug_info=True)


@pytest.mark.parametrize("program,lower,scopes", [
    ("gr", _gr_text, GR_SCOPES),
    ("grw", _grw_text, GRW_SCOPES),
    ("cp", _cp_text, CP_SCOPES),
])
def test_programs_name_their_stages(program, lower, scopes):
    text = lower()
    missing = [s for s in scopes if f"/{s}/" not in text]
    assert not missing, f"{program} program lost its scopes {missing}"


def _rec(root, tpl=0):
    return MissRecord(tpl, root, np.zeros(PARAM_LEN, np.int32), 0)


def test_miss_queue_counts_and_releases_dropped_keys():
    q = MissQueue(max_retries=2, maxlen=2)
    q.push([_rec(1), _rec(2), _rec(1)])  # the second 1 is in flight
    assert (q.pushed, q.deduped, len(q)) == (2, 1, 2)
    q.push([_rec(3)])  # full: the oldest record (1) is dropped
    assert q.dropped == 1 and len(q) == 2 and q.max_depth == 2
    assert [item[0].root for item in q.q] == [2, 3]
    q.push([_rec(1)])  # its key was released, so it is accepted again
    assert q.pushed == 4 and q.deduped == 1 and q.dropped == 2
    (rec, attempts, pushed_at), = q.drain(1)
    assert rec.root == 3 and attempts == 0
    q.requeue(rec, attempts, pushed_at)  # first abort: back in the queue
    assert q.retried == 1 and q.q[-1] == (rec, 1, pushed_at)
    q.drain(5)
    q.requeue(rec, 1, pushed_at)  # out of retries: discarded, key released
    assert q.discarded == 1 and len(q) == 0
    q.push([_rec(3)])
    assert len(q) == 1
    assert q.counts() == {"pushed": 5, "deduped": 1, "dropped": 2,
                          "retried": 1, "discarded": 1, "max_depth": 2}


def test_populator_records_each_committed_wait():
    """A stand-in CP step (root 3 conflicts once, odd roots are not
    cacheable) drives the real drain loop: every committed insert, and
    only those, records its wait from its first push."""
    aborted_once = set()

    def step_builder(tpl_idx, bucket):
        def step(store_exec, store_commit, cache, ttable, roots, params,
                 mask, read_versions):
            r = np.asarray(roots)
            m = np.asarray(mask)
            conflict = m & (r == 3) & np.array([x not in aborted_once for x in r])
            aborted_once.update(int(x) for x in r[conflict])
            ok = m & ~conflict & (r % 2 == 0)
            return cache, ok, conflict
        return step

    pop = CachePopulator(None, {0: (0, -1)}, step_builder=step_builder)
    tr = Tracer()
    pop.queue.push([_rec(r) for r in (2, 3, 4, 5)])
    pop.drain(None, None, "cache", None, k=8, tracer=tr)
    assert (pop.committed, pop.aborted, len(pop.queue)) == (2, 1, 1)
    pop.drain(None, None, "cache", None, k=8, tracer=tr)
    snap = tr.snapshot()
    assert pop.committed == 2 and pop.queue.retried == 1 and len(pop.queue) == 0
    assert snap["cp_wait"]["count"] == 2  # roots 2 and 4; 3 and 5 are odd
    assert snap["cp_wait"]["total_s"] > 0
    for span in ("cp_pack", "cp_dispatch", "cp_sync", "cp_settle"):
        assert snap[span]["count"] >= 2, span
