"""Asynchronous, transactional cache population (§4's CP threads).

A cache miss enqueues ``(template, root, params, read_version)``. A drain
step re-executes the one-hop sub-query at the *current* committed version
(CP transactions take their own read version), then commits the insert with
an optimistic conflict check: if any vertex the result depends on (root +
produced leaves) was written after the CP read version, the insert aborts —
exactly how FDB's OCC prevents a CP transaction from installing a stale
entry over a concurrent gRW-Tx. Aborted entries are retried a bounded
number of times and then discarded (§4).

Keeping population here — and never on the gR-Tx path — preserves the
paper's separation of read and write paths.
"""

from __future__ import annotations

import time
from collections import deque
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import runtime
from repro.core.cache import CacheSpec, CacheState, cache_insert
from repro.core.engine import EngineSpec, MissRecord
from repro.core.keys import PARAM_LEN
from repro.core.runtime import onehop_exec_view
from repro.core.templates import TemplateTable, PredSpec
from repro.graphstore.store import GlobalStoreView, GraphStore
from repro.graphstore.txn import conflicts
from repro.obs.trace import NULL_TRACER
from repro.utils import take_along0


class MissQueue:
    """Host-side FIFO of cache misses with retry accounting.

    Each record carries its push time (``perf_counter``), kept across
    retries, so the populator can report how long a miss waited before
    its entry went in. Counts: ``pushed`` (records accepted), ``deduped``
    (refused: the same key is already in flight), ``dropped`` (the oldest
    record evicted by a full queue), ``retried`` and ``discarded`` (OCC
    aborts requeued and given up), and ``max_depth``, the deepest the
    queue has been. A dropped or discarded record releases its key, so
    the same miss can be pushed and populated again.
    """

    def __init__(self, max_retries: int = 3, maxlen: int = 100_000):
        self.q: deque = deque()
        self.maxlen = maxlen
        self.max_retries = max_retries
        self.pushed = 0
        self.deduped = 0
        self.dropped = 0
        self.retried = 0
        self.discarded = 0
        self.max_depth = 0
        self._seen_inflight: set = set()

    def push(self, records):
        for r in records:
            key = _key(r)
            if key in self._seen_inflight:
                self.deduped += 1  # identical miss already in flight
                continue
            self._seen_inflight.add(key)
            self.pushed += 1
            self._append((r, 0, time.perf_counter()))

    def _append(self, item):
        if len(self.q) >= self.maxlen:
            old = self.q.popleft()[0]
            self.dropped += 1
            self._release(old)
        self.q.append(item)
        self.max_depth = max(self.max_depth, len(self.q))

    def drain(self, k: int):
        """Pop up to ``k`` ``(record, attempts, pushed_at)`` items."""
        out = []
        while self.q and len(out) < k:
            out.append(self.q.popleft())
        return out

    def requeue(self, rec, attempts, pushed_at):
        if attempts + 1 >= self.max_retries:
            self.discarded += 1
            self._release(rec)
        else:
            self.retried += 1
            self._append((rec, attempts + 1, pushed_at))

    def done(self, rec):
        self._release(rec)

    def _release(self, rec):
        self._seen_inflight.discard(_key(rec))

    def counts(self) -> dict:
        return {"pushed": self.pushed, "deduped": self.deduped,
                "dropped": self.dropped, "retried": self.retried,
                "discarded": self.discarded, "max_depth": self.max_depth}

    def __len__(self):
        return len(self.q)


def _key(rec):
    return (rec.tpl_idx, rec.root, tuple(np.asarray(rec.params).tolist()))


def _tpl_row(stacked: PredSpec, t: int) -> PredSpec:
    return PredSpec(*(getattr(stacked, f)[t] for f in PredSpec._fields))


def populate_step(
    espec: EngineSpec,
    store_exec: GraphStore,
    store_commit: GraphStore,
    cache: CacheState,
    ttable: TemplateTable,
    tpl_idx: int,
    direction: int,
    edge_label: int,
    roots,
    params,
    mask,
    read_versions,
    exec_view=None,
    commit_mask=None,
    allreduce=None,
):
    """One CP transaction batch for one template (jit this with static
    espec/tpl_idx/direction/edge_label via functools.partial).

    Executes against ``store_exec`` (the CP read snapshot) and commits
    against ``store_commit`` (current state at commit time): entries whose
    read set was written in between abort. Returns (cache', committed[B],
    aborted[B]).

    ``exec_view`` overrides the miss-execution storage view (the
    partitioned tier passes a ``BlockStoreView`` over owner-local blocks);
    ``store_exec``/``store_commit`` then only supply ``.version`` /
    ``.vversion`` (a ``PartitionedGraphStore`` satisfies both).

    ``commit_mask`` + ``allreduce`` split the transaction across shards
    (the routing-table tier): ``mask`` selects the rows this shard
    *executes* (its storage owns them) and ``commit_mask`` the rows whose
    cache entry it *inserts* (its cache block owns them). The computed
    bundle — leaves, counts, the post-OCC commit verdict — crosses shards
    through ``allreduce`` (a psum inside shard_map): exactly one shard
    executes each row, every other shard contributes zeros, so the sum
    reconstructs the bundle at the inserting shard. With both defaulted
    (single host, or exec == commit shard) nothing is reduced and the
    path is byte-identical to the fused transaction.

    The stages run under the named scopes ``cp/exec`` (the miss
    re-execution), ``cp/occ`` (the conflict check) and ``cp/insert``, so a
    profiler trace gives each its device time.
    """
    pr = _tpl_row(ttable.pr, tpl_idx)
    pe = _tpl_row(ttable.pe, tpl_idx)
    pl = _tpl_row(ttable.pl, tpl_idx)
    view = exec_view if exec_view is not None else GlobalStoreView(
        espec.store, store_exec
    )
    with jax.named_scope("cp/exec"):
        leaves, lmask, n_true, trunc, stats = onehop_exec_view(
            espec, view, direction, edge_label, pr, pe, pl, roots, params, mask
        )
        cacheable = mask & ~trunc & (n_true <= espec.result_width)
    cp_read_version = store_exec.version

    with jax.named_scope("cp/occ"):
        # OCC conflict check per entry: the root plus every vertex the
        # execution observed (scanned neighbors, not just qualifying leaves
        # — a write to a filtered-out neighbor can change the result too)
        read_set = jnp.concatenate([roots[:, None], stats["scanned"]], axis=1)
        read_mask = jnp.concatenate(
            [mask[:, None], stats["scanned_mask"]], axis=1
        )
        conflict = conflicts(
            espec.store, store_commit, cp_read_version, read_set, read_mask,
            axis=1,
        )
        # the write itself must also be enabled for this template
        # (lifecycle) — reads may only be served for enabled templates, but
        # populating while installed-for-writes is safe and matches §4.1
        # Phase 2.
        ok = cacheable & ~conflict & ttable.read_enabled[tpl_idx]

    with jax.named_scope("cp/insert"):
        insert_ok = ok
        if commit_mask is not None:
            assert allreduce is not None, "the CP split needs a reducer"
            # ship the executed bundle to the inserting shard: one owner
            # per row contributes, everyone else adds zeros
            leaves = allreduce(jnp.where(ok[:, None], leaves, 0))
            n_true = allreduce(jnp.where(ok, n_true, 0))
            ok_g = allreduce(ok.astype(jnp.int32)) > 0
            insert_ok = ok_g & commit_mask

        cache = cache_insert(
            espec.cache,
            cache,
            jnp.full(roots.shape, tpl_idx, jnp.int32),
            roots,
            params,
            leaves,
            n_true,
            jnp.full(roots.shape, cp_read_version, jnp.int32),
            insert_ok,
        )
    return cache, ok, cacheable & conflict


class CachePopulator:
    """Host orchestrator: drains a MissQueue and runs CP transactions.

    ``templates_meta[t] = (direction, edge_label)`` — static per template.
    ``step_builder(tpl_idx, bucket)`` optionally supplies the jitted CP step
    (same signature as ``populate_step`` minus the static args); the sharded
    runtime uses this to run population inside ``shard_map`` against the
    co-partitioned cache shards while reusing this orchestrator unchanged.
    """

    _BUCKETS = runtime.BUCKETS[:4]

    def __init__(self, espec: EngineSpec, templates_meta, max_retries: int = 3,
                 step_builder=None):
        self.espec = espec
        self.meta = templates_meta
        self.queue = MissQueue(max_retries=max_retries)
        self._jitted = {}
        self._step_builder = step_builder
        self.committed = 0
        self.aborted = 0

    def _fn(self, tpl_idx: int, bucket: int):
        key = (tpl_idx, bucket)
        if key not in self._jitted:
            if self._step_builder is not None:
                self._jitted[key] = self._step_builder(tpl_idx, bucket)
            else:
                espec = self.espec
                direction, edge_label = self.meta[tpl_idx]
                import functools

                self._jitted[key] = jax.jit(
                    functools.partial(
                        populate_step, espec, tpl_idx=tpl_idx, direction=direction,
                        edge_label=edge_label,
                    )
                )
        return self._jitted[key]

    def drain(self, store_exec, store_commit, cache, ttable, k: int = 128,
              tracer=NULL_TRACER):
        """Process up to k queued misses. Returns the new cache.

        CP batches are packed with vectorized numpy slicing (no per-row
        Python re-packing). Batches need no dedup pass: ``MissQueue.push``
        holds each in-flight (tpl, root, params) key exactly once until it
        is done or discarded, and duplicate keys *within* one jitted insert
        are resolved last-writer-wins by the vectorized ``cache_insert``.

        ``tracer`` times the host phases (``cp_pack``: queue pop, packing
        and the host-to-device copies; ``cp_dispatch``; ``cp_sync``: the
        blocking read of the verdicts; ``cp_settle``: requeue and release)
        and records each committed insert's ``cp_wait``, from the miss's
        push to the commit's verdict.
        """
        with tracer.span("cp_pack"):
            chunks = list(self._chunks(self.queue.drain(k)))
        for t, bucket, chunk, args in chunks:
            with tracer.span("cp_dispatch"):
                cache, ok, conflicted = self._fn(t, bucket)(
                    store_exec=store_exec,
                    store_commit=store_commit,
                    cache=cache,
                    ttable=ttable,
                    **args,
                )
            with tracer.span("cp_sync"):
                ok = np.asarray(ok)
                conflicted = np.asarray(conflicted)
            with tracer.span("cp_settle"):
                now = time.perf_counter()
                for j, (rec, attempts, pushed_at) in enumerate(chunk):
                    if conflicted[j]:
                        self.aborted += 1
                        self.queue.requeue(rec, attempts, pushed_at)
                    else:
                        if ok[j]:
                            self.committed += 1
                            tracer.record("cp_wait", now - pushed_at)
                        self.queue.done(rec)
        return cache

    def _chunks(self, batch):
        """Group popped queue items by template and pack each bucket-sized
        chunk: yields ``(tpl_idx, bucket, items, device arguments)``."""
        by_tpl: dict = {}
        for item in batch:
            by_tpl.setdefault(item[0].tpl_idx, []).append(item)
        for t, items in by_tpl.items():
            n = len(items)
            roots_all = np.fromiter((rec.root for rec, _, _ in items), np.int32, n)
            params_all = np.stack(
                [np.asarray(rec.params, np.int32) for rec, _, _ in items]
            ).reshape(n, PARAM_LEN)
            vers_all = np.fromiter(
                (rec.read_version for rec, _, _ in items), np.int32, n
            )
            bucket = runtime.bucket_for(n, self._BUCKETS, clamp=True)
            for lo in range(0, n, bucket):
                chunk = items[lo : lo + bucket]
                nb = len(chunk)
                roots = np.zeros(bucket, np.int32)
                params = np.zeros((bucket, PARAM_LEN), np.int32)
                vers = np.zeros(bucket, np.int32)
                m = np.zeros(bucket, bool)
                roots[:nb] = roots_all[lo : lo + nb]
                params[:nb] = params_all[lo : lo + nb]
                vers[:nb] = vers_all[lo : lo + nb]
                m[:nb] = True
                yield t, bucket, chunk, dict(
                    roots=jnp.asarray(roots),
                    params=jnp.asarray(params),
                    mask=jnp.asarray(m),
                    read_versions=jnp.asarray(vers),
                )
