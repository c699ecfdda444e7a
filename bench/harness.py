"""One benchmark run: set-up, the measured window, the check against the
reference, and the result line.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file found by its name in ``BENCHMARK.json``:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/cells/<cell>.json`` (the cell's fixed offered rate) and
``bench/metrics/<metric>.py`` (a reader with ``read(run) -> float | None``).

The served path is a single-threaded open loop over a precomputed
schedule. Each iteration commits every due gRW-Tx that fits one mutation
batch (a group commit, acknowledged when ``run_grw_tx`` returns), serves
each plan's due gR-Txs in one ``run_gr_tx_batch`` call of at most the
traffic's ``batch_cap`` rows, and with the cache on pushes the misses into a
``ShardedMissDrain`` and drains a fixed number. A request's latency runs
from its due time to the return of the call that served it.
"""

from __future__ import annotations

import importlib.util
import inspect
import json
import shutil
import tempfile
import time
from collections import Counter, deque
from dataclasses import dataclass, field
from pathlib import Path

import jax
import numpy as np
from repro.core.runtime import BUCKETS, MissRecord, bucket_for
from repro.distributed.graph_serve import ShardedMissDrain
from repro.graphstore import make_mutation_batch
from repro.graphstore.store import ingest_host
from repro.launch.compile_cache import enable_compile_cache
from repro.obs.trace import NULL_TRACER, Tracer

from bench import bytes_model, graphgen, system, trace_reduce
from bench.graphgen import READ, WRITE
from bench.reference import Reference, hop_specs

ROOT = Path(__file__).resolve().parents[1]
GRACE_S = 60.0  # how long past the window a due request may still be served


# ---------------------------------------------------------------- loading
@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    rate: float
    end_to_end: list
    per_layer: list
    root: Path = ROOT


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bm = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bm["workloads"]}
    if name not in work:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    cell = json.loads((root / "bench" / "cells" / f"{name}.json").read_text())
    if int(traffic.get("chips", 1)) != int(w["chips"]):
        raise SystemExit(f"{name}: traffic is for {traffic.get('chips')} "
                         f"chips, the cell asks for {w['chips']}")
    return Cell(name, int(w["chips"]), config, traffic, float(cell["rate_per_s"]),
                [m for m in bm["end_to_end"] if _applies(m, name)],
                [m for m in bm["per_layer"] if _applies(m, name)], root)


def load_reader(root: Path, metric: str):
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------- counting
class CompileClock:
    """Counts and sums JAX's own compile events: jaxpr traces, lowerings
    and backend compiles (a persistent-cache load replaces the last), and
    persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0  # traces + lowerings + backend compiles
        self.compiles = 0  # backend compiles alone
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.events += 1
            self.compiles += event.endswith("backend_compile_duration")

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def percentile(x, q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


# ---------------------------------------------------------------- serving
@dataclass
class Served:
    """What the loop recorded, per request index of the schedule."""

    done: np.ndarray  # seconds from the window's start, nan if never
    seen: np.ndarray  # reads: commits applied before dispatch
    answers: dict = field(default_factory=dict)  # read index -> row
    commits: list = field(default_factory=list)  # write indices per commit
    gr_sums: Counter = field(default_factory=Counter)
    gr_steps: int = 0
    gr_requests: int = 0
    bad_rows: set = field(default_factory=set)  # flagged by the program
    commit_metrics: Counter = field(default_factory=Counter)
    commit_steps: int = 0
    overflowed_writes: set = field(default_factory=set)
    spans: dict = field(default_factory=dict)  # benchmark span -> [n, s]
    calls: list = field(default_factory=list)  # (seconds, span, start)
    iter_ends: list = field(default_factory=list)  # from the window's start


class Loop:
    """The served path: the program's runtime and state, driven by the
    schedule."""

    def __init__(self, sysm, pstore, cache, sched, traffic, graph):
        self.sys = sysm
        self.rt = sysm.rt
        self.store = pstore
        self.cache = cache
        self.sched = sched
        self.traffic = traffic
        self.graph = graph
        self.mk_batch = make_mutation_batch
        caps = inspect.signature(make_mutation_batch).parameters["caps"].default
        self.caps = {"new_edges": caps[1], "del_edges": caps[2],
                     "set_vprops": caps[4]}
        # every bucket a batch of up to batch_cap rows pads to
        self.batch_cap = int(traffic["batch_cap"])
        self.buckets = [b for b in BUCKETS if b <= bucket_for(self.batch_cap)]
        self.k_drain = int(traffic["drain_per_iter"])
        self.drain = None
        if sysm.rt.use_cache:
            self.drain = ShardedMissDrain(sysm.rt, sysm.tpl_meta)
        n = len(sched)
        self.out = Served(np.full(n, np.nan), np.full(n, -1, np.int64))
        self.recording = False
        self.t0 = 0.0

    # ----------------------------------------------------------- pieces
    def _span(self, name: str, seconds: float, start: float):
        if self.recording:
            c = self.out.spans.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += seconds
            self.out.calls.append((seconds, name, start - self.t0))

    def group(self, pending: deque) -> list:
        """The due writes, in order, that fit one mutation batch with no
        two setting the same vertex property."""
        taken, used, keys = [], Counter(), set()
        while pending:
            ch = self.sched.writes[self.sched.write[pending[0]]]
            need = Counter({k: len(ch.get(k, ())) for k in self.caps})
            props = {(v, p) for v, p, _ in ch.get("set_vprops", ())}
            if any(used[k] + need[k] > self.caps[k] for k in self.caps) or (
                    props & keys):
                break
            used.update(need)
            keys |= props
            taken.append(pending.popleft())
        return taken

    def commit(self, idxs: list) -> None:
        chs = [self.sched.writes[self.sched.write[i]] for i in idxs]
        mb = self.mk_batch(
            self.sys.espec.store,
            new_edges=[e for c in chs for e in c.get("new_edges", ())],
            del_edges=[e for c in chs for e in c.get("del_edges", ())],
            set_vprops=[s for c in chs for s in c.get("set_vprops", ())],
        )
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("grw_commit"):
            self.store, self.cache, m = self.rt.run_grw_tx(
                self.store, self.cache, self.sys.ttable, mb)
        done = time.perf_counter()
        self._span("grw_commit", done - t, t)
        self.out.commits.append(list(idxs))
        if m["op_overflow"] or m["store_append_overflow"]:
            self.out.overflowed_writes.update(idxs)
        if self.recording:
            self.out.commit_steps += 1
            self.out.commit_metrics.update(
                {k: int(v) for k, v in m.items() if isinstance(v, int)})
        self.out.done[idxs] = done - self.t0

    def serve(self, p: int, idxs: list, record: bool = True):
        roots = self.sched.root[idxs]
        t = time.perf_counter()
        name = self.sched.plan_names[p]
        with jax.profiler.TraceAnnotation(f"gr_step.{name}"):
            res, misses, m = self.rt.run_gr_tx_batch(
                self.store, self.cache, self.sys.ttable, self.sys.plans[p], roots)
        done = time.perf_counter()
        self._span(f"gr_step.{name}", done - t, t)
        if record:
            o = self.out
            o.done[idxs] = done - self.t0
            o.seen[idxs] = len(o.commits)
            for i, row in zip(idxs, res):
                o.answers[int(i)] = row.copy()
            if m.get("route_overflow") or m.get("deferred") or m.get("truncated"):
                o.bad_rows.update(int(i) for i in idxs)
            if self.recording:
                o.gr_steps += 1
                o.gr_requests += len(idxs)
                o.gr_sums.update({k: int(v) for k, v in m.items()})
        return misses

    def drain_step(self, misses: list) -> None:
        if self.drain is None:
            return
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("cp_drain"):
            self.drain.push(misses)
            self.cache = self.drain.drain(self.store, self.store, self.cache,
                                          self.sys.ttable, self.k_drain)
            jax.block_until_ready(self.cache)
        self._span("cp_drain", time.perf_counter() - t, t)

    def iteration(self, pend_w: deque, pend_r: list) -> None:
        with jax.profiler.TraceAnnotation("batching"):
            group = self.group(pend_w) if pend_w else []
        if group:
            self.commit(group)
        misses = []
        for p, q in enumerate(pend_r):
            if q:
                with jax.profiler.TraceAnnotation("batching"):
                    take = [q.popleft() for _ in range(min(len(q), self.batch_cap))]
                misses += self.serve(p, take)
        self.drain_step(misses)

    # ---------------------------------------------------------- phases
    def warm_programs(self, rng) -> None:
        """Compile every program the window uses: each plan at each
        bucket, each CP step bucket of each cached template, the commit."""
        s = self.sched
        for p in range(len(self.sys.plans)):
            for b in self.buckets:
                self.serve(p, np.resize(np.flatnonzero(s.plan == p), b),
                           record=False)
        self.commit([])
        if self.drain is None:
            return
        top = bucket_for(self.k_drain, BUCKETS[:4], clamp=True)
        keys = {(h.tpl_idx, tuple(int(x) for x in h.params))
                for plan in self.sys.plans for h in plan.hops if h.tpl_idx >= 0}
        version = int(self.store.version)
        for tpl, params in sorted(keys):
            label = self.sys.tpl_root_label[tpl]
            lo, hi = self.graph.ranges[label]
            for b in (x for x in BUCKETS[:4] if x <= top):
                roots = rng.choice(np.arange(lo, hi), b, replace=False)
                self.drain.push([MissRecord(tpl, int(r), np.asarray(params, np.int32),
                                            version) for r in roots])
                self.cache = self.drain.drain(self.store, self.store, self.cache,
                                              self.sys.ttable, b)

    def warm_traffic(self) -> None:
        """The warm-up segment, untimed, in chunks of ``warmup_chunk``; the
        CP queue is drained empty after each chunk."""
        s, chunk = self.sched, int(self.traffic["warmup_chunk"])
        for lo in range(0, s.n_warmup, chunk):
            idx = range(lo, min(lo + chunk, s.n_warmup))
            pend_w = deque(i for i in idx if s.kind[i] == WRITE)
            pend_r = [deque() for _ in self.sys.plans]
            for i in idx:
                if s.kind[i] == READ:
                    pend_r[s.plan[i]].append(i)
            while pend_w or any(pend_r):
                self.iteration(pend_w, pend_r)
            while self.drain is not None and self.drain.pending():
                self.drain_step([])

    def window(self, seconds: float) -> float:
        """Serve the window's requests as they fall due; returns the time
        (from the window's start) at which the first iteration that ended
        after the window ended."""
        s = self.sched
        n, i = len(s), s.n_warmup
        pend_w, pend_r = deque(), [deque() for _ in self.sys.plans]
        cut = None
        self.recording = True
        self.t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - self.t0
            while i < n and s.due[i] <= now:
                (pend_w if s.kind[i] == WRITE else pend_r[s.plan[i]]).append(i)
                i += 1
            if not pend_w and not any(pend_r):
                if i >= n:
                    break
                with jax.profiler.TraceAnnotation("waiting"):
                    time.sleep(max(0.0, min(s.due[i] - now, 0.01)))
                continue
            if now > seconds + GRACE_S:
                break
            self.iteration(pend_w, pend_r)
            end = time.perf_counter() - self.t0
            self.out.iter_ends.append(end)
            if cut is None and end >= seconds:
                cut = end
        self.recording = False
        return seconds if cut is None else cut


# ------------------------------------------------------------------ check
def answer(plan: dict, row, as_ids: bool, cfg) -> object:
    """A served row as the reference states answers, or ``None`` where the
    row repeats an entry."""
    final = plan.get("final", "ids")
    if final == "count":
        return int(row)
    row = np.asarray(row)
    got = row[row != -1].astype(np.int64)
    if len(np.unique(got)) != len(got):
        return None
    if final == "values" and as_ids:
        got = got + int(cfg["listing_id_base"])  # the stated bijection
    return tuple(sorted(int(x) for x in got))


def check(cell: Cell, graph, sched, out: Served, sysm, final_vprops, readback,
          plans_hops) -> dict:
    """Compare every read served for the window and every acknowledged
    write with the reference. Returns counts and the per-request trails the
    byte model reads."""
    cfg, traffic = cell.config, cell.traffic
    plans = traffic["plans"]
    ref = Reference(cfg, graph)
    win = np.arange(sched.n_warmup, len(sched))
    reads = [int(i) for i in win if sched.kind[i] == READ and int(i) in out.answers]
    reads.sort(key=lambda i: (out.seen[i], i))
    wrong, over_width, trails = 0, 0, {}
    F = int(cfg["frontier"])
    applied = 0
    examples = []
    for i in reads:
        while applied < out.seen[i]:
            for w in out.commits[applied]:
                ref.apply(sched.writes[sched.write[w]])
            applied += 1
        p = int(sched.plan[i])
        want, trail = ref.plan(plans_hops[p], plans[p], int(sched.root[i]))
        trails[i] = trail
        if any(len(fr) > F or max(c, default=0) > F for fr, c in trail):
            over_width += 1
        got = answer(plans[p], out.answers[i], sysm.final_ids_from_values[p], cfg)
        if got != want:
            wrong += 1
            if len(examples) < 3:
                examples.append((i, plans[p]["name"], int(sched.root[i]),
                                 str(got)[:120], str(want)[:120]))
    while applied < len(out.commits):
        for w in out.commits[applied]:
            ref.apply(sched.writes[sched.write[w]])
        applied += 1
    # acknowledged writes read back: every vertex property they set, and
    # the read-back plan's answer at every vertex whose edges they changed
    lost = 0
    acked = [w for c in out.commits for w in c]
    props = {(v, p) for w in acked
             for v, p, _ in sched.writes[sched.write[w]].get("set_vprops", ())}
    for v, p in props:
        lost += int(final_vprops[(v, p)] != ref.vprops[v, p])
    rb_plan = readback["plan"]
    for root, row in readback["rows"].items():
        want, _ = ref.plan(plans_hops[rb_plan], plans[rb_plan], root)
        got = answer(plans[rb_plan], row, sysm.final_ids_from_values[rb_plan], cfg)
        lost += int(got != want)
    return {"checked": len(reads), "wrong": wrong, "over_width": over_width,
            "lost": lost, "props_read_back": len(props),
            "edges_read_back": len(readback["rows"]), "examples": examples,
            "trails": trails, "ref": ref}


# -------------------------------------------------------------------- run
def require_chips(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {len(devs)} {devs[0].platform} "
                         "device(s)")
    if len(devs) < chips:
        raise SystemExit(f"{chips} chips needed, found {len(devs)}")
    return devs


def run(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_tpu: bool = True, log=print, on_window=None) -> dict:
    """One run of ``cell``. Returns the result line's object. ``on_window``
    is called with ``(schedule, served)`` once the window has closed."""
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = require_chips(cell.chips) if require_tpu else jax.devices()
    clock = CompileClock()
    cfg, traffic = cell.config, cell.traffic
    log(f"cell {cell.name} seed {seed} rate {cell.rate}/s window {seconds} s "
        f"device {devs[0].platform} {devs[0].device_kind} x{cell.chips}; "
        f"compile cache {cache_dir}")

    t = time.perf_counter()
    graph = graphgen.make_graph(cfg, seed)
    sched = graphgen.make_schedule(cfg, traffic, graph, seed, cell.rate, seconds)
    t_gen = time.perf_counter() - t
    espec = system.engine_spec(cfg)
    t = time.perf_counter()
    host = ingest_host(espec.store, graph.vlabel, graph.vprops, graph.esrc,
                       graph.edst, graph.elabel, graph.eprops)
    t_ingest = time.perf_counter() - t
    sysm = system.build(cfg, traffic, espec, cell.chips,
                        system.block_capacity(cfg, cell.chips, host))
    t = time.perf_counter()
    pstore = sysm.rt.partition_store(host)
    cache = sysm.rt.empty_cache()
    jax.block_until_ready((pstore, cache))
    del host
    t_part = time.perf_counter() - t
    log(f"graph: {len(graph.vlabel)} vertices, {graph.n_edges} edges "
        f"({graph.includes[1] - graph.includes[0]} includes); schedule "
        f"{sched.n_warmup} warm-up + {len(sched) - sched.n_warmup} window "
        f"requests, {len(sched.writes)} writes; generate {t_gen:.3f} s, ingest "
        f"{t_ingest:.3f} s, partition {t_part:.3f} s")

    loop = Loop(sysm, pstore, cache, sched, traffic, graph)
    del pstore, cache
    t = time.perf_counter()
    c0 = clock.compiles
    loop.warm_programs(graphgen.rng_for(seed, 4))
    jax.block_until_ready(loop.cache)
    log(f"programs: {clock.compiles - c0} compiled or loaded in "
        f"{time.perf_counter() - t:.3f} s (compile events {clock.seconds:.3f} s, "
        f"persistent-cache hits {clock.cache_hits})")
    t = time.perf_counter()
    loop.warm_traffic()
    jax.block_until_ready(loop.cache)
    log(f"warm-up traffic: {sched.n_warmup} requests in "
        f"{time.perf_counter() - t:.3f} s, {len(loop.out.commits)} commits, "
        f"compiles so far {clock.compiles}")

    tracer = Tracer() if trace else NULL_TRACER
    loop.rt.tracer = tracer
    trace_path = None
    if trace:
        trace_path = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_path)
    compiles0 = clock.events
    setup_s = time.perf_counter() - t_start
    cut = loop.window(seconds)
    window_compiles = clock.events - compiles0
    if trace:
        jax.profiler.stop_trace()
    loop.rt.tracer = NULL_TRACER
    if on_window is not None:
        on_window(sched, loop.out)
    stats = devs[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    out = loop.out

    # read back acknowledged writes from the program's state
    acked = [w for c in out.commits for w in c]
    vp = {(v, p) for w in acked
          for v, p, _ in sched.writes[sched.write[w]].get("set_vprops", ())}
    if vp:
        vids = np.asarray(sorted({v for v, _ in vp}), np.int32)
        rows = np.asarray(loop.store.vprops[vids])
        at = {int(v): k for k, v in enumerate(vids)}
        final_vprops = {(v, p): int(rows[at[v], p]) for v, p in vp}
    else:
        final_vprops = {}
    names = [p["name"] for p in traffic["plans"]]
    rb = names.index(traffic["readback_plan"])
    touched = sorted({int(e[0]) for w in acked
                      for e in sched.writes[sched.write[w]].get("new_edges", ())}
                     | {int(graph.esrc[e]) for w in acked
                        for e in sched.writes[sched.write[w]].get("del_edges", ())})
    rb_rows = {}
    for lo in range(0, len(touched), loop.batch_cap):
        part = np.asarray(touched[lo:lo + loop.batch_cap], np.int32)
        if len(part) not in loop.buckets:  # stay on a warmed bucket
            part = np.resize(part, next(b for b in loop.buckets if b >= len(part)))
        res, _, _ = loop.rt.run_gr_tx_batch(loop.store, loop.cache,
                                            sysm.ttable, sysm.plans[rb], part)
        for r, row in zip(part, res):
            rb_rows[int(r)] = np.copy(row)
    del loop.store, loop.cache, loop.drain

    plans_hops = [hop_specs(cfg, p) for p in traffic["plans"]]
    t = time.perf_counter()
    chk = check(cell, graph, sched, out, sysm, final_vprops,
                {"plan": rb, "rows": rb_rows}, plans_hops)
    t_check = time.perf_counter() - t

    win = np.arange(sched.n_warmup, len(sched))
    w_reads = win[sched.kind[win] == READ]
    w_writes = win[sched.kind[win] == WRITE]
    lat = (out.done - sched.due) * 1e3
    never = int(np.isnan(out.done[win]).sum())
    late = int(np.sum(~(out.done[win] <= cut)))
    failed = len(set(int(i) for i in win[~(out.done[win] <= cut)])
                 | ({int(i) for i in win} & (out.bad_rows | out.overflowed_writes)))
    gr_lat = lat[w_reads][~np.isnan(lat[w_reads])]
    grw_lat = lat[w_writes][~np.isnan(lat[w_writes])]
    log(f"window: {len(win)} requests ({len(w_reads)} gR, {len(w_writes)} gRW), "
        f"{out.gr_steps} gR steps, {out.commit_steps} commits, served after "
        f"the window plus one iteration {late}, never served {never}, compiles "
        f"in the window {window_compiles}; the first iteration past the "
        f"window ended at {cut:.3f} s; host reference {t_check:.3f} s over {chk['checked']} "
        f"reads, {chk['props_read_back']} written properties and "
        f"{chk['edges_read_back']} read-back roots")
    if len(gr_lat):
        log(f"gR-Tx latency: {len(gr_lat)} reads, p50 {percentile(gr_lat, 50):.3f} "
            f"ms, p90 {percentile(gr_lat, 90):.3f} ms, p99 "
            f"{percentile(gr_lat, 99):.3f} ms, max {gr_lat.max():.3f} ms")
    if len(grw_lat):
        log(f"gRW-Tx latency: {len(grw_lat)} writes, median "
            f"{percentile(grw_lat, 50):.3f} ms, max {grw_lat.max():.3f} ms")
    slow = sorted(out.calls, reverse=True)[:6]
    log("slowest calls in the window: " + "; ".join(
        f"{n} {d:.3f} s at {t0:.3f} s" for d, n, t0 in slow))
    for ex in chk["examples"]:
        log(f"wrong: request {ex[0]} plan {ex[1]} root {ex[2]} got {ex[3]} "
            f"want {ex[4]}")

    compared = {
        "wrong_reads": (chk["wrong"], 0),
        "lost_writes": (chk["lost"], 0),
        "never_served": (never, 0),
        "over_width": (chk["over_width"], 0),
    }
    correct = all(v <= lim for v, lim in compared.values())
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(len(win)),
              "failed": int(failed)}
    if not trace:
        e2e = {
            "gr_p50_ms": percentile(gr_lat, 50) if len(gr_lat) else None,
            "gr_p99_ms": percentile(gr_lat, 99) if len(gr_lat) else None,
            "hbm_peak_gb": peak / 1e9,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}
    else:
        red = None
        for p in Path(trace_path).rglob("*.xplane.pb"):
            red = trace_reduce.reduce(trace_reduce.load(str(p)))
        run_rec = RunRecord(
            cell=cell, out=out, spans=tracer.snapshot(), window_compiles=window_compiles,
            trace=red, needed_bytes=bytes_model.gr_bytes(cell, chk, sched),
            peaks=bytes_model.peaks(devs[0].device_kind, require_tpu),
        )
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(cell.root, m["name"])(run_rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {
                "device_ops": [[k, v] for k, v in red["top_ops"]],
                "idle_gaps": [[k, v] for k, v in red["idle_gaps"]],
            }
            mods = sorted(red["modules"].items(), key=lambda kv: -kv[1][1])
            log("device programs: " + "; ".join(
                f"{k} x{c} {s:.6f} s" for k, (c, s) in mods[:12]))
        shutil.rmtree(trace_path, ignore_errors=True)
    result["metrics"] = metrics
    result["device"] = device
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    return result


@dataclass
class RunRecord:
    """What a per-layer metric reader gets."""

    cell: Cell
    out: Served
    spans: dict  # the program's own spans in the window: name -> stats
    window_compiles: int
    trace: dict | None
    needed_bytes: int
    peaks: dict
