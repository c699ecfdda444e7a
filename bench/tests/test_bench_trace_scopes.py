"""The trace reduction by named scope and program span
(``bench/trace_scopes.py``), on a trace recorded on the CPU and on
hand-made traces in the layout of a TPU's (device planes with ``XLA
Ops`` and ``XLA Modules`` lines, HLO protos in ``/host:metadata``)."""

import time

import pytest

from bench import trace_reduce, trace_scopes
from bench.harness import ROOT, RunRecord, Served, load_reader

NEW_READERS = ("probe_device_ms", "miss_exec_device_ms",
               "miss_exec_device_ms.nocache", "merge_device_ms",
               "merge_device_ms.nocache", "grw_apply_device_ms",
               "grw_apply_device_ms.nocache", "grw_invalidate_device_ms",
               "cp_wait_ms", "gr_step_gap_ms", "gr_step_gap_ms.nocache")
ACCEPTED_READERS = ("gr_host_ms", "window_compiles", "gr_step_device_ms",
                "gr_step_roofline", "hit_ratio", "edges_scanned_per_gr",
                "cp_drain_ms", "grw_step_ms", "impacted_per_commit",
                "device_idle_share")


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """Four gR-like steps of a jitted ``fused_under_test`` (a program
    whose name starts as the gR step's does, and which no other test
    compiles) with a probe scope, a miss scope inside a ``lax.cond`` and a
    merge scope, under benchmark annotations, with a ``Tracer``'s spans
    around the host phases."""
    import jax
    import jax.numpy as jnp
    from repro.obs.trace import NULL_TRACER, Tracer

    @jax.jit
    def fused_under_test(x, k):
        with jax.named_scope("hop0/probe"):
            y = x * 2.0 + 1.0

        def miss(a):
            with jax.named_scope("hop0/miss_exec"):
                return jnp.sin(a @ a).sum(axis=0)

        z = jax.lax.cond(k > 0, miss, lambda a: a.sum(axis=0), y)
        with jax.named_scope("hop0/merge"):
            return jnp.sort(z)

    x = jnp.ones((384, 384))
    fused_under_test(x, 1).block_until_ready()
    d = tmp_path_factory.mktemp("scopes")
    tr = Tracer()
    jax.profiler.start_trace(str(d))
    for _ in range(4):
        with jax.profiler.TraceAnnotation("gr_step.q_test"):
            with tr.span("gr_prepare"):
                time.sleep(0.005)
            with tr.span("gr_dispatch"):
                y = fused_under_test(x, 1)
            with tr.span("gr_sync"):
                y.block_until_ready()
            with NULL_TRACER.span("gr_unpack"):
                time.sleep(0.002)
        with jax.profiler.TraceAnnotation("waiting"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    (path,) = d.rglob("*.xplane.pb")
    return d, str(path), tr.snapshot()


def _busy_s(pd, xspace):
    """The union of the op intervals over the window, in seconds."""
    lo, hi = trace_scopes.window_of(pd)
    evs = [(s, e) for lines in trace_scopes.device_ops(pd, xspace).values()
           for line in lines for s, e, _, _ in line]
    return sum(e - s for s, e in trace_reduce._clip(
        trace_reduce._merge(evs), lo, hi)) / 1e9


def test_self_time_per_scope_on_a_cpu_trace(cpu_trace):
    _, path, spans = cpu_trace
    pd, xspace = trace_scopes.load(path)
    table = trace_scopes.reduce(pd, xspace, spans)["scopes"]["jit_fused_under_test"]
    assert table["hop0/probe"] > 0 and table["hop0/miss_exec"] > 0
    assert table["hop0/miss_exec"] > table["hop0/probe"]  # the matmul
    # the conditional encloses its branch's ops: raw durations count them
    # twice, self times add up to the busy time exactly
    raw = sum(e - s for lines in trace_scopes.device_ops(pd, xspace).values()
              for line in lines for s, e, _, _ in line)
    busy = _busy_s(pd, xspace)
    assert raw / 1e9 > busy * 1.2
    assert sum(table.values()) == pytest.approx(busy, rel=1e-6)


def test_idle_time_by_program_span_on_a_cpu_trace(cpu_trace):
    _, path, spans = cpu_trace
    pd, xspace = trace_scopes.load(path)
    lo, hi = trace_scopes.window_of(pd)
    idle = trace_scopes.reduce(pd, xspace, spans)["idle"]
    step = idle["gr_step.q_test"]
    assert step["gr_prepare"] >= 0.018  # four 5 ms sleeps, device idle
    assert "gr_unpack" not in step  # the null tracer annotates nothing
    assert step["untracked"] >= 0.007  # the null-traced 2 ms sleeps
    assert idle["waiting"]["untracked"] >= 0.035
    total = sum(v for t in idle.values() for v in t.values())
    assert total == pytest.approx((hi - lo) / 1e9 - _busy_s(pd, xspace), rel=1e-6)


def _vint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _pb(field, payload):
    payload = payload.encode() if isinstance(payload, str) else payload
    return _vint(field << 3 | 2) + _vint(len(payload)) + payload


def _hlo(ops) -> str:
    """A text-proto string of the ``HloProto`` of one computation whose
    instructions are ``ops``: ``[(name, op_name)]``."""
    comp = b"".join(_pb(2, _pb(1, n) + _pb(7, _pb(2, o))) for n, o in ops)
    return "".join(f"\\{x:03o}" for x in _pb(1, _pb(3, comp)))


def _tpu_like(spans: bool) -> bytes:
    """A trace in a TPU's layout, times in us. ``jit_fused`` (program 7)
    runs 0-11: a 2 us probe fusion whose metadata names its ``op_name``
    (``tf_op``, by reference), a 6 us conditional around a 4 us miss
    fusion, and a 3 us merge sort, which name theirs only through the
    program's HLO proto. ``jit_local_grw`` (program 9) runs 14-20: a
    3 us apply and a 3 us invalidate. The host is in ``gr_step.q_fig1``
    0-12.5, ``grw_commit`` 13-21 and ``waiting`` 21-30; with ``spans``,
    in the program's ``gr_sync`` span 11-12.5."""
    import jax

    fused = _hlo([("cond.3", "jit(fused)/hop0/miss_exec/cond"),
                  ("fusion.1", "jit(fused)/hop0/miss_exec/cond/branch_1_fun/"
                               "hop0/miss_exec/gather"),
                  ("sort.5", "jit(fused)/hop0/merge/sort")])
    grw = _hlo([("fusion.6", "jit(local_grw)/shard_map/grw/apply/scatter"),
                ("fusion.7", "jit(local_grw)/shard_map/grw/invalidate/scatter")])
    span = ("events { metadata_id: 4 offset_ps: 11000000 duration_ps: 1500000 }"
            if spans else "")
    ev = "events {{ metadata_id: {} offset_ps: {}000000 duration_ps: {}000000 }}"
    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {ev.format(1, 0, 11)} {ev.format(8, 14, 6)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {ev.format(4, 0, 2)} {ev.format(2, 2, 6)} {ev.format(3, 3, 4)}
    {ev.format(5, 8, 3)} {ev.format(6, 14, 3)} {ev.format(7, 17, 3)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_fused(7)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%cond.3 = s32[] conditional(x)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%fusion.1 = s32[8] fusion(y)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%fusion.2 = s32[8] fusion(z)"
    stats {{ metadata_id: 1 ref_value: 2 }} }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "%sort.5 = s32[8] sort(w)" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "%fusion.6 = s32[8] fusion(a)" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "%fusion.7 = s32[8] fusion(b)" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "jit_local_grw(9)" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "jit(fused)/hop0/probe/gather:" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 12500000 }}
    {ev.format(2, 13, 8)} {ev.format(3, 21, 9)} {span} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "gr_step.q_fig1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "grw_commit" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "waiting" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "gr_sync" }} }}
}}
planes {{ id: 3 name: "/host:metadata"
  event_metadata {{ key: 7 value {{ id: 7 name: "jit_fused(7)"
    stats {{ metadata_id: 1 bytes_value: "{fused}" }} }} }}
  event_metadata {{ key: 9 value {{ id: 9 name: "jit_local_grw(9)"
    stats {{ metadata_id: 1 bytes_value: "{grw}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
}}''')


def _profile(xspace):
    import jax

    return jax.profiler.ProfileData.from_serialized_xspace(xspace)


def test_tpu_layout_scopes_and_idle():
    xspace = _tpu_like(spans=True)
    red = trace_scopes.reduce(_profile(xspace), xspace, ["gr_sync"])
    us = pytest.approx
    assert red["scopes"] == {
        "jit_fused": {"hop0/probe": us(2e-6), "hop0/miss_exec": us(6e-6),
                      "hop0/merge": us(3e-6)},
        "jit_local_grw": {"grw/apply": us(3e-6), "grw/invalidate": us(3e-6)}}
    assert red["idle"] == {"gr_step.q_fig1": {"gr_sync": us(1.5e-6)},
                           "untracked": {"untracked": us(0.5e-6)},
                           "grw_commit": {"untracked": us(2e-6)},
                           "waiting": {"untracked": us(9e-6)}}


def _record(red, spans=None, gr_steps=4):
    out = Served(None, None)
    out.gr_steps, out.gr_requests, out.commit_steps = gr_steps, 8, 1
    out.gr_sums.update(hits=3, cache_reads=4, edges_scanned=40)
    out.spans["cp_drain"] = [2, 0.004]
    return RunRecord(cell=None, out=out, spans=spans or {}, window_compiles=0,
                     trace=red, needed_bytes=100,
                     peaks={"hbm_bytes_per_s": 8.19e11})


def test_program_spans_leave_the_trace_reduction_as_it_was():
    """The same device work and benchmark annotations, with and without a
    program span on the host: ``reduce`` and every reader of its result
    read the same."""
    plain, spanned = (trace_reduce.reduce(_profile(_tpu_like(s)))
                      for s in (False, True))
    assert plain == spanned
    spans = {"gr_dispatch": {"count": 4, "total_s": 0.002},
             "gr_unpack": {"count": 4, "total_s": 0.001},
             "grw_step": {"count": 1, "total_s": 0.2}}
    with_prepare = dict(spans, gr_prepare={"count": 4, "total_s": 0.003})
    for name in ACCEPTED_READERS:
        read = load_reader(ROOT, name)
        assert read(_record(plain, spans)) == read(_record(spanned, with_prepare)), name


def test_new_readers_read_the_trace_of_the_run(tmp_path):
    xspace = _tpu_like(spans=True)
    (tmp_path / "run.xplane.pb").write_bytes(xspace)
    spans = {"gr_sync": {"count": 1, "total_s": 1.5e-6},
             "cp_wait": {"count": 4, "total_s": 0.02}}
    rec = _record(trace_reduce.reduce(_profile(xspace)), spans, gr_steps=1)

    def run(rec):  # stands in for bench.harness.run, whose readers run here
        trace_path = str(tmp_path)  # noqa: F841 — read through the caller's frame
        return {name: load_reader(ROOT, name)(rec) for name in NEW_READERS}

    got = run(rec)
    want = {"probe_device_ms": 0.002, "miss_exec_device_ms": 0.006,
            "merge_device_ms": 0.003, "grw_apply_device_ms": 0.003,
            "grw_invalidate_device_ms": 0.003, "gr_step_gap_ms": 0.0015,
            "cp_wait_ms": 5.0}
    for name, value in got.items():
        assert value == pytest.approx(want[name.replace(".nocache", "")]), name
    # outside a harness run, or without a trace, a trace reader reads nothing
    assert load_reader(ROOT, "probe_device_ms")(rec) is None
    assert run(_record(None, spans))["probe_device_ms"] is None
