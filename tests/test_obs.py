"""Observability tier (``repro.obs``) — host-side unit tests.

Covers the streaming histogram's quantile accuracy and exact merge
algebra (property-tested; hypothesis-accelerated when the package is
present, seeded-random otherwise), the Span/Tracer accounting + JSONL
export + per-span overhead bound, the event-schema validators and the
``repro.obs.validate`` CLI, the owner-stage attribution math, and the
``ServeTelemetry`` aggregator end to end. The device side of the tier
(the owner-stage block riding the serving step's stacked all-reduce)
is exercised on the 8-device mesh in ``tests/test_sharded_collectives``.
"""

import json
import time

import numpy as np
import pytest

from repro.obs.histogram import LatencyHistogram
from repro.obs.metrics import (
    OWNER_STAGE_FIELDS,
    attribute_step_seconds,
    hit_locality,
    owner_stage_rows,
)
from repro.obs.schema import LATENCY_CLASSES, validate_event
from repro.obs.telemetry import ServeTelemetry
from repro.obs.trace import NULL_TRACER, JsonlTraceWriter, NullTracer, Tracer
from repro.obs.validate import main as validate_cli
from repro.obs.validate import validate_file

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container has no hypothesis — seeded fallback below
    HAVE_HYPOTHESIS = False


# ----------------------------------------------------------------- histogram
def test_histogram_quantile_within_one_bucket_of_sample_quantile():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-6.0, sigma=1.0, size=4000)  # ~ms scale
    h = LatencyHistogram()
    h.record_many(samples)
    res = h.resolution
    for q in (0.5, 0.95, 0.99):
        true = float(np.quantile(samples, q))
        est = h.quantile(q)
        assert true / res <= est <= true * res, (q, est, true)
    assert h.count == samples.size
    assert h.mean == pytest.approx(samples.mean())


def test_histogram_weighted_record_and_edges():
    h = LatencyHistogram()
    h.record(0.01, weight=0)  # non-positive weight is a no-op
    assert h.count == 0
    assert np.isnan(h.quantile(0.5))  # empty histogram
    h.record(0.01, weight=5)
    assert h.count == 5
    # out-of-range samples clamp into the edge buckets, never crash
    h.record(1e-12)
    h.record(1e6)
    assert h.count == 7
    with pytest.raises(ValueError):
        h.quantile(1.5)


def _merge_property(samples_a, samples_b):
    """merge(h_a, h_b) must equal the histogram of the concatenated
    stream exactly (counts), so its quantiles match the concat-sample
    quantiles within one bucket ratio."""
    h_a, h_b, h_cat = (LatencyHistogram() for _ in range(3))
    h_a.record_many(samples_a)
    h_b.record_many(samples_b)
    both = np.concatenate([samples_a, samples_b])
    h_cat.record_many(both)
    merged = h_a.merge(h_b)
    assert np.array_equal(merged.counts, h_cat.counts)
    assert merged.sum_seconds == pytest.approx(h_cat.sum_seconds)
    res = merged.resolution
    ordered = np.sort(both)
    for q in (0.5, 0.95, 0.99):
        # the histogram's inverted-CDF rule selects the bucket holding
        # the rank-ceil(q*n) sample; compare against that same sample
        # (numpy's default linear interpolation is a different estimator
        # and can legitimately land a bucket away at small n)
        rank = max(int(np.ceil(q * ordered.size)), 1) - 1
        true = float(ordered[rank])
        # clamp: samples beyond the bucket range can only be resolved to
        # the edge bucket, which the ratio bound cannot hold for
        if merged.lo * res <= true <= merged.hi / res:
            est = merged.quantile(q)
            assert true / res <= est <= true * res, (q, est, true)
    # in-place merge agrees with the pure one
    assert np.array_equal(h_a.merge_in(h_b).counts, merged.counts)


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(
        st.lists(st.floats(1e-6, 50.0, allow_nan=False), min_size=1,
                 max_size=200),
        st.lists(st.floats(1e-6, 50.0, allow_nan=False), min_size=1,
                 max_size=200),
    )
    def test_histogram_merge_equals_concat(sa, sb):
        _merge_property(np.asarray(sa), np.asarray(sb))
else:
    @pytest.mark.parametrize("seed", range(10))
    def test_histogram_merge_equals_concat(seed):
        rng = np.random.default_rng(seed)
        sa = rng.lognormal(-5.0, 2.0, size=int(rng.integers(1, 400)))
        sb = rng.lognormal(-7.0, 1.5, size=int(rng.integers(1, 400)))
        _merge_property(sa, sb)


def test_histogram_merge_rejects_spec_mismatch():
    with pytest.raises(ValueError, match="bucket specs"):
        LatencyHistogram().merge(LatencyHistogram(lo=1e-6))
    with pytest.raises(ValueError, match="bucket specs"):
        LatencyHistogram().merge_in(LatencyHistogram(buckets_per_decade=8))


def test_histogram_dict_roundtrip():
    h = LatencyHistogram()
    h.record_many(np.random.default_rng(1).lognormal(-6, 1, 100))
    h2 = LatencyHistogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert np.array_equal(h.counts, h2.counts)
    assert h2.quantile(0.95) == h.quantile(0.95)
    bad = h.to_dict()
    bad["counts"] = bad["counts"][:-1]
    with pytest.raises(ValueError, match="counts length"):
        LatencyHistogram.from_dict(bad)


# -------------------------------------------------------------------- tracer
def test_tracer_accounting_and_jsonl_export(tmp_path):
    path = tmp_path / "t.jsonl"
    with JsonlTraceWriter(str(path)) as w:
        tr = Tracer(sink=w)
        for _ in range(3):
            with tr.span("phase_a", shard=1):
                pass
        t_b = time.time()
        with tr.span("phase_b"):
            time.sleep(0.002)
    snap = tr.snapshot()
    assert snap["phase_a"]["count"] == 3
    assert snap["phase_b"]["total_s"] >= 0.002
    assert set(snap["phase_a"]) >= {"count", "total_s", "p50", "p99"}
    assert tr.histogram("phase_a").count == 3
    events = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(events) == 4 == w.events_written
    for ev in events:
        assert validate_event(ev) == "span"
    assert events[0]["attrs"] == {"shard": 1}
    # a span event's ts is its wall-clock start, not its end
    assert events[3]["name"] == "phase_b"
    assert abs(events[3]["ts"] - t_b) < 1e-3


def test_tracer_spans_annotate_the_profiler_trace(tmp_path):
    """A ``Tracer`` span is also a profiler annotation of the same name,
    on the trace's clock; ``NULL_TRACER`` annotates nothing."""
    import jax

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    with tr.span("traced_phase"):
        time.sleep(0.003)
    with NULL_TRACER.span("null_phase"):
        time.sleep(0.001)
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    events = [ev for plane in pd.planes for line in plane.lines
              for ev in line.events]
    (note,) = [ev for ev in events if ev.name == "traced_phase"]
    assert note.duration_ns >= 3e6
    assert note.duration_ns / 1e9 <= tr.snapshot()["traced_phase"]["total_s"] + 1e-3
    assert not any(ev.name == "null_phase" for ev in events)


def test_null_tracer_is_shared_and_stateless():
    assert NullTracer().span("x") is NULL_TRACER.span("y")
    with NULL_TRACER.span("anything", k=1):
        pass
    NULL_TRACER.record("x", 1.0)
    assert NULL_TRACER.snapshot() == {}
    assert not NULL_TRACER.enabled


def test_span_overhead_bound():
    """The serve loop runs several spans per batch; pin the per-span cost
    far below a batch (bound is ~25x the measured ~1-2 us, to stay
    robust on loaded CI runners)."""
    tr = Tracer(sink=None)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("hot"):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 50e-6, f"span overhead {per_span*1e6:.1f} us"
    t0 = time.perf_counter()
    for _ in range(n):
        with NULL_TRACER.span("hot"):
            pass
    per_null = (time.perf_counter() - t0) / n
    assert per_null < 10e-6, f"null-span overhead {per_null*1e6:.1f} us"


# ------------------------------------------------------- owner attribution
def test_attribute_step_seconds_balanced_and_skewed():
    n, S = 4, len(OWNER_STAGE_FIELDS)
    balanced = np.full((n, S), 10, dtype=np.int64)
    per = attribute_step_seconds(0.8, balanced)
    # balanced mesh reproduces the collective-step semantics: every owner
    # observes the full step wall-clock
    assert np.allclose(per, 0.8)
    skewed = np.zeros((n, S), dtype=np.int64)
    skewed[2, 0] = 30  # frontier_rows — all the work at owner 2
    per = attribute_step_seconds(0.8, skewed)
    assert per[2] == pytest.approx(0.8 * n)
    assert np.allclose(np.delete(per, 2), 0.0)
    assert per.sum() == pytest.approx(0.8 * n)  # conserved total
    # zero work anywhere: uniform fallback, never NaN
    assert np.allclose(
        attribute_step_seconds(0.5, np.zeros((n, S), np.int64)), 0.5)


def test_owner_stage_rows_and_hit_locality():
    n, S = 3, len(OWNER_STAGE_FIELDS)
    m = np.zeros((n, S), dtype=np.int64)
    hits = OWNER_STAGE_FIELDS.index("probe_hits")
    miss = OWNER_STAGE_FIELDS.index("miss_rows")
    m[0, hits], m[0, miss] = 9, 1
    m[1, hits], m[1, miss] = 0, 5
    rows = owner_stage_rows(m)
    assert [r["probe_hits"] for r in rows] == [9, 0, 0]
    assert set(rows[0]) == set(OWNER_STAGE_FIELDS)
    loc = hit_locality(m)
    assert loc[0] == pytest.approx(0.9)
    assert loc[1] == 0.0
    assert loc[2] == 0.0  # no probes at all: defined as 0, not NaN
    with pytest.raises(ValueError):
        attribute_step_seconds(1.0, np.zeros((n, S - 1), np.int64))


# ----------------------------------------------------- telemetry aggregator
def _synthetic_stage(n, rng):
    return rng.integers(0, 50, (n, len(OWNER_STAGE_FIELDS))).astype(np.int64)


def test_serve_telemetry_stream_is_schema_valid(tmp_path):
    path = tmp_path / "serve.jsonl"
    n = 4
    tel = ServeTelemetry(n, trace_path=str(path))
    rng = np.random.default_rng(0)
    # spans may fire before the first batch (journal startup checkpoint):
    # meta must still be the first event in the stream
    with tel.tracer.span("checkpoint"):
        pass
    for b in range(6):
        stage = _synthetic_stage(n, rng)
        per = tel.record_gr(
            0.01, {"hits": 3, "misses": 2, "requests": 5}, owner_stage=stage)
        assert per is not None and per.shape == (n,)
        tel.record_grw(0.02)
        tel.record_cp_drain(0.005)
        if b % 2 == 1:
            snap = tel.snapshot(b)
            assert validate_event(snap, shards=n) == "snapshot"
    rep = tel.report()
    assert validate_event(rep, shards=n) == "report"
    assert rep["batches"] == 6
    assert rep["counters"]["hits"] == 18
    assert rep["latency"]["gr_step"]["count"] == 6  # once per step
    for cls in LATENCY_CLASSES:
        assert rep["latency"][cls]["count"] > 0
    tel.close()
    counts = validate_file(str(path), expect_snapshots=3, expect_report=True)
    assert counts["snapshot"] == 3 and counts["report"] == 1
    assert validate_cli([str(path), "--expect-snapshots", "3",
                         "--expect-report"]) == 0


def test_serve_telemetry_without_device_attribution():
    tel = ServeTelemetry(2)  # no trace path: aggregate-only mode
    assert tel.record_gr(0.01, {"hits": 0, "misses": 4}) is None
    rep = tel.report()
    # one sample per step, however many rows hit or missed
    assert rep["latency"]["gr_step"]["count"] == 1
    assert rep["latency"]["grw"]["count"] == 0
    assert rep["latency"]["grw"]["p99"] is None  # empty class -> null
    assert validate_event(rep, shards=2) == "report"


def test_validate_cli_rejects_malformed_streams(tmp_path):
    # span before meta
    p1 = tmp_path / "bad1.jsonl"
    p1.write_text('{"type":"span","name":"x","dur_s":0.1,"ts":1.0}\n')
    with pytest.raises(ValueError, match="first event"):
        validate_file(str(p1))
    assert validate_cli([str(p1)]) == 1
    # owner_stage row count contradicting the meta shard count
    tel = ServeTelemetry(3)
    snap = tel.snapshot(0)
    snap["owner_stage"] = snap["owner_stage"][:-1]
    with pytest.raises(ValueError, match="owner rows"):
        validate_event(snap, shards=3)
    # negative counter inside an owner row
    snap2 = tel.snapshot(1)
    snap2["owner_stage"][0]["probe_hits"] = -1
    with pytest.raises(ValueError, match="non-negative"):
        validate_event(snap2, shards=3)
    # unknown event type
    with pytest.raises(ValueError, match="unknown event type"):
        validate_event({"type": "bogus"})
