"""The trace reduction, on a small trace recorded on the CPU and on
hand-made intervals."""

import time

import pytest

from bench import trace_reduce


def test_merge_and_label():
    assert trace_reduce._merge([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]
    notes = sorted([("batching", 0, 10), ("gr_step.q", 2, 6)], key=lambda n: n[1])
    assert trace_reduce._label(notes, 3) == "gr_step.q"
    assert trace_reduce._label(notes, 8) == "batching"
    assert trace_reduce._label(notes, 11) == "untracked"
    assert trace_reduce.is_annotation("gr_step.q_fig1")
    assert not trace_reduce.is_annotation("gr_stepper")


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return (x @ x).sum()

    x = jnp.ones((384, 384))
    step(x).block_until_ready()
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    for _ in range(4):
        with jax.profiler.TraceAnnotation("gr_step.q_test"):
            step(x).block_until_ready()
        with jax.profiler.TraceAnnotation("waiting"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    (path,) = d.rglob("*.xplane.pb")
    return trace_reduce.load(str(path))


def test_reduce_cpu_trace(cpu_trace):
    red = trace_reduce.reduce(cpu_trace)
    assert red["devices"] == 1
    assert 0.08 <= red["window_s"] < 5.0  # four 20 ms sleeps and the steps
    assert 0.0 < red["busy_s"] < red["window_s"]
    labels = dict(red["idle_gaps"])
    assert labels.get("waiting", 0.0) >= 0.06  # the sleeps are idle
    assert set(labels) <= {"waiting", "gr_step.q_test", "untracked"}
    assert sum(labels.values()) == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert red["top_ops"] and all(s > 0 for _, s in red["top_ops"])
    # a window that excludes everything sees nothing busy
    lo = min(s for _, s, _ in trace_reduce.collect(cpu_trace)[2])
    empty = trace_reduce.reduce(cpu_trace, window_ns=(lo - 2_000_000, lo - 1_000_000))
    assert empty["busy_s"] == 0.0 and empty["modules"] == {}
