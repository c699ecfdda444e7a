"""The harness end to end on the CPU at a tiny size: a cell defined only by
new files and a new ``workloads`` entry is found and run, its check comes
out correct, and it comes out not correct with the served path broken
underneath in each way the cells can break (``bench/control.py``). The
chip path itself stays TPU-only."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import control, harness

REPO = Path(__file__).resolve().parents[2]
SEED = 4_294_967_311  # past 32 bits


def _tiny_root(tmp: Path) -> Path:
    """A checkout whose BENCHMARK.json gains two cells, each defined by new
    files only: a tiny configuration, a write-heavy mix and a rate."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    traffic = json.loads((REPO / "bench" / "traffic" / "rhat.json").read_text())
    traffic.update(name="tiny-mix", read_share=0.8, batch_cap=32,
                   warmup_requests=100, warmup_chunk=50, drain_per_iter=8)
    (root / "bench" / "traffic" / "tiny-mix.json").write_text(json.dumps(traffic))
    for base in ("ecom-cache", "ecom-nocache"):
        cfg = json.loads((REPO / "bench" / "configs" / f"{base}.json").read_text())
        cfg.update(name=f"tiny-{base}", v_total=1024, edge_capacity=1900,
                   cache_slots_total=128 if cfg["use_cache"] else 64)
        (root / "bench" / "configs" / f"tiny-{base}.json").write_text(json.dumps(cfg))
        name = f"tiny-{base}.tiny-mix"
        (root / "bench" / "cells" / f"{name}.json").write_text('{"rate_per_s": 60}')
        bm["configs"].append({"name": f"tiny-{base}", "source": "test",
                              "file": f"bench/configs/tiny-{base}.json",
                              "reduced": [], "why": "test"})
        bm["workloads"].append({"name": name, "config": f"tiny-{base}",
                                "traffic": "tiny-mix", "chips": 1, "why": "test"})
        for m in bm["end_to_end"] + bm["per_layer"]:
            if f"{base}.rhat" in m.get("workloads", ()):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("bench"))


def _run(root, name, trace=False, fault=None):
    cell = harness.load_cell(name, root)
    lines = []
    t = time.perf_counter()
    if fault is None:
        return harness.run(cell, SEED, 2.0, trace, t_start=t, require_tpu=False,
                           log=lines.append), lines
    with control.planted(fault):
        return harness.run(cell, SEED, 2.0, trace, t_start=t, require_tpu=False,
                           log=lines.append), lines


def test_a_cell_of_new_files_runs_and_is_correct(root):
    res, lines = _run(root, "tiny-ecom-cache.tiny-mix", trace=True)
    assert res["correct"], (res["compared"], lines[-6:])
    assert res["attempted"] == 120
    assert list(res)[-1] == "compared"
    m = res["metrics"]
    assert m["window_compiles"]["value"] == 0
    assert 0 < m["hit_ratio"]["value"] <= 100
    assert m["grw_step_ms"]["value"] > 0 and m["gr_host_ms"]["value"] > 0
    assert 0 <= res["device"]["busy_s"] <= res["device"]["window_s"]
    assert "device_ops" in res["breakdown"] and "idle_gaps" in res["breakdown"]
    assert set(res["metrics"]) >= {"hit_ratio", "cp_drain_ms", "device_idle_share"}
    res, lines = _run(root, "tiny-ecom-cache.tiny-mix")
    assert set(res["metrics"]) == {"gr_p50_ms", "hbm_peak_gb", "setup_s"}
    res, lines = _run(root, "tiny-ecom-nocache.tiny-mix")
    assert res["correct"], (res["compared"], lines[-6:])
    assert set(res["metrics"]) == {"gr_p99_ms", "hbm_peak_gb", "setup_s"}
    res, lines = _run(root, "tiny-ecom-nocache.tiny-mix", trace=True)
    assert res["correct"], (res["compared"], lines[-6:])
    m = res["metrics"]
    assert all(k.endswith(".nocache") for k in m), sorted(m)
    assert m["window_compiles.nocache"]["value"] == 0
    assert m["grw_step_ms.nocache"]["value"] > 0 and m["gr_host_ms.nocache"]["value"] > 0


@pytest.mark.parametrize("cell,fault,number", [
    ("tiny-ecom-cache.tiny-mix", "stale_cache", "wrong_reads"),
    ("tiny-ecom-nocache.tiny-mix", "drop_commit", "lost_writes"),
    ("tiny-ecom-cache.tiny-mix", "drop_half", "wrong_reads"),
    ("tiny-ecom-nocache.tiny-mix", "alter_answer", "wrong_reads"),
])
def test_a_broken_served_path_is_not_correct(root, cell, fault, number):
    res, _ = _run(root, cell, fault=fault)
    assert not res["correct"]
    c = res["compared"][number]
    assert c["value"] > c["limit"], res["compared"]


def test_the_run_fails_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = [sys.executable, "bench/run.py", "--workload", "ecom-cache.rhat",
            "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(args, cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and "no TPU" in p.stderr and p.stdout.strip() == ""
    # a directory with only BENCHMARK.json and the benchmark's files
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    env.pop("PYTHONPATH", None)
    p = subprocess.run(args, cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
