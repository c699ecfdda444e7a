"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call`` CSV rows summarizing each benchmark,
then each benchmark's own detailed table. Reduced op counts keep the whole
run CPU-friendly; pass --full for the EXPERIMENTS.md-scale runs.

The parent never imports JAX: every benchmark runs in a child process of
its own (a device belongs to one process at a time), and the children
share the persistent compile cache at ``compile_cache_dir()``. The mesh
benchmarks (``bench_*.py`` run as a module) give themselves 8 virtual CPU
devices.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# self-sufficient invocation: python benchmarks/run.py [...]
for _p in (REPO_ROOT, os.path.join(REPO_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from repro.launch.compile_cache import ENV, compile_cache_dir  # noqa: E402

# mesh benchmarks: name -> (module, the JSON it writes at the repo root)
MESH_BENCHES = {
    # sharded vs host gRW-Tx commit
    "grw_invalidation": ("benchmarks.bench_grw", "BENCH_grw_invalidation.json"),
    # partitioned storage tier: memory / throughput / route skew
    "partitioned_store": ("benchmarks.bench_partitioned",
                          "BENCH_partitioned_store.json"),
    # block maintenance: sustained gRW appends with compaction + capacity
    # elasticity
    "block_maintenance": ("benchmarks.bench_maintenance",
                          "BENCH_block_maintenance.json"),
    # durability + hitless growth: hot-swap vs blocking recompile across a
    # live growth event
    "elasticity": ("benchmarks.bench_elasticity", "BENCH_elasticity.json"),
    # live shard failover: detection, degraded serving, journal-replay
    # recovery/migration under traffic
    "failover": ("benchmarks.bench_failover", "BENCH_failover.json"),
    # routing tier: static modulo vs locality routing + hot-vertex
    # migration on a colliding hot set
    "routing": ("benchmarks.bench_routing", "BENCH_routing.json"),
}


def _child_env():
    env = dict(os.environ)
    env.setdefault(ENV, compile_cache_dir())
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO_ROOT, "src"), REPO_ROOT,
                    env.get("PYTHONPATH")) if p
    )
    return env


def _bench_subprocess(module: str, out_name: str):
    """Run a mesh benchmark in its own process; it persists its JSON at
    the repo root."""
    path = os.path.join(REPO_ROOT, out_name)
    subprocess.run(
        [sys.executable, "-m", module, "--json", path],
        check=True, env=_child_env(), cwd=REPO_ROOT,
    )
    with open(path) as f:
        out = json.load(f)
    print(f"wrote {path}")
    return out


def _bench_hop_pipeline(batch=512):
    """Old vs fused hop pipeline; persists BENCH_hop_pipeline.json at the
    repo root so the perf trajectory is tracked across PRs."""
    from benchmarks import bench_latency

    out = bench_latency.hop_pipeline(batch=batch)
    path = os.path.join(REPO_ROOT, "BENCH_hop_pipeline.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}")
    return out


def _in_process_benches(full: bool) -> dict:
    """Benchmarks a ``--child`` process runs itself (imports are lazy: the
    parent never loads these modules)."""
    n = 300 if full else 60

    def latency():
        from benchmarks import bench_latency

        return bench_latency.main(
            n_ops=n, json_path=os.path.join(REPO_ROOT, "BENCH_latency.json"))

    def invalidation():
        from benchmarks import bench_invalidation

        return bench_invalidation.main(n_writes=n)

    def errors():
        from benchmarks import bench_errors

        return bench_errors.main(n_ops=max(n // 2, 40))

    def codec():
        from benchmarks import bench_codec

        return bench_codec.main()

    def roofline():
        from benchmarks import roofline

        return roofline.main()

    return {
        # fused vs host-orchestrated hop pipeline (BENCH_hop_pipeline.json)
        "hop_pipeline": lambda: _bench_hop_pipeline(batch=512),
        # Table 1 + 3 + 4 + 5 + 7 + 8 (C±Q± latency percentiles, per class;
        # BENCH_latency.json feeds the p99 regression guard)
        "latency_tables_1_3_5": latency,
        # Table 2 + 6 (impacted keys per write type)
        "invalidation_tables_2_6": invalidation,
        # Table 9 (error rates)
        "errors_table_9": errors,
        # §4 codec micro-benchmark
        "codec_zstd": codec,
        # §Roofline summary from the dry-run artifacts
        "roofline": roofline,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child is not None:
        # one in-process benchmark, in the process that owns the device
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        try:
            _in_process_benches(args.full)[args.child]()
        except FileNotFoundError as e:
            print(f"skipped ({e})")
        return

    names = list(_in_process_benches(args.full))
    names[1:1] = list(MESH_BENCHES)  # the historical order
    rows = []
    for name in names:
        if args.only and args.only not in name:
            continue
        print(f"\n=== {name} ===", flush=True)
        t0 = time.perf_counter()
        if name in MESH_BENCHES:
            _bench_subprocess(*MESH_BENCHES[name])
        else:
            cmd = [sys.executable, os.path.abspath(__file__), "--child", name]
            subprocess.run(cmd + (["--full"] if args.full else []),
                           check=True, env=_child_env(), cwd=REPO_ROOT)
        rows.append((name, (time.perf_counter() - t0) * 1e6))
    print("\nname,us_per_call")
    for nm, us in rows:
        print(f"{nm},{us:.0f}")


if __name__ == "__main__":
    main()
