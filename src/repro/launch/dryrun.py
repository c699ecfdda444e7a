"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, record memory/cost analysis and collective bytes.

Run it as its own process: run as a program, it asks XLA for 512 virtual
CPU devices before JAX is imported; importing it sets nothing. Results
accumulate under experiments/dryrun/ as one JSON per cell so partial
progress survives crashes.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch gat-cora --mesh multipod
"""

import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512"
    ).strip()

import argparse
import json
import re
import time
import traceback

import jax

from repro import configs as configs_pkg
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|u32|s8|u8|pred)\[([\d,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES[dtype]
    return total


_OP_RE = re.compile(
    r"\s((?:all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?)\(%?"
)


def collective_bytes(hlo: str) -> dict:
    """Sum output bytes of every collective op in the optimized HLO.

    Output-shape bytes approximate the data each collective materializes per
    device module; '-done' halves of async pairs never match (no shape
    before them), so nothing double counts."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)", stripped)
        if not m:
            continue
        rhs = m.group(1)
        op = _OP_RE.search(rhs)
        if not op:
            continue
        kind = op.group(1).replace("-start", "")
        shape_part = rhs[: op.start(1)]
        out[kind] += _shape_bytes(shape_part)
        counts[kind] += 1
    out["counts"] = counts
    return out


def _loop_analysis(hlo: str) -> dict:
    from repro.launch.hlo_analysis import analyze

    try:
        a = analyze(hlo)
        return dict(
            collectives_weighted=a["weighted"],
            dominant_trip=a["dominant_trip"],
            n_loops=len(a["loops"]),
            trips=sorted({l["trip"] for l in a["loops"]}, reverse=True)[:8],
        )
    except Exception as e:  # noqa: BLE001
        return dict(error=str(e))


def run_cell(arch: str, shape: str, mesh_kind: str, out_dir: str) -> dict:
    multi = mesh_kind == "multipod"
    mesh = make_production_mesh(multi_pod=multi)
    t0 = time.time()
    rec = dict(arch=arch, shape=shape, mesh=mesh_kind, ok=False)
    try:
        step, shardings, args = build_cell(arch, shape, mesh)
        with mesh:
            lowered = jax.jit(step, in_shardings=shardings).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        rec.update(
            ok=True,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=dict(
                argument_bytes=getattr(mem, "argument_size_in_bytes", None),
                output_bytes=getattr(mem, "output_size_in_bytes", None),
                temp_bytes=getattr(mem, "temp_size_in_bytes", None),
                generated_code_bytes=getattr(mem, "generated_code_size_in_bytes", None),
            ),
            cost=dict(
                flops=cost.get("flops", 0.0),
                bytes_accessed=cost.get("bytes accessed", 0.0),
                transcendentals=cost.get("transcendentals", 0.0),
            ),
            collectives=collective_bytes(hlo),
            loop_analysis=_loop_analysis(hlo),
            hlo_lines=len(hlo.splitlines()),
        )
        print(
            f"[OK ] {arch}/{shape}/{mesh_kind}: compile={t_compile:.0f}s "
            f"flops={rec['cost']['flops']:.3e} "
            f"coll={sum(v for k, v in rec['collectives'].items() if k != 'counts'):.3e}B "
            f"temp={rec['memory']['temp_bytes']}"
        )
    except Exception as e:  # noqa: BLE001 — record and continue
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        print(f"[FAIL] {arch}/{shape}/{mesh_kind}: {rec['error'][:200]}")
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape}__{mesh_kind}.json".replace("/", "_")
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None, choices=[None, "pod", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    cells = configs_pkg.all_cells()
    if args.arch:
        cells = [c for c in cells if c["arch"] == args.arch]
    if args.shape:
        cells = [c for c in cells if c["shape"] == args.shape]
    meshes = [args.mesh] if args.mesh else ["pod", "multipod"]

    n_ok = n_fail = n_skip = 0
    for c in cells:
        if c["skip"]:
            print(f"[SKIP] {c['arch']}/{c['shape']}: {c['skip'][:90]}")
            n_skip += 1
            rec = dict(arch=c["arch"], shape=c["shape"], mesh="-", ok=True, skipped=c["skip"])
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, f"{c['arch']}__{c['shape']}__skip.json"), "w") as f:
                json.dump(rec, f, indent=1)
            continue
        for mk in meshes:
            fname = os.path.join(args.out, f"{c['arch']}__{c['shape']}__{mk}.json")
            if args.skip_done and os.path.exists(fname):
                with open(fname) as f:
                    if json.load(f).get("ok"):
                        n_ok += 1
                        continue
            rec = run_cell(c["arch"], c["shape"], mk, args.out)
            n_ok += int(rec["ok"])
            n_fail += int(not rec["ok"])
    print(f"\ndry-run: {n_ok} ok, {n_fail} failed, {n_skip} skipped")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
