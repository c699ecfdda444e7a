"""Runs of a cell with the served path broken on purpose: the check
against the reference has to come out not correct in each.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s> --fault <name>

Faults, each planted in the program's runtime for this process only:

- ``stale_cache``: a commit applies its writes to the store but keeps the
  cache as it was, so no entry is invalidated (the control of a cached
  configuration: it breaks the guarantee that no gR-Tx reads a stale
  entry);
- ``drop_commit``: a commit returns the state unchanged (the control of an
  uncached configuration: a gR-Tx no longer sees the writes committed
  before it, and acknowledged writes are lost);
- ``drop_half``: each gR step serves the first half of its batch and
  returns empty rows for the rest;
- ``alter_answer``: the first row of every gR step is changed where it is
  produced.

The benchmark's own runs never plant these. The last line of standard
output is the run's JSON result, as ``bench/run.py`` prints it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

FAULTS = ("stale_cache", "drop_commit", "drop_half", "alter_answer")


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` in ``ShardedTxnRuntime`` for the duration."""
    import numpy as np

    from repro.distributed.graph_serve import ShardedTxnRuntime as RT

    grw, gr = RT.run_grw_tx, RT.run_gr_tx_batch

    def stale_cache(self, store, cache, *a, **k):
        store2, _, m = grw(self, store, cache, *a, **k)
        return store2, cache, m

    def drop_commit(self, store, cache, *a, **k):
        _, _, m = grw(self, store, cache, *a, **k)
        return store, cache, m

    def drop_half(self, store, cache, ttable, plan, roots, **k):
        roots = np.asarray(roots)
        h = max(len(roots) // 2, 1)
        res, misses, m = gr(self, store, cache, ttable, plan, roots[:h], **k)
        rest = np.full((len(roots) - h,) + res.shape[1:], -1 if res.ndim > 1 else 0,
                       res.dtype)
        return np.concatenate([res, rest]), misses, m

    def alter_answer(self, store, cache, ttable, plan, roots, **k):
        res, misses, m = gr(self, store, cache, ttable, plan, roots, **k)
        res = np.array(res)
        if res.ndim == 1:
            res[0] += 1
        else:
            res[0, 0] = res[0, 0] + 1 if res[0, 0] >= 0 else int(roots[0])
        return res, misses, m

    fake = {"stale_cache": stale_cache, "drop_commit": drop_commit,
            "drop_half": drop_half, "alter_answer": alter_answer}[fault]
    name = "run_grw_tx" if fault in ("stale_cache", "drop_commit") else "run_gr_tx_batch"
    orig = getattr(RT, name)
    setattr(RT, name, fake)
    try:
        yield
    finally:
        setattr(RT, name, orig)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", required=True, choices=FAULTS)
    args = ap.parse_args(argv)

    from bench import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    cell = harness.load_cell(args.workload)
    with planted(args.fault):
        result = harness.run(cell, args.seed, args.seconds, False,
                             t_start=T_START, log=log)
    for name, c in result["compared"].items():
        log(f"compared {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
