"""Mean time of one gRW commit step over the window, in ms: the program's
``grw_step`` span in ``ShardedTxnRuntime.run_grw_tx``, which blocks on the
step's scalar outputs."""


def read(run):
    s = run.spans.get("grw_step")
    return 1e3 * s["total_s"] / s["count"] if s and s["count"] else None
