"""The gR serving step's share of its HBM roofline, in %: the least bytes
the window's one-hop work must read (``bench/bytes_model.py``) over the
device time of the gR steps (``jit_fused`` in the trace) times the chip's
peak HBM bandwidth (``bench/peaks.json``). A hop does next to no
arithmetic, so bandwidth is its roofline."""

PROGRAM = "jit_fused"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    secs = sum(s for k, (_, s) in run.trace["modules"].items()
               if k.startswith(PROGRAM))
    if secs <= 0 or not run.needed_bytes:
        return None
    return 100.0 * run.needed_bytes / (secs * float(run.peaks["hbm_bytes_per_s"]))
