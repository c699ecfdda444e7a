"""Host time per gR step outside the device: the program's ``gr_dispatch``
plus ``gr_unpack`` spans (``ShardedTxnRuntime.run_gr_tx_batch``), mean per
step over the window, in ms."""


def read(run):
    s = run.spans
    if "gr_dispatch" not in s:
        return None
    n = s["gr_dispatch"]["count"]
    total = s["gr_dispatch"]["total_s"] + s.get("gr_unpack", {}).get("total_s", 0.0)
    return 1e3 * total / n if n else None
