"""Share of the traced window in which no operation ran on the device, in
%: one minus the union of the device-op intervals over the window,
averaged over the chips (``bench/trace_reduce.py``)."""


def read(run):
    t = run.trace
    if t is None or t["window_s"] <= 0 or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
