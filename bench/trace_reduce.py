"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy and idle time over the traced window,
device time per compiled program, the device operations that took most
time, and the idle gaps labelled by the benchmark annotation the host was
inside when the device went idle.

Device events come from the ``XLA Ops`` and ``XLA Modules`` lines of each
``/device:`` plane. A trace recorded on XLA's CPU backend has no device
plane; there the op events of the CPU client's threads stand in, so the
reduction can be tested without a chip. Host annotations are events on
any host line whose name is one of the benchmark's annotation names.

    python -m bench.trace_reduce <trace.xplane.pb>   # prints the summary
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict

ANNOTATIONS = ("grw_commit", "gr_step.", "cp_drain", "batching", "waiting")
_SUFFIX = re.compile(r"\(\d+\)$")


def _merge(intervals):
    """Union of [start, end) intervals, sorted and merged."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def is_annotation(name: str) -> bool:
    return any(name == a or (a.endswith(".") and name.startswith(a))
               for a in ANNOTATIONS)


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def collect(pd):
    """Device ops and modules per device, and host annotations, as
    ``(name, start_ns, end_ns)`` lists."""
    ops, modules, notes = defaultdict(list), defaultdict(list), []
    cpu_ops = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name == "XLA Ops":
                for ev in line.events:  # "%fusion.3 = s32[...] fusion(...)"
                    ops[plane.name].append(
                        (ev.name.split(" = ")[0], ev.start_ns,
                         ev.start_ns + ev.duration_ns))
            elif device and line.name == "XLA Modules":
                for ev in line.events:
                    modules[plane.name].append(
                        (_SUFFIX.sub("", ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns))
            elif not device:
                cpu_client = line.name.startswith("tf_XLAPjRtCpuClient")
                for ev in line.events:
                    if is_annotation(ev.name):
                        notes.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif (cpu_client and ev.duration_ns > 0
                          and not ev.name.startswith(("ThreadpoolListener",
                                                      "end: "))):
                        cpu_ops.append((ev.name, ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
    if not ops and cpu_ops:
        ops["/host:CPU"] = cpu_ops
    return dict(ops), dict(modules), notes


def reduce(pd, window_ns=None, top: int = 10) -> dict:
    """The summary of one trace. ``window_ns`` is ``(start, end)`` on the
    trace's clock; by default the span of the benchmark's annotations.
    ``busy_s`` is averaged over the devices; module and op seconds are
    summed over devices."""
    ops, modules, notes = collect(pd)
    if window_ns is None:
        if not notes:
            raise ValueError("no benchmark annotation in the trace")
        window_ns = (min(s for _, s, _ in notes), max(e for _, _, e in notes))
    lo, hi = window_ns
    window_s = (hi - lo) / 1e9
    busy, gaps = [], defaultdict(float)
    op_s, mod = defaultdict(float), {}
    notes_sorted = sorted(notes, key=lambda n: n[1])
    for dev, evs in ops.items():
        merged = _clip(_merge([(s, e) for _, s, e in evs]), lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps[_label(notes_sorted, (g0 + g1) / 2)] += (g1 - g0) / 1e9
        for name, s, e in evs:
            if e > lo and s < hi:
                op_s[name] += (min(e, hi) - max(s, lo)) / 1e9
    for dev, evs in modules.items():
        for name, s, e in evs:
            if e > lo and s < hi:
                c = mod.setdefault(name, [0, 0.0])
                c[0] += 1
                c[1] += (min(e, hi) - max(s, lo)) / 1e9
    n_dev = max(len(busy), 1)
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "devices": len(busy),
        "modules": mod,
        "top_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(((k, v / n_dev) for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def _label(notes_sorted, t) -> str:
    """The innermost annotation covering time ``t`` (the latest started)."""
    best = None
    for name, s, e in notes_sorted:
        if s > t:
            break
        if e >= t:
            best = name
    return best or "untracked"


if __name__ == "__main__":
    print(json.dumps(reduce(load(sys.argv[1])), indent=1))
