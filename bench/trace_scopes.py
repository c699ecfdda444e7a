"""Reduce a JAX profiler trace (``.xplane.pb``) to device self time per
named scope and to device-idle time labelled by the program's spans.

- **Self time per scope.** The program names its stages with
  ``jax.named_scope`` (``hop<h>/probe``, ``grw/apply``, ``cp/exec``, ...
  in :data:`SCOPE`). XLA keeps the name in each HLO instruction's
  ``op_name``. A TPU's trace gives it with each op (the ``tf_op`` stat
  of the op's metadata); otherwise it is looked up in the program's HLO
  proto, which the trace carries in its ``/host:metadata`` plane, by
  the op's instruction and program. An op counts for the innermost
  known scope in its ``op_name``. Ops nest: a ``conditional`` encloses
  its branch's ops, so an op's self time is its duration less the ops
  directly inside it, and self times add up to the busy time.
- **Idle time by span.** Each device-idle interval of the window is
  split by the benchmark annotation (``bench/trace_reduce.ANNOTATIONS``)
  and by the program span (a ``repro.obs.trace.Tracer`` span, which is
  also a profiler annotation) that the host was innermost in; time in no
  span is ``untracked``.

As in ``bench/trace_reduce.py``, a trace recorded on XLA's CPU backend
has no device plane, and the op events of the CPU client's threads stand
in, so the reduction can be tested without a chip.

    python -m bench.trace_scopes <trace.xplane.pb> [span ...]  # prints both tables
"""

from __future__ import annotations

import functools
import json
import re
import sys
import traceback
from collections import defaultdict
from pathlib import Path

from bench import trace_reduce

# the program's stage scopes; the innermost match in an op_name wins
SCOPE = re.compile(
    r"(?:^|/)(hop\d+/(?:route|probe|miss_exec|unroute|merge)|finalize"
    r"|reduce_metrics|grw/(?:apply|derive|gate|invalidate|signals)"
    r"|cp/(?:exec|occ|insert))(?=/|$)"
)
UNSCOPED = "unscoped"
UNTRACKED = "untracked"
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


# ------------------------------------------------------- protobuf decoding
def _varint(b, i):
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        s += 7
        if x < 0x80:
            return r, i


def _fields(b):
    """``(field number, value)`` of one serialized protobuf message: ints
    for varints, zero-copy slices for length-delimited fields
    (fixed-width fields are skipped)."""
    b = memoryview(b)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt in (1, 5):
            i += 8 if wt == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wt} at byte {i}")
        yield f, v


def _text(v) -> str:
    return bytes(v).decode()


def _op_names(hlo_proto) -> dict:
    """``instruction name -> op_name`` of one ``HloProto``
    (hlo_module=1 > computations=3 > instructions=2 > name=1,
    metadata=7 > op_name=2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, ins in _fields(comp):
                if h != 2:
                    continue
                name = op_name = None
                for k, v in _fields(ins):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op_name = next((_text(w) for j, w in _fields(v)
                                        if j == 2), None)
                if name and op_name:
                    out[name] = op_name
    return out


def hlo_op_names(xspace) -> dict:
    """``program_id -> (module name, {instruction: op_name})`` from the
    HLO protos in a serialized ``XSpace``'s ``/host:metadata`` plane
    (XSpace planes=1; XPlane name=2, event_metadata=4, stat_metadata=5;
    XEventMetadata id=1, name=2, stats=5; XStat metadata_id=1,
    bytes_value=6). Program ids are strings, as event stats give them."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        evmeta, stat_names = [], {}
        for g, v in _fields(plane):
            if g == 2 and _text(v) != "/host:metadata":
                break
            if g == 4:
                evmeta.append(v)
            elif g == 5:
                d = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[d.get(1)] = _text(d.get(2, b""))
        for entry in evmeta:
            meta = dict(_fields(entry)).get(2, b"")
            pid, name, proto = None, "", None
            for j, w in _fields(meta):
                if j == 1:
                    pid = w
                elif j == 2:
                    name = _text(w)
                elif j == 5 and proto is None:
                    st = dict(_fields(w))
                    if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                        proto = st[6]
            if pid is not None and proto is not None:
                out[str(pid)] = (_PROGRAM_ID.sub("", name), _op_names(proto))
    return out


# ------------------------------------------------------------ trace reading
def load(path: str):
    """``(ProfileData, serialized XSpace)`` of one ``.xplane.pb``."""
    return trace_reduce.load(path), Path(path).read_bytes()


def scope_of(op_name: str | None) -> str:
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else UNSCOPED


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def _device_planes(xspace):
    """``device -> (lines, event metadata)`` of each ``/device:`` plane,
    decoded from the serialized ``XSpace`` (``ProfileData`` gives an
    event's name but not its metadata's stats, where a TPU keeps each
    op's ``tf_op``, the HLO ``op_name``). ``lines`` maps a line's name to
    its events ``(start_ns, end_ns, metadata_id)``; the metadata maps an
    id to ``(name, {stat: value})``. XPlane name=2, lines=3,
    event_metadata=4, stat_metadata=5; XLine name=2, timestamp_ns=3,
    events=4; XEvent metadata_id=1, offset_ps=2, duration_ps=3;
    XEventMetadata name=2, stats=5; XStat metadata_id=1, uint64=3,
    int64=4, str=5, ref=7 (a stat_metadata id whose name is the value)."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        parts = defaultdict(list)
        for g, v in _fields(plane):
            if g == 2 and not _text(v).startswith("/device:"):
                break
            parts[g].append(v)
        if not parts[2]:
            continue
        names = {}
        for entry in parts[5]:
            d = dict(_fields(dict(_fields(entry)).get(2, b"")))
            names[d.get(1)] = _text(d.get(2, b""))
        meta = {}
        for entry in parts[4]:
            d = defaultdict(list)
            for k, v in _fields(dict(_fields(entry)).get(2, b"")):
                d[k].append(v)
            stats = {}
            for st in d[5]:
                x = dict(_fields(st))
                key = names.get(x.get(1))
                if 7 in x:
                    stats[key] = names.get(x[7])
                elif 5 in x:
                    stats[key] = _text(x[5])
                elif 3 in x or 4 in x:
                    stats[key] = str(x.get(3, x.get(4)))
            mid = d[1][0] if d[1] else 0
            meta[mid] = (_text(d[2][0]) if d[2] else "", stats)
        lines = {}
        for line in parts[3]:
            name, ts, evs = "", 0, []
            for k, v in _fields(line):
                if k == 2:
                    name = _text(v)
                elif k == 3:
                    ts = v
                elif k == 4:
                    e = dict(_fields(v))
                    start = ts + e.get(2, 0) // 1000
                    evs.append((start, start + e.get(3, 0) // 1000, e.get(1, 0)))
            lines[name] = evs
        out[_text(parts[2][0])] = (lines, meta)
    return out


def device_ops(pd, xspace) -> dict:
    """``device -> lines -> [(start_ns, end_ns, module, op_name)]``: the
    ``XLA Ops`` line of each device plane, or on the CPU backend the op
    events of the CPU client's threads. A TPU op's ``op_name`` is its
    metadata's ``tf_op``; failing that, and on the CPU, the instruction's
    ``op_name`` in its program's HLO proto (:func:`hlo_op_names`) by
    program id, which on a device plane the enclosing ``XLA Modules``
    event names. ``op_name`` is ``None`` where neither is found."""
    hlo = hlo_op_names(xspace)
    # a program's id as its events give it may be one the metadata plane
    # does not know (a CPU program loaded from the persistent compilation
    # cache); its module's name then finds it where only one program has it
    by_name = defaultdict(list)
    for entry in hlo.values():
        by_name[entry[0]].append(entry)
    ops, cpu = {}, []
    for dev, (lines, meta) in _device_planes(xspace).items():
        if "XLA Ops" not in lines:
            continue
        mods = sorted(lines.get("XLA Modules", ()))
        evs, k = [], 0
        for s, e, mid in sorted(lines["XLA Ops"]):
            while k + 1 < len(mods) and mods[k + 1][0] <= s:
                k += 1
            name, stats = meta.get(mid, ("", {}))
            mod = (meta.get(mods[k][2], ("?", {}))[0]
                   if mods and mods[k][0] <= s < mods[k][1] else "?")
            pid = stats.get("program_id")
            if pid is None:
                m = _PROGRAM_ID.search(mod)
                pid = m and m.group(1)
            op = stats.get("hlo_op") or name.split(" = ")[0].lstrip("%")
            op_name = stats.get("tf_op") or hlo.get(pid, ("", {}))[1].get(op)
            evs.append((s, e, _PROGRAM_ID.sub("", mod), op_name))
        ops[dev] = [evs]
    for plane in pd.planes:
        if plane.name.startswith(("/device:", "/host:metadata")):
            continue
        for line in plane.lines:
            if not line.name.startswith("tf_XLAPjRtCpuClient"):
                continue
            evs = []
            for ev in line.events:
                op, pid = _stat(ev, "hlo_op"), str(_stat(ev, "program_id"))
                if op is None or ev.duration_ns <= 0:
                    continue
                mod = _stat(ev, "hlo_module") or hlo.get(pid, ("?",))[0]
                same = by_name.get(mod, [])
                _, table = hlo.get(pid) or (same[0] if len(same) == 1 else ("", {}))
                evs.append((ev.start_ns, ev.start_ns + ev.duration_ns, mod,
                            table.get(op)))
            cpu.append(evs)
    if not ops and any(cpu):
        ops["/host:CPU"] = cpu
    return ops


def _self_times(evs):
    """Each event's duration less the events directly inside it (the
    events of one line nest or are disjoint)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][0], -evs[i][1]))
    own = [e[1] - e[0] for e in evs]
    stack = []
    for i in order:
        s, e = evs[i][0], evs[i][1]
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def window_of(pd):
    """The span of the benchmark's annotations, as ``trace_reduce.reduce``
    takes it."""
    notes = trace_reduce.collect(pd)[2]
    if not notes:
        raise ValueError("no benchmark annotation in the trace")
    return min(s for _, s, _ in notes), max(e for _, _, e in notes)


def scope_seconds(ops, window_ns) -> dict:
    """``module -> {scope: device self seconds}`` over the window,
    averaged over the devices; ``ops`` is :func:`device_ops`. Ops whose
    ``op_name`` holds no known scope count as ``unscoped``."""
    lo, hi = window_ns
    out = defaultdict(lambda: defaultdict(float))
    for lines in ops.values():
        for evs in lines:
            for (s, e, module, op_name), own in zip(evs, _self_times(evs)):
                if e <= lo or s >= hi or own <= 0:
                    continue
                frac = (min(e, hi) - max(s, lo)) / (e - s)
                out[module][scope_of(op_name)] += own * frac / 1e9
    n_dev = max(len(ops), 1)
    return {m: {k: v / n_dev for k, v in t.items()} for m, t in out.items()}


def idle_by_span(pd, ops, spans, window_ns) -> dict:
    """``annotation -> {span: seconds}``: the device-idle time of the
    window, split by the innermost benchmark annotation and the innermost
    program span (a host event named in ``spans``) the host was in,
    averaged over the devices."""
    spans = set(spans)
    lo, hi = window_ns
    notes, marks = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if trace_reduce.is_annotation(ev.name):
                    notes.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                elif ev.name in spans:
                    marks.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    out = defaultdict(lambda: defaultdict(float))
    for lines in ops.values():
        busy = trace_reduce._clip(trace_reduce._merge(
            [(s, e) for evs in lines for s, e, _, _ in evs]), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for (a, b), note, mark in _segments(gaps, notes, marks):
            out[note][mark] += (b - a) / 1e9
    n_dev = max(len(ops), 1)
    return {k: {s: v / n_dev for s, v in t.items()} for k, t in out.items()}


def _innermost(active, p):
    """Drop the intervals that ended by ``p``; the name of the latest
    started of the rest (the shorter on a tie), or ``untracked``."""
    active[:] = [x for x in active if x[1] > p]
    return max(active, key=lambda x: (x[0], -x[1]))[2] if active else UNTRACKED


def _segments(gaps, notes, marks):
    """Split each gap at every annotation and span edge inside it; yield
    each piece with its innermost annotation and span."""
    cuts = sorted({x for s, e, _ in notes + marks for x in (s, e)})
    notes, marks = sorted(notes), sorted(marks)
    ni = mi = ci = 0
    act_n, act_m = [], []
    for a, b in gaps:
        while ci < len(cuts) and cuts[ci] <= a:
            ci += 1
        j = ci
        while j < len(cuts) and cuts[j] < b:
            j += 1
        points = [a, *cuts[ci:j], b]
        for p, q in zip(points, points[1:]):
            while ni < len(notes) and notes[ni][0] <= p:
                act_n.append(notes[ni])
                ni += 1
            while mi < len(marks) and marks[mi][0] <= p:
                act_m.append(marks[mi])
                mi += 1
            yield (p, q), _innermost(act_n, p), _innermost(act_m, p)


# ------------------------------------------------------------- for readers
def reduce(pd, xspace, spans, window_ns=None) -> dict:
    """Both tables of one trace: ``scopes`` (:func:`scope_seconds`) and
    ``idle`` (:func:`idle_by_span`); the window is by default the span of
    the benchmark's annotations."""
    window = window_of(pd) if window_ns is None else window_ns
    ops = device_ops(pd, xspace)
    return {"scopes": scope_seconds(ops, window),
            "idle": idle_by_span(pd, ops, spans, window)}


@functools.lru_cache(maxsize=1)
def reduce_file(path: str, spans: tuple) -> dict:
    """Both tables of one trace file, logged on standard error."""
    red = reduce(*load(path), spans)
    print(format_tables(red), file=sys.stderr, flush=True)
    return red


def trace_dir() -> str | None:
    """The trace directory of the run in progress. ``bench.harness.run``
    passes its readers the trace reduced by ``bench/trace_reduce.py``
    alone; the trace file itself stays in the directory it keeps in its
    local ``trace_path`` until the readers have run."""
    frame = sys._getframe()
    while frame is not None:
        if frame.f_code.co_name == "run" and "trace_path" in frame.f_locals:
            return frame.f_locals["trace_path"]
        frame = frame.f_back
    return None


def for_run(run) -> dict | None:
    """Both tables for the traced run whose per-layer metrics are being
    read, or ``None`` when it has no trace."""
    path = trace_dir() if run.trace is not None else None
    files = list(Path(path).rglob("*.xplane.pb")) if path else []
    if not files:
        return None
    try:
        return reduce_file(str(files[-1]), tuple(sorted(run.spans)))
    except Exception:  # noqa: BLE001 — a reader must not end the run
        traceback.print_exc()
        return None


def device_ms(run, program: str, pattern: str, per: int) -> float | None:
    """Self time of the scopes matching ``pattern`` in the programs whose
    name starts with ``program``, in ms per ``per``; ``None`` where the
    trace holds no such scope."""
    red = for_run(run)
    if red is None or not per:
        return None
    rx = re.compile(pattern)
    secs = [s for mod, table in red["scopes"].items() if mod.startswith(program)
            for scope, s in table.items() if rx.fullmatch(scope)]
    return 1e3 * sum(secs) / per if secs else None


def format_tables(red: dict) -> str:
    rows = ["device self time per scope (s):"]
    for mod, table in sorted(red["scopes"].items()):
        tot = sum(table.values())
        rows.append(f"  {mod}: total {tot:.6f}; " + "; ".join(
            f"{k} {v:.6f}" for k, v in sorted(table.items(), key=lambda kv: -kv[1])))
    rows.append("device idle by annotation and program span (s):")
    for note, table in sorted(red["idle"].items(),
                              key=lambda kv: -sum(kv[1].values())):
        rows.append(f"  {note}: total {sum(table.values()):.6f}; " + "; ".join(
            f"{k} {v:.6f}" for k, v in sorted(table.items(), key=lambda kv: -kv[1])))
    return "\n".join(rows)


if __name__ == "__main__":
    red = reduce(*load(sys.argv[1]), sys.argv[2:])
    print(format_tables(red))
    print(json.dumps(red, indent=1))
