"""The eCommerce watch-list world and its request schedule, made in bulk
from a seed with numpy.

Users own watch-lists (``owns``), watch-lists include listings
(``includes``, edge property ``IsActive``) and listings are sold by users
(``sold_by``). Vertex ids are laid out by label: users, then watch-lists,
then listings. Every size and share comes from the configuration file;
the generator only enforces the caps it states, so that no vertex's
adjacency exceeds ``max_deg`` and no plan's frontier exceeds ``frontier``
at any point of the schedule (a truncated row would be a wrong result).

The schedule is one list of requests in arrival order: a warm-up segment
(untimed) and the measured window. Counts per kind are fixed by the
traffic file and the rate. Where the traffic file gives a ``shape_seed``,
the arrival times, the sequence of kinds, plans and write types, and the
popularity rank of every read's root come from that seed, and the run's
seed only maps ranks to vertices (a seeded permutation per label), makes
the graph and draws the writes' targets and values: every seed then does
the same work on other data. Writes are drawn in schedule order against a
running copy of the degrees, so the caps hold after every prefix.

Nothing here imports the system under test.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

MISSING = -(2**31) + 1  # the store's "property absent" value
READ, WRITE = 0, 1
W_NOOP, W_UPSERT, W_LAST_SEEN, W_DEL_EDGES = 0, 1, 2, 3
WRITE_TYPES = {"upsert": W_UPSERT, "last_seen": W_LAST_SEEN,
               "del_edges": W_DEL_EDGES}


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per stream; any integer seed, negative or
    past 64 bits, maps to one SeedSequence entropy value."""
    return np.random.default_rng([int(seed) % 2**64, stream])


@dataclass
class Graph:
    """The generated world: ``ingest`` arrays plus the label ranges."""

    vlabel: np.ndarray  # int32 [V]
    vprops: np.ndarray  # int32 [V, n_vprops]
    esrc: np.ndarray  # int32 [E]
    edst: np.ndarray
    elabel: np.ndarray
    eprops: np.ndarray  # int32 [E, n_eprops]
    ranges: dict  # label name -> (lo, hi)
    includes: tuple  # (lo, hi) eid range of the includes edges

    @property
    def n_edges(self) -> int:
        return len(self.esrc)


def label_counts(cfg) -> dict:
    """Vertices per label from the configuration's shares."""
    V = int(cfg["v_total"])
    shares = cfg["graph"]["shares"]
    names = list(shares)
    counts = {n: int(round(V * shares[n])) for n in names[:-1]}
    counts[names[-1]] = V - sum(counts.values())
    return counts


def _bounded_slots(rng, n_items: int, n_bins: int, cap: int) -> np.ndarray:
    """``n_items`` bin ids, uniform, with no bin used more than ``cap``
    times: the draws past a bin's cap are drawn again."""
    out = rng.integers(0, n_bins, n_items, dtype=np.int64)
    while True:
        counts = np.bincount(out, minlength=n_bins)
        crowded = np.flatnonzero(counts[out] > cap)  # items in full bins
        if not len(crowded):
            return out
        order = crowded[np.argsort(out[crowded], kind="stable")]
        srt = out[order]
        rank = np.arange(len(srt)) - np.searchsorted(srt, srt, side="left")
        over = order[rank >= cap]
        out[over] = rng.integers(0, n_bins, len(over), dtype=np.int64)


def make_graph(cfg, seed: int) -> Graph:
    g = cfg["graph"]
    sch = cfg["schema"]
    VL, EL = sch["vertex_labels"], sch["edge_labels"]
    VP, EP = sch["vertex_props"], sch["edge_props"]
    rng = rng_for(seed, 1)
    counts = label_counts(cfg)
    U, W, L = counts["user"], counts["watchlist"], counts["listing"]
    u0, w0, l0 = 0, U, U + W
    V = U + W + L
    ranges = {"user": (u0, u0 + U), "watchlist": (w0, w0 + W),
              "listing": (l0, l0 + L)}

    vlabel = np.empty(V, np.int32)
    for name, (lo, hi) in ranges.items():
        vlabel[lo:hi] = VL[name]
    vprops = np.full((V, int(cfg["n_vprops"])), MISSING, np.int32)
    vprops[l0:, VP["Status"]] = rng.integers(0, 2, L, dtype=np.int32)
    vprops[l0:, VP["ListingId"]] = (
        int(cfg["listing_id_base"]) + np.arange(l0, V, dtype=np.int64)
    ).astype(np.int32)
    vprops[:, VP["LastSeen"]] = 0

    # owns: each watch-list has one owner; a user owns at most
    # watchlists_per_user_max of them
    owner = _bounded_slots(rng, W, U, int(g["watchlists_per_user_max"]))
    own_src = (u0 + owner).astype(np.int32)
    own_dst = np.arange(w0, w0 + W, dtype=np.int32)

    # includes: geometric list sizes capped at list_size_initial_max; a
    # listing is on at most watchers_per_listing_max lists; a pair appears
    # once
    size = np.minimum(rng.geometric(1.0 / float(g["list_size_mean"]), W),
                      int(g["list_size_initial_max"]))
    inc_w = np.repeat(np.arange(w0, w0 + W, dtype=np.int64), size)
    inc_l = l0 + _bounded_slots(rng, len(inc_w), L,
                                int(g["watchers_per_listing_max"]))
    pair = inc_w * V + inc_l
    _, first = np.unique(pair, return_index=True)
    keep = np.sort(first)
    inc_src = inc_w[keep].astype(np.int32)
    inc_dst = inc_l[keep].astype(np.int32)
    inc_active = rng.integers(0, 2, len(keep), dtype=np.int32)

    # sold_by: each listing has one seller
    seller = _bounded_slots(rng, L, U, int(g["listings_per_seller_max"]))
    sold_src = np.arange(l0, V, dtype=np.int32)
    sold_dst = (u0 + seller).astype(np.int32)

    esrc = np.concatenate([own_src, inc_src, sold_src])
    edst = np.concatenate([own_dst, inc_dst, sold_dst])
    elabel = np.concatenate([
        np.full(W, EL["owns"], np.int32),
        np.full(len(inc_src), EL["includes"], np.int32),
        np.full(L, EL["sold_by"], np.int32),
    ])
    eprops = np.full((len(esrc), int(cfg["n_eprops"])), MISSING, np.int32)
    eprops[W:W + len(inc_src), EP["IsActive"]] = inc_active
    return Graph(vlabel, vprops, esrc, edst, elabel, eprops, ranges,
                 (W, W + len(inc_src)))


# ------------------------------------------------------------- schedule
@dataclass
class Schedule:
    """Requests in arrival order. ``due`` is seconds from the window's
    start (warm-up requests have ``due < 0`` and are served untimed)."""

    due: np.ndarray  # float64 [N]
    kind: np.ndarray  # int8 READ / WRITE
    plan: np.ndarray  # int16 plan index (reads), -1 for writes
    root: np.ndarray  # int32 (reads)
    write: np.ndarray  # int32 index into ``writes`` (writes), -1 for reads
    writes: list = field(default_factory=list)  # per write: dict of changes
    n_warmup: int = 0
    plan_names: tuple = ()

    def __len__(self):
        return len(self.due)


def exact_counts(total: int, weights) -> np.ndarray:
    """Largest-remainder split of ``total`` by ``weights``."""
    w = np.asarray(weights, np.float64)
    raw = total * w / w.sum()
    out = np.floor(raw).astype(np.int64)
    rest = total - out.sum()
    out[np.argsort(-(raw - out), kind="stable")[:rest]] += 1
    return out


def draw_ranks(rng, n: int, size: int, roots_cfg) -> np.ndarray:
    """0-based popularity ranks over ``n`` items: bounded Zipf (draws past
    ``n`` are drawn again) or uniform."""
    if roots_cfg["dist"] == "uniform":
        return rng.integers(0, n, size, dtype=np.int64)
    a = float(roots_cfg["a"])
    out = rng.zipf(a, size).astype(np.int64)
    while True:
        bad = np.flatnonzero(out > n)
        if not len(bad):
            return out - 1
        out[bad] = rng.zipf(a, len(bad))


def step_phases(seconds: float, rates_x, gap_s: float) -> list:
    """``(start, end, rate factor)`` of each step of a rate sweep: the
    window split into equal steps, each followed by ``gap_s`` seconds with
    no arrivals in which the backlog drains."""
    n = len(rates_x)
    step = seconds / n - gap_s
    return [(i * (step + gap_s), i * (step + gap_s) + step, float(x))
            for i, x in enumerate(rates_x)]


def arrival_times(rng, rate: float, seconds: float, arrivals) -> np.ndarray:
    """Poisson arrivals with a fixed count per phase: ``rate * length``
    uniform points per phase, sorted. ``on_off`` alternates phases of
    ``on_s`` seconds at ``on_rate_x * rate`` and ``off_s`` at
    ``off_rate_x * rate``; ``steps`` is a rate sweep (``step_phases``)."""
    if arrivals["process"] == "poisson":
        phases = [(0.0, seconds, rate)]
    elif arrivals["process"] == "steps":
        phases = [(lo, hi, rate * x) for lo, hi, x in step_phases(
            seconds, arrivals["rates_x"], float(arrivals.get("gap_s", 0.0)))]
    elif arrivals["process"] == "on_off":
        phases, t, on = [], 0.0, True
        while t < seconds:
            length = float(arrivals["on_s" if on else "off_s"])
            x = float(arrivals["on_rate_x" if on else "off_rate_x"])
            phases.append((t, min(t + length, seconds), rate * x))
            t, on = t + length, not on
    else:
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    parts = [lo + np.sort(rng.random(int(round(r * (hi - lo))))) * (hi - lo)
             for lo, hi, r in phases]
    return np.concatenate(parts)


class _WriteState:
    """Running degrees the write draws keep under the configuration's caps."""

    def __init__(self, cfg, graph: Graph):
        g = cfg["graph"]
        self.list_max = int(g["list_size_max"])
        self.watch_max = int(g["watchers_per_listing_max"])
        lo, hi = graph.includes
        self.dst = graph.edst[lo:hi]
        self.lo = lo
        V = len(graph.vlabel)
        src = graph.esrc[lo:hi]
        self.list_size = np.bincount(src, minlength=V)
        self.watchers = np.bincount(self.dst, minlength=V)
        # includes eids are grouped by watch-list (generated in that order)
        self.ptr = np.concatenate([[0], np.cumsum(self.list_size)])
        self.deleted: set = set()
        self.added: set = set()  # (watch-list, listing) pairs

    def has_pair(self, w: int, l: int) -> bool:
        if (w, l) in self.added:
            return True
        a, b = self.ptr[w], self.ptr[w + 1]
        hit = np.flatnonzero(self.dst[a:b] == l)
        return any(self.lo + a + int(i) not in self.deleted for i in hit)


def make_writes(rng, cfg, graph: Graph, types: np.ndarray, traffic, pick):
    """The changes of each write, in order. ``pick(label, k)`` draws ``k``
    vertices of a label by the traffic's root distribution."""
    sch = cfg["schema"]
    VP, EP, EL = sch["vertex_props"], sch["edge_props"], sch["edge_labels"]
    st = _WriteState(cfg, graph)
    spec = {w["type"]: w for w in traffic["writes"]}
    lo, hi = graph.includes
    out = []
    for t in types:
        if t == W_NOOP:
            out.append({})
        elif t == W_UPSERT:
            for _ in range(10000):
                w, l = int(pick("watchlist", 1)[0]), int(pick("listing", 1)[0])
                if (st.list_size[w] < st.list_max
                        and st.watchers[l] < st.watch_max
                        and not st.has_pair(w, l)):
                    break
            else:
                raise RuntimeError("no upsert fits the list-size caps")
            st.list_size[w] += 1
            st.watchers[l] += 1
            st.added.add((w, l))
            out.append({
                "set_vprops": [(l, VP["Status"], int(rng.integers(0, 2)))],
                "new_edges": [(w, l, EL["includes"],
                               [int(rng.integers(0, 2))]
                               + [MISSING] * (int(cfg["n_eprops"]) - 1))],
            })
            assert EP["IsActive"] == 0
        elif t == W_LAST_SEEN:
            v = int(pick("listing", 1)[0])
            out.append({"set_vprops": [
                (v, VP["LastSeen"], int(rng.integers(1, 2**30)))]})
        elif t == W_DEL_EDGES:
            d = spec["del_edges"]
            k = int(rng.integers(int(d["min"]), int(d["max"]) + 1))
            eids = []
            while len(eids) < k:
                e = int(rng.integers(lo, hi))
                if e not in st.deleted:
                    st.deleted.add(e)
                    eids.append(e)
                    st.list_size[graph.esrc[e]] -= 1
                    st.watchers[graph.edst[e]] -= 1
            out.append({"del_edges": eids})
        else:
            raise ValueError(t)
    return out


def make_schedule(cfg, traffic, graph: Graph, seed: int, rate: float,
                  seconds: float) -> Schedule:
    rng = rng_for(seed, 2)
    # the shape of the work: arrivals, kinds, plans and root ranks
    shape = rng if "shape_seed" not in traffic else rng_for(
        int(traffic["shape_seed"]), 3)
    plans = traffic["plans"]
    n_warm = int(traffic["warmup_requests"])
    t_win = arrival_times(shape, rate, seconds, traffic["arrivals"])
    n_win = len(t_win)
    # warm-up requests sit before the window, in order, untimed
    due = np.concatenate([np.linspace(-1.0, -1e-3, n_warm), t_win])

    # one Zipf rank -> vertex map per label, so the hot set is scattered
    rank_map = {name: lo + rng.permutation(hi - lo)
                for name, (lo, hi) in graph.ranges.items()}

    def pick(label: str, k: int) -> np.ndarray:
        ranks = draw_ranks(rng, len(rank_map[label]), k, traffic["roots"])
        return rank_map[label][ranks]

    kind, plan, wtype = [], [], []
    for n in (n_warm, n_win):  # exact shares in each segment
        n_w = int(round(n * (1.0 - float(traffic["read_share"]))))
        pc = exact_counts(n - n_w, [p["weight"] for p in plans])
        wts = traffic["writes"]
        wc = exact_counts(n_w, [w["weight"] for w in wts])
        types = []
        for w, c in zip(wts, wc):
            noop = int(round(c * float(w.get("noop_share", 0.0))))
            types += [W_NOOP] * noop + [WRITE_TYPES[w["type"]]] * (c - noop)
        seg_kind = np.array([READ] * (n - n_w) + [WRITE] * n_w, np.int8)
        seg_plan = np.repeat(np.arange(len(plans)), pc).tolist() + [-1] * n_w
        seg_w = [-1] * (n - n_w) + types
        perm = shape.permutation(n)
        kind.append(seg_kind[perm])
        plan.append(np.asarray(seg_plan, np.int16)[perm])
        wtype.append(np.asarray(seg_w, np.int8)[perm])
    kind = np.concatenate(kind)
    plan = np.concatenate(plan)
    wtype = np.concatenate(wtype)

    root = np.full(len(kind), -1, np.int32)
    for i, p in enumerate(plans):
        idx = np.flatnonzero(plan == i)
        m = rank_map[p["root_label"]]
        root[idx] = m[draw_ranks(shape, len(m), len(idx), traffic["roots"])]
    widx = np.flatnonzero(kind == WRITE)
    writes = make_writes(rng, cfg, graph, wtype[widx], traffic, pick)
    write = np.full(len(kind), -1, np.int32)
    write[widx] = np.arange(len(widx), dtype=np.int32)
    return Schedule(due, kind, plan, root, write, writes, n_warm,
                    tuple(p["name"] for p in plans))
