"""Reduce a profiler trace to the numbers the per-layer metrics read.

``summarize`` reads a ``jax.profiler.ProfileData``, or anything shaped like
it (planes with a ``name`` and ``lines``; lines with a ``name`` and
``events``; events with ``name``, ``start_ns``, ``duration_ns`` and
``stats``), so a small recorded trace (``tests/data``) checks the arithmetic
without a chip.

Device planes are ``/device:<platform>:<n>``.  Their ``XLA Modules`` line
holds one event per program run; device busy time is the union of those
intervals inside the window.  The ``XLA Ops`` line holds every operation,
those inside a loop once per iteration, so it can hold millions of events:
it is read once, without their stats, except for the ops of the programs
whose kernels a metric needs (``want_stats``).  Host events are the spans
on the host plane, the benchmark's own ``bench.*`` among them; the window
is the host span ``bench.window``.

On a TPU v5e the profiler keeps about 6 million op events and drops the
rest, and a program's module events with them.  A trace that holds
``OPS_KEPT`` op events or more is taken to have been cut (``cut``): its
window ends at the end of its last op, and the metrics that need the whole
window, or the work done in it, read nothing from it.  The harness traces
a fixed amount of work (the traffic's ``trace_work``), sized to hold well
under the limit.
"""
from __future__ import annotations

import dataclasses
import re
from bisect import bisect_right

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
OPS_KEPT = 5_500_000


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:")


def program_name(name: str) -> str:
    """An XLA module's name without the id XLA appends: ``jit_run_sse(12)``
    and ``jit_run_sse.3`` both read ``jit_run_sse``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"\.\d+$", "", name)


def union_ns(starts, ends, lo: float, hi: float) -> float:
    """Length of the union of the intervals ``[starts[i], ends[i])``,
    clipped to ``[lo, hi]``."""
    s = np.clip(np.asarray(starts, float), lo, hi)
    e = np.clip(np.asarray(ends, float), lo, hi)
    if s.size == 0:
        return 0.0
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    # an interval adds what it reaches past everything before it
    prev = np.concatenate([[lo], reach[:-1]])
    return float(np.sum(np.maximum(0.0, reach - np.maximum(s, prev))))


def gaps(starts, ends, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    s = np.asarray(starts, float)
    e = np.asarray(ends, float)
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.concatenate([[lo], np.maximum(lo, np.maximum.accumulate(e))])
    nxt = np.concatenate([s, [hi]])
    g = [(float(a), float(min(b, hi))) for a, b in zip(reach, nxt)
         if b > a and a < hi]
    return g


@dataclasses.dataclass
class Summary:
    """What one traced window holds, averaged over the device planes."""
    window_s: float
    busy_s: float                      # union of program runs, per chip
    n_devices: int
    program_s: dict                    # program name -> device seconds
    op_s: dict                         # (program, op) -> device seconds
    kernel_ops: list                   # (program, op, seconds, stats)
    gaps: list                         # (host label, idle seconds)
    n_ops: int = 0                     # op events read, all planes
    cut: bool = False                  # the profiler's op limit was reached

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_in(self, pattern: str) -> float:
        """Device seconds of every program whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for p, s in self.program_s.items() if rx.search(p))

    def top_ops(self, n: int = 10) -> list:
        return [[f"{p}/{o}", s] for (p, o), s in sorted(
            self.op_s.items(), key=lambda kv: -kv[1])[:n]]


def _host_label(host: list, t: float) -> str:
    """What the host was doing at ``t``: the outermost ``bench.`` span and
    the innermost span, of any thread, that hold ``t``."""
    holding = [h for h in host if h[0] <= t < h[1]]
    if not holding:
        return "host: no span"
    bench = [h for h in holding if h[2].startswith("bench.")
             and h[2] != WINDOW_SPAN]
    inner = min(holding, key=lambda h: h[1] - h[0])[2]
    outer = max(bench, key=lambda h: h[1] - h[0])[2] if bench else "host"
    return outer if inner == outer else f"{outer} > {inner}"


def summarize(pd, want_stats: str = r"$^", max_gaps: int = 10,
              min_host_ns: float = 1e3) -> Summary:
    """Reduce one traced window; ``want_stats`` matches the programs whose
    ops are returned with their stats in ``kernel_ops``."""
    want = re.compile(want_stats)
    host, dev_planes = [], []
    for plane in pd.planes:
        if is_device_plane(plane.name):
            dev_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    d = e.duration_ns
                    if d >= min_host_ns or e.name.startswith("bench."):
                        host.append((e.start_ns, e.start_ns + d, e.name))
    win = [h for h in host if h[2] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = win[0][0], win[0][1]
    host = [h for h in host if h[1] > lo and h[0] < hi]

    n = max(len(dev_planes), 1)
    planes = []
    kernel_ops: list = []
    op_s: dict = {}
    n_ops = 0
    for plane in dev_planes:
        lines = {line.name: line for line in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       program_name(e.name))
                      for e in (lines[MODULES_LINE].events
                                if MODULES_LINE in lines else ()))
        mods = [m for m in mods if m[1] > lo and m[0] < hi]
        starts = [m[0] for m in mods]
        per_name: dict = {}
        kept, last = 0, lo
        for e in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            kept += 1
            s = e.start_ns
            last = max(last, s + e.duration_ns)
            if s >= hi or s + e.duration_ns <= lo:
                continue
            i = bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            d = min(s + e.duration_ns, hi) - max(s, lo)
            k = (prog, e.name)
            per_name[k] = per_name.get(k, 0.0) + d
            if want.search(prog):
                kernel_ops.append((prog, e.name, d * 1e-9, {
                    k_: v for k_, v in e.stats
                    if isinstance(v, (int, float, str))}))
        for k, v in per_name.items():
            op_s[k] = op_s.get(k, 0.0) + v * 1e-9 / n
        n_ops += kept
        planes.append((mods, kept, last))
    # a trace that reached the profiler's limit holds nothing after its
    # last op: the window ends there
    cut = False
    for _mods, kept, last in planes:
        if kept >= OPS_KEPT:
            hi, cut = min(hi, last), True
    host = [h for h in host if h[0] < hi]

    busy = 0.0
    program_s: dict = {}
    first_intervals = None
    for mods, _kept, _last in planes:
        ms = np.array([m[0] for m in mods], float)
        me = np.array([m[1] for m in mods], float)
        busy += union_ns(ms, me, lo, hi)
        if first_intervals is None:
            first_intervals = (ms, me)
        for s, e, p in mods:
            if e > lo and s < hi:
                program_s[p] = program_s.get(p, 0.0) + (
                    min(e, hi) - max(s, lo)) * 1e-9 / n

    gap_list = gaps(*first_intervals, lo, hi) if first_intervals is not None \
        else [(lo, hi)]
    # the longest gaps, summed by what the host was doing in each
    gap_list.sort(key=lambda g: g[0] - g[1])
    by_label: dict = {}
    for s, e in gap_list[:200]:
        k = _host_label(host, (s + e) / 2)
        by_label[k] = by_label.get(k, 0.0) + (e - s) * 1e-9
    labelled = sorted(by_label.items(), key=lambda kv: -kv[1])[:max_gaps]
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
                   n_devices=len(dev_planes), program_s=program_s, op_s=op_s,
                   kernel_ops=kernel_ops, gaps=labelled, n_ops=n_ops,
                   cut=cut)


def excerpt(pd, ms: float = 50.0) -> dict:
    """The first ``ms`` milliseconds of the window, every line, as plain
    JSON: a recorded trace small enough to keep with the tests."""
    host = [e for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events if e.name == WINDOW_SPAN]
    lo = host[0].start_ns
    hi = lo + ms * 1e6
    out = {"planes": []}
    for p in pd.planes:
        if not (is_device_plane(p.name) or p.name.startswith("/host:")):
            continue
        lines = []
        for line in p.lines:
            evs = []
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    evs.append([e.name, lo, hi - lo, {}])
                elif e.start_ns < hi and e.start_ns + e.duration_ns > lo and (
                        is_device_plane(p.name) or e.duration_ns >= 1e3
                        or e.name.startswith("bench.")):
                    stats = {k: v for k, v in e.stats
                             if isinstance(v, (int, float, str))} \
                        if is_device_plane(p.name) else {}
                    evs.append([e.name, e.start_ns, e.duration_ns, stats])
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            out["planes"].append({"name": p.name, "lines": lines})
    return out


class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def from_excerpt(d: dict):
    """A ``ProfileData``-shaped object from :func:`excerpt`'s JSON."""
    return _Obj(planes=[_Obj(name=p["name"], lines=[_Obj(
        name=l["name"], events=[_Obj(name=n, start_ns=s, duration_ns=t,
                                     stats=list(st.items()))
                                for n, s, t, st in l["events"]])
        for l in p["lines"]]) for p in d["planes"]])
