"""From a profiler trace to busy time, idle gaps and time per operation.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``load`` reads it into plain tuples ``(name, start_ns, end_ns)``: the host
thread lines named ``python...`` (the benchmark's ``bench.*`` spans and
JAX's own dispatch spans) and, for each device plane (``/device:...``), its
``XLA Ops`` and ``XLA Modules`` lines.  ``XLA Ops`` nests: a ``while`` op
spans the ops of its body.  ``reduce`` then works on those tuples only, so a
test can hand it events written by hand.

Within the window (the host span named ``bench.window``):

* busy: the union of the device's op intervals, averaged over the devices
  that ran any op;
* ops: the self time of each op name (its events' time less that of the
  ops nested in them), and modules: the time of each program, both summed
  over the events and averaged over those devices;
* idle gaps: the stretches of the window in which no op ran, each named by
  the innermost host span that covers its midpoint (what the host was
  doing meanwhile).
"""
from __future__ import annotations

import glob
import os

import numpy as np

WINDOW = "bench.window"
OPS, MODULES = "XLA Ops", "XLA Modules"
NAMED_GAPS = 200  # gaps named one by one; the shorter rest are lumped


def find(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    host, devices = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name.startswith("python"):
                    host += [(e.name, e.start_ns, e.end_ns)
                             for e in line.events]
        elif plane.name.startswith("/device:"):
            lines = {line.name: [(e.name, e.start_ns, e.end_ns)
                                 for e in line.events]
                     for line in plane.lines if line.name in (OPS, MODULES)}
            if lines.get(OPS):
                devices[plane.name] = lines
    return {"host": host, "devices": devices}


def _union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _self_times(events):
    """``(name, self time)`` of each event of a line whose events nest."""
    out, stack = [], []  # stack: [name, end, self time]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()[::2]))
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    return out + [tuple(x[::2]) for x in stack]


def _namer(host):
    """A function naming the shortest host span that covers a time."""
    spans = [(n, s, e) for n, s, e in host if n != WINDOW]
    names = [n for n, _, _ in spans]
    start = np.array([s for _, s, _ in spans], np.float64)
    end = np.array([e for _, _, e in spans], np.float64)

    def name(t: float) -> str:
        cover = np.flatnonzero((start <= t) & (end >= t))
        if not cover.size:
            return "(no host span)"
        return names[cover[np.argmin(end[cover] - start[cover])]]

    return name


def reduce(events: dict, top: int = 10) -> dict | None:
    """Busy and idle seconds, device time by op and module, named gaps.

    Returns None where the trace has no window span or no device op.
    """
    win = [(s, e) for n, s, e in events["host"] if n == WINDOW]
    if not win or not events["devices"]:
        return None
    lo, hi = win[0]
    busy, ops, modules, gaps = [], {}, {}, []
    for lines in events["devices"].values():
        op_ev = _clip(lines.get(OPS, []), lo, hi)
        if not op_ev:
            continue
        merged = _union((s, e) for _, s, e in op_ev)
        busy.append(sum(e - s for s, e in merged))
        for name, t in _self_times(op_ev):
            ops[name] = ops.get(name, 0) + t
        for name, s, e in _clip(lines.get(MODULES, []), lo, hi):
            modules[name] = modules.get(name, 0) + (e - s)
        edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not busy:
        return None
    n_dev = len(busy)
    name_at = _namer([ev for ev in events["host"]
                      if ev[2] > lo and ev[1] < hi])
    gaps.sort(key=lambda g: g[0] - g[1])
    by_host: dict = {}
    for i, (s, e) in enumerate(gaps):
        name = (name_at((s + e) / 2) if i < NAMED_GAPS else "(shorter gaps)")
        by_host[name] = by_host.get(name, 0) + (e - s)

    def table(d: dict, k: int) -> list:
        rows = sorted(d.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / n_dev / 1e9] for name, ns in rows]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "n_devices": n_dev,
        "ops": {k: v / n_dev / 1e9 for k, v in ops.items()},
        "modules": {k: v / n_dev / 1e9 for k, v in modules.items()},
        "device_ops": table(ops, top),
        "idle_gaps": table(by_host, top),
    }
