"""The program's spans, counters and named device ops in the traced window.

The program marks its layers on the profiler's clock: host spans
``cpapr.*`` (``jax.profiler.TraceAnnotation``; counters ride on them as
attributes) and device scopes ``cpapr.*`` (``jax.named_scope``, which
reaches each device op's ``tf_op``).  ``load`` reads the newest trace
under ``bench/.trace`` (``trace_reduce.find``) into a window:

* ``window``: ``(start_ns, end_ns)`` of the host span ``bench.window``;
* ``host``: the events ``(name, start_ns, end_ns, stats)`` of the host's
  ``python`` lines (``ProfileData``), clipped to the window;
* ``devices``: per device plane, ``ops``, its ``XLA Ops`` events
  ``(tf_op, start_ns, end_ns)``, and ``modules``, its ``XLA Modules``
  events ``(name, start_ns, end_ns)``, clipped to the window.  ``tf_op``
  is a stat of each op's event *metadata*, which ``ProfileData`` does not
  expose, so ``device_events`` decodes the ``XSpace`` wire format itself.

Each file is parsed once per process; ``of(record)`` keeps the window on
the record, so every reader of a run shares it.  The readers work on the
window alone, so a test can hand them events written by hand.
"""
from __future__ import annotations

import functools
from pathlib import Path

import numpy as np

import trace_reduce

TRACE_DIR = Path(__file__).resolve().parent / ".trace"
PREFIX = "cpapr."
SOLVE = "bench.solve"


# --- the XSpace wire format (tensorflow/tsl/profiler/protobuf/xplane.proto)

def _varint(buf: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes):
    """``(field number, value)`` of a message: an int for a varint, bytes
    for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"wire type {kind} is not in XSpace")
        yield key >> 3, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def device_events(data: bytes) -> dict:
    """``{plane: {line: [(name, start_ns, end_ns)]}}`` of the device
    planes, each ``XLA Ops`` event named by its op's ``tf_op`` (its
    metadata name where it has none)."""
    out = {}
    for field, plane in _fields(data):
        if field != 1:  # XSpace.planes
            continue
        name, raw_lines, meta, stat_names = "", [], {}, {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 3:
                raw_lines.append(v)
            elif f in (4, 5):  # map entries: key 1, value 2
                entry = dict(_fields(v))
                (meta if f == 4 else stat_names)[entry[1]] = entry.get(2, b"")
        if not name.startswith("/device:"):
            continue
        stat_names = {k: dict(_fields(v)).get(2, b"").decode()
                      for k, v in stat_names.items()}
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        names = {k: _op_name(v, tf_op[0] if tf_op else None, stat_names)
                 for k, v in meta.items()}
        got = {}
        for raw in raw_lines:
            line = dict((f, v) for f, v in _fields(raw) if f != 4)
            lname = line.get(2, b"").decode()
            if lname not in (trace_reduce.OPS, trace_reduce.MODULES):
                continue
            t0 = _signed(line.get(3, 0))
            events = []
            for f, v in _fields(raw):
                if f == 4:
                    ev = dict(_fields(v))
                    s = t0 + _signed(ev.get(2, 0)) / 1e3
                    events.append((names.get(ev.get(1), ""), s,
                                   s + ev.get(3, 0) / 1e3))
            got[lname] = events
        if got.get(trace_reduce.OPS):
            out[name] = got
    return out


def _op_name(meta: bytes, tf_op_id, stat_names: dict) -> str:
    fields = list(_fields(meta))
    for f, v in fields:
        if f == 5 and tf_op_id is not None:  # XEventMetadata.stats
            stat = dict(_fields(v))
            if stat.get(1) == tf_op_id:
                if 5 in stat:
                    return stat[5].decode()
                if 7 in stat:  # a reference to a stat name
                    return stat_names.get(stat[7], "")
    return next((v.decode() for f, v in fields if f == 2), "")


# --- one window -------------------------------------------------------------

@functools.lru_cache(maxsize=2)
def parse(path: str) -> dict:
    """Host events with their stats and device events with their
    ``tf_op``, of one trace file."""
    from jax.profiler import ProfileData

    host = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name.startswith("python"):
                    host += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                             for e in line.events]
    devices = {
        plane: {"ops": lines.get(trace_reduce.OPS, []),
                "modules": lines.get(trace_reduce.MODULES, [])}
        for plane, lines in device_events(Path(path).read_bytes()).items()}
    return {"host": host, "devices": devices}


def window(events: dict) -> dict | None:
    """The events clipped to ``bench.window``; None without one."""
    win = [(s, e) for n, s, e, _ in events["host"]
           if n == trace_reduce.WINDOW]
    if not win:
        return None
    lo, hi = win[0]
    host = [(n, max(s, lo), min(e, hi), st) for n, s, e, st in events["host"]
            if e > lo and s < hi and n != trace_reduce.WINDOW]
    devices = {d: {k: trace_reduce._clip(v, lo, hi) for k, v in ev.items()}
               for d, ev in events["devices"].items()}
    return {"window": (lo, hi), "host": host, "devices": devices}


def load(trace_dir=TRACE_DIR) -> dict | None:
    path = trace_reduce.find(str(trace_dir))
    return window(parse(path)) if path else None


def of(record: dict) -> dict | None:
    """The traced run's window, loaded once per record; None untraced."""
    if "spans" not in record:
        record["spans"] = load() if record.get("trace") else None
    return record["spans"]


# --- what the readers take from a window ----------------------------------

def named(w: dict, name: str) -> list:
    return [ev for ev in w["host"] if ev[0] == name]


def scope(tf_op: str) -> str | None:
    """The innermost ``cpapr.*`` component of an op's name stack."""
    parts = [p.split(":")[0] for p in tf_op.split("/")]
    inner = [p for p in parts if p.startswith(PREFIX)]
    return inner[-1] if inner else None


def _busy_devices(w: dict) -> list:
    return [d for d in w["devices"].values() if d["ops"]]


def op_seconds(w: dict) -> dict:
    """Self seconds of the device ops by ``tf_op``, averaged over the
    devices that ran any op."""
    devs = _busy_devices(w)
    out: dict = {}
    for d in devs:
        for name, t in trace_reduce._self_times(d["ops"]):
            out[name] = out.get(name, 0.0) + t / 1e9 / len(devs)
    return out


def scope_seconds(w: dict) -> dict:
    """Self seconds of the device ops by innermost ``cpapr.*`` scope
    (None: no scope)."""
    out: dict = {}
    for name, t in op_seconds(w).items():
        out[scope(name)] = out.get(scope(name), 0.0) + t
    return out


def module_seconds(w: dict, module: str) -> float:
    """Device seconds of the programs ``module`` (``jit__update``)."""
    devs = _busy_devices(w)
    return sum(e - s for d in devs for n, s, e in d["modules"]
               if n.split("(")[0] == module) / 1e9 / max(len(devs), 1)


def idle_within(w: dict):
    """A function ``(start_ns, end_ns) -> idle ns`` in that stretch: the
    time no device op ran, averaged over the devices that ran any."""
    curves = []
    for d in _busy_devices(w):
        merged = np.array(trace_reduce._union((s, e) for _, s, e in d["ops"]),
                          np.float64).reshape(-1, 2)
        curves.append((merged[:, 0], merged[:, 1], np.concatenate(
            [[0.0], np.cumsum(merged[:, 1] - merged[:, 0])])))

    def busy_until(t: float, starts, ends, cum) -> float:
        k = int(np.searchsorted(starts, t, side="right"))
        return cum[k] - (max(0.0, ends[k - 1] - t) if k else 0.0)

    def idle(s: float, e: float) -> float:
        if not curves:
            return 0.0
        busy = sum(busy_until(e, *c) - busy_until(s, *c) for c in curves)
        return float((e - s) - busy / len(curves))

    return idle


def idle_by_span(w: dict) -> dict:
    """Idle seconds of the window by the innermost ``cpapr.*`` span over
    them, with ``bench.solve`` for idle time in a solve under no such span
    and ``(outside)`` for the rest."""
    idle = idle_within(w)
    spans = [ev for ev in w["host"] if ev[0].startswith(PREFIX) or
             ev[0] == SOLVE]
    out: dict = {}
    stack: list = []  # [name, end, self idle]

    def close():
        name, _, t = stack.pop()
        out[name] = out.get(name, 0.0) + t / 1e9

    for name, s, e, _ in sorted(spans, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            close()
        t = idle(s, e)
        if stack:
            stack[-1][2] -= t
        stack.append([name, e, t])
    while stack:
        close()
    lo, hi = w["window"]
    out["(outside)"] = idle(lo, hi) / 1e9 - sum(out.values())
    return out
