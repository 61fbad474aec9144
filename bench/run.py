"""Run one cell of the benchmark once, on the chip, and print its result.

    python3 bench/run.py --workload chicago-r16.solve --seed 7 --seconds 30 --trace 0
    python3 bench/run.py --list

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``), checked against the limits in
``bench/limits/<cell>.json``.  Each metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, a configuration, a mix or a
metric adds files and entries; nothing here changes.

The run refuses to start without a TPU, or with fewer chips than the cell
asks for, or on a chip that ``bench/peaks.json`` does not list.  It makes
its data from ``--seed``, warms up the cell's shapes, measures for
``--seconds`` (with ``--trace 1``, under the profiler, and then reports the
per-layer metrics instead of the end-to-end ones), checks a sample of the
window's answers against ``bench/reference.py``, and prints one JSON line
last.  ``bench/calibrate.py`` reads the readings that set the limits.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = HERE / ".jax_cache"
TRACE_DIR = HERE / ".trace"
sys.path.insert(0, str(HERE))


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str):
    """The ``read(record)`` of ``bench/metrics/<name>.py``, or where there
    is no such file, of the file of the name's first part: ``idle_share.x``
    is read by ``idle_share.py`` unless ``idle_share.x.py`` is there."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def resolve(bench: dict, name: str) -> dict:
    """A cell with its configuration, mix, limits and metrics, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(cells: {sorted(cells)})")
    cell = cells[name]
    limits = HERE / "limits" / f"{name}.json"
    return {
        "cell": cell,
        "config": load_json(HERE / "configs" / f"{cell['config']}.json"),
        "mix": load_json(HERE / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(limits) if limits.exists() else {},
        "end_to_end": cell_metrics(bench, name, False),
        "per_layer": cell_metrics(bench, name, True),
    }


def list_cells(bench: dict) -> None:
    for w in bench["workloads"]:
        c = resolve(bench, w["name"])
        print(f"{w['name']}: config {c['config']['tensor']} "
              f"nnz={c['config']['nnz']} R={c['config']['rank']}, mix "
              f"{w['traffic']} ({c['mix']['loop']} loop, entry "
              f"{c['mix']['entry']}), {w['chips']} chip(s); limits "
              f"{sorted(c['limits'])}; end to end "
              f"{[m['name'] for m in c['end_to_end']]}; per layer "
              f"{[m['name'] for m in c['per_layer']]}")


def check_device(chips: int, peaks: dict):
    """The chip's description, or an error message where it will not do."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        return None, f"no TPU found (jax platform {d.platform!r})"
    if len(devices) < chips:
        return None, f"the cell needs {chips} chips, found {len(devices)}"
    if d.device_kind not in peaks:
        return None, (f"device kind {d.device_kind!r} is not in "
                      f"bench/peaks.json ({sorted(peaks)})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}, None


def enable_cache() -> None:
    """JAX's persistent cache at a fixed path in the checkout, for every
    program, so that only a cell's first run in a checkout compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(c: dict, seed: int, seconds: float, trace: bool,
             control: bool = False, t_start: float = T_START) -> dict:
    """Drive the cell once; returns its record."""
    import drive

    mix, config, name = c["mix"], c["config"], c["cell"]["name"]
    trace_dir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = str(TRACE_DIR)
    if mix["loop"] != "closed":
        raise ValueError(f"mix {c['cell']['traffic']!r}: no driver for a "
                         f"{mix['loop']!r} loop")
    record = drive.solves(config, mix, seed, seconds, trace_dir, t_start,
                          control)
    record.update(cell=name, seconds=seconds, config=config, mix=mix)
    if trace:
        import trace_reduce

        path = trace_reduce.find(trace_dir)
        record["trace"] = (trace_reduce.reduce(trace_reduce.load(path))
                           if path else None)
    return record


def judge(checks: dict, limits: dict) -> tuple:
    """``(correct, table)``: each number compared beside its limit."""
    table, correct = {}, bool(limits)
    for key, lim in limits.items():
        if not isinstance(lim, dict):  # a note beside the limits
            continue
        value = checks.get(key)
        ok = value is not None and math.isfinite(value) \
            and value <= lim["limit"]
        correct &= ok
        table[key] = {"value": value, "limit": lim["limit"]}
    return correct, table


def result_line(c: dict, record: dict, device: dict, trace: bool,
                peaks: dict) -> dict:
    record["peaks"] = peaks[device["kind"]]
    metrics = {}
    for m in c["per_layer" if trace else "end_to_end"]:
        value = load_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct, table = judge(record["checks"], c["limits"])
    device = dict(device, memory_peak_bytes=record["memory_peak_bytes"])
    out = {"correct": correct and record["failed"] == 0,
           "attempted": record["attempted"], "failed": record["failed"],
           "metrics": metrics, "device": device}
    if trace and record.get("trace"):
        tr = record["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = table
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--list", action="store_true",
                    help="list the cells as found from their files")
    args = ap.parse_args(argv)
    bench_file = ROOT / "BENCHMARK.json"
    if not bench_file.exists():
        print(f"run.py: {bench_file} not found", file=sys.stderr)
        return 2
    bench = load_json(bench_file)
    if args.list:
        list_cells(bench)
        return 0
    if args.workload is None:
        ap.error("--workload is required")
    c = resolve(bench, args.workload)
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]

    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"run.py: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    peaks = load_json(HERE / "peaks.json")
    device, err = check_device(c["cell"]["chips"], peaks)
    if err:
        print(f"run.py: {err}", file=sys.stderr)
        return 1
    print(f"device: {device}", flush=True)
    enable_cache()
    record = run_cell(c, args.seed, seconds, bool(args.trace))
    out = result_line(c, record, device, bool(args.trace), peaks)
    for key, row in out["checks"].items():
        print(f"check {key}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
