"""Readings that set a cell's limits, in one process.

    python3 bench/calibrate.py --workload uber-r16.solve --seeds 11 12 13 --control 3

One short window per seed at the cell's own size and load, each printing
the numbers compared (the program against the float32 reference) and, for
the first ``--control`` seeds, the control's (the bfloat16 reference
against the float32 one).  The limit of each number is set between the
largest sound reading and the smallest control reading.

Like ``run.py``, it runs only on a TPU.  It is not part of a benchmark run.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    c = run.resolve(bench, args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    device, err = run.check_device(c["cell"]["chips"],
                                   run.load_json(run.HERE / "peaks.json"))
    if err:
        print(f"calibrate.py: {err}", file=sys.stderr)
        return 1
    run.enable_cache()
    print(f"device: {device}", flush=True)
    readings = []
    for k, seed in enumerate(args.seeds):
        t0 = time.time()
        rec = run.run_cell(c, seed, args.seconds, False,
                           control=k < args.control, t_start=t0)
        row = {"seed": seed, "attempted": rec["attempted"],
               "failed": rec["failed"], **rec["checks"]}
        readings.append(row)
        print(f"reading {json.dumps(row)} ({time.time() - t0:.1f} s)",
              flush=True)
    for key in sorted({k for r in readings for k in r}):
        if key.startswith("control."):
            vals = [r[key] for r in readings if key in r]
            print(f"summary {key}: smallest {min(vals)!r} of {vals}")
        elif key not in ("seed", "attempted", "failed"):
            vals = [r[key] for r in readings if key in r]
            print(f"summary {key}: largest {max(vals)!r} of {vals}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
