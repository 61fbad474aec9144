"""Shared set-up of the benchmark's tests: tiny cells on the CPU.

Run them with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402

TINY_DIMS = {"chicago": [40, 8, 12, 9], "uber": [20, 8, 30, 40]}


def tiny_cell(name: str) -> dict:
    """The cell as ``run.resolve`` finds it, cut to a size the CPU runs in
    seconds; its limits are the cell's own."""
    c = run.resolve(run.load_json(BENCH.parent / "BENCHMARK.json"), name)
    cfg, mix = c["config"], c["mix"]
    cfg["dims"] = TINY_DIMS[cfg["tensor"]]
    cfg["nnz"] = 3000
    mix["request"]["max_outer"] = 3
    return c


def run_tiny(c: dict, seed: int = 12345678901, control: bool = False) -> tuple:
    """Drive a tiny cell once, past the chip check: ``(record, line)``."""
    import time

    record = run.run_cell(c, seed, 1.0, False, control,
                          t_start=time.time())
    device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    return record, run.result_line(c, record, device, False,
                                   run.load_json(BENCH / "peaks.json"))
