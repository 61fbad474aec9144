"""The harness finds cells, mixes and metrics by name, and reads metrics."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import run

BENCH = Path(__file__).resolve().parents[1]


def test_every_cell_resolves_to_files():
    bench = run.load_json(BENCH.parent / "BENCHMARK.json")
    for w in bench["workloads"]:
        c = run.resolve(bench, w["name"])
        assert c["limits"], f"{w['name']} has no limits file"
        names = [m["name"] for m in c["end_to_end"] + c["per_layer"]]
        assert "setup_s" in names
        for m in c["end_to_end"] + c["per_layer"]:
            assert callable(run.load_reader(m["name"]))
        for m in c["per_layer"]:
            assert m["moves"] in [e["name"] for e in c["end_to_end"]]


def test_a_cell_added_by_files_alone_is_listed(tmp_path):
    """A new mix, metric and cell: new files and entries, no edit."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    bench = run.load_json(BENCH.parent / "BENCHMARK.json")
    mix = run.load_json(BENCH / "traffic" / "solve_restarts.json")
    mix["request"]["max_outer"] = 20
    (tmp_path / "bench" / "traffic" / "long_solves.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "kkt_last.long.py").write_text(
        "def read(record):\n    return None\n")
    bench["workloads"].append({"name": "uber-r16.long", "config": "uber-r16",
                               "traffic": "long_solves", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "kkt_last.long", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "driver", "moves": "sweep_s",
                               "workloads": ["uber-r16.long"]})
    bench["end_to_end"][0]["workloads"].append("uber-r16.long")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = subprocess.run([sys.executable, "bench/run.py", "--list"],
                         cwd=tmp_path, capture_output=True, text=True,
                         check=True).stdout
    line = [s for s in out.splitlines() if s.startswith("uber-r16.long")]
    assert line and "mix long_solves (closed loop" in line[0]
    assert "kkt_last.long" in line[0] and "sweep_s" in line[0]


def test_a_metric_is_read_by_its_full_name_else_its_first_part(
        tmp_path, monkeypatch):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "idle_share.py").write_text(
        "def read(record):\n    return 1\n")
    (tmp_path / "metrics" / "idle_share.x.py").write_text(
        "def read(record):\n    return 2\n")
    monkeypatch.setattr(run, "HERE", tmp_path)
    assert run.load_reader("idle_share.x")({}) == 2
    assert run.load_reader("idle_share.y")({}) == 1


def test_no_program_no_result(tmp_path):
    """A checkout of the benchmark's files alone exits non-zero, silent."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "uber-r16.solve", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True)
    assert p.returncode != 0 and p.stdout.strip() == ""


def _record():
    return {
        "seconds": 10.0, "window_s": 12.0, "setup_s": 30.0, "sweeps": 24,
        "compiles_in_window": 7,
        "solves": [{"n_outer": 5, "inner": 200}, {"n_outer": 5,
                                                   "inner": 200}],
        "work": {"nnz": 1000, "dims": [10, 20, 30], "rank": 4},
        "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        "trace": {"window_s": 4.0, "busy_s": 1.0,
                  "modules": {"jit__update(3)": 0.5, "jit_sort": 0.2}},
    }


@pytest.mark.parametrize("name,want", [
    ("setup_s", 30.0),
    ("sweep_s", 0.5),
    ("idle_share.solve", 75.0),
    ("compiles_in_window.solve", 7),
])
def test_readers(name, want):
    assert run.load_reader(name)(_record()) == pytest.approx(want)


def test_readers_of_an_empty_trace_read_nothing():
    r = _record()
    r["trace"] = None
    assert run.load_reader("idle_share.solve")(r) is None
    assert run.load_reader("phi_roofline.solve")(r) is None


def test_phi_roofline_reads_only_mode_updates():
    import counts
    r = _record()
    f, b = counts.solve_work(1000, [10, 20, 30], 4, 5, 200)
    least, bound = counts.least_time(2 * f, 2 * b, r["peaks"])
    assert run.load_reader("phi_roofline.solve")(r) == pytest.approx(
        100 * least / 0.5)
    r["trace"]["modules"] = {"jit_sort": 0.2}
    assert run.load_reader("phi_roofline.solve")(r) is None
