"""`BENCHMARK.json` and the files it names, the peak table, and a run on a
machine with no TPU. Nothing here describes or touches a TPU."""
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import bench_trace  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    assert len(w["why"]) <= 200
    c = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    cfg = json.loads((ROOT / c["file"]).read_text())
    assert cfg["name"] == c["name"] and cfg["chips"] == w["chips"]
    assert set(c["reduced"]) <= set(cfg["reduced"])
    assert all(k in cfg for k in c["reduced"])
    mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    assert mix["name"] == w["traffic"] and mix["loop"] == "closed"
    for m in BENCH["per_layer"]:
        if cell in m.get("workloads", CELLS):
            path = HERE / "metrics" / f"{m['name']}.py"
            spec = importlib.util.spec_from_file_location("m", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            assert callable(mod.read)


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_metric_cells_report_what_it_moves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        reporting = moved.get("workloads", CELLS)
        assert set(m.get("workloads", CELLS)) <= set(reporting), m["name"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for cell in CELLS:
        assert any(cell in m.get("workloads", CELLS)
                   for m in BENCH["per_layer"])


def test_peak_table():
    v5e = bench_trace.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        bench_trace.peaks("TPU v99")


def _bench_only(tmp_path):
    """A directory holding only `BENCHMARK.json` and the files under
    `paths`."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _python(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_cpu_run_exits_nonzero_without_result(tmp_path):
    # the chip check comes before the program is needed, so the run needs
    # no more than the benchmark's own files
    cwd = _bench_only(tmp_path)
    p = _python(cwd, "benchmarks/chip/run.py", "--workload", CELLS[0],
                "--seed", "2147483999", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
    assert not (cwd / ".jax_cache").exists()   # no chip: no cache


def test_run_outside_a_checkout_exits_nonzero(tmp_path):
    # past the chip check, the program is not there to import
    code = ("import sys; sys.path.insert(0, 'benchmarks/chip'); "
            "import jax, bench_harness as H; "
            f"H.run_cell({CELLS[0]!r}, 1, 1.0, False, "
            "devices=jax.devices()[:1])")
    q = _python(_bench_only(tmp_path), "-c", code)
    assert q.returncode != 0
    assert q.stdout.strip() == ""
    assert "repro" in q.stderr
