"""BENCHMARK.json against the contract, and every file it names."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness as H
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MANIFEST = H.load_json(H.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert MANIFEST["paths"] == ["bench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51


def test_names_units_and_bounds():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + [
        c["name"] for c in MANIFEST["configs"]]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_every_file_by_name(cell):
    c = H.find_cell(H.ROOT, cell)
    assert hasattr(c.driver(), "setup")
    e2e = [m["name"] for m in c.metrics("end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = c.metrics("per_layer")
    assert per_layer
    for m in per_layer:
        assert hasattr(c.reader(m["name"]), "read")
        assert m["moves"] in e2e
    assert c.chips in (1, 4)
    assert c.config["name"] == c.workload["config"]


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_run_seconds_fit_a_full_check_of_24_cells():
    runs = 2 + 14 * 24
    total = runs * (MANIFEST["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_config_files_state_source_and_limits():
    for c in MANIFEST["configs"]:
        conf = H.load_json(H.ROOT / c["file"])
        assert conf["reduced"] == c["reduced"] and conf["limits"]
        assert (H.ROOT / conf["reference"]).is_file()


def test_peaks_table_refuses_an_unknown_device():
    assert H.peaks_for(H.ROOT, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        H.peaks_for(H.ROOT, "cpu")


def test_run_refuses_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(H.ROOT / "bench" / "run.py"),
                        "--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "correct" not in p.stdout


DUMMY_DRIVER = '''
from bench import harness as H
import jax.numpy as jnp


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.x = jnp.arange(ctx.cell.config["n"], dtype=jnp.float32)

    def window(self, seconds):
        y = (self.x * 2).block_until_ready()
        self.y = y
        return H.Window(seconds=seconds, attempted=1, failed=0,
                        metrics={"dummy_rate": float(y.sum())})

    def release(self):
        pass

    def check(self):
        return {"dummy_err": (float(abs(self.y - 2 * self.x).max()), 0.0)}


def setup(ctx):
    return Cell(ctx)
'''


def test_a_cell_added_as_files_alone_is_found_and_runs(tmp_path):
    root = tiny.tiny_root(tmp_path)
    b = root / "bench"
    (b / "configs" / "dummy-cfg.json").write_text(json.dumps(
        {"name": "dummy-cfg", "reduced": [], "n": 8,
         "reference": "bench/reference/jacobi.py", "limits": {"dummy_err": 0}}))
    (b / "traffic" / "dummy_mix.json").write_text(json.dumps({"driver": "dummy"}))
    (b / "drivers" / "dummy.py").write_text(DUMMY_DRIVER)
    (b / "metrics" / "dummy_share.py").write_text(
        "def read(ctx, win, trace):\n    return 42.0\n")
    m = H.load_json(root / "BENCHMARK.json")
    m["configs"].append({"name": "dummy-cfg", "source": "https://example.org",
                         "file": "bench/configs/dummy-cfg.json", "reduced": [],
                         "why": "a test"})
    m["workloads"].append({"name": "dummy_cell", "config": "dummy-cfg",
                           "traffic": "dummy_mix", "chips": 1, "why": "a test"})
    m["end_to_end"].append({"name": "dummy_rate", "unit": "1/s", "better": "higher",
                            "bound": 0.1, "source": "host_clock",
                            "workloads": ["dummy_cell"]})
    m["per_layer"].append({"name": "dummy_share", "unit": "%", "better": "higher",
                           "source": "program_counter", "layer": "dummy",
                           "moves": "dummy_rate", "workloads": ["dummy_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    out = tiny.run_cell(root, "dummy_cell", trace=0)
    assert out["correct"] and out["metrics"]["dummy_rate"]["value"] == 56.0
    assert set(out["metrics"]) == {"dummy_rate", "setup_s"}
    out = tiny.run_cell(root, "dummy_cell", trace=1)
    assert out["metrics"] == {"dummy_share": {"value": 42.0, "unit": "%"}}
    assert list(out)[-1] == "checks"
