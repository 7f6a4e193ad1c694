"""Every cell, whole, at a size the CPU holds (Pallas in interpret mode)."""
from __future__ import annotations

import pytest

from bench.tests import tiny

CELLS = ["jacobi_kernel", "serve_sessions"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("cells"))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(root, cell):
    out = tiny.run_cell(root, cell, seed=2**33 + 5, seconds=1.0)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2
    assert out["device"]["platform"] == "cpu"
    for name, check in out["checks"].items():
        assert check["value"] <= check["limit"], name


@pytest.mark.parametrize("cell", ["serve_sessions"])
def test_traced_run_reports_its_span_metrics(root, cell):
    out = tiny.run_cell(root, cell, seed=3, seconds=5.0, trace=1)
    assert out["correct"], out
    assert out["device"]["window_s"] > 0
    assert out["metrics"]["queue_wait_p90_ms.serve"]["value"] > 0
    assert out["metrics"]["itl_p90_ms.serve"]["value"] > 0
