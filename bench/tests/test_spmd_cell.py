"""The four-chip SPMD stencil cell, whole, on four virtual CPU devices at a
tiny lattice: correct as it is, traced as well as not, and not correct
with the halo planes zeroed.  It runs in a child process, since the device count is fixed when
JAX starts."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import harness as H

CHILD = r'''
import json, sys
from pathlib import Path
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax.numpy as jnp
from bench import harness as H
from bench.tests import tiny
from repro.stencil import jacobi

root = tiny.tiny_root(Path(sys.argv[2]))
m = H.load_json(root / "BENCHMARK.json")
for w in m["workloads"]:
    if w["name"] == "jacobi_spmd_4chip":
        w["chips"] = 4
(root / "BENCHMARK.json").write_text(json.dumps(m))
tiny._patch(root / "bench" / "traffic" / "spmd_sweeps.json", tiny.STENCIL_TRAFFIC)
out = {"sound": tiny.run_cell(root, "jacobi_spmd_4chip", seed=2**33 + 3)}
out["traced"] = tiny.run_cell(root, "jacobi_spmd_4chip", seed=2**33 + 5, trace=1)
jacobi._halo_exchange = lambda local, axis: (jnp.zeros_like(local[-1]),
                                             jnp.zeros_like(local[0]))
out["no_halo"] = tiny.run_cell(root, "jacobi_spmd_4chip", seed=2**33 + 3)
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, str(H.ROOT),
                        str(tmp_path_factory.mktemp("spmd"))],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_spmd_cell_runs_correct_on_four_devices(runs):
    out = runs["sound"]
    assert out["correct"], out
    assert out["device"]["count"] == 4 and out["failed"] == 0
    assert set(out["metrics"]) == {"mlups", "setup_s"}
    assert out["checks"]["last_sweep_rel_err"]["value"] <= 2e-5


def test_spmd_cell_with_the_halo_zeroed_is_not_correct(runs):
    out = runs["no_halo"]
    assert not out["correct"]
    assert out["checks"]["last_sweep_rel_err"]["value"] > 2e-5


def test_spmd_cell_traced_reports_the_whole_sweep_share(runs):
    out = runs["traced"]
    assert out["correct"], out
    # the CPU trace holds no device plane: the device readers stay silent
    assert 0 < out["metrics"]["stencil_mfu"]["value"] <= 100
    assert not {"idle_share.stencil", "collective_share.spmd"} & set(out["metrics"])
