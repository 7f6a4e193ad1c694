"""The four-chip scattered-placement stencil cell, whole, on four virtual CPU
devices at a tiny lattice: correct as it is, traced as well as not, and not
correct with the halo planes zeroed.  It runs in a child process, since the
device count is fixed when JAX starts."""
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
import jax
import jax.numpy as jnp
from bench import harness as H
from bench.tests import tiny

root = tiny.tiny_root(Path(sys.argv[2]))
m = H.load_json(root / "BENCHMARK.json")
for w in m["workloads"]:
    if w["name"] == "jacobi_spmd_scattered_4chip":
        w["chips"] = 4
(root / "BENCHMARK.json").write_text(json.dumps(m))
# 16 slabs of 8 rows: a sampled block of 4 + 2 rows fits inside one
tiny._patch(root / "bench" / "configs" / "jacobi-2400x600x600-f32.json",
            {"ni": 128})
tiny._patch(root / "bench" / "traffic" / "spmd_scattered_sweeps.json",
            tiny.STENCIL_TRAFFIC)
cell = "jacobi_spmd_scattered_4chip"
out = {"sound": tiny.run_cell(root, cell, seed=2**33 + 3)}
out["traced"] = tiny.run_cell(root, cell, seed=2**33 + 5, trace=1)
gather = jax.lax.all_gather
jax.lax.all_gather = lambda x, axis, **kw: jnp.zeros_like(gather(x, axis, **kw))
out["no_halo"] = tiny.run_cell(root, cell, seed=2**33 + 3)
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, str(H.ROOT),
                        str(tmp_path_factory.mktemp("scattered"))],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_scattered_cell_runs_correct_on_four_devices(runs):
    out = runs["sound"]
    assert out["correct"], out
    assert out["device"]["count"] == 4 and out["failed"] == 0
    assert set(out["metrics"]) == {"mlups", "setup_s"}
    assert out["checks"]["last_sweep_rel_err"]["value"] <= 2e-5
    assert out["checks"]["sampled_sweep_rel_err"]["value"] <= 2e-5


def test_scattered_cell_with_the_halos_zeroed_is_not_correct(runs):
    out = runs["no_halo"]
    assert not out["correct"]
    assert out["checks"]["last_sweep_rel_err"]["value"] > 2e-5


def test_scattered_cell_traced_reports_the_whole_sweep_share(runs):
    out = runs["traced"]
    assert out["correct"], out
    # the CPU trace holds no device plane: the device reader stays silent
    assert 0 < out["metrics"]["stencil_mfu"]["value"] <= 100
    assert "idle_share.stencil" not in out["metrics"]
