"""The two SPMD Jacobi sweeps on four virtual CPU devices: each runs the
Pallas kernel (interpreted here) once per device, with the slab-face halo
planes as its operands, and gives the reference's bits.  The slabs are cut
so that the block's rows fall below the paper's 10.  It runs in a child
process, since the device count is fixed when JAX starts."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

CHILD = r'''
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels.jacobi.ref import jacobi_sweep_ref
from repro.stencil.jacobi import (JacobiGridConfig, make_contiguous_sweep,
                                  make_scattered_sweep, reassemble_scattered,
                                  scatter_lattice)

mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
rows = NamedSharding(mesh, P("data", None, None))
out = {}
# (ni, nj, nk), blocks a device in the scattered sweep, c
for shape, bpd, c in [((32, 16, 128), 2, 1 / 6),   # slabs of 8 and 4 rows
                      ((96, 24, 32), 4, 1 / 6),    # 24 and 6 rows
                      ((48, 12, 20), 3, 0.25)]:    # 12 and 4 rows, nj not 8k
    cfg = JacobiGridConfig(*shape)
    f = jnp.asarray(np.random.default_rng(list(shape)).standard_normal(shape),
                    jnp.float32)
    ref = np.asarray(jacobi_sweep_ref(f, c))
    cc = jnp.float32(c)
    with jax.set_mesh(mesh):
        contig = jax.jit(make_contiguous_sweep(cfg))
        fs = jax.device_put(f, rows)
        y = np.asarray(contig(fs, cc))
        text = str(jax.make_jaxpr(contig)(fs, cc))
        out["contiguous-%d" % shape[0]] = {
            "equal": bool((y == ref).all()), "max_err": float(abs(y - ref).max()),
            "kernel": "pallas_call" in text,
            "padded": "f32[%d,%d,%d]" % (shape[0] // 4 + 2, *shape[1:]) in text}
        scat = jax.jit(make_scattered_sweep(cfg, bpd))
        fs = jax.device_put(scatter_lattice(f, 4, bpd), rows)
        y = np.asarray(reassemble_scattered(scat(fs, cc), 4, bpd))
        text = str(jax.make_jaxpr(scat)(fs, cc))
        out["scattered-%d" % shape[0]] = {
            "equal": bool((y == ref).all()), "max_err": float(abs(y - ref).max()),
            "kernel": "pallas_call" in text,
            "padded": "f32[%d,%d,%d]" % (shape[0] // (4 * bpd) + 2, *shape[1:])
                      in text}
print(json.dumps(out))
'''

CASES = [f"{s}-{n}" for s in ("contiguous", "scattered") for n in (32, 96, 48)]


@pytest.fixture(scope="module")
def sweeps():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", CHILD], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
def test_sweep_gives_the_reference_bits(sweeps, case):
    assert sweeps[case]["equal"], sweeps[case]


@pytest.mark.parametrize("case", CASES)
def test_sweep_runs_the_kernel_on_unpadded_slabs(sweeps, case):
    assert sweeps[case]["kernel"] and not sweeps[case]["padded"], sweeps[case]
