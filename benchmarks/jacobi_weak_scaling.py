"""Beyond-paper: the distributed Jacobi sweep's collective traffic under
locality (contiguous) vs locality-oblivious (scattered) block assignment,
measured from compiled HLO at increasing device counts.

This is the paper's central claim transplanted to the TPU tier: the
nonlocal-traffic gap grows linearly with blocks-per-device for the
scattered schedule while staying constant for the locality schedule.

The figures are bytes counted from compiled HLO, not times, so each device
count runs in a child process pinned to the CPU (``JAX_PLATFORMS=cpu``,
virtual host devices); no child touches an accelerator.  A child that fails
fails the run.  Emits CSV:
devices,schedule,collective_bytes_per_dev,ratio_vs_contiguous,platform
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(n)d"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.stencil.jacobi import (JacobiGridConfig, make_contiguous_sweep,
                                  make_scattered_sweep, scatter_lattice)
from repro.roofline.hlo_cost import analyze_text

n = %(n)d
mesh = jax.make_mesh((n,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
cfg = JacobiGridConfig(ni=16 * n, nj=24, nk=32)
f = jnp.zeros((cfg.ni, cfg.nj, cfg.nk), jnp.float32)
c = jnp.float32(1/6)
out = {}
with jax.set_mesh(mesh):
    fs = jax.device_put(f, NamedSharding(mesh, P("data", None, None)))
    txt = jax.jit(make_contiguous_sweep(cfg)).lower(fs, c).compile().as_text()
    out["contiguous"] = sum(analyze_text(txt).coll.values())
    bpd = 4
    fs2 = jax.device_put(scatter_lattice(f, n, bpd),
                         NamedSharding(mesh, P("data", None, None)))
    txt2 = jax.jit(make_scattered_sweep(cfg, blocks_per_dev=bpd)).lower(fs2, c).compile().as_text()
    out["scattered"] = sum(analyze_text(txt2).coll.values())
print("RESULT " + json.dumps(out))
"""


def main(device_counts=(4, 8)) -> list[str]:
    lines = ["devices,schedule,collective_bytes_per_dev,ratio_vs_contiguous,"
             "platform"]
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src") + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    for n in device_counts:
        proc = subprocess.run([sys.executable, "-c", _CHILD % {"n": n}],
                              env=env, capture_output=True, text=True,
                              timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(f"{n}-device child failed "
                               f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}")
        res = next(json.loads(ln[len("RESULT "):])
                   for ln in proc.stdout.splitlines()
                   if ln.startswith("RESULT "))
        ratio = res["scattered"] / max(res["contiguous"], 1)
        lines.append(f"{n},contiguous,{res['contiguous']:.0f},1.0,cpu-hlo")
        lines.append(f"{n},scattered,{res['scattered']:.0f},{ratio:.1f},cpu-hlo")
    return lines


if __name__ == "__main__":
    for line in main():
        print(line)
