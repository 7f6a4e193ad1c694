"""The paper's toy model as a real distributed JAX application.

Runs the blocked Jacobi solver on a mesh of every device JAX sees, under the
two block→device schedules (locality/contiguous vs scattered/round-robin),
verifies both give identical physics, and compares their compiled collective
traffic — the TPU-tier version of the paper's local-vs-nonlocal access
measurement.  On a CPU host, ask for virtual devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/stencil_locality.py
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import build_assignment
from repro.kernels.jacobi.ref import jacobi_sweep_ref
from repro.roofline.hlo_cost import analyze_text
from repro.stencil.jacobi import (JacobiGridConfig, make_contiguous_sweep,
                                  make_scattered_sweep, reassemble_scattered,
                                  run_runtime_sweep, scatter_lattice)

SLABS_PER_DEV = 4          # slabs of 10 rows


def main():
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    cfg = JacobiGridConfig(ni=10 * SLABS_PER_DEV * n_dev, nj=48, nk=64)
    rng = np.random.default_rng(0)
    f = jnp.asarray(rng.standard_normal((cfg.ni, cfg.nj, cfg.nk)), jnp.float32)
    c = jnp.float32(1 / 6)

    # the schedule builder chooses contiguous slabs given block homes
    homes = np.repeat(np.arange(n_dev), SLABS_PER_DEV)
    assign = build_assignment(homes, np.ones(len(homes)), n_dev)
    print(f"schedule: locality={assign.locality_fraction:.0%} "
          f"imbalance={assign.imbalance:.1%} moved={assign.moved}")

    ref = jacobi_sweep_ref(f)
    with jax.set_mesh(mesh):
        fs = jax.device_put(f, NamedSharding(mesh, P("data", None, None)))
        contig = jax.jit(make_contiguous_sweep(cfg))
        out = contig(fs, c)
        err_c = float(jnp.max(jnp.abs(out - ref)))
        coll_c = sum(analyze_text(
            contig.lower(fs, c).compile().as_text()).coll.values())

        bpd = 2
        scat = jax.jit(make_scattered_sweep(cfg, blocks_per_dev=bpd))
        fs2 = jax.device_put(scatter_lattice(f, n_dev, bpd),
                             NamedSharding(mesh, P("data", None, None)))
        out2 = reassemble_scattered(scat(fs2, c), n_dev, bpd)
        err_s = float(jnp.max(jnp.abs(out2 - ref)))
        coll_s = sum(analyze_text(
            scat.lower(fs2, c).compile().as_text()).coll.values())

    # the same sweep as *online* runtime tasks: slabs homed contiguously on
    # 4 domains, scheduled by the paper's locality queues (repro.runtime)
    out_rt, rt = run_runtime_sweep(f, di=10, num_domains=4,
                                   workers_per_domain=2)
    err_r = float(jnp.max(jnp.abs(out_rt - ref)))

    print(f"contiguous (locality) : err={err_c:.1e} "
          f"collective={coll_c/1024:.0f} KiB/dev")
    print(f"scattered (oblivious) : err={err_s:.1e} "
          f"collective={coll_s/1024:.0f} KiB/dev")
    print(f"runtime    (online)   : err={err_r:.1e} "
          f"local={rt.local_fraction:.0%} steals={rt.stolen}")
    print(f"-> locality schedule moves {coll_s/max(coll_c,1):.0f}x fewer "
          f"bytes across domains for the same answer")


if __name__ == "__main__":
    main()
