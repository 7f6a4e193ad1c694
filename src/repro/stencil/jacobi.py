"""The paper's blocked Jacobi solver as a distributed JAX application.

This is where the paper's locality story becomes measurable on a TPU mesh:
the lattice's i-axis is decomposed into slabs of blocks, and the
*block → device assignment* plays the role of page placement.

  * ``contiguous`` assignment (= the paper's parallel first touch +
    locality queues): each device owns one contiguous slab; a sweep needs
    exactly two boundary planes per device, exchanged with its mesh
    neighbours via ``lax.ppermute`` — minimal "nonlocal traffic".

  * ``scattered`` assignment (= dynamic scheduling with no locality
    control): slabs are strided over devices, so *every* slab boundary
    crosses a device boundary and each device must fetch ``blocks_per_dev*2``
    remote planes — the halo volume (and hence the collective roofline term
    of the compiled HLO) inflates by ~``blocks_per_dev``x.

Both SPMD sweeps are XLA sweeps: each device applies the jitted
``jacobi_sweep_ref`` to its halo-padded slabs (the Pallas kernel is the
single-device path, ``repro.kernels.jacobi.ops.jacobi_sweep``).  The
schedule builder of ``repro.core.assignment`` chooses the contiguous slabs
when given block homes, demonstrating the end-to-end path
placement → locality queues → SPMD assignment → fewer collective bytes.

``run_runtime_sweep`` adds a third, *online* execution path: slab updates
submitted as tasks to the ``repro.runtime`` executor, with the paper's
locality queues scheduling them dynamically (identical physics, observable
local/steal statistics).  Each slab update is one jitted dispatch on the
default device.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels.jacobi.ref import jacobi_sweep_ref
from ..runtime import Executor, RuntimeStats, StealGovernor


@dataclasses.dataclass(frozen=True)
class JacobiGridConfig:
    ni: int = 240
    nj: int = 60
    nk: int = 64
    axis: str = "data"          # mesh axis the i-axis is sharded over


def _halo_exchange(local: jnp.ndarray, axis: str) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fetch the previous slab's last plane and next slab's first plane.

    Contiguous slab ownership ⇒ one ppermute in each direction (the
    locality-optimal schedule).  Edge devices receive zeros (Dirichlet).
    """
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    up = jax.lax.ppermute(local[-1], axis, fwd)     # from idx-1's last plane
    down = jax.lax.ppermute(local[0], axis, bwd)    # from idx+1's first plane
    up = jnp.where(idx == 0, jnp.zeros_like(up), up)
    down = jnp.where(idx == n - 1, jnp.zeros_like(down), down)
    return up, down


def make_contiguous_sweep(cfg: JacobiGridConfig):
    """shard_map'd sweep with contiguous slab ownership (locality schedule)."""

    def sweep_local(f_local: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
        up, down = _halo_exchange(f_local, cfg.axis)
        padded = jnp.concatenate([up[None], f_local, down[None]], axis=0)
        # interior update on the padded slab, then crop the halo rows: the
        # ref applies Dirichlet at the padded-slab boundary, but rows 0/-1
        # of the crop saw the true halo planes, so values are exact.
        return jacobi_sweep_ref(padded)[1:-1]

    def sweep(f: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
        return jax.shard_map(
            sweep_local,
            in_specs=(P(cfg.axis, None, None), P()),
            out_specs=P(cfg.axis, None, None),
        )(f, c)

    return sweep


def make_scattered_sweep(cfg: JacobiGridConfig, blocks_per_dev: int):
    """Sweep under a locality-oblivious (strided) block→device assignment.

    Device d owns i-slabs {d, d+D, d+2D, ...}: every slab boundary is a
    device boundary, so the halo for *each* owned slab must come from a
    different device.  Implemented as an all-gather of every slab's boundary
    planes — the honest communication cost of scattering.
    """

    def sweep_local(f_local: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
        axis = cfg.axis
        n = jax.lax.axis_size(axis)
        d = jax.lax.axis_index(axis)
        si = f_local.shape[0] // blocks_per_dev     # rows per slab
        # boundary planes of my slabs: (blocks_per_dev, 2, nj, nk)
        slabs = f_local.reshape(blocks_per_dev, si, *f_local.shape[1:])
        bounds = jnp.stack([slabs[:, 0], slabs[:, -1]], axis=1)
        # every device needs planes from (almost) every other: all-gather.
        all_bounds = jax.lax.all_gather(bounds, axis)   # (n, bpd, 2, nj, nk)

        def halo_for(slab_global_idx):
            total = n * blocks_per_dev
            prev_g = slab_global_idx - 1
            next_g = slab_global_idx + 1
            # global slab g is owned by device g % n as its (g // n)-th slab
            def plane(g, which):
                g_c = jnp.clip(g, 0, total - 1)
                p = all_bounds[g_c % n, g_c // n, which]
                valid = (g >= 0) & (g < total)
                return jnp.where(valid, p, jnp.zeros_like(p))
            return plane(prev_g, 1), plane(next_g, 0)

        outs = []
        for b in range(blocks_per_dev):
            g = d + b * n                      # strided ownership
            up, down = halo_for(g)
            padded = jnp.concatenate([up[None], slabs[b], down[None]], axis=0)
            outs.append(jacobi_sweep_ref(padded)[1:-1])
        return jnp.concatenate(outs, axis=0)

    def sweep(f: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
        return jax.shard_map(
            sweep_local,
            in_specs=(P(cfg.axis, None, None), P()),
            out_specs=P(cfg.axis, None, None),
        )(f, c)

    return sweep


def reassemble_scattered(out: jnp.ndarray, n_dev: int, blocks_per_dev: int) -> jnp.ndarray:
    """Map the scattered sweep's device-major row order back to lattice order.

    Device d's local output stacks its slabs [d, d+D, d+2D, ...]; lattice
    order interleaves them back.
    """
    si = out.shape[0] // (n_dev * blocks_per_dev)
    x = out.reshape(n_dev, blocks_per_dev, si, *out.shape[1:])
    x = jnp.swapaxes(x, 0, 1)                      # (bpd, n, si, ...)
    return x.reshape(n_dev * blocks_per_dev * si, *out.shape[1:])


def scatter_lattice(f: jnp.ndarray, n_dev: int, blocks_per_dev: int) -> jnp.ndarray:
    """Inverse of reassemble_scattered: lattice order -> device-major order."""
    si = f.shape[0] // (n_dev * blocks_per_dev)
    x = f.reshape(blocks_per_dev, n_dev, si, *f.shape[1:])
    x = jnp.swapaxes(x, 0, 1)
    return x.reshape(n_dev * blocks_per_dev * si, *f.shape[1:])


@functools.partial(jax.jit, static_argnames=("di",), donate_argnums=(0,))
def _update_slab(out: jax.Array, f: jax.Array, i0, c, di: int) -> jax.Array:
    """Write the sweep of rows ``[i0, i0 + di)`` of ``f`` into ``out``.

    ``i0`` is traced, so every slab shares one compiled program, and ``out``
    is donated, so the update is in place.
    """
    ni = f.shape[0]
    centre = jax.lax.dynamic_slice_in_dim(f, i0, di, axis=0)
    up = jax.lax.dynamic_slice_in_dim(f, jnp.maximum(i0 - 1, 0), 1, axis=0)
    down = jax.lax.dynamic_slice_in_dim(f, jnp.minimum(i0 + di, ni - 1), 1,
                                        axis=0)
    up = jnp.where(i0 > 0, up, jnp.zeros_like(up))
    down = jnp.where(i0 + di < ni, down, jnp.zeros_like(down))
    padded = jnp.concatenate([up, centre, down], axis=0)
    # the ref applies Dirichlet at the padded-slab i-faces, but the crop
    # keeps only rows that saw the true halo planes, so values are exact.
    slab = jacobi_sweep_ref(padded, c)[1:-1]
    return jax.lax.dynamic_update_slice_in_dim(out, slab, i0, axis=0)


def run_runtime_sweep(f, c: float = 1.0 / 6.0, di: int = 10,
                      num_domains: int = 4, workers_per_domain: int = 1,
                      steal_order: str = "cyclic",
                      governor: StealGovernor | None = None,
                      pool_cap: int = 256,
                      seed: int = 0,
                      trace=None,
                      spec=None) -> tuple[jax.Array, RuntimeStats]:
    """One whole-lattice sweep executed as online runtime tasks.

    The third execution path next to the shard_map'd SPMD sweeps above: the
    i-axis is cut into slabs of ``di`` rows, each slab update is one
    ``runtime.Task`` homed on a locality domain (contiguous slab→domain
    map = the paper's parallel first touch), and a ``runtime.Executor``
    schedules them.  Each task is one jitted dispatch that updates its slab
    of the output in place on the default device.  A Jacobi sweep reads
    only the *old* array, so tasks commute and any schedule yields the
    exact ``jacobi_sweep_ref`` answer — the scheduling policy changes the
    local/steal statistics, never the physics.  Returns
    ``(new_lattice, runtime_stats)``.

    ``trace`` takes an optional ``repro.trace.TraceRecorder``: the sweep's
    slab-task schedule is then recorded for offline steal-storm analysis
    and deterministic replay (``repro.trace.replay`` re-drives the same
    slab arrival sequence under any policy; the replayed task payloads are
    placeholders — replay studies the *schedule*, not the physics).

    ``spec`` takes a ``repro.spec.RuntimeSpec`` and builds the executor
    from it (the preferred path — the scheduling-policy kwargs above are
    then ignored, and a recorded trace embeds the spec so ``replay(trace)``
    reconstructs the schedule with no factory).
    """
    f = jnp.asarray(f)
    ni = f.shape[0]
    if ni % di != 0:
        raise ValueError(f"i extent {ni} not divisible by slab size {di}")
    nslabs = ni // di
    out = jnp.zeros_like(f)
    c = jnp.asarray(c, f.dtype)

    def update_slab(task, worker):
        nonlocal out
        out = _update_slab(out, f, task.payload * di, c, di=di)

    if spec is not None:
        if spec.trace.record:
            from ..spec import SpecError
            raise SpecError(
                "run_runtime_sweep returns only (lattice, stats) and cannot "
                "hand back a spec-declared recorder; record via the trace= "
                "kwarg (and TraceSpec(record=False)) instead")
        num_domains = spec.num_domains
        ex = spec.build(handler=update_slab).executor
    else:
        ex = Executor(num_domains, [d for d in range(num_domains)
                                    for _ in range(workers_per_domain)],
                      handler=update_slab, steal_order=steal_order,
                      governor=governor, pool_cap=pool_cap, seed=seed)
    if trace is not None:
        trace.attach(ex)
    for s in range(nslabs):
        home = s * num_domains // nslabs       # contiguous slabs per domain
        ex.submit(ex.make_task(payload=s, home=home))
    ex.run_until_drained()
    return out, ex.stats


@functools.lru_cache(maxsize=None)
def paper_flops_per_site() -> int:
    return 6  # five adds + one multiply (paper: 8/3 bytes per flop at 16 B/site)
