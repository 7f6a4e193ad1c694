"""Bring-up smoke: the stencil and serving paths at real size on a TPU.

    python chip_smoke.py                # one chip: phases 1-3
    python chip_smoke.py --four-chips   # four chips: the SPMD stencil sweeps

Everything runs in this one process, and nothing falls back: without a TPU
the script exits non-zero before any phase, and a phase that fails raises.
The phases are plain functions that take their sizes, so tests can call
them small on the CPU.  The wall seconds printed per phase are set-up
including compilation, not a measurement.  The last line of a passing run
is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import spec  # noqa: E402
from repro.core.tasks import PAPER_GRID  # noqa: E402
from repro.kernels.jacobi.kernel import jacobi_sweep_pallas  # noqa: E402
from repro.kernels.jacobi.ops import jacobi_sweep, stored_order  # noqa: E402
from repro.kernels.jacobi.ref import jacobi_sweep_ref  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.serve import MAX_SEQ, build, serve  # noqa: E402
from repro.stencil.jacobi import (JacobiGridConfig,  # noqa: E402
                                  make_contiguous_sweep, make_scattered_sweep,
                                  reassemble_scattered, run_runtime_sweep,
                                  scatter_lattice)

PAPER_SHAPE = (PAPER_GRID.ni, PAPER_GRID.nj, PAPER_GRID.nk)  # 2400x600x600
BLOCKS = (10, 8)        # (di, dj): dj must be a multiple of 8 on the chip
SWEEPS = 3
TOL = 1e-5


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


@functools.partial(jax.jit, static_argnums=(0,))
def _lattice(shape: tuple[int, ...], seed) -> jax.Array:
    """A seeded f32 lattice, made on the device."""
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32)


@jax.jit
def _max_abs_err(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.max(jnp.abs(a - b))


def _peak_bytes(dev: jax.Device) -> str:
    stats = dev.memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


def stencil_kernel(shape=PAPER_SHAPE, blocks=BLOCKS, sweeps: int = SWEEPS,
                   seed: int = 0, interpret: bool = False) -> np.ndarray:
    """Phase 1: ``sweeps`` sweeps of the Pallas kernel, each checked against
    the jitted reference.  Returns the first sweep, on the host.

    The kernel runs on the lattice in the order the device stores it
    (i-minor on the TPU at the paper's grid), with the (di, dj) block over
    the two stored-major axes.  At the paper's grid the input, the output
    and the reference's output take 10.5 GB, so the first sweep is kept on
    the host, not on the device.
    """
    di, dj = blocks
    x = _lattice(shape, seed)
    order = stored_order(x)
    if not interpret:
        txt = jacobi_sweep_pallas.lower(x, di=di, dj=dj,
                                        order=order).as_text()
        _check("tpu_custom_call" in txt, "Jacobi kernel did not lower to "
               "a compiled TPU kernel")
    first = None
    for t in range(sweeps):
        y = jacobi_sweep(x, di=di, dj=dj, interpret=interpret)
        y.block_until_ready()
        err = float(_max_abs_err(y, jacobi_sweep_ref(x)))
        print(f"  sweep {t}: shape={shape} stored_order={order} "
              f"blocks=({di},{dj},{shape[order[2]]}) "
              f"compiled={not interpret} max_abs_err={err!r}", flush=True)
        _check(err <= TOL, f"sweep {t}: kernel differs from reference by {err}")
        if first is None:
            first = np.asarray(y)
        x = y
    print(f"  peak_bytes_in_use={_peak_bytes(jax.devices()[0])}", flush=True)
    return first


def stencil_runtime(expected: np.ndarray, seed: int = 0,
                    slab_rows: int = PAPER_GRID.di) -> None:
    """Phase 2: one sweep as slab tasks under the ``paper_cyclic`` locality
    queues, checked against phase 1's first sweep."""
    f = _lattice(expected.shape, seed)
    out, stats = run_runtime_sweep(f, di=slab_rows,
                                   spec=spec.named("paper_cyclic"))
    dev = jax.devices()[0]
    _check(out.devices() == {dev}, f"runtime sweep ran on {out.devices()}")
    err = float(_max_abs_err(out, jnp.asarray(expected)))
    print(f"  slabs={stats.executed} device={dev.platform} "
          f"local_fraction={stats.local_fraction!r} steals={stats.stolen} "
          f"max_abs_err_vs_kernel={err!r}", flush=True)
    _check(err <= TOL, f"runtime sweep differs from the kernel by {err}")


def serving(arch: str = "qwen2-0.5b", smoke: bool = False, requests: int = 12,
            replicas: int = 3, seed: int = 0) -> None:
    """Phase 3: ``repro.launch.serve`` under two routing policies, with
    identical tokens required."""
    model, params = build(arch, smoke, seed)
    dev = jax.devices()[0]
    leaves = jax.tree.leaves(params) + jax.tree.leaves(model.init_cache(1, MAX_SEQ))
    _check(all(x.devices() == {dev} for x in leaves),
           "params or caches are not on the default device")
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"  model={model.cfg.name} dtype={model.cfg.dtype} "
          f"d_model={model.cfg.d_model} params={n_params} on {dev.platform}",
          flush=True)
    tokens = {}
    for policy in ("locality", "single_queue"):
        done, s = serve(model, params, policy, requests, replicas, seed)
        _check(len(done) == requests, f"{policy}: served {len(done)}")
        _check(all(len(r.out_tokens) == r.max_new for r in done),
               f"{policy}: a request got fewer than max_new tokens")
        tokens[policy] = [r.out_tokens for r in done]
        print(f"  policy={policy} served={s.served} "
              f"local={s.locality_fraction!r} stolen={s.stolen} "
              f"prefill_tokens={s.prefill_tokens} "
              f"req0={done[0].out_tokens}", flush=True)
    _check(tokens["locality"] == tokens["single_queue"],
           "token lists differ between routing policies")


def four_chips(shape=PAPER_SHAPE, blocks_per_dev: int = 4, seed: int = 0,
               n_dev: int = 4) -> None:
    """Phase 4: the contiguous and scattered SPMD sweeps on an ``n_dev``
    mesh, each checked against the jitted reference."""
    devs = jax.devices()
    _check(len(devs) >= n_dev, f"need {n_dev} devices, found {len(devs)}")
    mesh = jax.make_mesh((n_dev,), ("data",), devices=devs[:n_dev],
                         axis_types=(jax.sharding.AxisType.Auto,))
    rows = NamedSharding(mesh, P("data", None, None))
    cfg = JacobiGridConfig(ni=shape[0], nj=shape[1], nk=shape[2])
    c = jnp.float32(1 / 6)
    with jax.set_mesh(mesh):
        f = jax.jit(_lattice, static_argnums=(0,), out_shardings=rows)(
            shape, seed)
        ref = jax.jit(jacobi_sweep_ref, out_shardings=rows)(f)
        out = jax.jit(make_contiguous_sweep(cfg))(f, c)
        err_c = float(_max_abs_err(out, ref))
        del out
        scatter = jax.jit(scatter_lattice, static_argnums=(1, 2),
                          out_shardings=rows)
        gather = jax.jit(reassemble_scattered, static_argnums=(1, 2),
                         out_shardings=rows)
        fs = scatter(f, n_dev, blocks_per_dev)
        out = jax.jit(make_scattered_sweep(cfg, blocks_per_dev))(fs, c)
        err_s = float(_max_abs_err(gather(out, n_dev, blocks_per_dev), ref))
    print(f"  mesh={n_dev}x{devs[0].platform} shape={shape} "
          f"contiguous max_abs_err={err_c!r} "
          f"scattered(blocks_per_dev={blocks_per_dev}) max_abs_err={err_s!r}",
          flush=True)
    _check(err_c <= TOL, f"contiguous sweep differs by {err_c}")
    _check(err_s <= TOL, f"scattered sweep differs by {err_s}")


def _phase(name: str, fn, *args):
    print(f"phase {name}", flush=True)
    t0 = time.perf_counter()
    out = fn(*args)
    print(f"phase {name} ok: {time.perf_counter() - t0:.1f} s wall "
          f"(set-up including compilation)", flush=True)
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD stencil sweeps on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    cache_dir = use_compile_cache()
    print(f"device: kind={dev.device_kind!r} count={len(devs)} "
          f"compile_cache={cache_dir}", flush=True)

    if args.four_chips:
        _phase("four_chips", four_chips, PAPER_SHAPE, 4, args.seed)
    else:
        first = _phase("stencil_kernel", stencil_kernel, PAPER_SHAPE, BLOCKS,
                       SWEEPS, args.seed)
        _phase("stencil_runtime", stencil_runtime, first, args.seed)
        del first
        _phase("serving", serving, "qwen2-0.5b", False, 12, 3, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
