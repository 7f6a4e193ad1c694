"""The window shared by the stencil cells: sweeps back to back.

Each sweep feeds the next, and one sweep stays enqueued on the device
while the host waits for the one before it, so the device never waits for
the host between sweeps.  The window ends when the last sweep's result is
ready.  ``mlups`` is the lattice-site updates completed, over the window's
seconds, over 1e6.

What is compared with the reference:

- the window's last sweep, at every site: its input is kept (it is alive
  during that sweep anyway), and the check reads it after the window;
- one sweep drawn from the seed among the first ``sample_before``: a block
  of ``sample_rows`` rows, drawn from the seed, of its input and output is
  copied out as the window runs.
"""
from __future__ import annotations

import functools
import math
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness as H
from bench.reference import jacobi as ref


@functools.partial(jax.jit, static_argnums=(0,))
def _normal(shape, key):
    return jax.random.normal(key, shape, jnp.float32)


def lattice(shape: tuple[int, int, int], seed: int) -> jax.Array:
    """A seeded float32 lattice, made on the device in one call."""
    return _normal(tuple(shape), H.prng_key(seed))


@functools.partial(jax.jit, static_argnums=(2,))
def _rows(x, a, n):
    return jax.lax.dynamic_slice_in_dim(x, a, n, axis=0)


def sites(config: dict) -> int:
    return config["ni"] * config["nj"] * config["nk"]


class SweepCell:
    """A stencil cell: ``step`` maps a lattice to its next sweep."""

    def __init__(self, ctx: H.Context, step: Callable, x0: jax.Array):
        cfg, tr = ctx.cell.config, ctx.cell.traffic
        self.ctx, self.step = ctx, step
        self.sites = sites(cfg)
        rng = np.random.default_rng([ctx.seed, 1])
        self.sample_k = int(rng.integers(0, tr["sample_before"]))
        h = int(tr["sample_rows"])
        self.rows_a = int(rng.integers(0, cfg["ni"] - h - 1))
        self.rows_n = h + 2
        # warm-up: every program the window runs, on its own shapes
        x1 = step(x0)
        _rows(x0, self.rows_a, self.rows_n).block_until_ready()
        x1.block_until_ready()
        del x0
        self.x = x1
        self.last = self.sample = None

    def window(self, seconds: float) -> H.Window:
        spans, step = self.ctx.spans, self.step
        a, h = self.rows_a, self.rows_n

        def take(x):
            return _rows(x, a, h)

        t0 = time.perf_counter()
        cur, self.x = self.x, None
        with spans("bench.dispatch"):
            out = step(cur)
        n, sample = 1, None
        while True:
            if sample is None and n - 1 == self.sample_k:
                sample = (take(cur), take(out))
            with spans("bench.wait"):
                cur.block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
            cur = out
            with spans("bench.dispatch"):
                out = step(cur)
            n += 1
        with spans("bench.wait"):
            out.block_until_ready()
        elapsed = time.perf_counter() - t0
        if sample is None:
            sample = (take(cur), take(out))
        self.last, self.sample = (cur, out), sample
        return H.Window(seconds=elapsed, attempted=n, failed=0,
                        metrics={"mlups": n * self.sites / elapsed / 1e6},
                        facts={"sweeps": n, "sites": self.sites})

    def release(self) -> None:
        self.step = None

    def control(self) -> dict[str, float]:
        """The control's reading on the window's last sweep: the reference
        in bfloat16, read as ``last_sweep_rel_err``."""
        return {"last_sweep_rel_err": _num(float(ref.control_rel_err(self.last[0])))}

    def check(self) -> dict[str, tuple[float, float]]:
        lim = float(self.ctx.cell.config["limits"]["rel_err"])
        x, y = self.last
        self.last = None
        last = float(ref.rel_err(x, y))
        del x, y
        sample = float(ref.rel_err_rows(*self.sample))
        return {"last_sweep_rel_err": (_num(last), lim),
                "sampled_sweep_rel_err": (_num(sample), lim)}


def _num(v: float) -> float:
    """A reading that JSON can carry: not-a-number or infinity (a sweep
    that blew up) reads as 1e300, which no limit admits."""
    return v if math.isfinite(v) else 1e300
