"""Compile the main device paths at real size for a described TPU v5e.

Nothing runs: each test lowers and compiles for a ``v5e:2x2`` topology that
is described, not attached, so the chip's compiler refuses here what it
would refuse on the chip (illegal block shapes, programs that do not fit).
The topology is described inside a fixture, never at import.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core.tasks import PAPER_GRID
from repro.kernels.jacobi.kernel import jacobi_sweep_pallas
from repro.kernels.jacobi.ops import stored_order
from repro.kernels.jacobi.ref import jacobi_sweep_ref
from repro.launch.serve import MAX_SEQ
from repro.models.model import build_model
from repro.serving.engine import greedy_decode_step, greedy_prefill
from repro.stencil.jacobi import (JacobiGridConfig, _update_slab,
                                  make_contiguous_sweep, make_scattered_sweep)

PAPER_SHAPE = (PAPER_GRID.ni, PAPER_GRID.nj, PAPER_GRID.nk)
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:4]), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_jacobi_kernel_paper_grid(one_chip):
    x = jax.ShapeDtypeStruct(PAPER_SHAPE, jnp.float32, sharding=one_chip)
    compiled = jacobi_sweep_pallas.lower(x).compile()   # default blocks
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes(compiled) <= HBM_BYTES


def test_jacobi_kernel_refuses_paper_block(one_chip):
    # dj = 10 breaks the sublane rule: the reason the default block is 10x8
    x = jax.ShapeDtypeStruct(PAPER_SHAPE, jnp.float32, sharding=one_chip)
    with pytest.raises(Exception, match="divisible by 8"):
        jacobi_sweep_pallas.lower(x, di=10, dj=10,
                                  order=stored_order(x)).compile()


def test_jacobi_sweep_keeps_stored_layout(one_chip):
    """The sweep as ``ops.jacobi_sweep`` runs it, on the lattice in the
    order the chip stores it: no relayout copy of the lattice on either
    side of the kernel, and no lattice-sized temporaries (two copies,
    7,372,800,000 bytes, with the kernel pinned to row-major order)."""
    x = jax.ShapeDtypeStruct(PAPER_SHAPE, jnp.float32, sharding=one_chip)
    compiled = jacobi_sweep_pallas.lower(x, di=10, dj=8,
                                         order=stored_order(x)).compile()
    text = compiled.as_text()
    assert not re.search(
        r"f32\[(2400,600,600|600,600,2400)\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
    assert re.search(r"%jacobi_sweep_pallas\S* = \S+ custom-call\(", text)


def test_reference_and_slab_update_paper_grid(one_chip):
    x = jax.ShapeDtypeStruct(PAPER_SHAPE, jnp.float32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    i0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ref = jacobi_sweep_ref.lower(x).compile()
    assert ref.memory_analysis().temp_size_in_bytes == 0
    slab = _update_slab.lower(x, x, i0, scalar, di=PAPER_GRID.di).compile()
    # the output is donated: the update writes in place
    assert slab.memory_analysis().alias_size_in_bytes > 0
    assert _bytes(slab) <= HBM_BYTES


@pytest.mark.parametrize("step", ["prefill", "decode_step"])
def test_qwen2_full_width_one_chip(one_chip, step):
    """The serving engine's programs: each model step with its greedy
    sample."""
    cfg = get_config("qwen2-0.5b")
    model = build_model(cfg, max_pos=256)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(model.abstract_params())
    caches = on_chip(jax.eval_shape(lambda: model.init_cache(1, MAX_SEQ)))
    if step == "prefill":
        toks = on_chip(jax.ShapeDtypeStruct((1, 16), jnp.int32))
        lowered = greedy_prefill.lower(model, params, toks, caches)
    else:
        toks = on_chip(jax.ShapeDtypeStruct((1, 1), jnp.int32))
        pos = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
        lowered = greedy_decode_step.lower(model, params, toks, pos, caches)
    compiled = lowered.compile()
    assert jax.tree.leaves(params)[0].dtype == jnp.bfloat16
    assert _bytes(compiled) <= HBM_BYTES


@pytest.mark.parametrize("step", ["prefill", "decode_step"])
def test_deepseek_v2_lite_share_one_chip(one_chip, step):
    """One chip's expert-parallel share of DeepSeek-V2-Lite at published
    widths (8 of 64 experts, a 1/8 vocabulary, 27 layers): the absorbed
    chunked prefill of a 4096-token prompt and the absorbed decode step,
    against an 8448-position latent cache."""
    from bench import harness as H
    from bench.drivers.serve_mla_moe import program_model
    model = program_model(H.load_json(
        H.ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8.json"))

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(model.abstract_params())
    caches = on_chip(jax.eval_shape(lambda: model.init_cache(1, 8448)))
    if step == "prefill":
        toks = on_chip(jax.ShapeDtypeStruct((1, 4096), jnp.int32))
        lowered = greedy_prefill.lower(model, params, toks, caches)
    else:
        toks = on_chip(jax.ShapeDtypeStruct((1, 1), jnp.int32))
        pos = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
        lowered = greedy_decode_step.lower(model, params, toks, pos, caches)
    compiled = lowered.compile()
    assert _bytes(compiled) <= HBM_BYTES


@pytest.mark.parametrize("schedule", ["contiguous", "scattered"])
def test_spmd_sweeps_four_chips(mesh4, schedule):
    cfg = JacobiGridConfig(ni=PAPER_SHAPE[0], nj=PAPER_SHAPE[1],
                           nk=PAPER_SHAPE[2])
    x = jax.ShapeDtypeStruct(PAPER_SHAPE, jnp.float32,
                             sharding=NamedSharding(mesh4, P("data")))
    c = jax.ShapeDtypeStruct((), jnp.float32,
                             sharding=NamedSharding(mesh4, P()))
    sweep = (make_contiguous_sweep(cfg) if schedule == "contiguous"
             else make_scattered_sweep(cfg, blocks_per_dev=4))
    with jax.set_mesh(mesh4):
        compiled = jax.jit(sweep).lower(x, c).compile()
    collective = ("collective-permute" if schedule == "contiguous"
                  else "all-gather")
    assert collective in compiled.as_text()
    assert _bytes(compiled) <= HBM_BYTES       # per device
