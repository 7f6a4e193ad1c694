"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.jacobi import ops as jacobi_ops
from repro.kernels.jacobi.kernel import jacobi_sweep_pallas
from repro.kernels.jacobi.ref import jacobi_sweep_ref
from repro.kernels.rglru.kernel import rglru_scan_pallas
from repro.kernels.rglru.ref import rglru_scan_ref
from repro.kernels.rwkv6.kernel import wkv6_pallas
from repro.kernels.rwkv6.ref import wkv6_ref

RNG = np.random.default_rng(0)


# stored orders, major to minor: row-major, the TPU's i-minor order for
# the paper's lattice, and j minor
STORED_ORDERS = [(0, 1, 2), (1, 2, 0), (2, 0, 1)]


class TestJacobi:
    @pytest.mark.parametrize("shape,block", [
        ((20, 20, 60), (10, 10)),
        ((8, 16, 128), (4, 8)),
        ((10, 10, 600), (10, 10)),     # the paper's block geometry
        ((30, 20, 32), (10, 5)),
        ((4, 4, 16), (2, 2)),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32])
    @pytest.mark.parametrize("order", STORED_ORDERS)
    def test_matches_oracle(self, shape, block, dtype, order):
        """``shape`` is the lattice as stored in ``order``: the block tiles
        its first two axes."""
        logical = tuple(shape[order.index(a)] for a in range(3))
        f = jnp.asarray(RNG.standard_normal(logical), dtype)
        out = jacobi_sweep_pallas(f, 1 / 6, di=block[0], dj=block[1],
                                  interpret=True, order=order)
        ref = jacobi_sweep_ref(f, 1 / 6)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    @pytest.mark.parametrize("order", list(itertools.permutations(range(3))))
    def test_orders_agree_bitwise(self, order):
        """The six terms are added in logical order whatever the stored
        order, so every order gives the row-major kernel's bits."""
        f = jnp.asarray(RNG.standard_normal((8, 16, 24)), jnp.float32)
        out = jacobi_sweep_pallas(f, di=4, dj=8, interpret=True, order=order)
        base = jacobi_sweep_pallas(f, di=4, dj=8, interpret=True)
        np.testing.assert_array_equal(out, base)

    def test_ops_takes_identity_order_on_cpu(self, monkeypatch):
        seen = []

        def spy(*args, **kwargs):
            seen.append(kwargs["order"])
            return jacobi_sweep_pallas(*args, **kwargs)

        monkeypatch.setattr(jacobi_ops, "jacobi_sweep_pallas", spy)
        f = jnp.asarray(RNG.standard_normal((8, 16, 24)), jnp.float32)
        out = jacobi_ops.jacobi_sweep(f, di=4, dj=8, interpret=True)
        assert seen == [(0, 1, 2)]
        np.testing.assert_allclose(out, jacobi_sweep_ref(f), atol=1e-5)

    @pytest.mark.parametrize("kind", ["array", "shape_on_device", "shape",
                                      "tracer"])
    def test_stored_order_row_major_on_cpu(self, kind):
        shape = (8, 16, 24)
        if kind == "tracer":
            seen = []
            jax.jit(lambda x: seen.append(jacobi_ops.stored_order(x)))(
                jnp.zeros(shape))
            (order,) = seen
        else:
            on_device = SingleDeviceSharding(jax.devices()[0])
            x = {"array": jnp.zeros(shape),
                 "shape_on_device": jax.ShapeDtypeStruct(
                     shape, jnp.float32, sharding=on_device),
                 "shape": jax.ShapeDtypeStruct(shape, jnp.float32)}[kind]
            order = jacobi_ops.stored_order(x)
        assert order == (0, 1, 2)

    def test_c_coefficient(self):
        f = jnp.asarray(RNG.standard_normal((8, 8, 16)), jnp.float32)
        out = jacobi_sweep_pallas(f, 0.25, di=4, dj=4, interpret=True)
        ref = jacobi_sweep_ref(f, 0.25)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_rejects_indivisible(self):
        f = jnp.zeros((9, 8, 16), jnp.float32)
        with pytest.raises(ValueError):
            jacobi_sweep_pallas(f, di=4, dj=4)


def _segments_ref(f, up, down, c=1 / 6):
    """The reference sweep of each of ``len(up)`` equal segments of ``f``'s
    rows, each padded with its halo planes, then cropped: the lattice the
    segments and halos stand for."""
    segs = jnp.split(f, up.shape[0], axis=0)
    return jnp.concatenate([
        jacobi_sweep_ref(jnp.concatenate([u[None], s, d[None]]), c)[1:-1]
        for s, u, d in zip(segs, up, down)])


class TestJacobiHalo:
    """``halo=(up, down)``: the i-faces of each segment see its halo planes."""

    @pytest.mark.parametrize("nseg", [1, 2, 4])
    @pytest.mark.parametrize("halos", ["zero", "seeded"])
    @pytest.mark.parametrize("order", [(0, 1, 2), (0, 2, 1)])
    def test_matches_padded_segments_bitwise(self, nseg, halos, order):
        rng = np.random.default_rng([nseg, len(halos), order[1]])
        f = jnp.asarray(rng.standard_normal((16, 16, 24)), jnp.float32)
        planes = (nseg, 16, 24)
        if halos == "zero":
            up = down = jnp.zeros(planes, jnp.float32)
        else:
            up, down = (jnp.asarray(rng.standard_normal(planes), jnp.float32)
                        for _ in range(2))
        out = jacobi_sweep_pallas(f, 1 / 6, di=4, dj=8, interpret=True,
                                  order=order, halo=(up, down))
        np.testing.assert_array_equal(out, _segments_ref(f, up, down))

    def test_block_rows_below_the_segment(self):
        """di divides a segment of 8 rows in two blocks: only the blocks
        at the segment's faces take the halo rows."""
        f = jnp.asarray(RNG.standard_normal((16, 8, 16)), jnp.float32)
        up, down = (jnp.asarray(RNG.standard_normal((2, 8, 16)), jnp.float32)
                    for _ in range(2))
        out = jacobi_sweep_pallas(f, 0.25, di=4, dj=8, interpret=True,
                                  halo=(up, down))
        np.testing.assert_array_equal(out, _segments_ref(f, up, down, 0.25))

    def test_no_halo_unchanged(self):
        """One segment with zero halos is the Dirichlet lattice: the same
        bits as the kernel without halos, and as the reference."""
        f = jnp.asarray(RNG.standard_normal((20, 16, 24)), jnp.float32)
        zero = jnp.zeros((1, 16, 24), jnp.float32)
        base = jacobi_sweep_pallas(f, di=10, dj=8, interpret=True)
        out = jacobi_sweep_pallas(f, di=10, dj=8, interpret=True,
                                  halo=(zero, zero))
        np.testing.assert_array_equal(out, base)
        np.testing.assert_array_equal(base, jacobi_sweep_ref(f))

    @pytest.mark.parametrize("order", [(1, 2, 0), (2, 0, 1), (1, 0, 2)])
    def test_refuses_i_not_stored_major(self, order):
        f = jnp.zeros((8, 8, 8), jnp.float32)
        zero = jnp.zeros((2, 8, 8), jnp.float32)
        with pytest.raises(ValueError, match="stored major"):
            jacobi_sweep_pallas(f, di=4, dj=8, interpret=True, order=order,
                                halo=(zero, zero))

    @pytest.mark.parametrize("planes,di", [
        ((5, 12, 8), 4),        # 12 rows do not cut into 5 segments
        ((2, 12, 8), 4),        # segments of 6 rows, blocks of 4
        ((2, 8, 8), 4),         # halo planes of the wrong width
    ])
    def test_refuses_segments_off_the_blocks(self, planes, di):
        f = jnp.zeros((12, 12, 8), jnp.float32)
        zero = jnp.zeros(planes, jnp.float32)
        with pytest.raises(ValueError):
            jacobi_sweep_pallas(f, di=di, dj=4, interpret=True,
                                halo=(zero, zero))


class TestFlashAttention:
    @pytest.mark.parametrize("b,hq,hkv,tq,tk,hd,causal,win,bq,bk", [
        (2, 4, 2, 128, 128, 32, True, 0, 64, 64),
        (1, 8, 1, 256, 256, 64, True, 0, 128, 128),     # MQA
        (2, 4, 4, 128, 128, 16, False, 0, 64, 32),      # bidirectional
        (1, 4, 2, 256, 256, 32, True, 96, 64, 64),      # sliding window
        (1, 2, 2, 64, 192, 32, True, 0, 32, 64),        # Tk > Tq (offset)
    ])
    def test_matches_oracle(self, b, hq, hkv, tq, tk, hd, causal, win, bq, bk):
        qo = tk - tq
        q = jnp.asarray(RNG.standard_normal((b, hq, tq, hd)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, hkv, tk, hd)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, hkv, tk, hd)), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, window=win, q_offset=qo,
                              bq=bq, bk=bk, interpret=True)
        ref = mha_ref(q, k, v, causal=causal, window=win, q_offset=qo)
        np.testing.assert_allclose(out, ref, atol=3e-5)

    def test_bf16(self):
        q = jnp.asarray(RNG.standard_normal((1, 2, 128, 32)), jnp.bfloat16)
        k = jnp.asarray(RNG.standard_normal((1, 2, 128, 32)), jnp.bfloat16)
        v = jnp.asarray(RNG.standard_normal((1, 2, 128, 32)), jnp.bfloat16)
        out = flash_attention(q, k, v, bq=64, bk=64, interpret=True)
        ref = mha_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=3e-2)


class TestRGLRU:
    @pytest.mark.parametrize("b,t,w,chunk", [
        (2, 128, 64, 32), (1, 256, 128, 128), (3, 64, 32, 64),
    ])
    def test_matches_oracle(self, b, t, w, chunk):
        a = jnp.asarray(RNG.uniform(0.5, 0.999, (b, t, w)), jnp.float32)
        bb = jnp.asarray(RNG.standard_normal((b, t, w)) * 0.1, jnp.float32)
        out = rglru_scan_pallas(a, bb, chunk=chunk, interpret=True)
        ref = rglru_scan_ref(a, bb)
        np.testing.assert_allclose(out, ref, atol=1e-5)


class TestWKV6:
    @pytest.mark.parametrize("b,t,h,hd,chunk", [
        (2, 64, 2, 16, 32), (1, 128, 4, 32, 64), (2, 32, 1, 8, 32),
    ])
    def test_matches_oracle(self, b, t, h, hd, chunk):
        r = jnp.asarray(RNG.standard_normal((b, t, h, hd)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, t, h, hd)) * 0.3, jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, t, h, hd)) * 0.3, jnp.float32)
        w = jnp.asarray(RNG.uniform(0.8, 0.999, (b, t, h, hd)), jnp.float32)
        u = jnp.asarray(RNG.standard_normal((h, hd)) * 0.3, jnp.float32)
        o, sT = wkv6_pallas(r, k, v, w, u, chunk=chunk, interpret=True)
        oref, sref = wkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(o, oref, atol=1e-4)
        np.testing.assert_allclose(sT, sref, atol=1e-4)

    def test_state_continuity_between_chunks(self):
        """Running 2T in one call == two T calls with state carried by hand
        (validates the chunk-boundary handling)."""
        b, t, h, hd = 1, 64, 2, 16
        r = jnp.asarray(RNG.standard_normal((b, 2 * t, h, hd)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, 2 * t, h, hd)) * 0.3, jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, 2 * t, h, hd)) * 0.3, jnp.float32)
        w = jnp.asarray(RNG.uniform(0.8, 0.999, (b, 2 * t, h, hd)), jnp.float32)
        u = jnp.asarray(RNG.standard_normal((h, hd)) * 0.3, jnp.float32)
        o_full, s_full = wkv6_pallas(r, k, v, w, u, chunk=32, interpret=True)
        o1, s1 = wkv6_ref(r[:, :t], k[:, :t], v[:, :t], w[:, :t], u)
        o2, s2 = wkv6_ref(r[:, t:], k[:, t:], v[:, t:], w[:, t:], u, s0=s1)
        np.testing.assert_allclose(o_full[:, :t], o1, atol=1e-4)
        np.testing.assert_allclose(o_full[:, t:], o2, atol=1e-4)
        np.testing.assert_allclose(s_full, s2, atol=1e-4)


class TestJacobiTemporal:
    """Temporal blocking (the paper's §4 outlook): two sweeps per HBM pass."""

    @pytest.mark.parametrize("shape,block", [
        ((20, 20, 32), (5, 5)),
        ((12, 8, 16), (4, 4)),
        ((10, 10, 600), (10, 10)),    # the paper's block geometry
        ((8, 8, 8), (2, 2)),          # minimal halo-legal block
    ])
    def test_two_steps_match_double_sweep(self, shape, block):
        from repro.kernels.jacobi.temporal import jacobi_two_step_pallas
        f = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
        out = jacobi_two_step_pallas(f, 1 / 6, di=block[0], dj=block[1],
                                     interpret=True)
        ref = jacobi_sweep_ref(jacobi_sweep_ref(f, 1 / 6), 1 / 6)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_rejects_shallow_blocks(self):
        from repro.kernels.jacobi.temporal import jacobi_two_step_pallas
        with pytest.raises(ValueError):
            jacobi_two_step_pallas(jnp.zeros((4, 4, 8), jnp.float32),
                                   di=1, dj=1)
