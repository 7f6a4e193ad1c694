"""Work functions against hand counts, and the readers built on them."""
from __future__ import annotations

import pytest

from bench import harness as H
from bench import work
from bench.harness import Window
from bench.xplane import Device, Event, Trace

PEAKS = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
TINY = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
        "num_attention_heads": 2, "num_key_value_heads": 1, "vocab_size": 32}


def test_jacobi_sweep_by_hand():
    # 2x3x4 lattice: 24 sites, 6 flops and 8 bytes (one f32 read, one write)
    assert work.jacobi_sweep(24) == (144.0, 192.0)


def test_least_time_takes_the_larger_bound_over_chips():
    assert work.least_time(1000.0, 10.0, PEAKS) == 10.0
    assert work.least_time(100.0, 1000.0, PEAKS, chips=4) == 25.0


def test_qwen2_params_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3x8x16 = 576 matmul weights;
    # q/k/v bias 8+4+4 and two norms 2x8 = 32; embedding 32x8 = 256
    mm, total = work.qwen2_params(TINY)
    assert mm == 2 * 576 + 256
    assert total == mm + 2 * 32 + 8


def test_qwen2_params_match_the_published_count():
    conf = H.load_json(H.ROOT / "bench" / "configs" / "qwen2-0.5b.json")
    assert work.qwen2_params(conf)[1] == 494_032_768


def test_qwen2_decode_step_by_hand():
    mm, total = work.qwen2_params(TINY)
    flops, nbytes = work.qwen2_decode_step(TINY, context=10)
    # attention: 2 layers x (QK and PV: 2 x 2 flops) x 2 heads x 4 dims x 10
    assert flops == 2 * mm + 2 * 4 * 2 * 4 * 10
    # bf16 weights, plus k and v of 1 head x 4 dims x 10 positions x 2 layers
    assert nbytes == 2 * total + 2 * 2 * 1 * 4 * 10 * 2


def _ctx(cell):
    return H.Context(cell=cell, seed=0, spans=H.Spans(), peaks=PEAKS,
                     interpret=True)


def test_kernel_roofline_reads_the_kernel_calls_only():
    cell = H.find_cell(H.ROOT, "jacobi_kernel")
    reader = cell.reader("jacobi_kernel_roofline")
    kern = "%jacobi_sweep_pallas.1 custom-call"
    ops = [Event(kern, 1.0, 3.0), Event("%copy.5 copy", 3.0, 4.0),
           Event(kern, 5.0, 7.0)]
    trace = Trace([Device("/device:TPU:0", ops, [])],
                  [Event("bench.window", 0.0, 10.0)])
    win = Window(10.0, 2, 0, {}, {"sweeps": 2, "sites": 5})
    # least time: 40 bytes / 10 B/s = 4 s; each kernel call takes 2 s
    assert reader.read(_ctx(cell), win, trace) == pytest.approx(200.0)
    assert reader.read(_ctx(cell), win, None) is None


def test_stencil_mfu_is_the_whole_window():
    cell = H.find_cell(H.ROOT, "jacobi_kernel")
    win = Window(8.0, 2, 0, {}, {"sweeps": 2, "sites": 5})
    assert cell.reader("stencil_mfu").read(_ctx(cell), win, None) == \
        pytest.approx(100.0)
