"""Each fault a cell can have, planted under the timed path, makes
``correct`` false; so does the control, the reference one precision down,
put in the program's place and driven through a whole run."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench import stencil
from bench.reference import jacobi as jref
from bench.reference import qwen2 as qref
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("faults"))


def _unchanged(x, *a, **k):
    return x + 0.0


def _altered(sweep):
    def altered(x, *a, **k):
        return sweep(x, *a, **k).at[3, 5, 7].add(1.0)
    return altered


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_kernel_cell_fault_is_not_correct(root, monkeypatch, fault):
    from repro.kernels.jacobi import ops
    monkeypatch.setattr(ops, "jacobi_sweep", _unchanged if fault == "unchanged"
                        else _altered(ops.jacobi_sweep))
    assert not tiny.run_cell(root, "jacobi_kernel")["correct"]


def test_serve_cell_altered_token_is_not_correct(root, monkeypatch):
    from repro.serving import engine
    run = engine.Replica.run

    def altered(self, req):
        out = run(self, req)
        if req.uid >= 0:
            out.out_tokens[1] = (out.out_tokens[1] + 1) % 512
        return out
    monkeypatch.setattr(engine.Replica, "run", altered)
    assert not tiny.run_cell(root, "serve_sessions")["correct"]


def test_serve_cell_decode_that_keeps_its_cache_is_not_correct(root, monkeypatch):
    from repro.models import model
    decode = model.Model.decode_step

    def stale(self, params, tokens, pos, caches):
        logits, _ = decode(self, params, tokens, pos, caches)
        return logits, caches
    monkeypatch.setattr(model.Model, "decode_step", stale)
    assert not tiny.run_cell(root, "serve_sessions")["correct"]


def test_jacobi_control_fails_the_limit():
    conf = H.load_json(H.ROOT / "bench" / "configs" / "jacobi-2400x600x600-f32.json")
    for seed in (1, 2, 3):
        x = stencil.lattice((40, 16, 128), seed)
        assert float(jref.control_rel_err(x)) > conf["limits"]["rel_err"]
        assert float(jref.rel_err(x, jref.sweep(x))) <= conf["limits"]["rel_err"]


def test_qwen2_control_fails_the_limit(root):
    cell = H.find_cell(root, "serve_sessions")
    limit = cell.config["limits"]["max_logit_gap"]
    readings = []
    for seed in (1, 2, 3):
        ctx = H.Context(cell=cell, seed=seed, spans=H.Spans(), peaks=tiny.PEAKS,
                        interpret=True)
        state = cell.driver().setup(ctx)
        state.window(1.0)
        state.release()
        readings.append((state.control()["max_logit_gap"],
                         state.check()["max_logit_gap"][0]))
    assert all(c > limit >= p for c, p in readings), readings


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_kernel_cell_with_the_control_in_its_place_is_not_correct(
        root, monkeypatch, seed):
    """The bfloat16 reference sweeps in the kernel's place."""
    from repro.kernels.jacobi import ops
    monkeypatch.setattr(ops, "jacobi_sweep",
                        lambda x, *a, **k: jref.control_sweep(x))
    out = tiny.run_cell(root, "jacobi_kernel", seed=seed)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_cell_with_the_control_in_its_place_is_not_correct(
        root, monkeypatch, seed):
    """The float8 reference decodes greedily in ``Replica.run``'s place, on
    the served weights.  The tiny copy's limit is set from readings at its
    own widths (PERF.md); the committed limit is held against the control
    at full size on the chip."""
    from repro.serving import engine
    sizes = H.find_cell(root, "serve_sessions").config

    def control_run(self, req):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), self.params)
        seq = np.zeros(self.max_seq, np.int32)
        n = len(req.tokens)
        seq[:n] = req.tokens
        for _ in range(req.max_new):
            at = jnp.asarray([n - 1], jnp.int32)
            tok = int(qref.control_tokens(params, sizes, jnp.asarray(seq), at)[0])
            req.out_tokens.append(tok)
            seq[n] = tok
            n += 1
        return req
    monkeypatch.setattr(engine.Replica, "run", control_run)
    out = tiny.run_cell(root, "serve_sessions", seed=seed)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert not out["correct"], out["checks"]
