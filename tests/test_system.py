"""End-to-end behaviour tests for the paper's system.

The headline claims, executed:
  1. the locality-queue layer recovers static-first-touch throughput under
     dynamic scheduling (simulator, all three ccNUMA test beds);
  2. the same scheduler drives a real distributed JAX app end to end
     (training runs, learns, checkpoints, resumes — see test_checkpoint /
     test_distributed for the sharded halves);
  3. serving with locality queues preserves outputs while improving cache
     locality (test_serving).
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, list_archs, reduce_config
from repro.core import (TESTBED, SMALL_GRID, OpenMPLocalityQueues,
                        StaticWorksharing, place, simulate)
from repro.data.pipeline import make_batch_iterator
from repro.models.model import build_model
from repro.train.loop import LoopConfig, Trainer
from repro.train.optimizer import AdamWConfig


def test_headline_claim_all_testbeds():
    """Locality queues within 10% of optimal static placement — the paper's
    central result — on Istanbul, Nehalem EP and Nehalem EX."""
    for topo in TESTBED.values():
        homes = place("static1", SMALL_GRID, topo)
        ft = simulate(SMALL_GRID, topo, StaticWorksharing(),
                      place("static", SMALL_GRID, topo)).mlups
        lq = simulate(SMALL_GRID, topo, OpenMPLocalityQueues("kji"),
                      homes, seed=0).mlups
        assert lq > 0.9 * ft, (topo.name, lq, ft)


def test_training_learns_synthetic_structure():
    """A reduced model on the synthetic corpus must beat the unigram floor
    quickly — the bigram structure is learnable."""
    cfg = reduce_config(get_config("qwen2-0.5b"))
    model = build_model(cfg, max_pos=64)
    data = make_batch_iterator(cfg.vocab_size, 32, 8, seed=0)
    trainer = Trainer(model, data,
                      LoopConfig(total_steps=25, checkpoint_every=1000,
                                 log_every=1000),
                      AdamWConfig(lr=3e-3, warmup_steps=3, total_steps=25),
                      log_fn=lambda s: None)
    out = trainer.run()
    first, last = out["losses"][0], np.mean(out["losses"][-5:])
    assert last < first - 0.5, (first, last)


def test_all_archs_registered():
    archs = list_archs()
    assert len(archs) == 11
    for required in ("qwen2-0.5b", "qwen2-1.5b", "minicpm3-4b", "gemma3-1b",
                     "deepseek-v2-lite",
                     "qwen3-moe-30b-a3b", "phi3.5-moe-42b-a6.6b",
                     "recurrentgemma-9b", "whisper-base",
                     "llama-3.2-vision-90b", "rwkv6-3b"):
        assert required in archs


def test_dryrun_results_present_and_clean():
    """The committed dry-run table must cover all 40 single-pod cells with
    no errors (deliverable e)."""
    import json
    from pathlib import Path
    p = Path(__file__).parent.parent / "experiments" / "dryrun.json"
    if not p.exists():
        pytest.skip("dryrun.json not generated yet")
    d = json.loads(p.read_text())
    single = {k: v for k, v in d.items() if k.endswith("|single")}
    assert len(single) == 40
    assert all(v["status"] in ("ok", "skipped") for v in single.values()), \
        {k: v.get("error") for k, v in single.items() if v["status"] == "error"}
    n_ok = sum(1 for v in single.values() if v["status"] == "ok")
    assert n_ok == 33   # 7 documented long_500k skips
