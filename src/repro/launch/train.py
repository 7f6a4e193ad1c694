"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --smoke --steps 50 --batch 8 --seq 128 --ckpt /tmp/ckpt

``--smoke`` shrinks the arch to its reduced config (CPU-runnable); without
it the full config is used (TPU deployment).  The loop resumes from the
newest checkpoint in --ckpt automatically.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from ..configs import get_config, reduce_config
from ..data.pipeline import make_batch_iterator
from ..models.model import build_model
from ..train.loop import LoopConfig, Trainer
from ..train.optimizer import AdamWConfig
from .cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_config(cfg)
    model = build_model(cfg, max_pos=max(args.seq, 128))

    data = make_batch_iterator(cfg.vocab_size, args.seq, args.batch,
                               seed=args.seed)

    # whisper / vlm smoke runs need their stub extras in every batch
    def with_extras(it):
        import numpy as np
        for batch in it:
            if cfg.encoder is not None:
                batch["frames"] = np.zeros(
                    (args.batch, cfg.encoder.num_frames, cfg.encoder.d_model),
                    np.float32)
            if cfg.vision is not None:
                batch["vision"] = np.zeros(
                    (args.batch, cfg.vision.num_image_tokens, cfg.d_model),
                    np.float32)
            yield batch

    trainer = Trainer(
        model, with_extras(data),
        LoopConfig(total_steps=args.steps, checkpoint_every=args.ckpt_every,
                   checkpoint_dir=args.ckpt, log_every=max(args.steps // 20, 1)),
        AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                    total_steps=args.steps),
    )
    out = trainer.run(seed=args.seed)
    losses = out["losses"]
    print(f"first-10 mean loss: {sum(losses[:10])/max(len(losses[:10]),1):.4f}")
    print(f"last-10  mean loss: {sum(losses[-10:])/max(len(losses[-10:]),1):.4f}")


if __name__ == "__main__":
    main()
