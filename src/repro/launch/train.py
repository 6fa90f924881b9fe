"""Training launcher.

Production use (TPU pod):
    python -m repro.launch.train --arch qwen3-8b --steps 10000 \
        --mesh 16x16 --ckpt-dir gs://...

CPU demo (reduced config, single device):
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
        --steps 20 --global-batch 4 --seq 64

The launcher wires mesh construction, sharded param/opt state init, the
data pipeline, checkpoint/resume and the straggler watchdog (runtime/).
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.configs.base import ModelConfig
from repro.configs.registry import arch_names, get_config, reduced_config
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.specs import model_module
from repro.optim.adamw import AdamWConfig
from repro.runtime.trainer import Trainer, TrainerConfig


def parse_mesh(spec: str):
    if not spec or spec == "1":
        return None
    dims = tuple(int(x) for x in spec.split("x"))
    names = {1: ("data",), 2: ("data", "model"),
             3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(dims, names)


def extra_batch_fn(cfg: ModelConfig, batch_size: int):
    import numpy as np
    if cfg.family == "vlm":
        def fn(step):
            rng = np.random.default_rng(step)
            return {"images": rng.standard_normal(
                (batch_size, cfg.n_image_tokens, cfg.d_model),
                dtype=np.float32)}
        return fn
    if cfg.family == "encdec":
        def fn(step):
            rng = np.random.default_rng(step)
            return {"frames": rng.standard_normal(
                (batch_size, cfg.n_frames, cfg.d_model), dtype=np.float32)}
        return fn
    return None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=arch_names())
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="", help="e.g. 16x16 or 2x16x16")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = parse_mesh(args.mesh)
    mod = model_module(cfg)

    tcfg = TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir,
                         grad_compression=args.grad_compression)
    opt = AdamWConfig(lr_peak=args.lr, warmup_steps=min(100, args.steps // 5
                                                        or 1),
                      decay_steps=args.steps)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.global_batch)

    trainer = Trainer(mod, cfg, tcfg, opt, dcfg, mesh=mesh,
                      extra_batch=extra_batch_fn(cfg, args.global_batch))

    def run():
        trainer.init_state()
        if args.resume and trainer.maybe_resume():
            print(f"resumed at step {trainer.global_step}")
        hist = trainer.run()
        trainer.save(blocking=True)
        for h in hist[:3] + hist[-3:]:
            print(json.dumps(h))
        print(f"straggler steps flagged: {trainer.watchdog.flagged}")

    if mesh is not None:
        with mesh:
            run()
    else:
        run()


if __name__ == "__main__":
    main()
