"""End-to-end LM training: ~100M-param model, a few hundred steps, with
checkpoint/restart fault tolerance demonstrated mid-run.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse
import dataclasses
import sys
import tempfile

sys.path.insert(0, "src")

import jax

from repro import compile_cache
from repro.configs import get_arch
from repro.distributed.sharding import ShardingCtx, make_rules
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.train.data import DataConfig
from repro.train.step import TrainConfig
from repro.train.train_loop import LoopConfig, train


def main():
    compile_cache.enable()
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=200)
    args = p.parse_args()

    # ~100M params: phi4-family geometry scaled to d=768/12L
    cfg = dataclasses.replace(
        get_arch("phi4_mini_3p8b"),
        n_layers=12, d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
        vocab_size=32000, attn_q_chunk=256, attn_kv_chunk=256, remat="none")
    model = build_model(cfg)
    n_params = sum(x.size for x in jax.tree.leaves(model.param_shapes()))
    print(f"arch: {cfg.name}-100m  params={n_params/1e6:.1f}M")

    mesh = make_local_mesh()
    ctx = ShardingCtx(mesh=mesh, rules=make_rules("train"))
    tc = TrainConfig(peak_lr=6e-4, total_steps=args.steps,
                     warmup_steps=args.steps // 10)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=256, global_batch=8)

    ckpt_dir = tempfile.mkdtemp(prefix="train_lm_ckpt_")
    half = args.steps // 2

    with jax.set_mesh(mesh):
        # phase 1: train to the midpoint, checkpointing
        r1 = train(model, tc, dc,
                   LoopConfig(total_steps=half, checkpoint_every=25,
                              checkpoint_dir=ckpt_dir, log_every=25),
                   ctx=ctx)
        print(f"phase 1 done at step {r1.final_step}: "
              f"loss {r1.losses[0]:.3f} -> {r1.losses[-1]:.3f}")
        print("simulating node failure + restart (auto-resume from checkpoint)")

        # phase 2: a fresh process would do exactly this — resume and finish
        r2 = train(model, tc, dc,
                   LoopConfig(total_steps=args.steps, checkpoint_every=50,
                              checkpoint_dir=ckpt_dir, log_every=25),
                   ctx=ctx)
        assert r2.resumed_from == r1.final_step, (r2.resumed_from, r1.final_step)
        print(f"phase 2 resumed from {r2.resumed_from}, finished at "
              f"{r2.final_step}: loss -> {r2.losses[-1]:.3f}")
        total_drop = r1.losses[0] - r2.losses[-1]
        print(f"total loss drop: {total_drop:.3f} "
              f"({'LEARNING ✓' if total_drop > 0.3 else 'check hyperparams'})")


if __name__ == "__main__":
    main()
