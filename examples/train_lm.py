"""End-to-end driver: train a ~110M-parameter LM with the full runtime —
sharded step, STAR-DP epoch commits, disk checkpointing, resume.

Full run (a few hundred steps, the deliverable configuration):
    PYTHONPATH=src python examples/train_lm.py --steps 300
Quick verification:
    PYTHONPATH=src python examples/train_lm.py --steps 10 --seq 128 --batch 4
"""
import argparse

from repro import compile_cache
from repro.configs.base import ArchConfig, BLOCK_ATTN_MLP
from repro.launch.mesh import make_host_mesh
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig

# ~110M params: GPT-2-small-scale llama-style decoder
LM110M = ArchConfig(
    name="demo-110m", family="dense", source="examples/train_lm.py",
    block=BLOCK_ATTN_MLP,
    n_layers=12, d_model=768, n_heads=12, n_kv_heads=12, d_head=64,
    d_ff=2048, vocab_size=32000,
    mlp_act="silu", mlp_gated=True, attn_chunk=256,
)


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default="/tmp/star_dp_110m")
    ap.add_argument("--steps-per-epoch", type=int, default=20)
    args = ap.parse_args()

    print(f"model: {LM110M.n_params()/1e6:.0f}M params")
    tr = Trainer(LM110M, make_host_mesh(), TrainerConfig(
        seq_len=args.seq, batch=args.batch, checkpoint_dir=args.ckpt,
        steps_per_epoch=args.steps_per_epoch,
        hp=AdamWConfig(lr=6e-4, warmup_steps=50)))
    meta = tr.restore_from_disk()
    if meta:
        print(f"resumed from committed step {meta['step']}")
    while tr.step < args.steps:
        m = tr.run(min(args.steps_per_epoch, args.steps - tr.step))
        print(f"step {m['step']:5d} loss {m['loss']:.4f} "
              f"gnorm {m['grad_norm']:.3f}", flush=True)
    print(f"done: {tr.step} steps, {tr.commit_log.fences} commits, "
          f"{tr.straggler_events} straggler events")


if __name__ == "__main__":
    main()
