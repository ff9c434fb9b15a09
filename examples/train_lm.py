"""End-to-end driver: train a ~100M LM for a few hundred steps on CPU.

Uses the full production path — ModelConfig zoo, synthetic sharded data
pipeline, AdamW (bf16 moments) + WSD schedule, per-layer remat, async
rotating checkpoints — on a reduced-but-not-tiny qwen2.5 config (~100M
params).  Loss drops from ~log(V) toward the noisy-bigram entropy floor of
the synthetic stream.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse
import dataclasses

from repro.configs import ARCHS
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import train_loop
from repro.train.step import TrainConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()
    enable_compile_cache()

    # ~100M-class: a 12-layer width-768 qwen-family model (~86M params;
    # ~2.5 s/step on one CPU core — a few hundred steps is a coffee break)
    cfg = dataclasses.replace(
        ARCHS["qwen2.5-3b"].reduced(),
        name="qwen2.5-100m", n_layers=12, d_model=768, n_heads=12,
        n_kv_heads=4, head_dim=64, d_ff=2048, vocab=2048, dtype="float32")
    from repro.models import model_spec, param_bytes
    print(f"model: {cfg.name} — "
          f"{param_bytes(model_spec(cfg)) // 4 / 1e6:.0f}M params")

    tcfg = TrainConfig(peak_lr=3e-3, total_steps=args.steps, remat="none")
    _, losses = train_loop(cfg, tcfg, steps=args.steps,
                           global_batch=args.batch, seq_len=args.seq,
                           ckpt_dir="artifacts/ckpt_train_lm",
                           ckpt_every=100, log_every=20)
    print(f"\nloss: {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"(uniform = {__import__('math').log(cfg.vocab):.2f})")
    assert losses[-1] < losses[0] - 0.5, "training did not learn"
    print("train_lm OK")


if __name__ == "__main__":
    main()
