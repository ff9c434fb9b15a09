"""The trainer: data pipeline → sharded train step → async checkpoints.

Runs the exact production step code at any scale:

* ``--arch <id> --smoke`` — reduced config on host CPU (the per-arch smoke
  path; also what examples/train_lm.py drives);
* full configs under a mesh — the same builder the dry-run uses.

Fault-tolerance loop (DESIGN.md §5): deterministic (seed, step)-keyed data,
async rotating checkpoints every ``--ckpt-every``, restore-on-start from the
latest checkpoint (elastic: the restoring mesh re-derives shardings from
logical axes, so N→M device restarts just work).  ``--simulate-failure k``
kills the process at step k to let tests exercise the restart path.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from ..configs import ARCHS
from ..data import DataConfig, SyntheticLMData
from ..checkpoint import CheckpointManager
from ..models.params import init_params
from ..models.transformer import model_spec
from ..optim import adamw_init, wsd_schedule
from ..train.step import TrainConfig, make_train_step
from .compile_cache import enable_compile_cache


def build_host_trainer(cfg, tcfg: TrainConfig, seed: int = 0):
    """Single-device trainer (smoke / examples): plain jit, no mesh."""
    step_fn = jax.jit(make_train_step(cfg, tcfg,
                                      wsd_schedule(tcfg.peak_lr,
                                                   tcfg.total_steps)),
                      donate_argnums=(0,))
    spec = model_spec(cfg)
    params = init_params(spec, jax.random.PRNGKey(seed),
                         dtype=jnp.dtype(tcfg.param_dtype))
    state = {"params": params, "opt": adamw_init(params)}
    return step_fn, state, spec


def train_loop(cfg, tcfg: TrainConfig, *, steps: int, global_batch: int,
               seq_len: int, seed: int = 0, ckpt_dir: str | None = None,
               ckpt_every: int = 50, log_every: int = 10,
               simulate_failure: int = 0):
    step_fn, state, spec = build_host_trainer(cfg, tcfg, seed)
    data = SyntheticLMData(
        DataConfig(global_batch, seq_len, cfg.vocab, seed=seed), cfg)

    start = 0
    mgr = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, keep=3)
        latest = mgr.latest_step()
        if latest is not None:
            restored, manifest = mgr.restore_latest(like=state)
            state = jax.tree_util.tree_map(jnp.asarray, restored)
            start = manifest["step"]
            print(f"[train] restored step {start} from {ckpt_dir}")

    losses = []
    t0 = time.perf_counter()
    for step in range(start, steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if step % log_every == 0 or step == steps - 1:
            dt = time.perf_counter() - t0
            print(f"[train] step {step:5d} loss {losses[-1]:.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f} ({dt:.1f}s)",
                  flush=True)
        if mgr and step > start and step % ckpt_every == 0:
            # tag with step+1: the saved state has THIS step applied, so a
            # restore resumes at the next step (no double-apply)
            mgr.save_async(state, step + 1,
                           meta={"arch": cfg.name, "seed": seed})
        if simulate_failure and step == simulate_failure:
            print(f"[train] simulating failure at step {step}", flush=True)
            if mgr:
                mgr.wait()
            sys.exit(42)
    if mgr:
        mgr.save_async(state, steps, meta={"arch": cfg.name, "seed": seed})
        mgr.wait()
    return state, losses


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--simulate-failure", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = cfg.reduced()
    tcfg = TrainConfig(peak_lr=args.lr, total_steps=args.steps,
                       remat=args.remat, microbatches=args.microbatches)
    _, losses = train_loop(
        cfg, tcfg, steps=args.steps, global_batch=args.batch,
        seq_len=args.seq, seed=args.seed, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, simulate_failure=args.simulate_failure)
    print(f"[train] done: first loss {losses[0]:.4f} "
          f"last loss {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
