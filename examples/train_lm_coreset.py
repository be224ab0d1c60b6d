"""End-to-end driver: train a ~100M-param llama-family model for a few
hundred steps with the paper's coreset batch selection, vs dense and uniform
baselines.

This is the first-class-framework integration of the paper (DESIGN.md §3):
each step scores the batch with party-local leverage scores (Algorithm 2 on
the model-axis feature slices), DIS-samples an m-row weighted coreset, and
runs the expensive forward/backward on the coreset only — an unbiased
gradient at ~fraction of the compute/communication.

  PYTHONPATH=src python examples/train_lm_coreset.py --steps 300 --mode coreset
  PYTHONPATH=src python examples/train_lm_coreset.py --compare   # all 3 modes
"""

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.core.selector import SelectorConfig
from repro.data.lm import TokenStream
from repro.optim.schedules import cosine_with_warmup
from repro.train import make_train_step, save_checkpoint, train_state_init
from repro.models.api import param_count


def small_llama():
    """~100M-param member of the llama3 family (full code path, CPU-feasible)."""
    return dataclasses.replace(
        get_arch("llama3.2-1b"),
        num_layers=4, d_model=512, num_heads=8, num_kv_heads=4, head_dim=64,
        d_ff=1536, vocab_size=8192, param_dtype=jax.numpy.float32,
        remat=False, attn_chunk=64,
    )


def train(mode: str, steps: int, batch: int, seq: int, seed: int = 0,
          ckpt_dir: str = None, score: str = "leverage"):
    cfg = small_llama()
    key = jax.random.PRNGKey(seed)
    state = train_state_init(key, cfg)
    n_params = param_count(state["params"])
    sel = (SelectorConfig(mode=mode, fraction=0.25, score=score)
           if mode != "none" else None)
    step = jax.jit(make_train_step(cfg, cosine_with_warmup(3e-4, 20, steps), sel))
    stream = iter(TokenStream(vocab=cfg.vocab_size, seq_len=seq,
                              batch_size=batch, seed=seed))
    losses, t0 = [], time.time()
    for i in range(steps):
        state, m = step(state, next(stream), jax.random.fold_in(key, i))
        losses.append(float(m["ce"]))
        if (i + 1) % max(steps // 10, 1) == 0:
            dt = (time.time() - t0) / (i + 1)
            print(f"[{mode:8s}] step {i+1:4d}/{steps} ce={losses[-1]:.4f} "
                  f"avg10={np.mean(losses[-10:]):.4f} {dt*1e3:.0f} ms/step")
    if ckpt_dir:
        save_checkpoint(ckpt_dir, state, steps)
        print(f"[{mode}] checkpoint saved to {ckpt_dir}")
    return np.asarray(losses), n_params


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mode", default="coreset", choices=["none", "uniform", "coreset"])
    ap.add_argument("--score", default="leverage", choices=["leverage", "norm"],
                    help="coreset score backend (norm = cheap row-norm ablation)")
    ap.add_argument("--compare", action="store_true")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    modes = ["none", "uniform", "coreset"] if args.compare else [args.mode]
    results = {}
    for mode in modes:
        losses, n_params = train(mode, args.steps, args.batch, args.seq,
                                 ckpt_dir=args.ckpt if mode == modes[-1] else None,
                                 score=args.score)
        results[mode] = losses
        print(f"[{mode:8s}] params={n_params/1e6:.1f}M "
              f"final ce={np.mean(losses[-10:]):.4f}")
    if args.compare:
        print("\nmode      final-10-avg   tokens-consumed-ratio")
        for mode, losses in results.items():
            frac = 1.0 if mode == "none" else 0.25
            print(f"{mode:8s}  {np.mean(losses[-10:]):12.4f}   {frac:.2f}")


if __name__ == "__main__":
    main()
