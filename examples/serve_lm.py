"""Serve a small model with batched requests through the ServeEngine
(prefill + KV-cached greedy/temperature decode).

  PYTHONPATH=src python examples/serve_lm.py --arch llama3.2-1b --batch 4
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import all_arch_names, get_arch
from repro.models import init_params
from repro.models.lm_serve import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=all_arch_names())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_arch(args.arch).reduced()     # CPU-feasible member of the family
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    engine = ServeEngine(cfg, params, cache_len=256)

    prompts = jax.random.randint(jax.random.fold_in(key, 1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab_size)
    prefix = None
    if cfg.kind == "encdec" or cfg.frontend != "none":
        prefix = jax.random.normal(jax.random.fold_in(key, 2),
                                   (args.batch, cfg.num_prefix, cfg.d_model))

    t0 = time.time()
    out = engine.generate(prompts, max_new_tokens=args.new_tokens,
                          temperature=args.temperature,
                          key=jax.random.fold_in(key, 3), prefix_embeds=prefix)
    dt = time.time() - t0
    toks = args.batch * args.new_tokens
    print(f"arch={args.arch} (reduced)  batch={args.batch}  "
          f"{toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s incl. prefill)")
    for b in range(args.batch):
        print(f"  request {b}: {list(map(int, out[b]))}")


if __name__ == "__main__":
    main()
