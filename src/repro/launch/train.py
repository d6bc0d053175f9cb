"""Distributed training launcher.

On the production mesh this runs the same jitted train_step the dry-run
lowers; on this CPU container it trains the small demo/reduced configs
for real (examples/train_grammar_lm.py drives it end-to-end).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch syncode-demo \
      --grammar json --steps 200 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.grammars import load_grammar
from repro.core.tokenizer import ByteTokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.training.data import GrammarDataPipeline, RandomTokenPipeline
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import train


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="syncode-demo")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test reduced variant")
    ap.add_argument("--grammar", default="json",
                    help="grammar for the synthetic data pipeline, or "
                         "'random' for random tokens")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    n_params = sum(l.size for l in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"vocab={cfg.vocab_size}")

    if args.grammar == "random":
        data = iter(RandomTokenPipeline(cfg, args.seq, args.batch,
                                        seed=args.seed))
    else:
        tok = ByteTokenizer(cfg.vocab_size)
        g, _ = load_grammar(args.grammar)
        data = iter(GrammarDataPipeline(g, tok, args.seq, args.batch,
                                        seed=args.seed))

    opt = AdamWConfig(lr=args.lr, warmup_steps=max(10, args.steps // 20),
                      total_steps=args.steps)
    params, result = train(model, params, data, args.steps, opt_cfg=opt,
                           checkpoint_path=args.checkpoint)
    print(f"final loss {result.losses[-1]:.4f} "
          f"({result.steps_per_sec:.2f} steps/s)")
    return params, result


if __name__ == "__main__":
    main()
