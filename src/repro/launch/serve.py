"""Serving launcher: batched generation with the ServeEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
      --batch 4 --prompt-len 16 --new-tokens 32
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import TransformerLM
from repro.serve.engine import ServeEngine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(args.seed))
    engine = ServeEngine(model, params,
                         max_len=args.prompt_len + args.new_tokens)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    t0 = time.time()
    out = engine.generate(prompts, args.new_tokens,
                          temperature=args.temperature, seed=args.seed)
    dt = time.time() - t0
    tput = args.batch * args.new_tokens / dt
    print(f"arch={cfg.name} generated {out.shape} in {dt:.2f}s "
          f"({tput:.1f} tok/s); first row: {out[0][:8].tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
