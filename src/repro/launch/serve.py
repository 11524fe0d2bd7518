"""Serving launcher: batched prefill + greedy decode for any architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b-smoke \
        --batch 4 --prompt-len 32 --new-tokens 32
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b-smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default="",
                    help="load params from a train.py checkpoint")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(args.seed))
    if args.checkpoint:
        from repro.checkpoint import load_checkpoint
        params, meta = load_checkpoint(args.checkpoint, params)
        print("loaded", args.checkpoint, meta)

    eng = ServeEngine(model, params,
                      max_len=args.prompt_len + args.new_tokens + 8)
    rng = np.random.RandomState(args.seed)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)}
    if cfg.is_encdec:
        batch["frames"] = jnp.asarray(
            rng.randn(args.batch, args.prompt_len // 4,
                      cfg.encoder.d_model) * 0.02, jnp.float32)
    elif cfg.frontend == "vision":
        nt = min(cfg.n_frontend_tokens, args.prompt_len // 2)
        batch["frontend_embeds"] = jnp.asarray(
            rng.randn(args.batch, cfg.n_frontend_tokens, cfg.d_model) * 0.02,
            jnp.float32)

    res = eng.generate(batch, args.new_tokens)
    print(f"arch={cfg.name} batch={args.batch} "
          f"prefill={res.prefill_time_s*1e3:.1f}ms "
          f"decode={res.decode_time_s*1e3:.1f}ms "
          f"throughput={res.tokens_per_s:.1f} tok/s")
    print("sample output ids:", res.tokens[0][:16].tolist())


if __name__ == "__main__":
    main()
