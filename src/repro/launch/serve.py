"""Serving launcher: batched generation with the KV-cache decode path.

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --reduced \
        --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--load", default=None, help="checkpoint to serve")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.compile_cache import configure_compile_cache
    from repro.models import ops_for
    from repro.serving import GenerationEngine

    configure_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    ops = ops_for(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = ops.init(cfg, key)
    if args.load:
        from repro.checkpoint import load_local
        params = load_local(args.load, like=params)

    B, S = args.batch, args.prompt_len
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.arch == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            key, (B, cfg.n_patches, cfg.d_model))
        batch["positions3"] = jnp.broadcast_to(
            jnp.arange(S + cfg.n_patches, dtype=jnp.int32)[None, None],
            (3, B, S + cfg.n_patches))
    if cfg.arch == "audio":
        batch["frames"] = jax.random.normal(key, (B, cfg.enc_seq, cfg.d_source))

    eng = GenerationEngine(cfg, params,
                           max_len=S + args.gen + cfg.n_patches + 1)
    t0 = time.time()  # latlint: disable=L001 CLI wall-clock throughput banner
    out, stats = eng.generate(batch, args.gen,
                              temperature=args.temperature, seed=args.seed)
    dt = time.time() - t0  # latlint: disable=L001 CLI wall-clock throughput banner
    print(f"[serve] arch={cfg.name} batch={B} prompt={S} generated={args.gen}")
    print(f"[serve] {stats['generated']} tokens in {dt:.2f}s "
          f"({stats['generated']/dt:.1f} tok/s incl. prefill+compile)")
    print(f"[serve] sample continuation: {out[0][:16].tolist()}")


if __name__ == "__main__":
    main()
