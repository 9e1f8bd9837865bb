"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
        --reduced --steps 100 --batch 8 --seq 256

Trains on one device of whatever backend JAX finds (no mesh, no
sharding), so without ``--reduced`` the config must fit that device
whole.  ``--reduced`` trains a smoke-scale variant of the family
(``--d-model``/``--layers``/``--vocab``); the config is never cut unless
the flag is given.  The multi-device shardings are only compiled, not run,
by ``repro.launch.dryrun``.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke) variant")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", choices=["cosine", "wsd"], default="cosine")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save", default=None, help="checkpoint path")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.data import make_batch_iterator
    from repro.launch.compile_cache import configure_compile_cache
    from repro.optim import cosine_schedule, wsd_schedule
    from repro.train import Trainer, make_train_step, train_state_init

    configure_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(n_layers=args.layers, d_model=args.d_model,
                          vocab=args.vocab)
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(
        jax.eval_shape(lambda: train_state_init(
            cfg, jax.random.PRNGKey(0)).params)))
    print(f"[train] arch={cfg.name} family={cfg.arch} params={n_params/1e6:.1f}M "
          f"backend={jax.default_backend()} devices={jax.device_count()}")

    if args.schedule == "wsd":
        sched = wsd_schedule(args.lr, args.steps // 10, 7 * args.steps // 10,
                             2 * args.steps // 10)
    else:
        sched = cosine_schedule(args.lr, args.steps // 10, args.steps)

    data = make_batch_iterator(cfg.vocab, args.seq, args.batch,
                               seed=args.seed)
    state = train_state_init(cfg, jax.random.PRNGKey(args.seed))
    trainer = Trainer(cfg, state, sched, data)
    t0 = time.time()  # latlint: disable=L001 CLI wall-clock throughput banner
    hist = trainer.run(args.steps, log_every=max(args.steps // 20, 1))
    dt = time.time() - t0  # latlint: disable=L001 CLI wall-clock throughput banner
    toks = args.steps * args.batch * args.seq
    print(f"[train] {args.steps} steps in {dt:.1f}s "
          f"({toks/dt:.0f} tok/s) loss {hist[0]['loss']:.3f} -> "
          f"{hist[-1]['loss']:.3f}")
    if args.save:
        from repro.checkpoint import save_local
        n = save_local(args.save, trainer.state.params)
        print(f"[train] saved {n/1e6:.1f} MB checkpoint to {args.save}")


if __name__ == "__main__":
    main()
