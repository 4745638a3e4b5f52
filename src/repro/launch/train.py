"""Training launcher (host-scale entry point; the mesh logic is identical
to the production dry-run — on a real TPU fleet the same script runs under
jax.distributed with the 16x16 / 2x16x16 mesh from launch.mesh).

  PYTHONPATH=src python -m repro.launch.train --arch nllb600m --smoke \
      --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from ..configs import REGISTRY, get_config, reduce_config
from ..data import SyntheticLM, SyntheticTranslation
from ..models import Ctx, build_model
from ..optim import warmup_cosine
from ..runtime import configure_compile_cache
from ..train import TrainLoop, make_train_step


def batches_for(cfg, batch: int, seq: int, seed: int = 0):
    if cfg.family in ("encdec", "audio"):
        ds = SyntheticTranslation(cfg.vocab_size, min(seq, cfg.enc_len or seq),
                                  seed)
        while True:
            b = ds.sample(batch)
            yield {k: jnp.asarray(v) for k, v in b.items()
                   if not isinstance(v, str)}
    else:
        ds = SyntheticLM(cfg.vocab_size, seq, seed)
        while True:
            yield {"tokens": jnp.asarray(ds.sample(batch)["tokens"])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="nllb600m", choices=sorted(REGISTRY))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--state-bits", type=int, default=32, choices=(8, 32))
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_config(cfg)
    model = build_model(cfg)
    ctx = Ctx(compute_dtype=jnp.float32 if args.smoke else jnp.bfloat16)
    init_state, step = make_train_step(
        model, lr_fn=lambda s: warmup_cosine(s, peak_lr=args.lr, warmup=10,
                                             total=args.steps),
        microbatches=args.microbatches, remat=args.remat,
        state_bits=args.state_bits, ctx=ctx)

    loop = TrainLoop(jax.jit(step, donate_argnums=0), args.ckpt_dir,
                     ckpt_every=args.ckpt_every)
    state = init_state(model.init(jax.random.PRNGKey(0)))
    state, start = loop.maybe_resume(state)
    state, history = loop.run(state, batches_for(cfg, args.batch, args.seq),
                              args.steps, start_step=start)
    print(f"done: {len(history)} steps, loss {history[0]:.4f} -> "
          f"{history[-1]:.4f}, stragglers={loop.stragglers}")


if __name__ == "__main__":
    main()
