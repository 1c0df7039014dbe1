"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train \
        --arch gemma2-2b-reduced --steps 200 --policy mxsf --block-mode 2d \
        --batch 16 --seq 128 --ckpt-dir /tmp/run1

Any assigned arch id works (append ``-reduced`` for the CPU-scale variant).
Fault tolerance is on by default: the run checkpoints every ``--ckpt-every``
steps and auto-resumes from the latest checkpoint in ``--ckpt-dir``.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from ..configs.base import get_config
from ..core.policy import QuantPolicy
from ..data.pipeline import lm_batch, vision_batch
from ..optim.adamw import OptConfig
from ..runtime import fault
from ..runtime.compile_cache import use_compile_cache
from ..train import step as T


def build_policy(name: str, block_mode: str, tile: int = 8,
                 block_1d: int = 64) -> QuantPolicy:
    if name == "bf16":
        return QuantPolicy(block_mode="none")
    return QuantPolicy(fwd_fmt=name, bwd_fmt=name, block_mode=block_mode,
                       tile=tile, block_1d=block_1d)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--policy", default="mxsf")
    ap.add_argument("--block-mode", default="2d", choices=["1d", "2d", "none"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--grad-compress", default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    policy = build_policy(args.policy, args.block_mode)
    ocfg = OptConfig(lr=args.lr, total_steps=args.steps,
                     warmup_steps=max(1, min(100, args.steps // 10)))
    tcfg = T.TrainConfig(remat=args.remat, microbatches=args.microbatches,
                         grad_compress=args.grad_compress,
                         xent_chunk=min(1024, args.seq))
    step_fn = jax.jit(T.make_train_step(cfg, policy, ocfg, tcfg),
                      donate_argnums=(0,))

    def init_fn():
        return T.init_state(jax.random.PRNGKey(args.seed), cfg, ocfg)

    def batch_fn(i):
        if cfg.family == "encoder":
            x, y = vision_batch(args.seed, i, args.batch, cfg.frontend_tokens,
                                cfg.d_model, cfg.n_classes)
            return {"embeds": x, "label": y}
        toks, labs = lm_batch(args.seed, i, args.batch, args.seq, cfg.vocab)
        batch = {"tokens": toks, "labels": labs}
        if cfg.family == "encdec":
            import jax.numpy as jnp
            batch["frames"] = jnp.zeros((args.batch, cfg.enc_seq, cfg.d_model),
                                        jnp.bfloat16)
        if cfg.frontend == "vision" and cfg.frontend_tokens:
            import jax.numpy as jnp
            batch["embeds"] = jnp.zeros(
                (args.batch, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
        return batch

    log = []
    t0 = time.time()

    def on_metrics(step, metrics):
        if step % args.log_every == 0 or step == args.steps - 1:
            row = {k: float(v) for k, v in metrics.items()}
            row["step"] = step
            row["wall_s"] = round(time.time() - t0, 1)
            log.append(row)
            print(f"step {step:5d} " +
                  " ".join(f"{k}={v:.4g}" for k, v in row.items()
                           if k != "step"), flush=True)

    fcfg = fault.FaultConfig(ckpt_dir=args.ckpt_dir,
                             ckpt_every=args.ckpt_every, async_save=True)
    state, dog = fault.train_loop(fcfg, init_fn, step_fn, batch_fn,
                                  args.steps, metrics_cb=on_metrics)
    print(f"done in {time.time() - t0:.1f}s; "
          f"stragglers at {dog.straggler_steps}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(log, f, indent=1)
    return state


if __name__ == "__main__":
    main()
