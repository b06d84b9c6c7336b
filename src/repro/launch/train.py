"""Training launcher: pjit train loop + compressed checkpointing + restart.

Runs on whatever devices exist (1 CPU here; the production mesh path is
exercised by dryrun.py).  Fault tolerance contract:
 * checkpoint every --save-every steps (atomic, compressed, mesh-independent)
 * --resume picks up the latest checkpoint: params/opt bitwise restored,
   data pipeline repositioned by step counter (O(1) skip)
 * --preempt-at N exits the process abruptly after step N (simulates a
   node failure for the restart test)

Example:
  python -m repro.launch.train --arch minicpm-2b --reduced \
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ck --save-every 20
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import CLI_IDS, get_config
from repro.data.tokens import stream_for
from repro.distributed.steps import make_train_step, shardings_for_train
from repro.launch.compile_cache import use_checkout_cache
from repro.launch.mesh import make_local_mesh
from repro.models import build_model


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--preempt-at", type=int, default=None)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_checkout_cache()

    cfg = get_config(CLI_IDS.get(args.arch, args.arch), reduced=args.reduced)
    model = build_model(cfg)
    mesh = make_local_mesh(args.data_par, args.model_par)
    stream = stream_for(cfg, args.batch, args.seq)
    batch0 = stream.batch_at(0)
    batch_shape = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch0
    )

    pshape, pspecs, in_sh, out_sh = shardings_for_train(
        model, mesh, batch_shape, fsdp=False
    )
    step_fn = jax.jit(
        make_train_step(model, mesh, lr=args.lr, n_micro=args.microbatch),
        in_shardings=in_sh, out_shardings=out_sh,
    )

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if args.resume and mgr and mgr.latest_step() is not None:
        tree, extra = mgr.restore_latest()
        start_step = int(extra["step"])
        put = lambda t, sh: jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x), s), t, sh
        )
        params = put(tree["params"], in_sh[0])
        m = put(tree["m"], in_sh[1])
        v = put(tree["v"], in_sh[2])
        opt_step = jnp.asarray(tree["opt_step"], jnp.int32)
        print(f"[resume] restored step {start_step} from {args.ckpt_dir}")
    else:
        with mesh:
            params = jax.jit(model.init, out_shardings=in_sh[0])(
                jax.random.PRNGKey(0)
            )
        m = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        v = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        opt_step = jnp.zeros((), jnp.int32)

    losses = []
    t0 = time.time()
    for step, batch in stream.batches(start_step):
        if step >= args.steps:
            break
        params, m, v, opt_step, metrics = step_fn(params, m, v, opt_step, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"step {step:5d} | loss {loss:.4f} | "
                  f"gnorm {float(metrics['grad_norm']):.3f} | {dt:.1f}s",
                  flush=True)
        if mgr and (step + 1) % args.save_every == 0:
            stats = mgr.save(
                step + 1,
                {"params": params, "m": m, "v": v, "opt_step": opt_step},
                extra={"data_step": step + 1, "loss": loss},
            )
            print(f"[ckpt] step {step+1} ratio {stats['ratio']:.3f}", flush=True)
        if args.preempt_at is not None and step + 1 >= args.preempt_at:
            print(f"[preempt] simulated failure after step {step+1}", flush=True)
            os._exit(17)

    if len(losses) >= 20:
        first = float(np.mean(losses[:5]))
        last = float(np.mean(losses[-5:]))
        print(f"loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
