"""Serving launcher: batched model prefill/decode, or the compressed tensor
server replaying many-client traffic over a shard store.

Model serving (the original seed loop)::

  python -m repro.launch.serve --arch rwkv6-3b --reduced \
      --batch 4 --prompt-len 32 --gen-len 16

Tensor serving (high-fan-out compressed reads; docs/serving.md)::

  python -m repro.launch.serve --tensors /path/to/shards \
      --clients 8 --requests 2000 --cache-mb 64

The tensor mode stands up a :class:`repro.serving.TensorServer` over the
directory's ``*.fpc`` containers, replays a zipfian tenant×tensor request
mix from N client threads, and prints p50/p99 latency plus cache/coalescing
counters — the operational face of the traffic-replay benchmark
(benchmarks/bench_serve.py).
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def serve_model(args) -> int:
    # heavy deps stay lazy: tensor mode must not pay jax/model import time
    import jax
    import jax.numpy as jnp

    from repro.configs import CLI_IDS, get_config
    from repro.models import build_model

    cfg = get_config(CLI_IDS.get(args.arch, args.arch), reduced=args.reduced)
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    params = model.init(jax.random.PRNGKey(0))

    b, s = args.batch, args.prompt_len
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(rng.normal(0, 1, (b, s, cfg.d_model)), cfg.cdt)
    if cfg.family == "vlm":
        p = min(8, s // 2)
        batch["patches"] = jnp.asarray(rng.normal(0, 1, (b, p, cfg.d_model)), cfg.cdt)
        batch["tokens"] = batch["tokens"][:, : s - p]

    max_len = s + args.gen_len
    prefill = jax.jit(lambda pp, bb: model.prefill(pp, bb, max_len))
    t0 = time.time()
    logits, cache = prefill(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    decode = jax.jit(model.decode_step)
    tok = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1).astype(jnp.int32)
    out_tokens = [np.asarray(tok)]
    t0 = time.time()
    for _ in range(args.gen_len - 1):
        logits_t, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits_t.astype(jnp.float32), axis=-1).astype(jnp.int32)
        out_tokens.append(np.asarray(tok))
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = np.stack(out_tokens, axis=1)
    tps = b * (args.gen_len - 1) / max(t_decode, 1e-9)
    print(f"prefill: {t_prefill*1e3:.1f} ms for {b}x{s}")
    print(f"decode:  {t_decode*1e3:.1f} ms for {args.gen_len-1} steps "
          f"({tps:.1f} tok/s)")
    print(f"sample generations (token ids):\n{gen[:2, :12]}")
    assert np.all(gen >= 0) and np.all(gen < cfg.vocab)
    return 0


def serve_tensors(args) -> int:
    from repro.serving import (
        TensorServer, percentiles, replay, zipf_schedule,
    )

    cache_bytes = None if args.cache_mb is None else args.cache_mb << 20
    with TensorServer(args.tensors, cache_bytes=cache_bytes) as srv:
        names = srv.names()
        if not names:
            print(f"no *.fpc containers under {args.tensors}", file=sys.stderr)
            return 2
        sizes = {name: srv.n_elements(name) for name in names}
        sched = zipf_schedule(sizes, args.requests, s=args.zipf,
                              slice_frac=args.slice_frac, seed=args.seed)
        t0 = time.time()
        lat = replay(srv, sched, clients=args.clients)
        wall = time.time() - t0
        p = percentiles(lat, (50, 90, 99))
        st = srv.stats()
        cache = st["cache"]
        served = st["requests_full"] + st["requests_slice"]
        hit_rate = cache["hits"] / max(cache["hits"] + cache["misses"], 1)
        print(f"served {served} requests over {len(names)} tensors "
              f"({args.clients} clients) in {wall:.2f}s "
              f"({served / max(wall, 1e-9):.0f} req/s)")
        print(f"latency us: p50={p[50]:.0f} p90={p[90]:.0f} p99={p[99]:.0f}")
        print(f"cache: hit-rate={hit_rate:.1%} hits={cache['hits']} "
              f"misses={cache['misses']} evictions={cache['evictions']} "
              f"bytes={cache['bytes']}")
        print(f"decodes: {st['decodes']} "
              f"({st['decoded_bytes'] / 1e6:.1f} MB decoded) "
              f"coalesced={st['coalesced']}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", help="model architecture (model-serving mode)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--tensors", metavar="DIR",
                    help="serve compressed tensors from this shard-store "
                         "directory instead of running a model")
    ap.add_argument("--clients", type=int, default=8,
                    help="concurrent client threads (tensor mode)")
    ap.add_argument("--requests", type=int, default=2000,
                    help="total replayed requests (tensor mode)")
    ap.add_argument("--cache-mb", type=int, default=None,
                    help="decoded-span cache budget in MiB "
                         "(default: REPRO_SERVE_CACHE_BYTES or 64)")
    ap.add_argument("--zipf", type=float, default=1.1,
                    help="zipf exponent of the tensor popularity mix")
    ap.add_argument("--slice-frac", type=float, default=0.5,
                    help="fraction of requests that read a sub-range")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_checkout_cache

    use_checkout_cache()
    if args.tensors:
        return serve_tensors(args)
    if not args.arch:
        ap.error("either --arch (model serving) or --tensors (compressed "
                 "tensor serving) is required")
    return serve_model(args)


if __name__ == "__main__":
    sys.exit(main())
