"""Deterministic synthetic token pipeline.

Design for fault tolerance and elasticity: the stream is a pure function of
(seed, step) — `batch_at(step)` is O(1), so resume-after-preemption and
re-sharding onto a different mesh need no iterator state beyond the step
counter (stored in the checkpoint manifest).  This is the "deterministic
data skip" strategy used by production trainers.

The generator emits Zipf-ish token ids with short-range repetition so the
loss actually decreases during the e2e example runs.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class TokenStream:
    vocab: int
    batch: int
    seq: int
    seed: int = 0

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed << 32) ^ step)
        # zipf-like marginal + markov-ish repetition for learnable structure
        base = rng.zipf(1.3, size=(self.batch, self.seq + 1)) % self.vocab
        rep = rng.random((self.batch, self.seq + 1)) < 0.3
        toks = base.copy()
        toks[:, 1:][rep[:, 1:]] = toks[:, :-1][rep[:, 1:]]
        toks = toks.astype(np.int32)
        return {
            "tokens": jnp.asarray(toks[:, :-1]),
            "labels": jnp.asarray(toks[:, 1:]),
        }

    def batches(self, start_step: int = 0):
        step = start_step
        while True:
            yield step, self.batch_at(step)
            step += 1


@dataclasses.dataclass(frozen=True)
class MultimodalStream:
    """Wraps TokenStream with stub frame/patch embeddings for encdec/vlm."""

    vocab: int
    batch: int
    seq: int
    d_model: int
    kind: str          # "frames" | "patches"
    prefix: int = 8
    seed: int = 0
    dtype: str = "float32"

    def batch_at(self, step: int) -> dict:
        ts = TokenStream(self.vocab, self.batch, self.seq, self.seed)
        b = ts.batch_at(step)
        rng = np.random.default_rng((self.seed << 32) ^ (step + 77))
        if self.kind == "frames":
            emb = rng.normal(0, 1, (self.batch, self.seq, self.d_model))
            return {
                "frames": jnp.asarray(emb, jnp.dtype(self.dtype)),
                "tokens": b["tokens"],
                "labels": b["labels"],
            }
        p = self.prefix
        emb = rng.normal(0, 1, (self.batch, p, self.d_model))
        return {
            "patches": jnp.asarray(emb, jnp.dtype(self.dtype)),
            "tokens": b["tokens"][:, : self.seq - p],
            "labels": b["labels"][:, : self.seq - p],
        }

    def batches(self, start_step: int = 0):
        step = start_step
        while True:
            yield step, self.batch_at(step)
            step += 1


def stream_for(cfg, batch: int, seq: int, seed: int = 0):
    if cfg.family == "encdec":
        return MultimodalStream(
            cfg.vocab, batch, seq, cfg.d_model, "frames", seed=seed,
            dtype=cfg.compute_dtype,
        )
    if cfg.family == "vlm":
        return MultimodalStream(
            cfg.vocab, batch, seq, cfg.d_model, "patches",
            prefix=min(cfg.frontend_len or 8, seq // 4), seed=seed,
            dtype=cfg.compute_dtype,
        )
    return TokenStream(cfg.vocab, batch, seq, seed)
