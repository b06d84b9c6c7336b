"""A published model's weights as its checkpoint lays them out, with values
drawn from the run's seed on the device.

The configuration's ``data`` entry holds the model's published sizes (its
``config.json``) and the law each kind of tensor is drawn from.  The tensor
list is the checkpoint's own: Whisper's encoder (two convolutions, the
sinusoidal positions, its layers, a final LayerNorm), then its decoder (the
token embedding, which the output head shares, learned positions, its
layers, a final LayerNorm), each in the order the checkpoint stores them.
The token embedding is written as blocks of ``vocab_block_rows`` rows, every
row kept, so that no operation is larger than the largest matrix.

``item(k)`` is ``(name, array)``: tensor ``k mod n`` of pass ``k div n``, a
device array drawn from ``(seed, pass, tensor)`` in float32 and rounded to
the configuration's dtype; the encoder's positions are the sinusoid itself,
alike on every pass.  A second ``Source`` of the same seed makes the same
words again for the comparison after the window.  Nothing here imports the
program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np


def sinusoids(length: int, channels: int, max_timescale: float = 10000.0
              ) -> np.ndarray:
    """Whisper's encoder positions: ``[sin | cos]`` of ``length`` positions
    over ``channels // 2`` timescales, in float32."""
    inc = np.log(max_timescale) / (channels // 2 - 1)
    inv = np.exp(-inc * np.arange(channels // 2, dtype=np.float32))
    t = np.arange(length, dtype=np.float32)[:, None] * inv[None, :]
    return np.concatenate([np.sin(t), np.cos(t)], axis=1).astype(np.float32)


def _attention(prefix: str, d: int) -> list:
    """One attention block as the checkpoint stores it: ``k_proj`` has no
    bias, ``v_proj``, ``q_proj`` and ``out_proj`` have one."""
    out = [(f"{prefix}.k_proj.weight", (d, d), "matrix")]
    for p in ("v_proj", "q_proj", "out_proj"):
        out += [(f"{prefix}.{p}.weight", (d, d), "matrix"),
                (f"{prefix}.{p}.bias", (d,), "bias")]
    return out


def _norm(prefix: str, d: int) -> list:
    return [(f"{prefix}.weight", (d,), "norm_weight"),
            (f"{prefix}.bias", (d,), "bias")]


def _ffn(prefix: str, d: int, f: int) -> list:
    return [(f"{prefix}.fc1.weight", (f, d), "matrix"),
            (f"{prefix}.fc1.bias", (f,), "bias"),
            (f"{prefix}.fc2.weight", (d, f), "matrix"),
            (f"{prefix}.fc2.bias", (d,), "bias")]


def tensors(p: dict) -> list:
    """``(name, shape, law)`` of every item of one pass, in the checkpoint's
    order, the token embedding split into blocks of rows."""
    d, mel = int(p["d_model"]), int(p["num_mel_bins"])
    enc = "model.encoder"
    out = [(f"{enc}.conv1.weight", (d, mel, 3), "matrix"),
           (f"{enc}.conv1.bias", (d,), "bias"),
           (f"{enc}.conv2.weight", (d, d, 3), "matrix"),
           (f"{enc}.conv2.bias", (d,), "bias"),
           (f"{enc}.embed_positions.weight",
            (int(p["max_source_positions"]), d), "sinusoid")]
    for i in range(int(p["encoder_layers"])):
        L = f"{enc}.layers.{i}"
        out += (_attention(f"{L}.self_attn", d)
                + _norm(f"{L}.self_attn_layer_norm", d)
                + _ffn(L, d, int(p["encoder_ffn_dim"]))
                + _norm(f"{L}.final_layer_norm", d))
    out += _norm(f"{enc}.layer_norm", d)
    dec = "model.decoder"
    vocab, rows = int(p["vocab_size"]), int(p["vocab_block_rows"])
    for b, r0 in enumerate(range(0, vocab, rows)):
        out.append((f"{dec}.embed_tokens.weight.block{b:02d}",
                    (min(rows, vocab - r0), d), "embedding"))
    out.append((f"{dec}.embed_positions.weight",
                (int(p["max_target_positions"]), d), "embedding"))
    for i in range(int(p["decoder_layers"])):
        L = f"{dec}.layers.{i}"
        out += (_attention(f"{L}.self_attn", d)
                + _norm(f"{L}.self_attn_layer_norm", d)
                + _attention(f"{L}.encoder_attn", d)
                + _norm(f"{L}.encoder_attn_layer_norm", d)
                + _ffn(L, d, int(p["decoder_ffn_dim"]))
                + _norm(f"{L}.final_layer_norm", d))
    out += _norm(f"{dec}.layer_norm", d)
    return out


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(words, sd, mean, shape: tuple, dtype):
    """``mean + sd * N(0, 1)`` drawn in float32 from the key that
    ``words`` (uint32: seed low, seed high, pass, tensor) folds to, rounded
    to ``dtype``."""
    key = jax.random.PRNGKey(words[0])
    for i in range(1, words.shape[0]):
        key = jax.random.fold_in(key, words[i])
    return (jax.random.normal(key, shape, jnp.float32) * sd + mean
            ).astype(dtype)


class Source:
    def __init__(self, config: dict, seed: int):
        p = config["data"]
        self.p = p
        self.items = tensors(p)
        self.seed = int(seed) % 2**64
        self.dtype = np.dtype(getattr(ml_dtypes, p["dtype"], p["dtype"]))
        self._sinusoid = None

    @property
    def items_per_pass(self) -> int:
        return len(self.items)

    def name(self, k: int) -> str:
        pas, t = divmod(k, self.items_per_pass)
        return f"p{pas:05d}.{self.items[t][0]}"

    def law(self, k: int) -> tuple[float, float]:
        """``(sd, mean)`` of item ``k``'s values."""
        _name, shape, law = self.items[k % self.items_per_pass]
        if law == "matrix":
            # PyTorch layout: (out, in[, kernel]); fan-in is all but the first
            return 1.0 / np.sqrt(np.prod(shape[1:])), 0.0
        if law == "norm_weight":
            return float(self.p["norm_weight_sd"]), 1.0
        return float(self.p[f"{law}_sd"]), 0.0

    def item(self, k: int):
        pas, t = divmod(k, self.items_per_pass)
        _name, shape, law = self.items[t]
        if law == "sinusoid":
            if self._sinusoid is None:
                self._sinusoid = jax.device_put(
                    sinusoids(*shape).astype(self.dtype))
            return self.name(k), self._sinusoid
        words = np.array([self.seed & 0xFFFFFFFF, self.seed >> 32, pas, t],
                         np.uint32)
        sd, mean = self.law(k)
        return self.name(k), _draw(words, np.float32(sd), np.float32(mean),
                                   shape=tuple(shape), dtype=self.dtype)

    def free(self) -> None:
        self._sinusoid = None
