"""SensorFormer: causal transformer over long per-car sensor histories.

The reference's sequence model is a batch-1, look_back-1 LSTM (SURVEY §2.5)
— semantically a next-step predictor.  SensorFormer is the TPU-native
generalization: the same next-step objective (predict sensor vector t+1
from 1..t) over *long* windows, so one model sees hours of per-car context.
Anomaly score = next-step prediction error, the sequence analogue of the
autoencoder's reconstruction error.

TPU mapping: pre-norm blocks, MXU-friendly dims (d_model multiple of 128
recommended at scale; small defaults for the 18-sensor demo), attention
dispatched by mode:
  'dense'  – jnp reference (CPU/tests)
  'flash'  – Pallas kernel (`ops.attention.flash_attention`), single chip
  'ring'   – sequence-parallel ring attention (`parallel.ring_attention`),
             call inside shard_map with T sharded over the mesh 'seq' axis
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attention_reference, flash_attention


def _flat_dot_general(lhs, rhs, dimension_numbers, precision=None):
    """A `DenseGeneral`'s product over its input's trailing axes as ONE
    2-D product of the flattened operands: `[B, T, H, D]` by
    `[H, D, d_model]` is `[B, T, H·D]` by `[H·D, d_model]`.  XLA gives
    a product of rank-4 operands a T-minor layout and copies the
    kernels' row-major arrays into it, forward and backward."""
    (contract, _), _ = dimension_numbers
    n = len(contract)
    return jnp.dot(lhs.reshape(lhs.shape[:-n] + (-1,)),
                   rhs.reshape((-1,) + rhs.shape[n:]), precision=precision)


class QKVProjection(nn.Module):
    """q, k and v, each `[B, T, H, D]`, out of ONE kernel
    `[d_model, 3, H, D]` and bias `[3, H, D]` — the parameters (names,
    shapes, initial values) of a `DenseGeneral((3, H, D))` — as three
    2-D products by the kernel's three column slices.  One product
    gives `[B, T, 3, H, D]`, which XLA lays out T-minor so that its
    three slices are free and then transposes, slice by slice, into the
    row-major arrays the flash kernels index in place (and the same
    back for dq, dk, dv); a slice of the weights is 4 MB where a slice
    of the activations is 16 MiB."""
    num_heads: int
    head_dim: int

    @nn.compact
    def __call__(self, x):
        shape = (x.shape[-1], 3, self.num_heads, self.head_dim)

        def kernel_init(rng, shape, dtype=jnp.float32):
            # as DenseGeneral: fans of the flattened [in, out] matrix
            flat = (shape[0], math.prod(shape[1:]))
            return nn.initializers.lecun_normal()(rng, flat, dtype).reshape(
                shape)

        kernel = self.param("kernel", kernel_init, shape)
        bias = self.param("bias", nn.initializers.zeros_init(), shape[1:])
        w, b = kernel.reshape(shape[0], 3, -1), bias.reshape(3, -1)
        return tuple((x @ w[:, i] + b[i]).reshape(x.shape[:-1] + shape[2:])
                     for i in range(3))


class MultiHeadAttention(nn.Module):
    d_model: int
    num_heads: int
    attn_mode: str = "dense"  # dense | flash | flash_interpret | ring
    ring_axis: str = "seq"

    @nn.compact
    def __call__(self, x):
        B, T, _ = x.shape
        H = self.num_heads
        D = self.d_model // H
        q, k, v = QKVProjection(H, D, name="qkv")(x)  # [B,T,H,D] each
        if self.attn_mode == "dense":
            o = attention_reference(q, k, v, causal=True)
        elif self.attn_mode == "flash":
            o = flash_attention(q, k, v, causal=True)
        elif self.attn_mode == "flash_interpret":
            o = flash_attention(q, k, v, causal=True, interpret=True)
        elif self.attn_mode == "ring":
            from ..parallel.ring_attention import ring_attention

            o = ring_attention(q, k, v, axis_name=self.ring_axis, causal=True)
        else:
            raise ValueError(f"unknown attn_mode {self.attn_mode}")
        return nn.DenseGeneral(self.d_model, axis=(-2, -1), name="out",
                               dot_general=_flat_dot_general)(o)


class Block(nn.Module):
    d_model: int
    num_heads: int
    mlp_ratio: int = 4
    attn_mode: str = "dense"
    ring_axis: str = "seq"

    @nn.compact
    def __call__(self, x):
        # named scopes: the fused XLA operations of each half carry
        # `attn` or `mlp` in their metadata, whatever flax names the
        # modules inside (a device trace groups by them)
        with jax.named_scope("attn"):
            x = x + MultiHeadAttention(
                self.d_model, self.num_heads, self.attn_mode,
                self.ring_axis, name="attn")(nn.LayerNorm(name="ln1")(x))
        with jax.named_scope("mlp"):
            h = nn.LayerNorm(name="ln2")(x)
            h = nn.Dense(self.d_model * self.mlp_ratio, name="mlp_in")(h)
            h = nn.gelu(h)
            h = nn.Dense(self.d_model, name="mlp_out")(h)
        return x + h


class SensorFormer(nn.Module):
    """Next-step sensor prediction over [B, T, features]."""

    features: int = 18
    d_model: int = 64
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 4096
    attn_mode: str = "dense"
    ring_axis: str = "seq"

    @nn.compact
    def __call__(self, x, positions: Optional[jnp.ndarray] = None):
        B, T, F = x.shape
        h = nn.Dense(self.d_model, name="embed")(x)
        if positions is None and T > self.max_len:
            raise ValueError(
                f"sequence length {T} exceeds max_len={self.max_len}; "
                f"under jit the Embed gather would silently clamp")
        pos = jnp.arange(T) if positions is None else positions
        pe = nn.Embed(self.max_len, self.d_model, name="pos")(pos)
        h = h + pe  # broadcasts over batch for [T]- or [B,T]-shaped positions
        for i in range(self.num_layers):
            h = Block(self.d_model, self.num_heads, attn_mode=self.attn_mode,
                      ring_axis=self.ring_axis, name=f"block{i}")(h)
        h = nn.LayerNorm(name="ln_f")(h)
        return nn.Dense(self.features, name="head")(h)

    @staticmethod
    def anomaly_scores(pred, x):
        """Per-step next-step prediction error: pred[t] estimates x[t+1]."""
        err = jnp.mean(jnp.square(pred[:, :-1] - x[:, 1:]), axis=-1)
        return err  # [B, T-1]
