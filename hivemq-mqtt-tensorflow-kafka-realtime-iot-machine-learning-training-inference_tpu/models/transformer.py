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

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attention_reference, flash_attention


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
        qkv = nn.DenseGeneral((3, H, D), name="qkv")(x)  # [B,T,3,H,D]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
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
        return nn.DenseGeneral(self.d_model, axis=(-2, -1), name="out")(o)


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
