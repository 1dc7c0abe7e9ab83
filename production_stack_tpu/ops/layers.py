"""Elementwise / normalization / rotary ops, expressed for XLA fusion.

These are deliberately plain jnp: XLA fuses RMSNorm and RoPE into the
surrounding matmuls on TPU, so Pallas is reserved for the one op XLA cannot
schedule well (paged attention over a block table, see ops/paged_attention.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float,
             offset: float = 0.0) -> jax.Array:
    """RMSNorm with float32 accumulation, cast back to input dtype.

    `offset` supports zero-centered norm weights (Gemma stores w - 1 and
    the model multiplies by 1 + w)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (weight.astype(jnp.float32) + offset)).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature (DeepSeek-V3's `yarn_get_mscale`):
    0.1 * mscale * ln(factor) + 1 past factor 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, yarn) -> jax.Array:
    """YaRN's frequencies (`models.config.YarnScaling`), as DeepSeek-V3's
    modelling code blends them: dims that turn more than `beta_fast`
    times within the original context keep their frequency, those that
    turn fewer than `beta_slow` times take it divided by `factor`, and a
    linear ramp over the dim index joins the two."""
    half = head_dim // 2
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) * (2.0 / head_dim))
    )

    def correction_dim(rotations):
        return (head_dim * math.log(
            yarn.original_max_position / (rotations * 2 * math.pi))
        ) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low)
        / max(high - low, 0.001), 0.0, 1.0)
    return inv_freq / yarn.factor * ramp + inv_freq * (1.0 - ramp)


def rope_cos_sin(
    positions: jax.Array, head_dim: int, theta: float, yarn=None,
    factor: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for the given positions. Returns (N, head_dim) each,
    `head_dim` being the dims that rotate (a layer kind's `rotary_dim`).

    HF-Llama convention: frequencies over the first half of the head dim,
    duplicated across halves (rotate-half formulation). `yarn`: the
    frequencies blended as YaRN does. `factor` multiplies cos and sin
    (a kind's `rope_factor`, HF's `attention_factor`); None = YaRN's
    mscale(factor, mscale) / mscale(factor, mscale_all_dim), 1 without.
    """
    half = head_dim // 2
    if yarn is not None:
        inv_freq = yarn_inv_freq(head_dim, theta, yarn)
    else:
        inv_freq = 1.0 / (
            theta
            ** (jnp.arange(0, half, dtype=jnp.float32) * (2.0 / head_dim))
        )
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(freqs), jnp.cos(freqs)], axis=-1)
    sin = jnp.concatenate([jnp.sin(freqs), jnp.sin(freqs)], axis=-1)
    if factor is None and yarn is not None:
        factor = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(
            yarn.factor, yarn.mscale_all_dim)
    if factor is not None and factor != 1.0:
        cos, sin = cos * factor, sin * factor
    return cos, sin


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(
    q: jax.Array,
    k: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Apply rotary embeddings.

    q: (N, num_heads, head_dim), k: (N, num_kv_heads, head_dim),
    cos/sin: (N, rotary_dim). With rotary_dim < head_dim (partial
    rotary) the leading rotary_dim dims of each head are rotated,
    half-split among themselves, and the rest pass unrotated.
    """
    cos = cos[:, None, :].astype(jnp.float32)
    sin = sin[:, None, :].astype(jnp.float32)
    r = cos.shape[-1]

    def rot(x):
        xf = x.astype(jnp.float32)
        if r == x.shape[-1]:
            return (xf * cos + _rotate_half(xf) * sin).astype(x.dtype)
        xr = xf[..., :r]
        return jnp.concatenate(
            [xr * cos + _rotate_half(xr) * sin, xf[..., r:]], axis=-1
        ).astype(x.dtype)

    return rot(q), rot(k)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array, act: str = "silu") -> jax.Array:
    """Gated MLP: (act(x @ w_gate) * (x @ w_up)) @ w_down.

    act: "silu" (Llama/Mistral/Qwen SwiGLU) or "gelu_tanh" (Gemma
    GeGLU)."""
    pre = jnp.dot(x, w_gate, preferred_element_type=jnp.float32)
    if act == "gelu_tanh":
        gate = jax.nn.gelu(pre, approximate=True)
    else:
        gate = jax.nn.silu(pre)
    up = jnp.dot(x, w_up, preferred_element_type=jnp.float32)
    return jnp.dot(
        (gate * up).astype(x.dtype), w_down,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
