"""Paged attention over a block-table-indexed KV cache — XLA reference path.

The KV cache is paged (vLLM-style "PagedAttention" capability, which the
reference stack gets from its external vLLM engines — reference:
src/vllm_router/stats/engine_stats.py scrapes `vllm:gpu_cache_usage_perc`).
Here the cache for all layers lives in HBM as a dense array of slots:

    k_cache, v_cache : (num_layers, num_blocks * block_size, num_kv_heads, head_dim)

A sequence owns an ordered list of blocks (its *block table*); the token at
absolute position p lives in slot `block_table[p // block_size] * block_size +
p % block_size`, so row i of the gathered context is absolute position i.

This module is the gather-based XLA implementation: correct everywhere (CPU
tests, TPU fallback), with the gather `cache[layer, slots]` fused by XLA into
a single HBM read per layer. The Pallas kernel in ops/pallas_attention.py
avoids materialising the gathered context entirely and is swapped in on TPU.

All shapes are static: context length is bucketed by the model runner, so jit
traces once per (bucket) variant.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MASK_VALUE = -1e30


def block_table_slots(block_table, block_size: int):
    """Expand a block table into per-position cache slots.

    block_table: (..., num_blocks) int -> slots (..., num_blocks * block_size)
    where slots[..., p] is the cache row holding absolute position p.
    Works on numpy and jax arrays.
    """
    offsets = jnp.arange(block_size, dtype=jnp.int32)
    bt = jnp.asarray(block_table, dtype=jnp.int32)
    slots = bt[..., :, None] * block_size + offsets
    return slots.reshape(*bt.shape[:-1], -1)


def _gqa_scores(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """q: (..., nq, d), k: (..., c, nkv, d) -> scores (..., nkv, g, c) fp32."""
    *lead, nq, d = q.shape
    nkv = k.shape[-2]
    g = nq // nkv
    qg = q.reshape(*lead, nkv, g, d).astype(jnp.float32)
    return jnp.einsum(
        "...kgd,...ckd->...kgc", qg, k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale


def _gqa_output(p: jax.Array, v: jax.Array) -> jax.Array:
    """p: (..., nkv, g, c), v: (..., c, nkv, d) -> out (..., nq, d) fp32."""
    *lead, nkv, g, _ = p.shape
    d = v.shape[-1]
    out = jnp.einsum(
        "...kgc,...ckd->...kgd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(*lead, nkv * g, d)


def _softmax(scores: jax.Array, sink: jax.Array | None) -> jax.Array:
    """Softmax over the last axis of (..., nkv, g, c) scores. `sink`
    ((nq,) float32): one learned logit per q head that joins the
    denominator and adds nothing to the numerator — an extra column
    that is dropped after the normalisation."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    col = jnp.broadcast_to(
        sink.astype(jnp.float32).reshape(scores.shape[-3:-1])[..., None],
        (*scores.shape[:-1], 1),
    )
    return jax.nn.softmax(
        jnp.concatenate([scores, col], axis=-1), axis=-1
    )[..., :-1]


def context_attention_decode(
    q: jax.Array,  # (batch, num_q_heads, head_dim)
    k_ctx: jax.Array,  # (batch, padded_ctx, num_kv_heads, head_dim)
    v_ctx: jax.Array,
    context_lens: jax.Array,  # (batch,) valid positions incl. the new token
    scale: float,
    window: int | None = None,  # sliding-window size; None = full context
    sink: jax.Array | None = None,  # (nq,) logits, see _softmax
) -> jax.Array:
    """One decode step over gathered per-sequence context. -> (b, nq,
    d_v): K and V may have different head widths.

    With `window`, the query (at position context_len-1) attends only
    its last `window` predecessors incl. itself (HF sliding-window
    semantics: keys j with q_pos - window < j <= q_pos)."""
    scores = _gqa_scores(q, k_ctx, scale)  # (b, nkv, g, c)
    c = k_ctx.shape[1]
    key_pos = jnp.arange(c)[None, :]
    valid = key_pos < context_lens[:, None]  # (b, c)
    if window is not None:
        valid = valid & (key_pos > context_lens[:, None] - 1 - window)
    scores = jnp.where(valid[:, None, None, :], scores, MASK_VALUE)
    p = _softmax(scores, sink)
    return _gqa_output(p, v_ctx).astype(q.dtype)


def context_attention_prefill(
    q: jax.Array,  # (t, num_q_heads, head_dim) — chunk queries (padded)
    k_ctx: jax.Array,  # (padded_ctx, num_kv_heads, head_dim)
    v_ctx: jax.Array,
    q_positions: jax.Array,  # (t,) absolute positions of the chunk tokens
    total_len: jax.Array,  # scalar: valid context positions (prefix + chunk)
    scale: float,
    window: int | None = None,  # sliding-window size; None = full context
    sink: jax.Array | None = None,  # (nq,) logits, see _softmax
) -> jax.Array:
    """Chunked-prefill attention for one sequence; causal over absolute
    positions (context rows ARE absolute positions). -> (t, nq, d_v).

    With `window`, each query attends only its last `window` positions
    incl. itself (keys j with q_pos - window < j <= q_pos)."""
    scores = _gqa_scores(q, k_ctx, scale)  # (t, nkv, g, c)
    c = k_ctx.shape[0]
    key_pos = jnp.arange(c)
    mask = (key_pos[None, :] <= q_positions[:, None]) & (
        key_pos[None, :] < total_len
    )  # (t, c)
    if window is not None:
        mask = mask & (
            key_pos[None, :] > q_positions[:, None] - window
        )
    scores = jnp.where(mask[:, None, None, :], scores, MASK_VALUE)
    p = _softmax(scores, sink)
    return _gqa_output(p, v_ctx).astype(q.dtype)
