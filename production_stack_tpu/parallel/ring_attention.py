"""Ring attention: sequence/context parallelism for long-context prefill.

The reference stack serves long contexts by scaling KV across hosts with
NCCL/LMCache tiers; the TPU-native answer is to shard the *sequence* axis
over a mesh axis and rotate KV blocks around the ICI ring (Ring Attention,
Liu et al. 2023 — see PAPERS.md), so each chip:

- holds one query block Q_i and one KV block KV_i of a long sequence,
- computes flash-style partial attention of Q_i against whichever KV
  block is resident, accumulating with an online softmax
  (running max `m`, normalizer `l`, weighted sum `o`),
- passes its KV block to the next chip with `lax.ppermute` each step.

After `sp` steps every query block has seen every KV block; HBM never
holds more than `seq/sp` keys per chip, so max context scales linearly
with the ring size. Compute and the permute overlap naturally: XLA
schedules the collective-permute concurrently with the einsums because
the DMA has no data dependency on them (the scaling-book "ring" recipe).

Causality is handled with *global positions*: query block i covers
positions [i*lq, (i+1)*lq); after r hops chip i holds the KV block
originally owned by chip (i - r) mod sp, so a single `qpos >= kpos`
mask covers the fully-visible, diagonal, and fully-masked cases without
branching (compiler-friendly: the loop body is one traced program).

GQA is supported directly: q heads are grouped onto kv heads inside the
einsum, so the rotated buffers stay at kv-head width (smaller ICI
payload than repeating kv to q width before the ring).

Composes with tensor parallelism: heads are whatever the caller's
shard_map left on-chip, so a ("tp", "sp") 2D mesh splits heads over tp
and sequence over sp (`ring_attention` takes the axis name; see
tests/test_ring_attention.py::test_ring_plus_tensor_parallel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import Mesh, PartitionSpec as P

SP_AXIS = "sp"


def _grouped_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """[b,lq,h,d] x [b,lk,hk,d] -> [b,h,lq,lk] with h = g*hk (GQA)."""
    b, lq, h, d = q.shape
    hk = k.shape[2]
    g = h // hk
    qg = q.reshape(b, lq, hk, g, d)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32)
    return s.reshape(b, h, lq, k.shape[1])


def _grouped_values(p: jax.Array, v: jax.Array) -> jax.Array:
    """[b,h,lq,lk] x [b,lk,hk,d] -> [b,lq,h,d] (f32 accumulation)."""
    b, h, lq, lk = p.shape
    hk = v.shape[2]
    g = h // hk
    pg = p.reshape(b, hk, g, lq, lk)
    o = jnp.einsum("bkgqs,bskd->bqkgd", pg, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, lq, h, v.shape[3])


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset: jax.Array | int | None = None,
    *,
    axis_name: str = SP_AXIS,
    causal: bool = True,
    scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Per-chip body: call inside shard_map with seq sharded on axis_name.

    q: [b, lq, h, d]; k, v: [b, lk, hk, d] (local blocks). Returns
    [b, lq, h, d] attention output for the local query block, in q.dtype.

    `q_offset` (optional, traced) shifts the query blocks' GLOBAL
    positions: chunked long-context prefill runs a [start, start+C)
    query slice against the full-sequence KV cache, so the causal mask
    must compare start-relative query rows to absolute key rows. None =
    the classic full-sequence ring (q and kv cover the same span).
    `window` applies HF sliding-window semantics (keys j with
    q_pos - window < j <= q_pos — ops/attention.py), so the ring
    reproduces what the engine's windowed prefill computes.
    """
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = d ** -0.5 if scale is None else scale
    sp = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    qpos = me * lq + lax.iota(jnp.int32, lq)
    if q_offset is not None:
        qpos = qpos + q_offset

    # derive the accumulators from q so they carry q's varying-axis type
    # (works for any enclosing mesh: plain sp ring or 2D tp x sp); fresh
    # jnp.zeros would be "unvarying" and the fori_loop carry check rejects
    # a body whose outputs vary over the manual axes
    zero_qhl = (q[..., 0] * 0.0).transpose(0, 2, 1).astype(jnp.float32)
    acc = (q * 0.0).astype(jnp.float32)
    m = zero_qhl - jnp.inf
    l = zero_qhl

    def body(r, carry):
        acc, m, l, k_blk, v_blk = carry
        src = (me - r) % sp  # original owner of the resident KV block
        s = _grouped_scores(q, k_blk) * scale  # [b,h,lq,lk] f32
        if causal:
            kpos = src * lk + lax.iota(jnp.int32, lk)
            mask = qpos[:, None] >= kpos[None, :]
            if window is not None:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # rows with every position masked so far keep m == -inf; exp(s - m)
        # would be NaN, so pin those rows to zero contribution
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - safe_m[..., None])
        if causal:
            p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr.transpose(0, 2, 1)[..., None] + _grouped_values(
            p, v_blk
        )
        m = m_new
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return acc, m, l, k_blk, v_blk

    acc, m, l, _, _ = lax.fori_loop(0, sp, body, (acc, m, l, k, v))
    norm = jnp.where(l == 0.0, 1.0, l).transpose(0, 2, 1)[..., None]
    return (acc / norm).astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis_name", "causal", "scale")
)
def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = SP_AXIS,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Full-array entry: q [b, S, h, d], k/v [b, S, hk, d] with S the
    global sequence; shards S over `axis_name` and runs the ring.

    S must divide evenly by the ring size (pad the prompt to the bucket,
    exactly as the engine's chunked prefill already does).
    """
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(
            ring_attention_local, axis_name=axis_name, causal=causal,
            scale=scale,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec
    )
    return fn(q, k, v)


def attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
    scale: float | None = None, window: int | None = None,
) -> jax.Array:
    """Unsharded oracle for tests: plain softmax attention with GQA."""
    d = q.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    s = _grouped_scores(q, k) * scale
    if causal:
        n, lk = q.shape[1], k.shape[1]
        qpos = lax.iota(jnp.int32, n)[:, None]
        kpos = lax.iota(jnp.int32, lk)[None, :]
        mask = qpos >= kpos
        if window is not None:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_values(p, v).astype(q.dtype)
