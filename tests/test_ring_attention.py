"""Ring attention (sequence parallelism) parity + composition tests on the
8-device CPU mesh (conftest pins jax to a virtual 8-CPU platform).

Oracle is plain softmax attention over the full sequence
(parallel/ring_attention.py:attention_reference); the ring must reproduce
it for causal/non-causal, GQA, and ring sizes 2/4/8, and must compose
with tensor-parallel head sharding on a 2D ("tp", "sp") mesh."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P


from production_stack_tpu.parallel.ring_attention import (
    attention_reference,
    ring_attention,
    ring_attention_local,
)


def _rand(b, s, h, hk, d, dtype=jnp.float32, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, hk, d), dtype)
    v = jax.random.normal(kv, (b, s, hk, d), dtype)
    return q, k, v


def _mesh(sp):
    return Mesh(np.array(jax.devices()[:sp]), ("sp",))


@pytest.mark.parametrize("sp", [2, 4, 8])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_full_attention(sp, causal):
    q, k, v = _rand(b=2, s=32, h=4, hk=4, d=16)
    want = attention_reference(q, k, v, causal=causal)
    got = ring_attention(q, k, v, _mesh(sp), causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("h,hk", [(8, 2), (4, 1)])
def test_ring_gqa(h, hk):
    q, k, v = _rand(b=1, s=32, h=h, hk=hk, d=8, seed=3)
    want = attention_reference(q, k, v, causal=True)
    got = ring_attention(q, k, v, _mesh(4), causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_bfloat16():
    q, k, v = _rand(b=1, s=64, h=4, hk=4, d=16, dtype=jnp.bfloat16, seed=7)
    want = attention_reference(q, k, v, causal=True)
    got = ring_attention(q, k, v, _mesh(8), causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_ring_plus_tensor_parallel():
    """2D mesh: heads over tp, sequence over sp — the serving-relevant
    combination (tp inside a chip group, sp across the ring)."""
    tp, sp = 2, 4
    mesh = Mesh(
        np.array(jax.devices()[: tp * sp]).reshape(tp, sp), ("tp", "sp")
    )
    q, k, v = _rand(b=1, s=32, h=4, hk=2, d=8, seed=11)
    want = attention_reference(q, k, v, causal=True)

    spec = P(None, "sp", "tp", None)
    fn = jax.shard_map(
        functools.partial(ring_attention_local, axis_name="sp"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_long_context_memory_shape():
    """Each chip sees only S/sp of the KV inside the ring body (the
    long-context scaling claim): verify via the traced local shapes."""
    sp = 8
    s = 128
    captured = {}

    def probe(q, k, v):
        captured["kv_local"] = k.shape
        return ring_attention_local(q, k, v, axis_name="sp")

    mesh = _mesh(sp)
    spec = P(None, "sp", None, None)
    q, k, v = _rand(b=1, s=s, h=2, hk=2, d=8)
    jax.shard_map(probe, mesh=mesh, in_specs=(spec, spec, spec),
              out_specs=spec)(q, k, v)
    assert captured["kv_local"][1] == s // sp


def test_ring_rejects_unpadded_sequence():
    q, k, v = _rand(b=1, s=30, h=2, hk=2, d=8)
    with pytest.raises(Exception):
        ring_attention(q, k, v, _mesh(4))
