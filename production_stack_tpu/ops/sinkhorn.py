"""Sinkhorn projection of a batch of small positive matrices, as ONE
operation on the device.

`sinkhorn(x, iters, eps)`: x (n, n, rows) float32, positive; `iters`
times every row x[i, :, r] over its sum + eps, then every column
x[:, j, r] over its sum + eps (hyper-connections' H_res,
models/layer_groups.hc_mix). The matrices are tiny (n = 4) and the rows
ride the lane axis, so the work is nothing; what costs is the NUMBER of
operations. Left to XLA, every sum and every division of the 20
iterations is an operation of its own: 300 a layer and step, each under
a microsecond, 1.47 M device events in a 5 s profiler trace of the
serving cell, which the profiler took 207 s to write out (my chip run,
PR 33). Written out elementwise instead, XLA fused an iteration's half
at best and took three times as long to trace and lower the programs
(`setup_trace_lower_s` 78 -> 242 s). So on the TPU the iterations run
inside one Pallas kernel over the whole (n, n, rows) block in VMEM; off
it (CPU tests, rehearsals) the same `_iterate` runs as plain jnp.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _iterate(x, iters: int, eps: float):
    for _ in range(iters):
        x = x / (jnp.sum(x, axis=1, keepdims=True) + eps)
        x = x / (jnp.sum(x, axis=0, keepdims=True) + eps)
    return x


def _kernel(x_ref, o_ref, *, iters, eps):
    o_ref[...] = _iterate(x_ref[...], iters, eps)


def sinkhorn(x: jax.Array, iters: int, eps: float,
             interpret: bool | None = None) -> jax.Array:
    """`interpret`: None = the kernel on a TPU and plain jnp elsewhere;
    True = the kernel in interpret mode (tests)."""
    if interpret is None and jax.default_backend() != "tpu":
        return _iterate(x, iters, eps)
    return pl.pallas_call(
        functools.partial(_kernel, iters=iters, eps=eps),
        name="sinkhorn",
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=bool(interpret),
    )(x)
