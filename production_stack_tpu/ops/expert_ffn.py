"""The routed experts' SwiGLU over expert-sorted rows, as ONE operation
on the device that reads only the experts that have rows.

`expert_ffn(xs, w_gate, w_up, w_down, sizes, skip, base)`: xs (m, d) are
rows sorted by expert: `skip` rows that are nobody's, then local expert
0's `sizes[0]` rows, expert 1's, and so on; the rows left over are
nobody's too, and nobody's rows come out zero. The weights are whole
stacks, (N, d, f) and (N, f, d), N >= base + len(sizes), and local
expert j is group `base + j` of them (`base`: the layer's offset into a
scanned run's stack, a traced scalar). Returns (m, d) float32:
`silu(x W_gate) * (x W_up)`, rounded to the rows' dtype, times `W_down`,
accumulated in float32.

Which groups to visit is data. The experts with rows are packed, in
their order, into a list that rides scalar prefetch with each one's
first row and size; the grid's first axis walks that list and is as long
as it (a dynamic bound: a step that does nothing still costs its third
of a microsecond, and of 64 experts a decode step of three live lanes
visits 11), its second the tiles of `f`, so an expert's three weight
tiles pass through VMEM once and `g`, `u` and `a` never reach HBM. An
expert's rows are contiguous, so it multiplies the row tile(s) of
`_ROW_TILE` that cover them and masks the rest: the MXU's work follows
the group, not m. The rows and the result stay whole in VMEM (m <=
`MAX_ROWS`; `moe.routed_experts` walks more pairs than that in passes).

Off the TPU (CPU tests, rehearsals) the same mathematics runs as three
`jax.lax.ragged_dot`s over all N groups with the other layers' groups
empty (`_plain`).

`w_gate=None`: experts WITHOUT a gate matrix, `act(x W_up) W_down` with
`act` relu squared ("relu2"): two weight tiles a step through a kernel
body of its own (`_kernel_ungated`), so the SwiGLU kernel above stays
what it compiled to; an `f` that no tile of `_F_TILES` divides takes its
largest divisor of whole 128-lane tiles up to `_F_MAX` (2,688 -> 896).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_ROWS = 512      # rows and float32 result whole in VMEM: 24 MiB at d 4096
_ROW_TILE = 128     # the MXU's height
_F_TILES = (512, 256, 128)
_F_MAX = 1024       # an ungated expert's f tile: two weight tiles a step


def activation(name: str, v):
    """An MLP's activation by its config name ("silu", "relu2")."""
    if name == "relu2":
        return jnp.square(jax.nn.relu(v))
    assert name == "silu", name
    return jax.nn.silu(v)


def _plain(xs, w_gate, w_up, w_down, sizes, skip, base, act="silu"):
    # ragged_dot's groups start at row 0: the skipped rows ride with the
    # first expert and are zeroed below
    group = lax.dynamic_update_slice(
        jnp.zeros((w_up.shape[0],), jnp.int32),
        sizes.at[0].add(skip).astype(jnp.int32), (base,))
    kw = dict(preferred_element_type=jnp.float32)
    if w_gate is None:
        a = activation(act, lax.ragged_dot(xs, w_up, group, **kw))
    else:
        g = lax.ragged_dot(xs, w_gate, group, **kw)
        u = lax.ragged_dot(xs, w_up, group, **kw)
        a = activation(act, g) * u
    a = a.astype(xs.dtype)
    y = lax.ragged_dot(a, w_down, group, **kw)
    row = jnp.arange(xs.shape[0])[:, None]
    return jnp.where((row >= skip) & (row < skip + jnp.sum(sizes)), y, 0.0)


def _kernel(meta_ref, wg_ref, wu_ref, wd_ref, x_ref, o_ref, *, steps, ts):
    w, t = pl.program_id(0), pl.program_id(1)

    @pl.when((w == 0) & (t == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    first = meta_ref[steps + w]
    size = meta_ref[2 * steps + w]

    def rows(r0):
        x = x_ref[pl.ds(r0, ts), :]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        row = r0 + lax.broadcasted_iota(jnp.int32, (ts, 1), 0)
        a = jnp.where((row >= first) & (row < first + size),
                      jax.nn.silu(g) * u, 0.0).astype(x.dtype)
        o_ref[pl.ds(r0, ts), :] += jnp.dot(
            a, wd_ref[0], preferred_element_type=jnp.float32)

    @pl.when(size > 0)
    def _():
        if ts == x_ref.shape[0]:
            rows(0)
        else:
            def tile(r, carry):
                rows(pl.multiple_of(r * ts, ts))
                return carry

            lax.fori_loop(first // ts, (first + size - 1) // ts + 1, tile, 0)


def _kernel_ungated(meta_ref, wu_ref, wd_ref, x_ref, o_ref, *, steps, ts,
                    act):
    """`_kernel` for experts without a gate matrix."""
    w, t = pl.program_id(0), pl.program_id(1)

    @pl.when((w == 0) & (t == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    first = meta_ref[steps + w]
    size = meta_ref[2 * steps + w]

    def rows(r0):
        x = x_ref[pl.ds(r0, ts), :]
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        row = r0 + lax.broadcasted_iota(jnp.int32, (ts, 1), 0)
        a = jnp.where((row >= first) & (row < first + size),
                      activation(act, u), 0.0).astype(x.dtype)
        o_ref[pl.ds(r0, ts), :] += jnp.dot(
            a, wd_ref[0], preferred_element_type=jnp.float32)

    @pl.when(size > 0)
    def _():
        if ts == x_ref.shape[0]:
            rows(0)
        else:
            def tile(r, carry):
                rows(pl.multiple_of(r * ts, ts))
                return carry

            lax.fori_loop(first // ts, (first + size - 1) // ts + 1, tile, 0)


def expert_ffn(xs: jax.Array, w_gate: jax.Array | None, w_up: jax.Array,
               w_down: jax.Array, sizes: jax.Array,
               skip: jax.Array | int = 0, base: jax.Array | int = 0,
               interpret: bool | None = None,
               act: str = "silu") -> jax.Array:
    """`interpret`: None = the kernel on a TPU and `_plain` elsewhere;
    True = the kernel in interpret mode (tests)."""
    if interpret is None and jax.default_backend() != "tpu":
        return _plain(xs, w_gate, w_up, w_down, sizes, skip, base, act)
    m, d = xs.shape
    f = w_up.shape[2]
    e_loc = sizes.shape[0]
    if m > MAX_ROWS:
        raise ValueError(f"{m} rows: expert_ffn holds at most {MAX_ROWS}")
    tf = next((t for t in _F_TILES if f % t == 0), f)
    if w_gate is None and f % 128 == 0:
        tf = max(t for t in range(128, min(f, _F_MAX) + 1, 128)
                 if f % t == 0)
    ts = math.gcd(m, _ROW_TILE)
    if ts % 16:
        ts = m  # no aligned tile divides the rows: one tile of them all
    steps = min(e_loc, m)  # no more experts can have rows than rows

    # the experts with rows, packed to the front in their order, each
    # with its first row and its size; by masks and sums, not a sort:
    # a handful of numbers, and every operation here is one more launch
    # a layer
    j = jnp.arange(e_loc)
    before = j[None, :] < j[:, None]                    # [j, i]: i < j
    has = sizes > 0
    first = skip + jnp.sum(jnp.where(before, sizes[None, :], 0), axis=1)
    slot = jnp.sum(before & has[None, :], axis=1)
    hit = has[None, :] & (slot[None, :] == jnp.arange(steps)[:, None])

    def packed(v):
        return jnp.sum(jnp.where(hit, v[None, :], 0), axis=1)

    meta = jnp.concatenate(
        [base + packed(j), packed(first), packed(sizes)]).astype(jnp.int32)
    # the grid is as long as the list (one step of nothing where it is
    # empty: the result is zeroed there)
    count = jnp.maximum(jnp.sum(has.astype(jnp.int32)), 1)

    whole = pl.BlockSpec((m, d), lambda w, t, meta: (0, 0),
                         memory_space=pltpu.VMEM)
    up = pl.BlockSpec((1, d, tf), lambda w, t, meta: (meta[w], 0, t),
                      memory_space=pltpu.VMEM)
    down = pl.BlockSpec((1, tf, d), lambda w, t, meta: (meta[w], t, 0),
                        memory_space=pltpu.VMEM)
    if w_gate is None:
        return pl.pallas_call(
            functools.partial(_kernel_ungated, steps=steps, ts=ts, act=act),
            name="expert_ffn",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(count, f // tf),
                in_specs=[up, down, whole],
                out_specs=whole,
            ),
            out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
            interpret=bool(interpret),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=64 * 2**20,
            ),
        )(meta, w_up, w_down, xs)
    assert act == "silu", act
    return pl.pallas_call(
        functools.partial(_kernel, steps=steps, ts=ts),
        name="expert_ffn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(count, f // tf),
            in_specs=[up, up, down, whole],
            out_specs=whole,
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        interpret=bool(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # three weight tiles twice over (24 MiB at d 4096) beside
            # the rows and their float32 result, twice over too: over
            # the default 16 MiB of scoped VMEM; a v5e has 128 MiB
            vmem_limit_bytes=64 * 2**20,
        ),
    )(meta, w_gate, w_up, w_down, xs)
