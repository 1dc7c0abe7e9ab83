"""The state-space (Mamba-2) mixer over packed rows of several sequences.

A mixer layer of `models/layer_groups.forward_blocks`. With u a normed
row: [z | xBC | dt] = u W_in; xBC <- silu(conv(xBC) + b), a causal
depthwise convolution of K taps over the SEQUENCE'S OWN last K - 1 rows;
xBC splits into x (H heads of P), B and C (G groups of N; head h uses
group h // (H / G)); d = softplus(dt + dt_bias), A = -exp(A_log), a
scalar a head; S_t = exp(d_t A) S_{t-1} + d_t x_t (x) B_t with S (H, P,
N); y_t = S_t C_t + D x_t; y <- RMSNorm_w(y silu(z)) over each of the G
groups of lanes separately; F = y W_out. S, d, the decays and the norm's
statistics are float32.

What a sequence carries a layer is ONE slot of two arrays, not pages:
`s` (layers, slots, H / k, N, k P) float32 and `conv` (layers, slots, K
- 1, C) in the model's dtype. Slot 0 is nobody's: rows that are no
tokens read and write it. `s` holds S PACKED (`pack`, `to_packed`): k
heads of one group side by side on the minor axis (k = 2 at P = 64: 128
lanes) and N on the axis before it, so that the decode update multiplies
whole rows of dx and decay over the sublanes, one column of B and C a
group over the lanes, and sums over N by adding vector registers: no
operation of the update moves data across lanes (`state_update`).

The rows of a step program belong to several sequences and must not
meet. `plan_rows` finds, once a forward pass, from what every program
ships already (each row's write slot and position) and the block
manager's three maps (`engine/block_manager.StateBlockManager`: block ->
the state slot of the sequence writing it, -> the snapshot slot to SAVE
to when the block's last position is computed, -> the snapshot slot to
LOAD from when its first is):

- the LANES among the leading rows: runs of rows of one sequence (a
  prefill chunk), at most `lanes` of at most `lane_rows` rows, computed
  by the scan in its chunked matrix form (`scan_chunked`: within a chunk
  of `chunk` rows a masked (C B^T o decay) x product, between chunks the
  carried S), each from its own slot's state, or from zero at position
  0, or from a snapshot's at a restored prefix hit;
- the `tail` trailing rows, one token of one sequence each (decode
  lanes), computed by the recurrence itself (`scan_step`).

Both write the new state to the sequence's slot and, where a row
computes the last position of a block that has a snapshot slot, there
too. Nothing else copies a state: a save and a restore are a second
index of a write and a read that happen anyway, inside the step
program, so the step thread waits for no copy.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


class RowPlan(NamedTuple):
    """`plan_rows`' result; (L, ...) the lanes, (b, ...) the tail."""
    lead: int                # rows before the tail
    rows: jax.Array          # (L, T) row of each lane position, clipped
    rows_out: jax.Array      # (L, T) the same, `lead` where not a token
    valid: jax.Array         # (L, T) bool
    length: jax.Array        # (L,)
    src: jax.Array           # (L,) slot the lane's state is read from
    zero: jax.Array          # (L,) bool: starts at position 0, from zero
    dst: jax.Array           # (L,) the sequence's slot (0: no lane)
    save: jax.Array          # (L,) snapshot slot to write too (0: none)
    t_src: jax.Array         # (b,) the same for the tail's rows
    t_zero: jax.Array
    t_dst: jax.Array
    t_save: jax.Array


def plan_rows(write_slots, positions, maps, block_size: int,
              lanes: int, lane_rows: int, tail: int) -> RowPlan:
    """`maps` (3, blocks) int32: a block's state slot, save slot, load
    slot. `lanes`, `lane_rows`, `tail`: the program's shape (static)."""
    lead = write_slots.shape[0] - tail
    blk, off = write_slots // block_size, write_slots % block_size
    slot = maps[0, blk]

    def edges(first, last):
        """(src, zero, save) from a sequence's first and last row."""
        load = jnp.where(off[first] == 0, maps[2, blk[first]], 0)
        src = jnp.where(load > 0, load, slot[first])
        save = jnp.where(off[last] == block_size - 1,
                         maps[1, blk[last]], 0)
        return src, positions[first] == 0, save

    if lead and lanes:
        s = slot[:lead]
        start = (s != 0) & (s != jnp.concatenate(
            [jnp.full((1,), -1, s.dtype), s[:-1]]))
        first = jnp.nonzero(start, size=lanes, fill_value=lead)[0]
        has = first < lead
        first = jnp.minimum(first, lead - 1)
        dst = jnp.where(has, s[first], 0)
        # a sequence's rows are one run: its slot names them
        length = jnp.sum(
            (s[None, :] == dst[:, None]) & has[:, None], axis=1)
        t = jnp.arange(lane_rows)
        valid = t[None, :] < length[:, None]
        rows = jnp.minimum(first[:, None] + t[None, :], lead - 1)
        src, zero, save = edges(first, jnp.maximum(first + length - 1, 0))
        src, save = jnp.where(has, src, 0), jnp.where(has, save, 0)
    else:
        dst = length = src = zero = save = jnp.zeros((0,), jnp.int32)
        rows = valid = jnp.zeros((0, 0), jnp.int32)
    tail_rows = lead + jnp.arange(tail)
    t_src, t_zero, t_save = edges(tail_rows, tail_rows)
    return RowPlan(
        lead=lead, rows=rows, rows_out=jnp.where(valid, rows, lead),
        valid=valid, length=length, src=src, zero=zero, dst=dst,
        save=save,
        t_src=t_src, t_zero=t_zero, t_dst=slot[lead:], t_save=t_save,
    )


def causal_conv(ext, w, b):
    """silu(depthwise causal convolution + b): `ext` (..., T + K - 1, C)
    the rows behind the K - 1 that came before them, `w` (K, C) with
    w[K - 1] on the row itself -> (..., T, C) float32."""
    taps = w.shape[0]
    t = ext.shape[-2] - taps + 1
    acc = b.astype(F32)
    for k in range(taps):
        acc = acc + ext[..., k:k + t, :].astype(F32) * w[k].astype(F32)
    return jax.nn.silu(acc)


def scan_chunked(x, dt, a, b, c, s0, chunk: int):
    """One sequence's rows through the recurrence, in chunks. x (T, H,
    P), dt (T, H) float32 (0 on rows that are no tokens: the state
    passes them unchanged), a (H,) negative, b and c (T, G, N), s0 (H,
    P, N) float32 -> (y (T, H, P) float32 WITHOUT the D term, the state
    after the last row). T is padded up to whole chunks here."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    hg = h // g
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                       for v in (x, dt, b, c))
    nc = (t + pad) // q
    xq = x.astype(F32).reshape(nc, q, g, hg, p)
    dtq = dt.reshape(nc, q, g, hg)
    bq = b.astype(F32).reshape(nc, q, g, n)
    cq = c.astype(F32).reshape(nc, q, g, n)
    cum = jnp.cumsum(dtq * a.reshape(g, hg), axis=1)       # (nc, q, g, hg)
    # within a chunk: row i reads row j <= i through exp(cum_i - cum_j)
    causal = jnp.tril(jnp.ones((q, q), bool))
    diff = cum[:, :, None] - cum[:, None, :]               # (nc, i, j, g, hg)
    decay = jnp.exp(jnp.where(causal[None, :, :, None, None], diff,
                              -jnp.inf))
    cb = jnp.einsum("cign,cjgn->cijg", cq, bq)
    w = cb[..., None] * decay * dtq[:, None]               # (nc, i, j, g, hg)
    y = jnp.einsum("cijgh,cjghp->cighp", w, xq)
    # a chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, -1:] - cum) * dtq              # (nc, q, g, hg)
    s_loc = jnp.einsum("cjgn,cjghp->cghpn", bq, xq * to_end[..., None])
    chunk_decay = jnp.exp(cum[:, -1])                      # (nc, g, hg)

    def carry(s, inp):
        loc, dec = inp
        return dec[..., None, None] * s + loc, s

    s_end, s_in = jax.lax.scan(
        carry, s0.reshape(g, hg, p, n), (s_loc, chunk_decay))
    y = y + jnp.einsum("cign,cghpn->cighp", cq, s_in) * jnp.exp(
        cum)[..., None]
    return (y.reshape(nc * q, h, p)[:t], s_end.reshape(h, p, n))


def scan_step(x, dt, a, b, c, s):
    """One token a row: x (r, H, P), dt (r, H) float32, b and c (r, G,
    N), s (r, H, P, N) float32 -> (y (r, H, P) float32 without the D
    term, the new s)."""
    r, h, p = x.shape
    g = b.shape[1]
    bh = jnp.repeat(b.astype(F32), h // g, axis=1)          # (r, H, N)
    ch = jnp.repeat(c.astype(F32), h // g, axis=1)
    dx = dt[..., None] * x.astype(F32)
    s = (jnp.exp(dt * a)[..., None, None] * s
         + dx[..., None] * bh[:, :, None, :])
    return jnp.sum(s * ch[:, :, None, :], axis=-1), s


def gated_group_norm(y, z, weight, groups: int, eps: float):
    """RMSNorm_w(y silu(z)) with the statistics taken over each of the
    `groups` groups of lanes separately; float32 in, float32 out."""
    r, d = y.shape
    v = (y * jax.nn.silu(z.astype(F32))).reshape(r, groups, d // groups)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    return v.reshape(r, d) * weight.astype(F32)


def mixer(cfg, u, lp, state: dict, l, plan: RowPlan):
    """The mixer of state layer `l` over the normed rows u (n, hidden)
    -> (F (n, hidden) in u's dtype, `state` with the rows' sequences
    advanced). `state` = {"s", "conv"} as the module docstring has
    them. The tail's update is `state_update`'s kernel on a TPU and
    `scan_step` elsewhere."""
    kernel = jax.default_backend() == "tpu"
    dtype = u.dtype
    n = u.shape[0]
    h, p, g, ns = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                   cfg.ssm_state)
    d, cdim, taps = cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_conv
    a = -jnp.exp(lp["A_log"].astype(F32))
    zxd = jnp.dot(u, lp["w_in"], preferred_element_type=F32)
    z = zxd[:, :d]
    xbc = zxd[:, d:d + cdim].astype(dtype)
    dt = jax.nn.softplus(zxd[:, d + cdim:] + lp["dt_bias"].astype(F32))
    s_all, conv_all = state["s"], state["conv"]
    y = jnp.zeros((n, h, p), F32)

    def split(xc):
        lead = xc.shape[:-1]
        return (xc[..., :d].reshape(*lead, h, p),
                xc[..., d:d + g * ns].reshape(*lead, g, ns),
                xc[..., d + g * ns:].reshape(*lead, g, ns))

    k = pack(cfg)

    def tail_at(src, zero):
        """The convolution's carried rows of the sequences at `src`."""
        return jnp.where(zero[:, None, None], 0, conv_all[l, src])

    def state_at(src, zero):
        return jnp.where(zero[:, None, None, None], 0.0,
                         from_packed(s_all[l, src], k))

    if plan.rows.shape[0]:
        with jax.named_scope("ssm_conv"):
            tail0 = tail_at(plan.src, plan.zero)
            xl = jnp.where(plan.valid[..., None], xbc[plan.rows], 0)
            ext = jnp.concatenate([tail0, xl], axis=1)
            at = plan.length[:, None] + jnp.arange(taps - 1)[None, :]
            new_tail = jnp.take_along_axis(ext, at[..., None], axis=1)
            xc = causal_conv(ext, lp["conv_w"], lp["conv_b"]).astype(dtype)
        with jax.named_scope("ssm_scan"):
            x_l, b_l, c_l = split(xc)
            dt_l = jnp.where(plan.valid[..., None], dt[plan.rows], 0.0)
            y_l, s_end = jax.vmap(
                lambda *v: scan_chunked(*v, chunk=cfg.ssm_chunk),
                in_axes=(0, 0, None, 0, 0, 0),
            )(x_l, dt_l, a, b_l, c_l, state_at(plan.src, plan.zero))
            y_l = y_l + lp["D"].astype(F32)[:, None] * x_l.astype(F32)
            y = y.at[plan.rows_out.reshape(-1)].set(
                y_l.reshape(-1, h, p), mode="drop")
            s_end = to_packed(s_end, k)
            for at_slot in (plan.dst, plan.save):
                s_all = s_all.at[l, at_slot].set(s_end)
                conv_all = conv_all.at[l, at_slot].set(new_tail)
    if plan.t_dst.shape[0]:
        with jax.named_scope("ssm_step"):
            ext = jnp.concatenate(
                [tail_at(plan.t_src, plan.t_zero), xbc[plan.lead:, None]],
                axis=1)
            xc = causal_conv(ext, lp["conv_w"], lp["conv_b"]).astype(
                dtype)[:, 0]
            x_t, b_t, c_t = split(xc)
            new_tail = ext[:, 1:]
            conv_all = conv_all.at[l, plan.t_dst].set(new_tail)
            if kernel:
                y_t, s_all = state_update(
                    s_all, l, plan.t_src, plan.t_dst, plan.t_zero, x_t,
                    dt[plan.lead:], a, b_t, c_t)
            else:
                y_t, s_new = scan_step(
                    x_t, dt[plan.lead:], a, b_t, c_t,
                    state_at(plan.t_src, plan.t_zero))
                s_all = s_all.at[l, plan.t_dst].set(to_packed(s_new, k))
            y_t = y_t + lp["D"].astype(F32)[:, None] * x_t.astype(F32)
            y = y.at[plan.lead:].set(y_t)

            # a lane crosses a snapshot boundary once in hundreds of
            # steps: the second write of every lane's state only then
            def save(arrs):
                return (arrs[0].at[l, plan.t_save].set(
                            arrs[0][l, plan.t_dst]),
                        arrs[1].at[l, plan.t_save].set(new_tail))

            s_all, conv_all = jax.lax.cond(
                jnp.any(plan.t_save > 0), save, lambda arrs: arrs,
                (s_all, conv_all))
    out = gated_group_norm(y.reshape(n, d), z, lp["ssm_norm"], g,
                           cfg.rms_norm_eps).astype(dtype)
    return (jnp.dot(out, lp["w_out"], preferred_element_type=F32).astype(
        dtype), {"s": s_all, "conv": conv_all})


# -- the state's layout, and the decode update as one kernel ----------------
def pack(cfg) -> int:
    """Heads side by side on the state's minor axis: as many as fill
    128 lanes and lie in one group."""
    return math.gcd(max(1, 128 // cfg.ssm_head_dim),
                    cfg.ssm_heads // cfg.ssm_groups)


def packed_shape(cfg) -> tuple[int, int, int]:
    """A sequence's state of one layer as the state group holds it."""
    k = pack(cfg)
    return (cfg.ssm_heads // k, cfg.ssm_state, k * cfg.ssm_head_dim)


def to_packed(s, k: int):
    """(..., H, P, N) -> (..., H / k, N, k P)."""
    *lead, h, p, n = s.shape
    s = s.reshape(*lead, h // k, k, p, n)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, h // k, n, k * p)


def from_packed(s, k: int):
    """(..., H / k, N, k P) -> (..., H, P, N)."""
    *lead, r, n, w = s.shape
    s = s.reshape(*lead, r, n, k, w // k)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, r * k, w // k, n)


def _update_kernel(meta_ref, s_ref, da_ref, dx_ref, b_ref, c_ref,
                   o_ref, y_ref, *, lanes, groups, rows_a_group):
    """One lane's state through one token, a packed row (k heads of one
    group, (N, k P)) at a time: s <- decay s + B (x) dx and y = sum_N s
    C. Decay and dx are rows over the sublanes, B and C one column a
    group over the lanes, the sum over N adds vector registers."""
    i = pl.program_id(0)
    live = meta_ref[1 + lanes + i] != 0
    # a lane that starts at position 0 starts from zero
    keep = jnp.where(meta_ref[1 + 2 * lanes + i] == 0, 1.0, 0.0)
    shape = s_ref.shape[1:]

    # a lane that is nobody's (it would write slot 0) computes nothing:
    # most lanes of a round under light load, and as they all name slot
    # 0 their states are not copied again either (a block whose index
    # stays is kept)
    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live)
    def _():
        for g in range(groups):
            b = jnp.broadcast_to(b_ref[:, g:g + 1], shape)
            c = jnp.broadcast_to(c_ref[:, g:g + 1], shape)
            for r in range(g * rows_a_group, (g + 1) * rows_a_group):
                s = (s_ref[r] * (da_ref[r:r + 1, :] * keep)
                     + b * dx_ref[r:r + 1, :])
                o_ref[r] = s
                y_ref[r:r + 1, :] = jnp.sum(s * c, axis=0, keepdims=True)


def state_update(s_all, l, src, dst, zero, x, dt, a, b, c,
                 interpret: bool = False):
    """`scan_step` on the state group in place: s_all (L, slots, H / k,
    N, k P) float32 (packed), lane i's state read at [l, src[i]] (from
    zero where `zero[i]`) and written at [l, dst[i]]; x (r, H, P), dt
    (r, H) float32, a (H,), b and c (r, G, N) -> (y (r, H, P) float32
    without the D term, s_all). ONE Mosaic kernel (`ssm_state_update` in
    a trace), a lane a grid step, the pool aliased to the result: a
    lane's state passes HBM once each way, where XLA's gather, fused
    update and scatter pass it three times (compile-only v5e, and
    measured: PERF.md, Findings PR 45). A lane that is nobody's (`dst`
    0) is skipped: its y is zero, and slot 0 holds whatever the
    result's buffer held, which no lane with a sequence reads."""
    r, h, p = x.shape
    g = b.shape[1]
    rows, _, width = s_all.shape[2:]
    f32 = jnp.float32
    # a head's decay, and dt x, along the state's minor axis
    da = jnp.repeat(jnp.exp(dt * a), p, axis=-1).reshape(r, rows, width)
    dx = (dt[..., None] * x.astype(f32)).reshape(r, rows, width)
    y, s_all = lane_update_call(
        _update_kernel, "ssm_state_update", s_all, l, src, dst, zero,
        [da, dx, jnp.swapaxes(b.astype(f32), 1, 2),
         jnp.swapaxes(c.astype(f32), 1, 2)],
        (rows, width), interpret, lanes=r, groups=g,
        rows_a_group=rows // g)
    return y.reshape(r, h, p), s_all


def lane_update_call(kernel, name: str, s_all, l, src, dst, zero,
                     lane_ins, y_block, interpret: bool, **kernel_kw):
    """A one-token update of the state group in place, a lane a grid
    step: `kernel(meta, s, *lane_ins, s_out, y, **kernel_kw)` sees lane
    i's slot [l, src[i]] of `s_all` (L, slots, a, b, c) as `s`, row i of
    each (r, ., .) array of `lane_ins`, and writes the slot [l, dst[i]]
    and row i of y (r, *y_block) float32; meta = [l | src | dst | zero]
    rides scalar-prefetch SMEM. The pool is aliased to the result, so a
    lane's state passes HBM once each way -> (y, s_all)."""
    r = src.shape[0]
    f32 = jnp.float32
    meta = jnp.concatenate([
        jnp.reshape(l, (1,)).astype(jnp.int32), src.astype(jnp.int32),
        dst.astype(jnp.int32), zero.astype(jnp.int32)])

    def lane(block):
        return pl.BlockSpec((None, *block), lambda i, m: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    def state(col):
        return pl.BlockSpec(
            (None, None, *s_all.shape[2:]),
            lambda i, m: (m[0], m[1 + col * r + i], 0, 0, 0),
            memory_space=pltpu.VMEM)

    s_all, y = pl.pallas_call(
        functools.partial(kernel, **kernel_kw),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r,),
            in_specs=[state(0), *(lane(x.shape[1:]) for x in lane_ins)],
            out_specs=[state(1), lane(y_block)],
        ),
        out_shape=[jax.ShapeDtypeStruct(s_all.shape, f32),
                   jax.ShapeDtypeStruct((r, *y_block), f32)],
        # the pool is the first operand after the scalars
        input_output_aliases={1: 0},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a lane's state in and out, twice over each
            vmem_limit_bytes=max(
                32 * 2**20, 5 * math.prod(s_all.shape[2:]) * 4),
        ),
    )(meta, s_all, *lane_ins)
    return y, s_all
