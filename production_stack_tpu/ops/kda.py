"""The gated delta-rule linear-attention (KDA) mixer over packed rows of
several sequences (Kimi Delta Attention, arXiv:2510.26692, on the gated
delta rule of arXiv:2412.06464).

A mixer layer of `models/layer_groups.forward_blocks`, letter "K". With
u a normed row, H heads of K key dims and V value dims: [v | k | q | f_a
| g_a | b] = u W_in (the row's six projections as ONE matrix product: a
decode step's cost in a trace follows its operations), [v | k | q] each
lane through a causal depthwise convolution over the SEQUENCE'S OWN
last taps - 1 rows, then silu; per head q <- q / |q| K ** -0.5, k <- k /
|k|; the log-decay g = -exp(A_log) softplus(f_a W_fb + dt_bias), a
K-vector a head (a decay PER CHANNEL of the key, where Mamba-2 has a
scalar); beta = sigmoid(b), a scalar a head. The
state S (K, V) a head, float32: St = Diag(exp(g_t)) S_{t-1}; S_t = St +
beta_t k_t (v_t - St^T k_t)^T; o_t = S_t^T q_t. y = RMSNorm_w(o_t) over
a head's V dims (one weight for all heads) x sigmoid(g_a W_gb); F = y
W_o. S, g, its cumulative sums, beta and the norms' statistics are
float32.

What a sequence carries is a slot of the STATE GROUP `ops/ssm.py`
describes, at H = G heads, N = K on the sublanes and P = V on the lanes:
`s` (layers, slots, H, K, V) float32 and `conv` (layers, slots, taps -
1, H V + 2 H K). The rows' sequences, the lanes of a prefill chunk, the
tail of decode rows and the snapshot slots are `ssm.plan_rows`'.

Two forms of one recurrence:

- prefill lanes, CHUNKED and exact (`scan_chunked`). In a chunk of C
  rows from the state S0, with G_r the sum of g up to row r: the
  pseudo-values U solve (I + A) U = Diag(beta) (V - (K o exp G) S0), A
  strictly lower triangular, A_ij = beta_i sum_c k_ic k_jc exp(G_ic -
  G_jc); o_r = (q_r o exp G_r)^T S0 + sum_{i <= r} (sum_c q_rc k_ic
  exp(G_rc - G_ic)) u_i; S_C = Diag(exp G_C) S0 + sum_i (k_i o exp(G_C -
  G_i)) u_i^T. Every exp(G_r - G_i) is ONE exponential of a difference
  that is not positive: the quotient exp(G_r) / exp(G_i) overflows under
  the decays the published initialisation gives (g down to -16 a row).
  (I + A)^-1 is built from the inverses of its diagonal blocks, doubled
  log2(C) times (block forward substitution as matrix products); what
  depends on S0 is matrix products only and runs in the scan over chunks.
- decode rows, the recurrence itself: `state_update`, one Mosaic kernel
  on the slot in place (`kda_state_update` in a trace), and `scan_step`
  off the TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from production_stack_tpu.ops import ssm
from production_stack_tpu.ops.ssm import F32, RowPlan

HIGHEST = jax.lax.Precision.HIGHEST
NORM_EPS = 1e-6


def l2_norm(x):
    """x / |x| over the last axis, float32 (|x| ** 2 + NORM_EPS under
    the root: a row of zeros, a lane's padding, stays zeros)."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + NORM_EPS)


def unit_lower_inverse(a):
    """(I + a)^-1 for `a` (..., C, C) strictly lower triangular, C a
    power of two: T <- T - T (a within the doubled blocks) T, from
    blocks of one row up (block forward substitution)."""
    c = a.shape[-1]
    i = jnp.arange(c)
    t = jnp.broadcast_to(jnp.eye(c, dtype=F32), a.shape)
    b = 1
    while b < c:
        # the lower-left b x b block of every 2b x 2b diagonal block
        mask = ((i[:, None] // (2 * b) == i[None, :] // (2 * b))
                & (i[:, None] // b > i[None, :] // b))
        ta = jnp.matmul(t, jnp.where(mask, a, 0.0), precision=HIGHEST)
        t = t - jnp.matmul(ta, t, precision=HIGHEST)
        b *= 2
    return t


def scan_chunked(q, k, v, g, beta, s0, chunk: int):
    """One sequence's rows through the recurrence, in chunks. q and k
    (T, H, K) float32, normed; v (T, H, V); g (T, H, K) float32 log-
    decays (<= 0), beta (T, H) float32 — both 0 on rows that are no
    tokens: the state passes them unchanged; s0 (H, K, V) float32 -> (o
    (T, H, V) float32, the state after the last row). T is padded up to
    whole chunks here; `chunk` is a power of two."""
    t, h, kd = q.shape
    c = chunk
    pad = -t % c
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, g, beta))
    nc = (t + pad) // c

    def heads_major(x):          # (T, H, ...) -> (nc, H, C, ...)
        return jnp.moveaxis(x.astype(F32).reshape(nc, c, *x.shape[1:]), 2, 1)

    q, k, v, g = (heads_major(x) for x in (q, k, v, g))
    beta = jnp.moveaxis(beta.reshape(nc, c, h), 2, 1)      # (nc, H, C)
    cum = jnp.cumsum(g, axis=2)                            # G_r
    # row i reads row j <= i through exp(G_i - G_j), a K-vector a pair
    causal = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(
        causal[:, :, None], cum[:, :, :, None] - cum[:, :, None, :],
        -jnp.inf))                                         # (nc, H, i, j, K)
    kk = jnp.sum(k[:, :, :, None] * k[:, :, None, :] * decay, -1)
    qk = jnp.sum(q[:, :, :, None] * k[:, :, None, :] * decay, -1)
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1),
                  beta[..., None] * kk, 0.0)
    t_beta = unit_lower_inverse(a) * beta[:, :, None, :]   # (I+A)^-1 Diag(b)
    gamma = jnp.exp(cum)
    k_in, q_in = k * gamma, q * gamma
    k_end = k * jnp.exp(cum[:, :, -1:] - cum)
    gamma_end = gamma[:, :, -1]                            # (nc, H, K)

    def step(s, xs):
        t_beta, qk, k_in, q_in, k_end, gamma_end, v = xs
        u = jnp.matmul(t_beta, v - jnp.matmul(k_in, s))
        o = jnp.matmul(q_in, s) + jnp.matmul(qk, u)
        s = gamma_end[..., None] * s + jnp.matmul(
            jnp.swapaxes(k_end, -1, -2), u)
        return s, o

    s_end, o = jax.lax.scan(
        step, s0, (t_beta, qk, k_in, q_in, k_end, gamma_end, v))
    return jnp.moveaxis(o, 1, 2).reshape(nc * c, h, -1)[:t], s_end


def scan_step(q, k, v, g, beta, s):
    """One token a row: q and k (r, H, K) float32, v (r, H, V), g (r, H,
    K), beta (r, H), s (r, H, K, V) float32 -> (o (r, H, V) float32, the
    new s)."""
    s = jnp.exp(g)[..., None] * s
    u = beta[..., None] * (v.astype(F32) - jnp.sum(s * k[..., None], -2))
    s = s + k[..., None] * u[..., None, :]
    return jnp.sum(s * q[..., None], -2), s


def _update_kernel(meta_ref, s_ref, cols_ref, bv_ref, o_ref, y_ref, *,
                   lanes, heads):
    """One lane's state through one token, a head (K, V) at a time: the
    decay, beta k, k and q are columns over the lanes (K on the
    sublanes), beta v a row: the decay multiplies whole rows, the two
    sums over K add vector registers, the rank-one term is a column
    times a row. Nothing crosses lanes."""
    i = pl.program_id(0)
    live = meta_ref[1 + lanes + i] != 0
    keep = jnp.where(meta_ref[1 + 2 * lanes + i] == 0, 1.0, 0.0)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live)
    def _():
        for h in range(heads):
            def col(j, h=h):
                return cols_ref[:, j * heads + h:j * heads + h + 1]

            s = s_ref[h] * (col(0) * keep)
            u = bv_ref[h:h + 1, :] - jnp.sum(s * col(1), axis=0,
                                             keepdims=True)
            s = s + col(2) * u
            o_ref[h] = s
            y_ref[h:h + 1, :] = jnp.sum(s * col(3), axis=0, keepdims=True)


def state_update(s_all, l, src, dst, zero, q, k, v, g, beta,
                 interpret: bool = False):
    """`scan_step` on the state group in place: s_all (L, slots, H, K,
    V) float32, lane i's state read at [l, src[i]] (from zero where
    `zero[i]`) and written at [l, dst[i]] -> (o (r, H, V) float32,
    s_all). ONE Mosaic kernel (`kda_state_update` in a trace) through
    `ssm.lane_update_call`: a lane a grid step, the pool aliased to the
    result, a lane that is nobody's (`dst` 0) skipped."""
    r, h, kd = q.shape
    vd = v.shape[-1]
    bk = beta[..., None] * k
    # (r, K, 4 H): [decay | beta k | k | q], a column a head each
    cols = jnp.swapaxes(jnp.concatenate(
        [jnp.exp(g), bk, k, q], axis=1).astype(F32), 1, 2)
    bv = beta[..., None] * v.astype(F32)
    y, s_all = ssm.lane_update_call(
        _update_kernel, "kda_state_update", s_all, l, src, dst, zero,
        [cols, bv], (h, vd), interpret, lanes=r, heads=h)
    return y, s_all


def gated_head_norm(o, gate, weight, eps: float):
    """RMSNorm_w(o) over each head's V dims (one weight (V,) for every
    head) x sigmoid(gate); o (n, H, V) float32 -> (n, H V) float32."""
    n, h, vd = o.shape
    y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    y = y * weight.astype(F32)
    return y.reshape(n, h * vd) * jax.nn.sigmoid(gate.astype(F32))


def mixer(cfg, u, lp, state: dict, l, plan: RowPlan):
    """The KDA mixer of state layer `l` over the normed rows u (n,
    hidden) -> (F (n, hidden) in u's dtype, `state` with the rows'
    sequences advanced): `ssm.mixer`'s contract and its walk of the
    plan, with this recurrence."""
    kernel = jax.default_backend() == "tpu"
    dtype = u.dtype
    n = u.shape[0]
    h, vd, kd, taps = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                       cfg.ssm_conv)
    d, cdim = h * vd, cfg.ssm_conv_dim
    # ONE projection of the row: [v | k | q | f_a | g_a | beta]
    proj = jnp.dot(u, lp["w_in"], preferred_element_type=F32)
    vkq = proj[:, :cdim].astype(dtype)
    g = -jnp.exp(lp["A_log"].astype(F32))[:, None] * jax.nn.softplus(
        (jnp.dot(proj[:, cdim:cdim + kd].astype(dtype), lp["w_fb"],
                 preferred_element_type=F32)
         + lp["dt_bias"].astype(F32)).reshape(n, h, kd))
    gate = jnp.dot(proj[:, cdim + kd:cdim + 2 * kd].astype(dtype),
                   lp["w_gb"], preferred_element_type=F32)
    beta = jax.nn.sigmoid(proj[:, cdim + 2 * kd:])
    s_all, conv_all = state["s"], state["conv"]
    o = jnp.zeros((n, h, vd), F32)
    no_bias = jnp.zeros((), F32)

    def split(xc):
        """[v | k | q] after the convolution -> q, k normed, v."""
        lead = xc.shape[:-1]
        k_ = l2_norm(xc[..., d:d + h * kd].reshape(*lead, h, kd))
        q_ = l2_norm(xc[..., d + h * kd:].reshape(*lead, h, kd))
        return q_ * kd ** -0.5, k_, xc[..., :d].reshape(*lead, h, vd)

    def tail_at(src, zero):
        return jnp.where(zero[:, None, None], 0, conv_all[l, src])

    def state_at(src, zero):
        return jnp.where(zero[:, None, None, None], 0.0, s_all[l, src])

    if plan.rows.shape[0]:
        with jax.named_scope("kda_conv"):
            tail0 = tail_at(plan.src, plan.zero)
            xl = jnp.where(plan.valid[..., None], vkq[plan.rows], 0)
            ext = jnp.concatenate([tail0, xl], axis=1)
            at = plan.length[:, None] + jnp.arange(taps - 1)[None, :]
            new_tail = jnp.take_along_axis(ext, at[..., None], axis=1)
            xc = ssm.causal_conv(ext, lp["conv_w"], no_bias).astype(dtype)
        with jax.named_scope("kda_chunk"):
            q_l, k_l, v_l = split(xc)
            g_l = jnp.where(plan.valid[..., None, None], g[plan.rows], 0.0)
            b_l = jnp.where(plan.valid[..., None], beta[plan.rows], 0.0)
            o_l, s_end = jax.vmap(
                lambda *x: scan_chunked(*x, chunk=cfg.ssm_chunk)
            )(q_l, k_l, v_l, g_l, b_l, state_at(plan.src, plan.zero))
            o = o.at[plan.rows_out.reshape(-1)].set(
                o_l.reshape(-1, h, vd), mode="drop")
            for at_slot in (plan.dst, plan.save):
                s_all = s_all.at[l, at_slot].set(s_end)
                conv_all = conv_all.at[l, at_slot].set(new_tail)
    if plan.t_dst.shape[0]:
        with jax.named_scope("kda_step"):
            with jax.named_scope("kda_conv"):
                ext = jnp.concatenate(
                    [tail_at(plan.t_src, plan.t_zero),
                     vkq[plan.lead:, None]], axis=1)
                xc = ssm.causal_conv(ext, lp["conv_w"], no_bias).astype(
                    dtype)[:, 0]
                new_tail = ext[:, 1:]
                conv_all = conv_all.at[l, plan.t_dst].set(new_tail)
            q_t, k_t, v_t = split(xc)
            g_t, b_t = g[plan.lead:], beta[plan.lead:]
            if kernel:
                o_t, s_all = state_update(
                    s_all, l, plan.t_src, plan.t_dst, plan.t_zero,
                    q_t, k_t, v_t, g_t, b_t)
            else:
                o_t, s_new = scan_step(
                    q_t, k_t, v_t, g_t, b_t,
                    state_at(plan.t_src, plan.t_zero))
                s_all = s_all.at[l, plan.t_dst].set(s_new)
            o = o.at[plan.lead:].set(o_t)

            # a lane crosses a snapshot boundary once in hundreds of
            # steps: the second write of every lane's state only then
            def save(arrs):
                return (arrs[0].at[l, plan.t_save].set(
                            arrs[0][l, plan.t_dst]),
                        arrs[1].at[l, plan.t_save].set(new_tail))

            s_all, conv_all = jax.lax.cond(
                jnp.any(plan.t_save > 0), save, lambda arrs: arrs,
                (s_all, conv_all))
    out = gated_head_norm(o, gate, lp["o_norm"],
                          cfg.rms_norm_eps).astype(dtype)
    return (jnp.dot(out, lp["w_o"], preferred_element_type=F32).astype(
        dtype), {"s": s_all, "conv": conv_all})
