"""Naive dense-attention reference implementation used to validate the paged
engine. Deliberately independent of the engine's attention/caching machinery:
full-sequence forward, dense causal mask, no paging, no chunking."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models.config import ModelConfig


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (n * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    # x: (t, heads, d)
    d = x.shape[-1]
    half = d // 2
    inv = 1.0 / (theta ** (np.arange(half) * 2.0 / d))
    freqs = np.asarray(positions)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(freqs), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(freqs), jnp.float32)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], -1).astype(x.dtype)


def dense_forward(cfg: ModelConfig, params: dict, token_ids: list[int]):
    """Full forward over the whole sequence; returns fp32 logits (t, vocab)."""
    t = len(token_ids)
    pos = np.arange(t)
    h = params["embed"][jnp.asarray(token_ids)]
    nq, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    mask = np.tril(np.ones((t, t), bool))

    for l in range(cfg.num_layers):
        lp = {k: v[l] for k, v in params["layers"].items()}
        x = _rms(h, lp["attn_norm"], cfg.rms_norm_eps)
        q = (x @ lp["wq"]).reshape(t, nq, d)
        k = (x @ lp["wk"]).reshape(t, nkv, d)
        v = (x @ lp["wv"]).reshape(t, nkv, d)
        if cfg.qkv_bias:
            q = q + lp["bq"].reshape(nq, d)
            k = k + lp["bk"].reshape(nkv, d)
            v = v + lp["bv"].reshape(nkv, d)
        q = _rope(q, pos, cfg.rope_theta)
        k = _rope(k, pos, cfg.rope_theta)
        g = nq // nkv
        qg = q.reshape(t, nkv, g, d).astype(jnp.float32)
        kf = k.astype(jnp.float32)
        scores = jnp.einsum("tkgd,skd->tkgs", qg, kf) * (d**-0.5)
        scores = jnp.where(mask[:, None, None, :], scores, -1e30)
        p = jax.nn.softmax(scores, -1)
        o = jnp.einsum("tkgs,skd->tkgd", p, v.astype(jnp.float32))
        h = h + (o.reshape(t, nq * d).astype(h.dtype) @ lp["wo"])
        x = _rms(h, lp["mlp_norm"], cfg.rms_norm_eps)
        act = jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])
        h = h + (act @ lp["w_down"]).astype(h.dtype)

    h = _rms(h, params["final_norm"], cfg.rms_norm_eps)
    lm = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    return (h @ lm).astype(jnp.float32)


def dense_greedy_generate(
    cfg: ModelConfig, params: dict, prompt: list[int], num_tokens: int
) -> list[int]:
    """Greedy decoding by full recompute each step (slow, obviously correct)."""
    ids = list(prompt)
    for _ in range(num_tokens):
        logits = dense_forward(cfg, params, ids)
        ids.append(int(jnp.argmax(logits[-1])))
    return ids[len(prompt) :]


def mimo_v2_routed_layer(cfg: ModelConfig, x, router, router_bias,
                         w_gate, w_up, w_down, first: int):
    """The routed expert layer of `mimo_v2_forward` over the experts
    given (global ids first, first+1, ...): sigma = sigmoid(x Wr) over
    every expert of the router, chosen = top-k of sigma + b, weights
    sigma_e / (sum_chosen sigma + 1e-20), output the sum over the chosen
    experts AMONG THOSE GIVEN of w_e * expert_e(x). Given all the
    router's experts (first = 0) it is the uncut layer; given a rank's
    slice, that rank's share. A Python loop over experts, float32."""
    logits = x @ router
    sigma = (jax.nn.sigmoid(logits) if cfg.router_scoring == "sigmoid"
             else jax.nn.softmax(logits, -1))
    sel = sigma + router_bias if cfg.router_bias else sigma
    chosen = jnp.argsort(-sel, -1)[:, :cfg.num_experts_per_tok]
    w = jnp.take_along_axis(sigma, chosen, 1)
    if cfg.router_renorm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    y = jnp.zeros_like(x)
    for e in range(w_gate.shape[0]):
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
        y = y + w_e[:, None] * (
            (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e])
    return y


def mimo_v2_forward(cfg: ModelConfig, params: dict, token_ids: list[int]):
    """The MiMo-V2 text decoder (`model_type: mimo_v2`), full forward
    over the whole sequence; returns float32 logits (t, vocab).

    float32 `jax.numpy`, a Python loop over layers, dense masks, no
    kernel, cache or batching; callers hold
    `jax.default_matmul_precision("highest")`. `params` is the tree
    `models/layer_groups.init_params` makes (one stacked tree per run of
    alike layers). The equations, per layer, with `x` a row of the
    residual stream after RMSNorm (eps `layernorm_epsilon`; before
    attention, before the MLP and at the end; no qk-norm, no biases,
    untied head):

    - q = x Wq (nq heads x d_k), k = x Wk (nkv x d_k), v = v_scale *
      (x Wv) (nkv x d_v); half-split rotary on dims [0, rotary_dim) of
      each q and k head, the rest unrotated; scores q.k / sqrt(d_k),
      causal; output (nq x d_v) Wo.
    - a full layer (kind 0) attends every earlier key with the plain
      softmax; a window layer keys j with q_pos - window < j <= q_pos,
      with its own nkv and theta, and a learned sink s_h per q head in
      the denominator: p_j = exp(a_j) / (exp(s_h) + sum_i exp(a_i)),
      nothing added to the numerator.
    - the first `dense_layers` layers: (silu(x Wg) * (x Wu)) Wd. The
      others: `mimo_v2_routed_layer` over the experts HELD HERE (ranks
      hold contiguous slices; `cfg.ep_rank` of `cfg.ep_size`).

    Departures from the published description: the 3 MTP layers, the
    vision tower and the audio encoder are left out (the text decoder
    only); what the experts NOT held here would add is left out, as in
    the program, and that partial sum goes on to the next layer.
    """
    f32 = jnp.float32
    t = len(token_ids)
    pos = np.arange(t)
    nq, dk, dv = cfg.num_heads, cfg.head_dim, cfg.v_dim
    rot = cfg.rope_dim
    causal = pos[None, :] <= pos[:, None]
    h = params["embed"][jnp.asarray(token_ids)].astype(f32)

    def rms(x, w):
        n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + cfg.rms_norm_eps)
        return n * w.astype(f32)

    def rope(x, theta):
        return jnp.concatenate(
            [_rope(x[..., :rot], pos, theta), x[..., rot:]], -1)

    for stack, (kind, routed, count, _) in zip(
            params["segments"], cfg.segments()):
        ak = cfg.attn_kinds[kind]
        nkv = ak.num_kv_heads
        mask = causal
        if ak.window:
            mask = mask & (pos[None, :] > pos[:, None] - ak.window)
        for c in range(count):
            lp = {k: v[c].astype(f32) for k, v in stack.items()}
            x = rms(h, lp["attn_norm"])
            q = rope((x @ lp["wq"]).reshape(t, nq, dk), ak.rope_theta)
            k = rope((x @ lp["wk"]).reshape(t, nkv, dk), ak.rope_theta)
            v = cfg.v_scale * (x @ lp["wv"]).reshape(t, nkv, dv)
            qg = q.reshape(t, nkv, nq // nkv, dk)
            s = jnp.einsum("tkgd,skd->tkgs", qg, k) * dk ** -0.5
            s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
            m = jnp.max(s, -1, keepdims=True)
            e = jnp.exp(s - m)
            den = jnp.sum(e, -1, keepdims=True)
            if ak.sink:
                sink = lp["sink"].reshape(1, nkv, nq // nkv, 1)
                den = den + jnp.exp(sink - m)
            o = jnp.einsum("tkgs,skd->tkgd", e / den, v)
            h = h + o.reshape(t, nq * dv) @ lp["wo"]
            x = rms(h, lp["mlp_norm"])
            if routed:
                h = h + mimo_v2_routed_layer(
                    cfg, x, lp["router"], lp.get("router_bias"),
                    lp["w_gate"], lp["w_up"], lp["w_down"],
                    first=cfg.ep_rank * cfg.local_experts)
            else:
                h = h + (jax.nn.silu(x @ lp["w_gate"])
                         * (x @ lp["w_up"])) @ lp["w_down"]

    h = rms(h, params["final_norm"])
    lm = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    return h @ lm.astype(f32)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's rotary frequencies as DeepSeek-V3's modelling code makes
    them: `inv_freq` and `inv_freq / factor` blended by a linear ramp
    over the dim index between the two correction dims."""
    inv = 1.0 / (theta ** (np.arange(0, dim, 2) / dim))

    def correction_dim(rotations):
        return dim * np.log(original / (rotations * 2 * np.pi)) / (
            2 * np.log(theta))

    low = max(np.floor(correction_dim(beta_fast)), 0)
    high = min(np.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0, 1)
    return inv / factor * ramp + inv * (1 - ramp)


def sinkhorn(m, iters: int, eps: float):
    """(..., n, n) positive -> rows over their sum + eps, then columns
    over theirs, `iters` times."""
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def hc_matrices(cfg: ModelConfig, X, phi, alpha, b):
    """The hyper-connection matrices of one sublayer from the streams X
    (t, n, h): (H_pre (t, n), H_post (t, n), H_res (t, n, n)); H_res[t,
    i, j] weighs stream j in new stream i."""
    t, n, h = X.shape
    v = X.reshape(t, n * h)
    xt = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                           + cfg.rms_norm_eps)
    proj = xt @ phi
    pre = jax.nn.sigmoid(alpha[0] * proj[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[:, n:2 * n] + b[n:2 * n])
    res = (alpha[2] * proj[:, 2 * n:] + b[2 * n:]).reshape(t, n, n)
    res = jnp.exp(jnp.clip(res, *cfg.hc_res_clamp))
    return pre, post, sinkhorn(res, cfg.hc_sinkhorn_iters, cfg.hc_eps)


def xing4_forward(cfg: ModelConfig, params: dict, token_ids: list[int]):
    """Xing4.0 (`model_type: xing4_0`), full forward over the whole
    sequence; returns float32 logits (t, vocab).

    float32 `jax.numpy`, Python loops over layers and experts, a dense
    mask, no kernel, cache or batching, latent attention UN-absorbed
    (every head's keys and values are made from the latent rows);
    callers hold `jax.default_matmul_precision("highest")`. `params` is
    the tree `models/layer_groups.init_params` makes. The equations,
    with n = `hc_mult` residual streams X (n, h) a token:

    - a sublayer F (attention or MLP) with its own phi (n h, 2n + n^2),
      alpha (3,), b: xt = RMSNorm(vec(X)) (no weight); [pre; post; res]
      = alpha * (xt phi) + b by parts; H_pre = sigmoid(pre), H_post = 2
      sigmoid(post), H_res = SK(exp(clamp(res, -30, 30))) (`sinkhorn`);
      u = H_pre X, y = F(RMSNorm_w(u)), X' = H_res X + H_post^T y. The
      streams start as n copies of the embedding and are summed before
      the final norm.
    - attention: c_q = RMSNorm(x W_dq), a head's q = c_q W_uq = [q_nope;
      q_rope]; [c_kv; k_r] = x W_dkv, c = RMSNorm(c_kv), [k_nope; v] = c
      W_ukv a head; scores (q_nope . k_nope + rope(q_rope) . rope(k_r))
      * scale, scale = d_k^-1/2 * m^2, m = 0.1 mscale_all_dim ln(factor)
      + 1; causal softmax; (sum p v) W_o. Rotary is half-split, with
      YaRN's frequencies (`yarn_inv_freq`); cos and sin times
      m(mscale) / m(mscale_all_dim).
    - the first `dense_layers` layers' MLP is a SwiGLU; the others:
      sigma = sigmoid(x W_g), chosen = top-k of sigma + bias, weights
      sigma_e / sum_chosen sigma * `routed_scaling`, output sum w_e
      E_e(x) + E_shared(x).

    Left out: the MTP module (`num_nextn_predict_layers`), which does
    not enter the main model's logits.
    """
    f32 = jnp.float32
    t = len(token_ids)
    pos = np.arange(t)
    n = cfg.hc_mult
    nq, dk, dv = cfg.num_heads, cfg.head_dim, cfg.v_dim
    rot = cfg.rope_dim
    nope = dk - rot
    ak = cfg.attn_kinds[0]
    lat = ak.latent_dim
    causal = pos[None, :] <= pos[:, None]
    eps = cfg.rms_norm_eps

    y = cfg.rope_yarn
    scale = dk ** -0.5
    inv = 1.0 / (ak.rope_theta ** (np.arange(0, rot, 2) / rot))
    cs_scale = 1.0
    if y is not None:
        inv = yarn_inv_freq(rot, ak.rope_theta, y.factor,
                            y.original_max_position, y.beta_fast,
                            y.beta_slow)

        def m(s):
            return 0.1 * s * np.log(y.factor) + 1.0 if y.factor > 1 else 1.0

        cs_scale = m(y.mscale) / m(y.mscale_all_dim)
        if y.mscale_all_dim:
            scale = scale * m(y.mscale_all_dim) ** 2
    fr = pos[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(fr) * cs_scale, f32)[:, None, :]
    sin = jnp.asarray(np.sin(fr) * cs_scale, f32)[:, None, :]

    def rope(x):  # (t, heads, rot)
        x1, x2 = x[..., :rot // 2], x[..., rot // 2:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def rms(x, w=None):
        out = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return out if w is None else out * w

    def attention(x, lp):
        q = (rms(x @ lp["w_dq"], lp["q_norm"]) @ lp["w_uq"]).reshape(
            t, nq, dk)
        ckv = x @ lp["w_dkv"]
        c = rms(ckv[:, :lat], lp["kv_norm"])
        k_r = rope(ckv[:, None, lat:])                     # (t, 1, rot)
        kv = (c @ lp["w_ukv"]).reshape(t, nq, nope + dv)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        s = (jnp.einsum("thd,shd->ths", q[..., :nope], k_nope)
             + jnp.einsum("thd,sd->ths", rope(q[..., nope:]), k_r[:, 0])
             ) * scale
        s = jnp.where(causal[:, None, :], s, -jnp.inf)
        o = jnp.einsum("ths,shd->thd", jax.nn.softmax(s, -1), v)
        return o.reshape(t, nq * dv) @ lp["wo"]

    def swiglu(x, g, u, d):
        return (jax.nn.silu(x @ g) * (x @ u)) @ d

    def experts(x, lp):
        sigma = jax.nn.sigmoid(x @ lp["router"])
        sel = sigma + lp["router_bias"] if cfg.router_bias else sigma
        chosen = jnp.argsort(-sel, -1)[:, :cfg.num_experts_per_tok]
        w = jnp.take_along_axis(sigma, chosen, 1)
        if cfg.router_renorm:
            w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
        w = w * cfg.routed_scaling
        out = jnp.zeros_like(x)
        for e in range(cfg.router_experts):
            w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)
            out = out + w_e[:, None] * swiglu(
                x, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
        if cfg.shared_experts:
            out = out + swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return out

    def sublayer(X, lp, sub, fn, norm):
        if n == 1:
            return X + fn(rms(X[:, 0], norm), lp)[:, None]
        pre, post, res = hc_matrices(
            cfg, X, lp[f"hc_{sub}_phi"], lp[f"hc_{sub}_alpha"],
            lp[f"hc_{sub}_b"])
        u = jnp.einsum("tj,tjh->th", pre, X)
        out = fn(rms(u, norm), lp)
        return (jnp.einsum("tij,tjh->tih", res, X)
                + post[:, :, None] * out[:, None, :])

    emb = params["embed"][jnp.asarray(token_ids)].astype(f32)
    X = jnp.broadcast_to(emb[:, None, :], (t, n, emb.shape[-1]))
    for stack, (_, routed, count, _) in zip(
            params["segments"], cfg.segments()):
        for c in range(count):
            lp = {k: v[c].astype(f32) for k, v in stack.items()}
            X = sublayer(X, lp, "attn", attention, lp["attn_norm"])
            mlp = experts if routed else (lambda x, lp: swiglu(
                x, lp["w_gate"], lp["w_up"], lp["w_down"]))
            X = sublayer(X, lp, "mlp", mlp, lp["mlp_norm"])
    h = rms(jnp.sum(X, 1), params["final_norm"].astype(f32))
    lm = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    return h @ lm.astype(f32)
