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
