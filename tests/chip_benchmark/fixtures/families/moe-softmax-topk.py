"""A family the harness has never seen, as a fixture of the tests.

The program's Mixtral-style preset shape: alike layers of grouped-query
attention and a block of `num_local_experts` SwiGLU experts, routed by a
softmax over the top `num_experts_per_tok` router logits; the tree that
`models/llama.py::init_params` makes for `is_moe`. NOT a configuration
of the benchmark: `test_chipbench_families.py` copies this file under
`tmp_path` beside a configuration that names it, to show that a new
architecture is served and checked with new files alone.
"""

from __future__ import annotations

import dataclasses


def hf_config(config: dict) -> dict:
    return dict(config)     # no key of its own


def init_params(mc, key, dtype):
    import jax
    import jax.numpy as jnp

    h, i, v, e = (mc.hidden_size, mc.intermediate_size, mc.vocab_size,
                  mc.num_experts)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    shapes = {
        "wq": ((h, mc.q_size), h), "wk": ((h, mc.kv_size), h),
        "wv": ((h, mc.kv_size), h), "wo": ((mc.q_size, h), mc.q_size),
        "moe_gate": ((h, e), h), "w_gate": ((e, h, i), h),
        "w_up": ((e, h, i), h), "w_down": ((e, i, h), i),
    }

    def one_layer(k):
        ks = jax.random.split(k, len(shapes))
        lp = {n: w(ks[j], s, f) for j, (n, (s, f)) in
              enumerate(sorted(shapes.items()))}
        lp["attn_norm"] = jnp.ones((h,), dtype)
        lp["mlp_norm"] = jnp.ones((h,), dtype)
        return lp

    k_embed, k_head, k_layers = jax.random.split(key, 3)
    params = {
        "embed": w(k_embed, (v, h), h),
        "layers": jax.lax.map(
            one_layer, jax.random.split(k_layers, mc.num_layers)),
        "final_norm": jnp.ones((h,), dtype),
    }
    if not mc.tie_word_embeddings:
        params["lm_head"] = w(k_head, (h, v), h)
    return params


def forward_logprobs(cfg, params, token_ids, rows):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    t = token_ids.shape[0]
    nq, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    top = cfg.num_experts_per_tok
    half = d // 2
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(half, dtype=f32) * 2.0 / d))
    freqs = jnp.arange(t, dtype=f32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(freqs)[:, None, :], jnp.sin(freqs)[:, None, :]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def rms(x, w):
        n = x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + cfg.rms_norm_eps)
        return n * w.astype(f32)

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def layer(h, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        x = rms(h, lp["attn_norm"])
        q = rope((x @ lp["wq"]).reshape(t, nq, d))
        k = rope((x @ lp["wk"]).reshape(t, nkv, d))
        v = (x @ lp["wv"]).reshape(t, nkv, d)
        qg = q.reshape(t, nkv, nq // nkv, d)
        s = jnp.einsum("tkgd,skd->tkgs", qg, k) * (d ** -0.5)
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        o = jnp.einsum("tkgs,skd->tkgd", jax.nn.softmax(s, -1), v)
        h = h + o.reshape(t, nq * d) @ lp["wo"]
        x = rms(h, lp["mlp_norm"])
        # each token's top experts, weighted by a softmax over THEIR
        # router logits alone; every expert is computed and most of it
        # thrown away, which a reference at tiny widths may do
        top_v, top_i = jax.lax.top_k(x @ lp["moe_gate"], top)
        weight = jax.nn.softmax(top_v, -1)                  # (t, top)
        out = jnp.zeros_like(h)
        for e in range(cfg.num_experts):
            y = (jax.nn.silu(x @ lp["w_gate"][e]) * (x @ lp["w_up"][e])
                 ) @ lp["w_down"][e]
            out = out + y * jnp.sum(
                jnp.where(top_i == e, weight, 0.0), -1, keepdims=True)
        return h + out, None

    h = params["embed"][token_ids].astype(f32)
    h, _ = jax.lax.scan(layer, h, params["layers"])
    h = rms(h, params["final_norm"])[rows]
    lm = (params["embed"].T if cfg.tie_word_embeddings
          else params["lm_head"])
    return jax.nn.log_softmax(h @ lm.astype(f32), -1)


def _dims(hf: dict):
    h, nq = hf["hidden_size"], hf["num_attention_heads"]
    d = hf.get("head_dim") or h // nq
    return h, nq, hf.get("num_key_value_heads", nq), d


def layer_stack_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """q, k, v, o, the router, the two norms and EVERY expert's gate, up
    and down: the program's exact path computes all of them for every
    token. (Only the two counts the readers divide by: the contract asks
    for no other.)"""
    h, nq, nkv, d = _dims(hf)
    e, i = hf["num_local_experts"], hf["intermediate_size"]
    layer = 2 * h * nq * d + 2 * h * nkv * d + h * e + e * 3 * h * i + 2 * h
    return hf["num_hidden_layers"] * layer * bytes_per_param


def kv_bytes_per_token(hf: dict, bytes_per_elem: int = 2) -> int:
    _, _, nkv, d = _dims(hf)
    return 2 * hf["num_hidden_layers"] * nkv * d * bytes_per_elem


def rehearsal_config(mc, tp: int):
    from production_stack_tpu.models import config as mcfg

    return dataclasses.replace(
        mcfg.TINY_MOE_DEBUG, name=mc.name,
        num_experts=mc.num_experts,
        num_experts_per_tok=mc.num_experts_per_tok,
        num_kv_heads=max(mcfg.TINY_MOE_DEBUG.num_kv_heads, tp),
        rms_norm_eps=mc.rms_norm_eps, rope_theta=mc.rope_theta,
        tie_word_embeddings=mc.tie_word_embeddings,
        max_model_len=mc.max_model_len)


def check(config: dict, mc) -> None:
    want = (config["num_local_experts"], config["num_experts_per_tok"])
    if (mc.num_experts, mc.num_experts_per_tok) != want:
        raise SystemExit(
            f"the file routes to {want[1]} of {want[0]} experts, the "
            f"program's ModelConfig to {mc.num_experts_per_tok} of "
            f"{mc.num_experts}")
    if mc.qkv_bias or mc.sliding_window or mc.hidden_act != "silu":
        raise SystemExit("this family has no biases, window or other "
                         "activation than silu")
