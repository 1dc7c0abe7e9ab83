"""The dense family: a Llama-class decoder of alike layers.

Mistral, Qwen2 and their kin as the two configurations that are here
publish them: RMSNorm, rotary embedding on half-split head dims, one kind
of grouped-query attention over the full context, SwiGLU, an untied or
tied lm_head, and q/k/v biases where the configuration's file says so
(`qkv_bias`, this family's one own key). The only file of the benchmark
that knows this parameter tree and these equations; `manifest.py` says
what a family file gives and how it is found.

Imports jax inside its functions only: `run.py` loads a family for its
counts and imports no jax.
"""

from __future__ import annotations

import dataclasses

# keys of a configuration file that are this family's, beside the ones
# every configuration has (`manifest.COMMON_KEYS`)
OWN_KEYS = ("qkv_bias",)
VOCAB_SLICES = 8


# -- 1. the config.json the program reads ----------------------------------
def hf_config(config: dict) -> dict:
    """The published keys."""
    return {k: v for k, v in config.items() if k not in OWN_KEYS}


# -- 2. the weights ---------------------------------------------------------
def init_params(mc, key, dtype):
    """All weights from the key, layer by layer; q/k/v biases non-zero,
    so that a dropped bias shows in the reference check."""
    import jax
    import jax.numpy as jnp

    h, i, v = mc.hidden_size, mc.intermediate_size, mc.vocab_size

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    shapes = {
        "wq": ((h, mc.q_size), h), "wk": ((h, mc.kv_size), h),
        "wv": ((h, mc.kv_size), h), "wo": ((mc.q_size, h), mc.q_size),
        "w_gate": ((h, i), h), "w_up": ((h, i), h), "w_down": ((i, h), i),
    }
    if mc.qkv_bias:
        shapes |= {"bq": ((mc.q_size,), 4), "bk": ((mc.kv_size,), 4),
                   "bv": ((mc.kv_size,), 4)}

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


# -- 3. the plain reference -------------------------------------------------
def forward_logprobs(cfg, params, token_ids, rows):
    """log-softmax over the vocabulary at `rows` of a full forward pass
    over `token_ids` (t,). Everything float32.

    A copy of `tests/reference_model.py::dense_forward` (the original
    stays where the program's own tests use it): no kernel, no cache, no
    batching, a dense causal mask over the whole sequence. One departure
    from a textbook loop: the layers are walked by `lax.scan` over the
    stacked weights and each layer's bf16 weights are upcast inside the
    step, so only one layer's float32 copy lives beside the serving
    cache, and the lm_head is applied to the asked rows only, in
    vocabulary slices."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    t = token_ids.shape[0]
    nq, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    pos = jnp.arange(t, dtype=f32)
    half = d // 2
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(half, dtype=f32) * 2.0 / d))
    freqs = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(freqs)[:, None, :], jnp.sin(freqs)[:, None, :]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def rms(x, w):
        n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return n * (w.astype(f32) + cfg.norm_weight_offset)

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def layer(h, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        x = rms(h, lp["attn_norm"])
        q, k, v = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
        if cfg.qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = rope(q.reshape(t, nq, d))
        k = rope(k.reshape(t, nkv, d))
        v = v.reshape(t, nkv, d)
        qg = q.reshape(t, nkv, nq // nkv, d)
        s = jnp.einsum("tkgd,skd->tkgs", qg, k) * (d ** -0.5)
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        o = jnp.einsum("tkgs,skd->tkgd", jax.nn.softmax(s, -1), v)
        h = h + o.reshape(t, nq * d) @ lp["wo"]
        x = rms(h, lp["mlp_norm"])
        h = h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])
                 ) @ lp["w_down"]
        return h, None

    h = params["embed"][token_ids].astype(f32) * cfg.embed_scale
    h, _ = jax.lax.scan(layer, h, params["layers"])
    h = rms(h, params["final_norm"])[rows]
    lm = (params["embed"].T if cfg.tie_word_embeddings
          else params["lm_head"])
    v = lm.shape[1]
    step = -(-v // VOCAB_SLICES)
    logits = jnp.concatenate([
        h @ lm[:, i:i + step].astype(f32) for i in range(0, v, step)
    ], -1)
    return jax.nn.log_softmax(logits, -1)


# -- 4. the counts: bytes and operations a step needs ----------------------
# Kept with the benchmark so that no PR that claims a gain can change how
# a share of a peak is counted. Inputs are a configuration file's dict.
# The readers divide by `layer_stack_bytes` and `kv_bytes_per_token`, and
# the contract asks for those two; the rest are this family's own, read
# by its tests (the sizes the configurations were chosen by).
def _dims(hf: dict) -> tuple[int, int, int, int, int, int, int]:
    h = hf["hidden_size"]
    nq = hf["num_attention_heads"]
    d = hf.get("head_dim") or h // nq
    nkv = hf.get("num_key_value_heads", nq)
    return (h, hf["intermediate_size"], hf["num_hidden_layers"], nq, nkv,
            d, hf["vocab_size"])


def layer_params(hf: dict) -> int:
    """Parameters of one decoder layer: q, k, v, o, gate, up, down, the
    two norms, and the q/k/v biases where the configuration's file says
    the projections carry them (`qkv_bias`; `check` refuses to serve
    where the program's own ModelConfig disagrees)."""
    h, i, _, nq, nkv, d, _ = _dims(hf)
    n = h * nq * d + 2 * h * nkv * d + nq * d * h + 3 * h * i + 2 * h
    if hf.get("qkv_bias"):
        n += nq * d + 2 * nkv * d
    return n


def total_params(hf: dict) -> int:
    h, _, layers, _, _, _, v = _dims(hf)
    embed = v * h * (1 if hf.get("tie_word_embeddings") else 2)
    return layers * layer_params(hf) + embed + h


def decode_weight_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of weights ONE decode step must read: every layer matrix
    and the lm_head; NOT the embedding table (a step gathers a few rows
    of it). KV-cache bytes are left out on purpose: a share computed
    from this is `weight_stream_share`, not a roofline share."""
    h, _, layers, _, _, _, v = _dims(hf)
    return (layers * layer_params(hf) + h * v + h) * bytes_per_param


def layer_stack_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of weights one pass through the layer stack reads: every
    layer matrix, norm and bias; neither embedding nor lm_head."""
    return hf["num_hidden_layers"] * layer_params(hf) * bytes_per_param


def kv_bytes_per_token(hf: dict, bytes_per_elem: int = 2) -> int:
    _, _, layers, _, nkv, d, _ = _dims(hf)
    return 2 * layers * nkv * d * bytes_per_elem


# -- 5. the rehearsal's shape ----------------------------------------------
def rehearsal_config(mc, tp: int):
    """A rehearsal checks control flow on the CPU, not speed: the tiny
    debug widths, keeping what selects code paths."""
    from production_stack_tpu.models import config as mcfg

    return dataclasses.replace(
        mcfg.TINY_DEBUG, name=mc.name, qkv_bias=mc.qkv_bias,
        num_kv_heads=max(mcfg.TINY_DEBUG.num_kv_heads, tp),
        rms_norm_eps=mc.rms_norm_eps, rope_theta=mc.rope_theta,
        tie_word_embeddings=mc.tie_word_embeddings,
        max_model_len=mc.max_model_len,
    )


# -- 6. the guard -----------------------------------------------------------
def check(config: dict, mc) -> None:
    """Refuse where the file and the program's ModelConfig disagree on
    what the counts and the reference rest on."""
    if mc.qkv_bias != bool(config.get("qkv_bias", False)):
        raise SystemExit(
            f"the configuration's file says qkv_bias="
            f"{config.get('qkv_bias')!r}, the program's ModelConfig says "
            f"{mc.qkv_bias}: the dense family would count other weights "
            "than are served")
    served = (mc.hidden_size, mc.intermediate_size, mc.num_layers,
              mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.vocab_size)
    if served != _dims(config):
        raise SystemExit(
            f"the configuration's file gives the shapes {_dims(config)}, "
            f"the program's ModelConfig {served}: the dense family would "
            "count other weights than are served")
    if mc.is_moe or mc.sliding_window or mc.hidden_act != "silu":
        raise SystemExit(
            "the dense family covers SwiGLU decoders of alike layers with "
            f"full attention; the program's ModelConfig has num_experts="
            f"{mc.num_experts}, sliding_window={mc.sliding_window}, "
            f"hidden_act={mc.hidden_act!r}: bring that architecture as a "
            "family file of its own")
