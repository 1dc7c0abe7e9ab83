"""Llama-class decoder as a pure-JAX functional module over a paged KV cache.

Design (TPU-first, not a torch translation):
- params are a pytree with layer weights **stacked on a leading layer axis**;
  the forward pass is a single `lax.scan` over layers, so XLA traces one layer
  and the compiled program is O(1) in depth (fast compiles, uniform MXU tiling)
- the KV cache for all layers is carried through the scan and updated with
  scatter writes (donated at the jit boundary -> in-place in HBM)
- attention is injected as a callback so the same forward serves prefill and
  decode (the model runner chooses gather pattern + masking), and so the
  Pallas kernel can be swapped in without touching model code
- everything is shape-static; bucketing happens in the model runner

Covers Llama 2/3/3.x, Mistral, Qwen2 (qkv_bias), Mixtral, Phi-3, Gemma, TinyLlama,
and a LOOPED stack (`cfg.ut_steps` > 1, `model_type: ouro`): the same scan
over layers runs once a pass inside a scan over passes, pass t writing and
reading cache layer t * num_layers + l, the final norm closing every pass.
At `ut_steps` 1 there is no outer loop: the program is the one traced before.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.ops.cache_write import plan_rows
from production_stack_tpu.ops.cache_write import write_kv as scatter_kv
from production_stack_tpu.ops.layers import (
    apply_rope,
    rms_norm,
    rope_cos_sin,
    swiglu,
)
from production_stack_tpu.ops.moe import moe_block

# attn_fn(q_rope, layer_idx, k_cache, v_cache) -> attn_out
AttnFn = Callable[[jax.Array, jax.Array, jax.Array, jax.Array], jax.Array]


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype = jnp.bfloat16
) -> dict:
    """Random-init parameters (scaled normal), layer weights stacked on axis 0."""
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    L = cfg.num_layers
    keys = iter(jax.random.split(key, 16))

    def w(key, shape, fan_in):
        scale = fan_in**-0.5
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            dtype
        )

    layers = {
        "attn_norm": jnp.ones((L, h), dtype),
        "mlp_norm": jnp.ones((L, h), dtype),
        "wq": w(next(keys), (L, h, cfg.q_size), h),
        "wk": w(next(keys), (L, h, cfg.kv_size), h),
        "wv": w(next(keys), (L, h, cfg.kv_size), h),
        "wo": w(next(keys), (L, cfg.q_size, h), cfg.q_size),
    }
    if cfg.is_moe:
        E = cfg.num_experts
        layers["moe_gate"] = w(next(keys), (L, h, E), h)
        layers["w_gate"] = w(next(keys), (L, E, h, i), h)
        layers["w_up"] = w(next(keys), (L, E, h, i), h)
        layers["w_down"] = w(next(keys), (L, E, i, h), i)
    else:
        layers["w_gate"] = w(next(keys), (L, h, i), h)
        layers["w_up"] = w(next(keys), (L, h, i), h)
        layers["w_down"] = w(next(keys), (L, i, h), i)
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, cfg.q_size), dtype)
        layers["bk"] = jnp.zeros((L, cfg.kv_size), dtype)
        layers["bv"] = jnp.zeros((L, cfg.kv_size), dtype)
    if cfg.sandwich_norm:
        layers["attn_out_norm"] = jnp.ones((L, h), dtype)
        layers["mlp_out_norm"] = jnp.ones((L, h), dtype)

    params = {
        "embed": w(next(keys), (v, h), h),
        "layers": layers,
        "final_norm": jnp.ones((h,), dtype),
    }
    if cfg.exit_gate:
        params["exit_gate_w"] = w(next(keys), (h,), h)
        params["exit_gate_b"] = jnp.zeros((), dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(keys), (h, v), h)
    return params


def decoder_layer(
    cfg: ModelConfig,
    h: jax.Array,          # (n, hidden)
    kc: jax.Array,         # cache (local or full layer axis)
    vc: jax.Array,
    lp: dict,              # this layer's param slice
    l: jax.Array,          # layer index INTO kc/vc (local under pp)
    *,
    cos: jax.Array,
    sin: jax.Array,
    write_slots: jax.Array,
    attn_fn,
    dtype,
    lora_ctx: tuple | None = None,  # (lz, scaling, uniform, slots)
    write_kv=scatter_kv,
):
    """One decoder layer over n token rows — the shared body of
    forward()'s layer scan and the pipeline-parallel phase loop
    (parallel/pp_serving.py). Writes the rows' K/V into the cache at
    `write_slots` BEFORE attn_fn runs, so attention sees them
    (`write_kv`: ops/cache_write.py, as the runner routes it)."""
    n = h.shape[0]

    def proj(x, target, base):
        out = jnp.dot(x, lp[target], preferred_element_type=jnp.float32)
        if base is not None:
            out = out + base.astype(jnp.float32)
        if lora_ctx is not None:
            lz, lora_scaling, lora_uniform, lora_slots = lora_ctx
            if lora_uniform:
                A = lz[f"{target}_A"][lora_slots]  # (in, r)
                B = lz[f"{target}_B"][lora_slots]  # (r, out)
                delta = jnp.dot(
                    jnp.dot(x, A, preferred_element_type=jnp.float32),
                    B.astype(jnp.float32),
                )
            else:
                A = lz[f"{target}_A"][lora_slots]  # (n, in, r)
                B = lz[f"{target}_B"][lora_slots]  # (n, r, out)
                t = jnp.einsum(
                    "ni,nir->nr", x, A,
                    preferred_element_type=jnp.float32,
                )
                delta = jnp.einsum(
                    "nr,nro->no", t, B,
                    preferred_element_type=jnp.float32,
                )
            out = out + delta * lora_scaling
        return out

    x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps,
                 cfg.norm_weight_offset)
    q = proj(x, "wq", lp["bq"] if cfg.qkv_bias else None)
    k = proj(x, "wk", lp["bk"] if cfg.qkv_bias else None)
    v = proj(x, "wv", lp["bv"] if cfg.qkv_bias else None)
    q = q.astype(dtype).reshape(n, cfg.num_heads, cfg.head_dim)
    k = k.astype(dtype).reshape(n, cfg.num_kv_heads, cfg.head_dim)
    v = v.astype(dtype).reshape(n, cfg.num_kv_heads, cfg.head_dim)
    q, k = apply_rope(q, k, cos, sin)

    kc, vc = write_kv(kc, vc, l, write_slots, k, v)

    def out_norm(y, name):
        # the "sandwich": a second norm on the sublayer's output
        if not cfg.sandwich_norm:
            return y
        return rms_norm(y, lp[name], cfg.rms_norm_eps,
                        cfg.norm_weight_offset)

    attn_out = attn_fn(q, l, kc, vc)  # (n, nq, d)
    h = h + out_norm(proj(
        attn_out.reshape(n, cfg.q_size).astype(dtype), "wo", None
    ).astype(dtype), "attn_out_norm")

    x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps,
                 cfg.norm_weight_offset)
    if cfg.is_moe:
        h = h + out_norm(moe_block(
            x, lp["moe_gate"], lp["w_gate"], lp["w_up"],
            lp["w_down"], cfg.num_experts_per_tok,
            cfg.moe_capacity_factor,
        ).astype(dtype), "mlp_out_norm")
    else:
        h = h + out_norm(
            swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"],
                   act=cfg.hidden_act), "mlp_out_norm")
    return h, kc, vc


def exit_mass(lam: jax.Array) -> jax.Array:
    """(T, r) gate values -> (T, r) exit distribution: a row leaves
    after pass t with p_t = lam_t * prod_{s<t} (1 - lam_s), and the last
    pass takes what is left."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(lam * before)[:-1], before[-1:]])


def forward(
    cfg: ModelConfig,
    params: dict,
    token_ids: jax.Array,  # (n,) int32
    positions: jax.Array,  # (n,) int32 absolute positions
    k_cache: jax.Array,  # (L, nkv, num_slots, d) — head-major (see
                         # ops/pallas_attention.py for the layout rationale)
    v_cache: jax.Array,
    write_slots: jax.Array,  # (n,) int32 cache rows for the new tokens
    attn_fn: AttnFn,
    logits_rows: jax.Array,  # (r,) int32 rows of h to project to logits
    lora: dict | None = None,  # LoraManager.buffers: (L, S, in, r)/(L, S, r, out) + scaling (S,)
    lora_slots: jax.Array | None = None,  # (n,) int32 adapter slot per token
    return_hidden: bool = False,  # final-norm hidden states instead of logits
    rows_valid: jax.Array | None = None,  # (r,) bool: logits rows that hold a token
    write_kv=scatter_kv,  # the layers' cache write (ops/cache_write.py)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run the decoder over n tokens; returns (logits[r, V] fp32, k_cache, v_cache).

    The caller is responsible for the attention gather pattern via attn_fn;
    this function writes the new tokens' K/V into the cache *before* calling
    attn_fn, so attention sees them.

    Multi-LoRA: when `lora`/`lora_slots` are given, each token's adapter
    rows are gathered per layer and scaling * (x @ A) @ B is added to the
    wq/wk/wv/wo projections (slot 0 is all-zero = no adapter), so one
    batch can mix adapters freely (see engine/lora.py).

    A looped stack with an exit gate may be handed its K side as
    {"c": cache, "stats": f32[ut_steps]}: the exit distribution of this
    call's `logits_rows` (those of `rows_valid`) is added to "stats" and
    the K side goes back in the same form (the runner's step programs
    carry it so; ModelRunner._enter_caches).
    """
    stats = None
    if isinstance(k_cache, dict):
        stats, k_cache = k_cache["stats"], k_cache["c"]
    dtype = params["embed"].dtype
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)

    h = params["embed"][token_ids].astype(dtype)
    if cfg.embed_scale != 1.0:
        # Gemma normalizer: hidden states enter the stack scaled by
        # sqrt(hidden_size)
        h = (h.astype(jnp.float32) * cfg.embed_scale).astype(dtype)

    # once a forward, not once a layer (the layers' writes read it)
    write_slots = plan_rows(write_slots, k_cache)

    use_lora = lora is not None
    if use_lora:
        # scalar lora_slots = whole batch uses one adapter (prefill runs
        # one sequence per step): skip the per-token gather entirely and
        # use plain (in, r) matmuls — per-token A/B copies would dominate
        # HBM traffic at prefill chunk sizes
        lora_uniform = jnp.ndim(lora_slots) == 0
        if lora_uniform:
            lora_scaling = lora["scaling"][lora_slots]  # scalar f32
        else:
            lora_scaling = lora["scaling"][lora_slots][:, None]  # (n, 1)
        lora_layers = {k: v for k, v in lora.items() if k != "scaling"}

    def layer(carry, xs):
        h, kc, vc = carry
        if use_lora:
            lp, l, lz = xs
            lora_ctx = (lz, lora_scaling, lora_uniform, lora_slots)
        else:
            lp, l = xs
            lora_ctx = None
        h, kc, vc = decoder_layer(
            cfg, h, kc, vc, lp, l,
            cos=cos, sin=sin, write_slots=write_slots, attn_fn=attn_fn,
            dtype=dtype, lora_ctx=lora_ctx, write_kv=write_kv,
        )
        return (h, kc, vc), None

    def stack(h, kc, vc, first=None):
        """The layers once, on cache layers first .. first + L - 1
        (None: 0 .. L - 1), and the final norm."""
        idx = jnp.arange(cfg.num_layers)
        if first is not None:
            idx = first + idx
        xs = ((params["layers"], idx, lora_layers) if use_lora
              else (params["layers"], idx))
        # named scopes put `layers/...` and `lm_head/...` into the
        # operation names a profiler trace shows (metadata only: the
        # program is the same)
        with jax.named_scope("layers"):
            (h, kc, vc), _ = jax.lax.scan(layer, (h, kc, vc), xs)
        h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps,
                     cfg.norm_weight_offset)
        return h, kc, vc

    if cfg.ut_steps == 1:
        h, k_cache, v_cache = stack(h, k_cache, v_cache)
    else:
        def one_pass(carry, t):
            with jax.named_scope("loop_pass"):
                h, kc, vc = stack(*carry, t * cfg.num_layers)
            lam = None
            if stats is not None:
                lam = jax.nn.sigmoid(
                    jnp.dot(h[logits_rows], params["exit_gate_w"],
                            preferred_element_type=jnp.float32)
                    + params["exit_gate_b"].astype(jnp.float32))
            return (h, kc, vc), lam

        (h, k_cache, v_cache), lam = jax.lax.scan(
            one_pass, (h, k_cache, v_cache), jnp.arange(cfg.ut_steps))
        if stats is not None:
            mass = exit_mass(lam)
            if rows_valid is not None:
                mass = jnp.where(rows_valid[None, :], mass, 0.0)
            stats = stats + jnp.sum(mass, axis=1)
    if stats is not None:
        k_cache = {"c": k_cache, "stats": stats}
    h_sel = h[logits_rows]  # (r, hidden)
    if return_hidden:
        return h_sel.astype(jnp.float32), k_cache, v_cache
    lm_head = (
        params["embed"].T
        if cfg.tie_word_embeddings
        else params["lm_head"]
    )
    with jax.named_scope("lm_head"):
        logits = jnp.dot(
            h_sel, lm_head, preferred_element_type=jnp.float32
        )
    return logits, k_cache, v_cache


# `scale` for attn_fn implementations; re-exported for the runner.
def attention_scale(cfg: ModelConfig) -> float:
    return cfg.head_dim**-0.5


