"""Pipeline parallelism as a SERVING config: a drop-in forward for the
engine's jitted steps with layers (and their KV) sharded over a `pp`
mesh axis.

This promotes parallel/pipeline.py's capability into the real engine
step loop (the reference deploys PP as a serving config:
helm/templates/ray-cluster.yaml + `pipelineParallelSize` in
values-15-minimal-pipeline-parallel-example.yaml; ours is
`--pipeline-parallel-size` on the engine + `pipelineParallelSize` in
helm/CRD). TPU-native shape: ONE jitted SPMD program per engine step —
no Ray actors, no per-stage processes, no p2p sends:

- params keep models/llama.py's stacked-layer layout with the layer
  axis sharded P("pp") (composing with tensor parallelism: the mesh is
  ("pp", "tp"), layer axis manual, head/ffn axes left to GSPMD auto
  via shard_map's partial-manual `axis_names={"pp"}`);
- the KV cache (L, nkv, slots, d) shards its layer axis the same way,
  so each stage's attention reads only stage-local cache;
- the phase loop runs S = pp_size static phases: at phase t every
  device runs its own layer slice, but only the device whose
  stage == t is holding REAL activations — the others write their
  garbage K/V to the reserved trash slot 0 and their outputs are
  discarded. Activations hand forward with `lax.ppermute` over ICI
  after each phase; the last stage's final output psums back to all
  devices for the replicated lm_head.

Utilization note: a single engine step keeps 1/S of the stages busy
(the classic pipeline bubble at microbatch=1). That is the same
steady-state utilization a Ray-staged decode has for one request
wave; pipelined PREFILL microbatching (parallel/pipeline.py) and
continuous batching fill the bubble in practice. The win PP buys is
the same as the reference's: models whose weights+KV exceed one
chip's HBM serve across chips without head-divisibility constraints.

Scope (validated in ModelRunner): dense decoders (MoE -> ep), no LoRA,
XLA attention path (the pallas kernels' own shard_map does not nest
inside the pp manual region yet).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from production_stack_tpu.models import llama
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.ops.layers import rms_norm, rope_cos_sin

PP_AXIS = "pp"


def validate_pp_serving(cfg: ModelConfig, pp: int, config) -> None:
    """Serving-config validation (engine boot, loud and early)."""
    if cfg.num_layers % pp:
        raise ValueError(
            f"model {cfg.name}: num_layers {cfg.num_layers} not "
            f"divisible by pipeline_parallel_size={pp}"
        )
    if cfg.is_moe:
        raise ValueError(
            "pipeline parallelism covers dense decoders; shard MoE "
            "models with expert parallelism (tensor_parallel_size)"
        )
    if config.enable_lora:
        raise ValueError(
            "--enable-lora is not supported with pipeline parallelism "
            "yet (adapter buffers are not stage-sharded)"
        )


def forward_pp(
    cfg: ModelConfig,
    params: dict,
    token_ids: jax.Array,   # (n,) int32
    positions: jax.Array,   # (n,) int32
    k_cache: jax.Array,     # (L, nkv, slots, d), layer axis P("pp")
    v_cache: jax.Array,
    write_slots: jax.Array,  # (n,) int32
    attn_fn,
    logits_rows: jax.Array,  # (r,) int32
    lora: dict | None = None,
    lora_slots: jax.Array | None = None,
    return_hidden: bool = False,
    *,
    mesh: jax.sharding.Mesh,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Same contract as models.llama.forward, staged over the pp axis.

    `attn_fn(q, l, kc, vc)` receives the STAGE-LOCAL cache with local
    layer indices — the engine's XLA gather closures index the cache by
    the layer argument, so they work unchanged on the shard."""
    if lora is not None:
        raise NotImplementedError("LoRA under pipeline parallelism")
    S = mesh.shape[PP_AXIS]
    dtype = params["embed"].dtype
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)

    h0 = params["embed"][token_ids].astype(dtype)
    if cfg.embed_scale != 1.0:
        h0 = (h0.astype(jnp.float32) * cfg.embed_scale).astype(dtype)

    layer_specs = jax.tree.map(lambda _: P(PP_AXIS), params["layers"])

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        # partial-manual: pp is manual here, tp (if present) stays
        # GSPMD-auto inside, so the Megatron shardings keep working
        axis_names=frozenset({PP_AXIS}),
        in_specs=(layer_specs, P(PP_AXIS), P(PP_AXIS), P(), P(), P(),
                  P()),
        out_specs=(P(), P(PP_AXIS), P(PP_AXIS)),
        check_vma=False,
    )
    def run(layers_local, kc, vc, h0, cos_, sin_, ws_real):
        stage = jax.lax.axis_index(PP_AXIS)
        L_loc = layers_local["attn_norm"].shape[0]

        def local_stack(h, kc, vc, ws):
            def body(carry, xs):
                h, kc, vc = carry
                lp, l = xs
                h, kc, vc = llama.decoder_layer(
                    cfg, h, kc, vc, lp, l,
                    cos=cos_, sin=sin_, write_slots=ws, attn_fn=attn_fn,
                    dtype=dtype,
                )
                return (h, kc, vc), None

            (h, kc, vc), _ = jax.lax.scan(
                body, (h, kc, vc),
                (layers_local, jnp.arange(L_loc)),
            )
            return h, kc, vc

        h = h0
        out = jnp.zeros_like(h0)
        for t in range(S):  # static phase loop, S is small
            # only the stage holding REAL activations writes real cache
            # rows; every other stage's garbage lands in trash slot 0
            ws = jnp.where(stage == t, ws_real,
                           jnp.zeros_like(ws_real))
            h2, kc, vc = local_stack(h, kc, vc, ws)
            if t == S - 1:
                out = jnp.where(stage == S - 1, h2, out)
            if S > 1:
                h = jax.lax.ppermute(
                    h2, PP_AXIS, [(i, i + 1) for i in range(S - 1)]
                )
        # all stages but the last contribute zeros
        out = jax.lax.psum(
            jnp.where(stage == S - 1, out, jnp.zeros_like(out)), PP_AXIS
        )
        return out, kc, vc

    h, k_cache, v_cache = run(
        params["layers"], k_cache, v_cache, h0, cos, sin, write_slots
    )
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps,
                 cfg.norm_weight_offset)
    h_sel = h[logits_rows]
    if return_hidden:
        return h_sel.astype(jnp.float32), k_cache, v_cache
    lm_head = (
        params["embed"].T
        if cfg.tie_word_embeddings
        else params["lm_head"]
    )
    logits = jnp.dot(h_sel, lm_head, preferred_element_type=jnp.float32)
    return logits, k_cache, v_cache
