"""Pipeline parallelism: GPipe-style microbatch pipeline over a `pp` mesh
axis, the TPU-native way — one SPMD program, layers sharded by stage,
activations handed between stages with `lax.ppermute` over ICI.

Reference parity: the reference stack deploys pipeline parallelism by
spreading one engine over a Ray cluster
(reference: helm/templates/ray-cluster.yaml + pipelineParallelSize in
values.yaml). A torch-style translation would spawn per-stage processes
and p2p sends; on TPU the idiomatic form is a single jitted program in
which every device runs the same code, `lax.axis_index("pp")` selects the
stage's role, and XLA schedules the stage compute and the ICI permutes
together (the "pipelining via ppermute on a layer-sharded scan" recipe
from the public scaling playbook).

Design:
- params keep the stacked-layer layout of models/llama.py; the layer axis
  is simply sharded P("pp") so stage s holds layers [s*L/S, (s+1)*L/S).
- the KV cache (L, nkv, slots, d) shards the same way: each stage owns
  the cache for its layers, so microbatch attention is stage-local.
- a prompt is split into M sequence-chunk microbatches (chunked-prefill
  semantics: chunk m attends causally to chunks 0..m, all already
  resident in the stage-local cache by pipeline construction).
- the schedule is the classic M+S-1 step loop: at step t, stage s works
  on microbatch t-s; out-of-range steps compute into a trash cache slot
  (bubble steps cost compute but can never corrupt state).
- stage outputs rotate forward with ppermute; the last stage's hidden
  states psum back to every device (all other stages contribute zeros),
  and the lm_head projection runs replicated outside the shard_map.

Composes with the rest of the stack: the produced KV is the same
head-major layout serving uses, so a pp prefill can feed the paged cache
or the disaggregated-prefill transfer chain (kv/transfer.py). Scope:
dense Llama-family decoders (MoE goes through ep, adapters through tp).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.ops.attention import context_attention_prefill
from production_stack_tpu.ops.layers import (
    apply_rope,
    rms_norm,
    rope_cos_sin,
    swiglu,
)

PP_AXIS = "pp"


def make_pp_mesh(pp_size: int, devices=None) -> Mesh:
    devs = devices if devices is not None else jax.devices()
    if pp_size > len(devs):
        raise ValueError(
            f"pipeline_parallel_size={pp_size} > available devices "
            f"{len(devs)}"
        )
    return Mesh(np.asarray(devs[:pp_size]), (PP_AXIS,))


def validate_pp(cfg: ModelConfig, pp_size: int) -> None:
    if cfg.num_layers % pp_size:
        raise ValueError(
            f"model {cfg.name}: num_layers {cfg.num_layers} not divisible "
            f"by pp={pp_size} (layers shard whole per stage)"
        )
    if cfg.is_moe:
        raise ValueError(
            "pipeline parallelism covers dense decoders; shard MoE models "
            "with expert parallelism instead (parallel/sharding.py)"
        )
    if cfg.sliding_window:
        raise ValueError(
            f"model {cfg.name}: sliding-window attention is served by "
            "the engine's XLA path; the pipeline prefiller attends full "
            "context"
        )


def pp_param_shardings(mesh: Mesh, cfg: ModelConfig) -> dict:
    """NamedSharding pytree: stacked layer axis split across stages."""

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    layers = {k: ns(PP_AXIS) for k in (
        "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo",
        "w_gate", "w_up", "w_down",
    )}
    if cfg.qkv_bias:
        layers.update(bq=ns(PP_AXIS), bk=ns(PP_AXIS), bv=ns(PP_AXIS))
    out = {
        "embed": ns(None, None),  # both pipeline ends need it
        "layers": layers,
        "final_norm": ns(None),
    }
    if not cfg.tie_word_embeddings:
        out["lm_head"] = ns(None, None)
    return out


def shard_params_pp(params: dict, mesh: Mesh, cfg: ModelConfig) -> dict:
    return jax.tree.map(
        lambda x, s: jax.device_put(x, s),
        params, pp_param_shardings(mesh, cfg),
    )


class PipelinedPrefiller:
    """Prefill one prompt through a pp-staged decoder.

    Returns per-token logits plus the full (layer-sharded) KV for the
    prompt — cache rows ARE absolute positions, the same contract
    chunked prefill uses, so downstream consumers are identical.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        mesh: Mesh,
        microbatch_tokens: int = 64,
        num_microbatches: int | None = None,
    ):
        validate_pp(cfg, mesh.shape[PP_AXIS])
        self.cfg = cfg
        self.mesh = mesh
        self.stages = mesh.shape[PP_AXIS]
        self.microbatch_tokens = microbatch_tokens
        # M >= S keeps every stage busy in steady state; correctness
        # holds for any M >= 1
        self.num_microbatches = num_microbatches or max(2, self.stages)
        self.params = shard_params_pp(params, mesh, cfg)
        self._fn = jax.jit(
            functools.partial(
                _pp_prefill, cfg, self.stages, self.num_microbatches,
                mesh,
            ),
            static_argnames=("chunk",),
        )

    def prefill(self, token_ids: list[int]):
        """-> (logits (T, V) f32, k_cache, v_cache, T).

        Caches are (L, nkv, M*chunk+1, d) — the final row is the bubble
        trash slot; valid rows are absolute positions [0, T).
        """
        T = len(token_ids)
        M = self.num_microbatches
        chunk = max(
            self.microbatch_tokens, -(-T // M)
        )  # ceil so M chunks always cover T
        pad = M * chunk - T
        toks = jnp.asarray(
            list(token_ids) + [0] * pad, jnp.int32
        )
        with self.mesh:
            logits, kc, vc = self._fn(self.params, toks, chunk=chunk)
        return logits[:T], kc, vc, T


def _pp_prefill(cfg, S, M, mesh, params, tokens, *, chunk):
    """Jitted body: shard_map pipeline + replicated lm_head."""
    T_pad = M * chunk
    slots = T_pad + 1  # +1 trash row for bubble steps
    dtype = params["embed"].dtype

    layer_specs = jax.tree.map(lambda _: P(PP_AXIS), params["layers"])
    cache_spec = P(PP_AXIS, None, None, None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(layer_specs, P(None, None), P(None)),
        out_specs=(P(None, None, None), cache_spec, cache_spec),
    )
    def run(layers_local, embed, tokens):
        stage = jax.lax.axis_index(PP_AXIS)
        L_loc = layers_local["wq"].shape[0]
        nkv, d = cfg.num_kv_heads, cfg.head_dim
        scale = cfg.head_dim**-0.5

        h0 = embed[tokens].astype(dtype)
        if cfg.embed_scale != 1.0:  # Gemma normalizer
            h0 = (h0.astype(jnp.float32) * cfg.embed_scale).astype(dtype)
        h0 = h0.reshape(M, chunk, -1)
        positions = jnp.arange(T_pad, dtype=jnp.int32).reshape(M, chunk)

        # initial carries are constants (replicated-typed); the loop body
        # makes them device-varying (stage-dependent), so pre-cast their
        # varying-manual-axes type or the fori_loop carry types mismatch
        def varying(x):
            return jax.lax.pcast(x, (PP_AXIS,), to="varying")

        kc0 = varying(jnp.zeros((L_loc, nkv, slots, d), dtype))
        vc0 = varying(jnp.zeros((L_loc, nkv, slots, d), dtype))
        out0 = varying(jnp.zeros((M, chunk, cfg.hidden_size), dtype))
        state0 = varying(jnp.zeros((chunk, cfg.hidden_size), dtype))

        def stack(h, kc, vc, mb_pos, write_slots, total_len):
            """This stage's layer slice over one microbatch."""
            cos, sin = rope_cos_sin(mb_pos, cfg.head_dim, cfg.rope_theta)

            def layer(carry, xs):
                h, kc, vc = carry
                lp, l = xs
                x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps,
                             cfg.norm_weight_offset)
                q = jnp.dot(x, lp["wq"],
                            preferred_element_type=jnp.float32)
                k = jnp.dot(x, lp["wk"],
                            preferred_element_type=jnp.float32)
                v = jnp.dot(x, lp["wv"],
                            preferred_element_type=jnp.float32)
                if cfg.qkv_bias:
                    q = q + lp["bq"].astype(jnp.float32)
                    k = k + lp["bk"].astype(jnp.float32)
                    v = v + lp["bv"].astype(jnp.float32)
                q = q.astype(dtype).reshape(chunk, cfg.num_heads, d)
                k = k.astype(dtype).reshape(chunk, nkv, d)
                v = v.astype(dtype).reshape(chunk, nkv, d)
                q, k = apply_rope(q, k, cos, sin)
                kh = k.swapaxes(0, 1)  # (nkv, chunk, d)
                vh = v.swapaxes(0, 1)
                for head in range(nkv):
                    kc = kc.at[l, head, write_slots].set(kh[head])
                    vc = vc.at[l, head, write_slots].set(vh[head])
                attn = context_attention_prefill(
                    q,
                    kc[l].swapaxes(0, 1),  # (slots, nkv, d)
                    vc[l].swapaxes(0, 1),
                    mb_pos,
                    total_len,
                    scale,
                )
                h = h + jnp.dot(
                    attn.reshape(chunk, cfg.q_size).astype(dtype),
                    lp["wo"], preferred_element_type=jnp.float32,
                ).astype(dtype)
                x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps,
                             cfg.norm_weight_offset)
                h = h + swiglu(x, lp["w_gate"], lp["w_up"],
                               lp["w_down"], act=cfg.hidden_act)
                return (h, kc, vc), None

            (h, kc, vc), _ = jax.lax.scan(
                layer, (h, kc, vc),
                (layers_local, jnp.arange(L_loc)),
            )
            return h, kc, vc

        def step(t, carry):
            state, kc, vc, outputs = carry
            mb = t - stage  # the microbatch this stage works on now
            valid = jnp.logical_and(mb >= 0, mb < M)
            mb_c = jnp.clip(mb, 0, M - 1)
            h_in = jnp.where(
                stage == 0,
                jax.lax.dynamic_index_in_dim(h0, mb_c, keepdims=False),
                state,
            )
            # bubble steps write into the trash row: they can never
            # corrupt a real position
            write_slots = jnp.where(
                valid,
                mb_c * chunk + jnp.arange(chunk, dtype=jnp.int32),
                jnp.full((chunk,), T_pad, jnp.int32),
            )
            mb_pos = jax.lax.dynamic_index_in_dim(
                positions, mb_c, keepdims=False
            )
            total_len = jnp.where(valid, (mb_c + 1) * chunk, 0)
            h_out, kc, vc = stack(
                h_in, kc, vc, mb_pos, write_slots, total_len
            )
            # last stage records microbatch t-(S-1) when it is real
            done = t - (S - 1)
            rec = jnp.logical_and(stage == S - 1, done >= 0)
            idx = jnp.clip(done, 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(
                outputs, idx, keepdims=False
            )
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(rec, h_out, cur), idx, 0
            )
            # hand this stage's activations to the next stage
            state = jax.lax.ppermute(
                h_out, PP_AXIS, [(i, i + 1) for i in range(S - 1)]
            )
            return state, kc, vc, outputs

        _, kc, vc, outputs = jax.lax.fori_loop(
            0, M + S - 1, step, (state0, kc0, vc0, out0)
        )
        # every stage except the last holds zeros; psum replicates the
        # real outputs to all devices for the replicated lm_head
        outputs = jax.lax.psum(
            jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)),
            PP_AXIS,
        )
        return outputs, kc, vc

    hidden, k_cache, v_cache = run(
        params["layers"], params["embed"], tokens
    )
    h = rms_norm(
        hidden.reshape(T_pad, cfg.hidden_size),
        params["final_norm"], cfg.rms_norm_eps,
        cfg.norm_weight_offset,
    )
    lm_head = (
        params["embed"].T
        if cfg.tie_word_embeddings
        else params["lm_head"]
    )
    logits = jnp.dot(h, lm_head, preferred_element_type=jnp.float32)
    return logits, k_cache, v_cache
