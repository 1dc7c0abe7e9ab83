"""Tensor-parallel sharding rules for the Llama family over an ICI mesh.

TPU-first replacement for the reference stack's `--tensor-parallel-size`
NCCL path (reference: helm/templates/deployment-vllm-multi.yaml:161,
operator vllmruntime_types.go:75): instead of explicit collective calls,
weights and KV cache carry `NamedSharding`s and XLA GSPMD inserts the
all-reduces on ICI.

Layout (Megatron-style, hidden activations replicated):
- attention: wq/wk/wv column-parallel (heads split across `tp`), wo
  row-parallel -> one psum per layer after the attention output projection;
- MLP: w_gate/w_up column-parallel, w_down row-parallel -> one psum;
- KV cache: sharded over the kv-head axis, so paged attention is fully
  local to each chip (q heads and kv heads split congruently for GQA);
- lm_head column-parallel over vocab for untied models; tied-embedding
  models (e.g. Llama-3.2-1B) keep the embedding/vocab projection
  replicated, since the same table serves token lookup.

num_kv_heads and num_heads must be divisible by the tp size (true for the
Llama/Mistral/Qwen2 family at tp in {1,2,4,8}).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from production_stack_tpu.models.config import ModelConfig

TP_AXIS = "tp"


def make_mesh(
    tp_size: int, devices: list | None = None
) -> Mesh:
    devs = devices if devices is not None else jax.devices()
    if tp_size > len(devs):
        raise ValueError(
            f"tensor_parallel_size={tp_size} > available devices {len(devs)}"
        )
    return Mesh(np.asarray(devs[:tp_size]), (TP_AXIS,))


def make_serving_mesh(
    tp_size: int, pp_size: int, devices: list | None = None
) -> Mesh:
    """("pp", "tp") mesh for the engine: TP groups ICI-contiguous within
    a stage (activation collectives stay on the fastest links), stages
    across the outer axis. pp_size == 1 keeps the single-axis tp mesh so
    every existing tp path (pallas shard_map, cache shardings) is
    byte-identical."""
    if pp_size <= 1:
        return make_mesh(tp_size, devices)
    devs = devices if devices is not None else jax.devices()
    need = tp_size * pp_size
    if need > len(devs):
        raise ValueError(
            f"pp({pp_size}) x tp({tp_size}) = {need} > available "
            f"devices {len(devs)}"
        )
    arr = np.asarray(devs[:need]).reshape(pp_size, tp_size)
    return Mesh(arr, ("pp", TP_AXIS))


def _layer_axis(mesh: Mesh):
    """'pp' when the mesh pipelines the stacked layer axis, else None."""
    return "pp" if "pp" in mesh.axis_names else None


def validate_tp(cfg: ModelConfig, tp_size: int) -> None:
    if cfg.num_heads % tp_size or cfg.num_kv_heads % tp_size:
        raise ValueError(
            f"model {cfg.name}: heads ({cfg.num_heads}/{cfg.num_kv_heads}) "
            f"not divisible by tp={tp_size}"
        )
    if cfg.is_moe:
        if cfg.num_experts % tp_size:
            raise ValueError(
                f"model {cfg.name}: num_experts {cfg.num_experts} not "
                f"divisible by tp={tp_size} (experts shard whole)"
            )
    elif cfg.intermediate_size % tp_size:
        raise ValueError(
            f"model {cfg.name}: intermediate_size "
            f"{cfg.intermediate_size} not divisible by tp={tp_size}"
        )
    if not cfg.tie_word_embeddings and cfg.vocab_size % tp_size:
        raise ValueError(
            f"model {cfg.name}: vocab_size {cfg.vocab_size} not divisible "
            f"by tp={tp_size} (lm_head is vocab-sharded)"
        )


def param_shardings(mesh: Mesh, cfg: ModelConfig) -> dict:
    """NamedSharding pytree matching models.llama.init_params.

    On a ("pp", "tp") serving mesh the stacked LAYER axis (axis 0 of
    every per-layer array) additionally shards over pp — each pipeline
    stage holds its own layer slice of the Megatron-sharded weights."""
    la = _layer_axis(mesh)

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    layers = {
        "attn_norm": ns(la, None),
        "mlp_norm": ns(la, None),
        "wq": ns(la, None, TP_AXIS),  # column: heads split
        "wk": ns(la, None, TP_AXIS),
        "wv": ns(la, None, TP_AXIS),
        "wo": ns(la, TP_AXIS, None),  # row: psum after
    }
    if cfg.is_moe:
        # expert parallelism over the same mesh axis: each chip holds
        # E/tp whole experts ((L, E, h, f) split on E); the router stays
        # replicated and XLA turns dispatch/combine into all_to_alls
        layers["moe_gate"] = ns(la, None, None)
        layers["w_gate"] = ns(la, TP_AXIS, None, None)
        layers["w_up"] = ns(la, TP_AXIS, None, None)
        layers["w_down"] = ns(la, TP_AXIS, None, None)
    else:
        layers["w_gate"] = ns(la, None, TP_AXIS)
        layers["w_up"] = ns(la, None, TP_AXIS)
        layers["w_down"] = ns(la, TP_AXIS, None)
    if cfg.qkv_bias:
        layers["bq"] = ns(la, TP_AXIS)
        layers["bk"] = ns(la, TP_AXIS)
        layers["bv"] = ns(la, TP_AXIS)
    if cfg.sandwich_norm:
        layers["attn_out_norm"] = ns(la, None)
        layers["mlp_out_norm"] = ns(la, None)
    out = {
        "embed": ns(None, None),  # replicated (logits need full hidden)
        "layers": layers,
        "final_norm": ns(None),
    }
    if not cfg.tie_word_embeddings:
        out["lm_head"] = ns(None, TP_AXIS)  # vocab split
    if cfg.exit_gate:
        out["exit_gate_w"] = ns(None)
        out["exit_gate_b"] = ns()
    return out


def cache_sharding(mesh: Mesh) -> NamedSharding:
    """KV cache (layers, kv_heads, slots, head_dim): split kv heads
    (and the layer axis per pipeline stage on a ("pp", "tp") mesh).

    Head-major layout — see ops/pallas_attention.py module docstring for
    why the hardware wants the slot run contiguous per head."""
    return NamedSharding(mesh, P(_layer_axis(mesh), TP_AXIS, None, None))


def shard_params(params: dict, mesh: Mesh, cfg: ModelConfig) -> dict:
    shardings = param_shardings(mesh, cfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, s), params, shardings
    )
