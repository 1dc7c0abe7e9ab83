"""HF checkpoint loading: safetensors/torch shards -> stacked JAX params.

The reference stack's engines load HF weights inside vLLM; our engine
loads them directly. Layout conversion: HF Llama-family per-layer
`{q,k,v,o}_proj.weight` are (out, in) torch matrices; our params store
them transposed (in, out) and stacked over layers on axis 0 so the
decoder runs as one lax.scan (models/llama.py init_params:36).

Zero-egress friendly: only local paths (a model directory, or an HF id
already present in the local HF cache) are accepted.
"""

from __future__ import annotations

import json
import os

import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)


def resolve_model_dir(model: str) -> str | None:
    """Local directory containing config.json + weights for `model`."""
    if os.path.isdir(model) and os.path.exists(
        os.path.join(model, "config.json")
    ):
        return model
    # HF cache layout: <cache>/models--org--name/snapshots/<rev>/
    cache = os.environ.get(
        "HF_HOME", os.path.expanduser("~/.cache/huggingface")
    )
    hub = os.path.join(cache, "hub", f"models--{model.replace('/', '--')}")
    snaps = os.path.join(hub, "snapshots")
    if os.path.isdir(snaps):
        # prefer the revision refs/main points at (the cache's notion of
        # "current"); fall back to any snapshot with a config.json
        ref_main = os.path.join(hub, "refs", "main")
        if os.path.exists(ref_main):
            with open(ref_main) as f:
                rev = f.read().strip()
            d = os.path.join(snaps, rev)
            if os.path.exists(os.path.join(d, "config.json")):
                return d
        for rev in sorted(os.listdir(snaps)):
            d = os.path.join(snaps, rev)
            if os.path.exists(os.path.join(d, "config.json")):
                return d
    return None


def _iter_tensors(model_dir: str):
    """Yield (name, np.ndarray) across all weight shards in the dir."""
    st_files = sorted(
        f for f in os.listdir(model_dir) if f.endswith(".safetensors")
    )
    if st_files:
        from safetensors import safe_open

        for fn in st_files:
            with safe_open(os.path.join(model_dir, fn),
                           framework="numpy") as f:
                for key in f.keys():
                    yield key, f.get_tensor(key)
        return
    bin_files = sorted(
        f for f in os.listdir(model_dir)
        if f.startswith("pytorch_model") and f.endswith(".bin")
    )
    if not bin_files:
        raise FileNotFoundError(
            f"no safetensors or pytorch_model*.bin in {model_dir}"
        )
    import torch

    for fn in bin_files:
        sd = torch.load(
            os.path.join(model_dir, fn), map_location="cpu",
            weights_only=True,
        )
        for key, t in sd.items():
            yield key, t.to(torch.float32).numpy()


def load_hf_weights(
    cfg: ModelConfig, model_dir: str, dtype=jnp.bfloat16
) -> dict:
    """Read an HF Llama/Mistral/Qwen2 checkpoint into our param tree."""
    L, h = cfg.num_layers, cfg.hidden_size
    np_dtype = np.dtype(jnp.dtype(dtype).name) if jnp.dtype(
        dtype) != jnp.bfloat16 else np.float32

    def alloc(shape):
        return np.zeros(shape, np_dtype)

    layers = {
        "attn_norm": alloc((L, h)),
        "mlp_norm": alloc((L, h)),
        "wq": alloc((L, h, cfg.q_size)),
        "wk": alloc((L, h, cfg.kv_size)),
        "wv": alloc((L, h, cfg.kv_size)),
        "wo": alloc((L, cfg.q_size, h)),
    }
    i_sz = cfg.intermediate_size
    if cfg.is_moe:
        E = cfg.num_experts
        layers["moe_gate"] = alloc((L, h, E))
        layers["w_gate"] = alloc((L, E, h, i_sz))
        layers["w_up"] = alloc((L, E, h, i_sz))
        layers["w_down"] = alloc((L, E, i_sz, h))
    else:
        layers["w_gate"] = alloc((L, h, i_sz))
        layers["w_up"] = alloc((L, h, i_sz))
        layers["w_down"] = alloc((L, i_sz, h))
    if cfg.qkv_bias:
        layers["bq"] = alloc((L, cfg.q_size))
        layers["bk"] = alloc((L, cfg.kv_size))
        layers["bv"] = alloc((L, cfg.kv_size))
    if cfg.sandwich_norm:
        layers["attn_out_norm"] = alloc((L, h))
        layers["mlp_out_norm"] = alloc((L, h))
    top: dict[str, np.ndarray] = {}

    # HF key suffix -> (our key, transpose?)
    per_layer = {
        "input_layernorm.weight": ("attn_norm", False),
        "post_attention_layernorm.weight": ("mlp_norm", False),
        # a looped stack's norms on the sublayers' outputs (`ouro`)
        "input_layernorm_2.weight": ("attn_out_norm", False),
        "post_attention_layernorm_2.weight": ("mlp_out_norm", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "self_attn.q_proj.bias": ("bq", False),
        "self_attn.k_proj.bias": ("bk", False),
        "self_attn.v_proj.bias": ("bv", False),
        "mlp.gate_proj.weight": ("w_gate", True),
        "mlp.up_proj.weight": ("w_up", True),
        "mlp.down_proj.weight": ("w_down", True),
    }
    # a looped stack's nn.Linear(hidden, 1): weight (1, h), bias (1,)
    exit_gate = {"early_exit_gate.weight": ("exit_gate_w", (h,)),
                 "early_exit_gate.bias": ("exit_gate_b", ())}
    n_loaded = 0
    for name, tensor in _iter_tensors(model_dir):
        key = name.removeprefix("model.")
        if key == "embed_tokens.weight":
            top["embed"] = np.asarray(tensor, np_dtype)
            n_loaded += 1
            continue
        if key == "norm.weight":
            top["final_norm"] = np.asarray(tensor, np_dtype)
            n_loaded += 1
            continue
        if name == "lm_head.weight":
            top["lm_head"] = np.asarray(tensor, np_dtype).T
            n_loaded += 1
            continue
        if cfg.exit_gate and key in exit_gate:
            ours, shape = exit_gate[key]
            top[ours] = np.asarray(tensor, np_dtype).reshape(shape)
            n_loaded += 1
            continue
        if not key.startswith("layers."):
            continue
        _, idx, *rest = key.split(".", 2)
        suffix = rest[0]
        # Mixtral MoE block (HF MixtralSparseMoeBlock):
        #   block_sparse_moe.gate.weight            [E, h]
        #   block_sparse_moe.experts.{e}.w1.weight  [f, h] -> w_gate
        #   block_sparse_moe.experts.{e}.w3.weight  [f, h] -> w_up
        #   block_sparse_moe.experts.{e}.w2.weight  [h, f] -> w_down
        if cfg.is_moe and suffix.startswith("block_sparse_moe."):
            arr = np.asarray(tensor, np.float32)
            if suffix == "block_sparse_moe.gate.weight":
                layers["moe_gate"][int(idx)] = arr.T.astype(np_dtype)
                n_loaded += 1
                continue
            parts = suffix.split(".")  # [...,'experts', e, w1, 'weight']
            if len(parts) == 5 and parts[1] == "experts":
                ours = {"w1": "w_gate", "w3": "w_up", "w2": "w_down"}.get(
                    parts[3]
                )
                if ours is not None:
                    layers[ours][int(idx), int(parts[2])] = arr.T.astype(
                        np_dtype
                    )
                    n_loaded += 1
            continue
        # Phi-3 fuses attention and MLP inputs into single matrices;
        # split the rows back out to the Llama-layout params
        if suffix == "self_attn.qkv_proj.weight":
            arr = np.asarray(tensor, np.float32)
            q, k, v = np.split(
                arr, [cfg.q_size, cfg.q_size + cfg.kv_size], axis=0
            )
            layers["wq"][int(idx)] = q.T.astype(np_dtype)
            layers["wk"][int(idx)] = k.T.astype(np_dtype)
            layers["wv"][int(idx)] = v.T.astype(np_dtype)
            n_loaded += 3
            continue
        if suffix == "mlp.gate_up_proj.weight":
            arr = np.asarray(tensor, np.float32)
            gate, up = np.split(arr, 2, axis=0)
            layers["w_gate"][int(idx)] = gate.T.astype(np_dtype)
            layers["w_up"][int(idx)] = up.T.astype(np_dtype)
            n_loaded += 2
            continue
        mapping = per_layer.get(suffix)
        if mapping is None:
            continue
        ours, transpose = mapping
        if ours not in layers:
            continue  # bias tensors on a model without qkv_bias
        arr = np.asarray(tensor, np.float32)
        layers[ours][int(idx)] = (arr.T if transpose else arr).astype(
            np_dtype
        )
        n_loaded += 1

    if "embed" not in top:
        raise ValueError(f"checkpoint at {model_dir} has no embed_tokens")
    # completeness: a partial shard set must never load as zero-filled
    # layers (n per-layer tensors + embed + final_norm [+ lm_head])
    dense_mlp = {"w_gate", "w_up", "w_down"}
    per_layer_count = len([
        k for k, (ours, _) in per_layer.items()
        if ours in layers and not (cfg.is_moe and ours in dense_mlp)
    ])
    if cfg.is_moe:
        per_layer_count += 1 + 3 * cfg.num_experts  # router + experts
    expected = (
        L * per_layer_count + 2 + (0 if cfg.tie_word_embeddings else 1)
        + (2 if cfg.exit_gate else 0)
    )
    if n_loaded < expected:
        raise ValueError(
            f"checkpoint at {model_dir} is incomplete: loaded {n_loaded} "
            f"of {expected} expected tensors (missing shards?)"
        )
    params = {
        "embed": jnp.asarray(top["embed"], dtype),
        "layers": {k: jnp.asarray(v, dtype) for k, v in layers.items()},
        "final_norm": jnp.asarray(top["final_norm"], dtype),
    }
    if cfg.exit_gate:
        for gate in ("exit_gate_w", "exit_gate_b"):
            params[gate] = jnp.asarray(top[gate], dtype)
    if not cfg.tie_word_embeddings:
        if "lm_head" in top:
            params["lm_head"] = jnp.asarray(top["lm_head"], dtype)
        else:
            logger.warning("no lm_head in checkpoint; tying to embeddings")
            params["lm_head"] = params["embed"].T
    logger.info(
        "loaded %d tensors from %s (%s)", n_loaded, model_dir, cfg.name
    )
    return params


def maybe_load(model: str, cfg: ModelConfig, dtype=jnp.bfloat16):
    """Load weights if `model` resolves to a local checkpoint, else None
    (the runner falls back to random init for presets/debug names).

    A checkpoint that RESOLVES but fails to load raises: silently serving
    random weights under a real model's name would be far worse than
    failing startup."""
    d = resolve_model_dir(model)
    if d is None:
        return None
    return load_hf_weights(cfg, d, dtype)
