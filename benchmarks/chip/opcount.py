"""Bytes and operations a step needs, from a configuration's shapes.

Kept with the benchmark so that no PR that claims a gain can change how
a share of a peak is counted. Inputs are the published `config.json`
keys as the configuration files hold them.
"""

from __future__ import annotations


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
    the projections carry them (`qkv_bias`; `engine_child.py` refuses to
    serve where the program's own ModelConfig disagrees)."""
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
