"""The mimo_v2 family: MiMo-V2-Flash / MiMo-V2.5's text decoder, as one
expert-parallel rank serves it.

`model_type: mimo_v2` (source: the published config.json; family
MiMo-V2-Flash 309B-A15B). The only file of the benchmark that knows this
parameter tree and these equations; `manifest.py` says what a family file
gives and how it is found. THE EQUATIONS, with `x` a row of the residual
stream; RMSNorm (eps `layernorm_epsilon`) before attention, before the
MLP and at the end; no qk-norm, no biases; untied head:

- Attention, both kinds: q = x Wq -> 64 heads x 192; k = x Wk -> nkv x
  192; v = 0.707 (x Wv) -> nkv x 128 (`attention_value_scale`, scaled
  before the cache). Rotary (half-split) on dims [0, 64) of each q and k
  head (`int(192 * partial_rotary_factor)`), dims [64, 192) unrotated.
  Scores q.k / sqrt(192), causal. Output (64 x 128) Wo.
- Full layers (`hybrid_layer_pattern` 0): nkv 4, theta 1e7, every earlier
  key, plain softmax.
- Window layers (1): nkv 8, theta 1e4 (`swa_rope_theta`), keys j with
  q_pos - 128 < j <= q_pos, and a learned sink s_h per q head in the
  denominator: p_j = exp(a_j) / (exp(s_h) + sum_i exp(a_i)), nothing
  added to the numerator.
- Layer 0 MLP (`moe_layer_freq` 0): (silu(x Wg) * (x Wu)) Wd at width
  16,384. Other layers: router logits x Wr over all 256 experts (float32,
  `highest`), sigma = sigmoid(logits), chosen = top-8 of sigma + b
  (`e_score_correction_bias`; `n_group` = `topk_group` = 1: no group
  limit), weights sigma_e / (sum_chosen sigma + 1e-20) (`norm_topk_prob`;
  `routed_scaling_factor` null = 1), output the sum over the chosen
  experts HELD HERE of w_e * expert_e(x) at width 2,048.

DEPARTURES from the published description: the text decoder only (the 3
MTP layers, the vision tower and the audio encoder are left out); what
the experts not held here would add is left out, in the program and in
the reference alike, and that partial sum goes on to the next layer (an
expert-parallel rank's share before the all-reduce).

A configuration of this family counts the experts HELD HERE under the
published key `n_routed_experts` (the contract's rule for a chip's share)
and says the rest under keys of its own: `router_experts` (the router's
published width), `ep_size` and `ep_rank` (how many ranks share a layer's
experts, and which one this is; ranks hold contiguous slices).

Imports jax inside its functions only: `run.py` loads a family for its
counts and imports no jax.
"""

from __future__ import annotations

import dataclasses

# keys of a configuration file that are this family's, beside the ones
# every configuration has (`manifest.COMMON_KEYS`). `ep_size` / `ep_rank`
# pass through to the program's config.json (it reads them under those
# names); `router_experts` becomes its `n_routed_experts`
OWN_KEYS = ("router_experts",)
VOCAB_SLICES = 8
QUERY_BLOCK = 512
# lanes a K row takes in the cache on the chip: 192 is stored as 256 (the
# 128-lane tile; `ModelRunner._k_store_dim`), so a token's KV bytes are
# counted at the stored width (measured: PERF.md, Findings PR 28)
K_STORE_LANES = 256


# -- 1. the config.json the program reads ----------------------------------
def hf_config(config: dict) -> dict:
    """The published keys, with `n_routed_experts` back at the router's
    width: the program is told the experts it holds by ep_size/ep_rank."""
    out = {k: v for k, v in config.items() if k not in OWN_KEYS}
    out["n_routed_experts"] = config["router_experts"]
    return out


def _segments(mc):
    """(kind, routed, count) runs of alike layers, as the program's tree
    stacks them (`ModelConfig.segments`)."""
    return [(k, r, c) for k, r, c, _ in mc.segments()]


# -- 2. the weights ---------------------------------------------------------
def init_params(mc, key, dtype):
    """All weights from the key, one run of alike layers at a time and
    layer by layer inside it; sinks and the router's selection bias
    non-zero, so that a dropped one shows in the reference check.

    THE SCALES. Every matrix has entries of ONE standard deviation,
    hidden ** -0.5 (a single `initializer_range` for every matrix is how
    the published family's own code initialises; written as a power of
    the width it carries to the rehearsal's widths), and the embedding's
    rows have unit variance an entry. Both decide what a cut of 7 random
    layers can show, and both were read on the CPU at published widths
    against 1 / sqrt(fan_in) everywhere, which this file had first
    (PERF.md, Findings PR 28, third session):
    - under 1 / sqrt(fan_in) an embedding row (rms 1/64) drowns in the
      first attention layer's output (rms 0.22, nearly the same vector
      on every row): the stream forgets which token a row holds, every
      row routes alike (0-6 of 16 local experts touched by 64 rows of
      varied tokens, a lottery of the seed) and greedy answers fall
      into cycles of 1-4 ids. With unit rows the token stays the
      largest single term of the stream;
    - top-k routing is discontinuous: where the 8th and 9th score lie
      closer than bfloat16's rounding of the stream, served path and
      float32 reference choose differently (2-5% of positions meet such
      a flip on a local expert, at any scale of the router). What one
      flipped expert then moves is its output beside the stream's norm:
      5.4% under 1 / sqrt(fan_in), where a stream of 7 layers is thin
      and an expert's down projection (fan-in 2,048) as loud as the
      dense layer's (16,384); 2.8% here. At 48 layers it is smaller
      still. The reference stays exact either way."""
    import jax
    import jax.numpy as jnp

    h, v = mc.hidden_size, mc.vocab_size
    nq, dk, dv = mc.num_heads, mc.head_dim, mc.v_dim

    def w(k, shape, std=h ** -0.5):
        return (jax.random.normal(k, shape, jnp.float32)
                * std).astype(dtype)

    def stack(k, kind, routed, count):
        ak = mc.attn_kinds[kind]
        nkv = ak.num_kv_heads
        shapes = {
            "wq": (h, nq * dk), "wk": (h, nkv * dk),
            "wv": (h, nkv * dv), "wo": (nq * dv, h),
        }
        if routed:
            e, f = mc.local_experts, mc.moe_intermediate_size
            shapes |= {
                "router": (h, mc.router_experts),
                "w_gate": (e, h, f), "w_up": (e, h, f),
                "w_down": (e, f, h),
            }
        else:
            i = mc.intermediate_size
            shapes |= {"w_gate": (h, i), "w_up": (h, i),
                       "w_down": (i, h)}

        def one_layer(k):
            ks = jax.random.split(k, len(shapes) + 2)
            lp = {n: w(ks[j], s) for j, (n, s) in
                  enumerate(sorted(shapes.items()))}
            lp["attn_norm"] = jnp.ones((h,), dtype)
            lp["mlp_norm"] = jnp.ones((h,), dtype)
            if ak.sink:
                lp["sink"] = jax.random.normal(ks[-1], (nq,), jnp.float32)
            if routed and mc.router_bias:
                lp["router_bias"] = 0.1 * jax.random.normal(
                    ks[-2], (mc.router_experts,), jnp.float32)
            return lp

        return jax.lax.map(one_layer, jax.random.split(k, count))

    segs = _segments(mc)
    k_embed, k_head, *k_segs = jax.random.split(key, 2 + len(segs))
    params = {
        "embed": w(k_embed, (v, h), 1.0),
        "segments": [stack(k, *seg) for k, seg in zip(k_segs, segs)],
        "final_norm": jnp.ones((h,), dtype),
    }
    if not mc.tie_word_embeddings:
        params["lm_head"] = w(k_head, (h, v))
    return params


# -- 3. the plain reference -------------------------------------------------
def forward_logprobs(cfg, params, token_ids, rows):
    """log-softmax over the vocabulary at `rows` of a full forward pass
    over `token_ids` (t,). Everything float32.

    A copy of `tests/reference_model.py::mimo_v2_forward` (the original
    stays where the program's own tests use it): no kernel, no cache, no
    batching, dense masks. Departures from a textbook loop, all to fit
    beside the serving cache: each run of alike layers is walked by
    `lax.scan` over its stacked weights with the layer's bf16 weights
    upcast inside the step; the experts are upcast and applied ONE AT A
    TIME (a whole expert layer in float32 is 1.6 GB); attention runs over
    blocks of QUERY_BLOCK query rows against all keys (a dense mask per
    block: 8k x 8k scores of 64 heads do not fit); the lm_head is applied
    to the asked rows only, in vocabulary slices."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    t = token_ids.shape[0]
    nq, dk, dv = cfg.num_heads, cfg.head_dim, cfg.v_dim
    rot, half = cfg.rope_dim, cfg.rope_dim // 2
    eps = cfg.rms_norm_eps
    qb = min(t, QUERY_BLOCK)
    n_blocks = -(-t // qb)
    pos = jnp.arange(t)

    def rms(x, w):
        n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return n * w.astype(f32)

    def rope(x, theta):
        inv = 1.0 / (theta ** (jnp.arange(half, dtype=f32) * 2.0 / rot))
        fr = pos.astype(f32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(fr)[:, None, :], jnp.sin(fr)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:rot]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)

    def attend(q, k, v, ak, sink):
        """q (t, nq, dk) over k, v (t, nkv, .), by blocks of query rows."""
        nkv = ak.num_kv_heads
        g = nq // nkv
        pad = n_blocks * qb - t
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            n_blocks, qb, nkv, g, dk)
        starts = jnp.arange(n_blocks) * qb

        def block(args):
            qblk, start = args
            qpos = start + jnp.arange(qb)
            mask = pos[None, :] <= qpos[:, None]
            if ak.window:
                mask &= pos[None, :] > qpos[:, None] - ak.window
            s = jnp.einsum("tkgd,skd->tkgs", qblk, k) * dk ** -0.5
            s = jnp.where(mask[:, None, None, :], s, -1e30)
            m = jnp.max(s, -1, keepdims=True)
            if sink is not None:
                m = jnp.maximum(m, sink.reshape(1, nkv, g, 1))
            e = jnp.exp(s - m)
            den = jnp.sum(e, -1, keepdims=True)
            if sink is not None:
                den = den + jnp.exp(sink.reshape(1, nkv, g, 1) - m)
            return jnp.einsum("tkgs,skd->tkgd", e / den, v)

        out = jax.lax.map(block, (qp, starts))
        return out.reshape(n_blocks * qb, nq * dv)[:t]

    def routed(x, lp):
        logits = jnp.dot(x, lp["router"].astype(f32),
                         precision=jax.lax.Precision.HIGHEST)
        sigma = (jax.nn.sigmoid(logits) if cfg.router_scoring == "sigmoid"
                 else jax.nn.softmax(logits, -1))
        sel = sigma + lp["router_bias"] if cfg.router_bias else sigma
        _, chosen = jax.lax.top_k(sel, cfg.num_experts_per_tok)
        w = jnp.take_along_axis(sigma, chosen, 1)
        if cfg.router_renorm:
            w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
        first = cfg.ep_rank * cfg.local_experts

        def expert(y, args):
            e, wg, wu, wd = args
            w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
            out = (jax.nn.silu(x @ wg.astype(f32)) * (x @ wu.astype(f32))
                   ) @ wd.astype(f32)
            return y + w_e[:, None] * out, None

        y, _ = jax.lax.scan(
            expert, jnp.zeros_like(x),
            (jnp.arange(cfg.local_experts), lp["w_gate"], lp["w_up"],
             lp["w_down"]))
        return y

    h = params["embed"][token_ids].astype(f32)
    for stack, (kind, is_routed, _) in zip(params["segments"],
                                           _segments(cfg)):
        ak = cfg.attn_kinds[kind]
        nkv = ak.num_kv_heads

        def layer(h, lp, ak=ak, nkv=nkv, is_routed=is_routed):
            x = rms(h, lp["attn_norm"])
            q = rope((x @ lp["wq"].astype(f32)).reshape(t, nq, dk),
                     ak.rope_theta)
            k = rope((x @ lp["wk"].astype(f32)).reshape(t, nkv, dk),
                     ak.rope_theta)
            v = cfg.v_scale * (x @ lp["wv"].astype(f32)).reshape(
                t, nkv, dv)
            o = attend(q, k, v, ak, lp["sink"] if ak.sink else None)
            h = h + o @ lp["wo"].astype(f32)
            x = rms(h, lp["mlp_norm"])
            if is_routed:
                return h + routed(x, lp), None
            return h + (jax.nn.silu(x @ lp["w_gate"].astype(f32))
                        * (x @ lp["w_up"].astype(f32))
                        ) @ lp["w_down"].astype(f32), None

        h, _ = jax.lax.scan(layer, h, stack)
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
def _kinds(hf: dict) -> dict[str, dict]:
    """Per attention kind: its layers here and its kv heads."""
    pattern = hf["hybrid_layer_pattern"]
    return {
        "full": {"layers": pattern.count(0),
                 "nkv": hf["num_key_value_heads"]},
        "window": {"layers": pattern.count(1),
                   "nkv": hf["swa_num_key_value_heads"]},
    }


def attention_params(hf: dict, kind: str) -> int:
    """q, k, v, o of one layer of `kind`, and its sinks."""
    h, nq = hf["hidden_size"], hf["num_attention_heads"]
    dk, dv = hf["head_dim"], hf["v_head_dim"]
    nkv = _kinds(hf)[kind]["nkv"]
    sink_key = ("add_swa_attention_sink_bias" if kind == "window"
                else "add_full_attention_sink_bias")
    return (h * nq * dk + h * nkv * (dk + dv) + nq * dv * h
            + (nq if hf.get(sink_key) else 0))


def expert_params(hf: dict) -> int:
    """One expert: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of one expert's weights: what a step reads for each local
    expert that has at least one row."""
    return expert_params(hf) * bytes_per_param


def expert_flops_per_row(hf: dict) -> int:
    """Multiply-adds x 2 of one (row, expert) pair."""
    return 2 * expert_params(hf)


def layer_params(hf: dict, index: int) -> int:
    """Parameters HELD HERE of layer `index`: its attention kind's
    projections, the two norms, and either the dense MLP or the router
    (full width, with its selection bias) and the local experts."""
    h = hf["hidden_size"]
    kind = "window" if hf["hybrid_layer_pattern"][index] else "full"
    n = attention_params(hf, kind) + 2 * h
    if hf["moe_layer_freq"][index]:
        router = h * hf["router_experts"] + (
            hf["router_experts"] if hf.get("topk_method") == "noaux_tc"
            else 0)
        return n + router + hf["n_routed_experts"] * expert_params(hf)
    return n + 3 * h * hf["intermediate_size"]


def total_params(hf: dict) -> int:
    h, v = hf["hidden_size"], hf["vocab_size"]
    embed = v * h * (1 if hf.get("tie_word_embeddings") else 2)
    return sum(layer_params(hf, i)
               for i in range(hf["num_hidden_layers"])) + embed + h


def layer_stack_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of EVERY weight the layer stack holds here (all 16 local
    experts of each routed layer, whether a step reads them or not);
    neither embedding nor lm_head. A decode step reads the experts that
    have rows, so `weight_stream_share` over this would overstate the
    bytes: this family's cell reports `moe_expert_roofline_share` over
    the counters instead."""
    return sum(layer_params(hf, i)
               for i in range(hf["num_hidden_layers"])) * bytes_per_param


def kv_bytes_per_token_by_kind(hf: dict, bytes_per_elem: int = 2) -> dict:
    """KV bytes one context token takes in ALL the layers of each kind,
    at the width the chip's cache stores (K at K_STORE_LANES)."""
    dv = hf["v_head_dim"]
    return {kind: k["layers"] * k["nkv"] * (K_STORE_LANES + dv)
            * bytes_per_elem for kind, k in _kinds(hf).items()}


def kv_bytes_per_token(hf: dict, bytes_per_elem: int = 2) -> int:
    """The FULL layers' bytes a token: what every context token costs
    for as long as its sequence lives. The window layers' bytes are a
    constant a sequence (window x their bytes) and are counted by kind
    (`kv_bytes_per_token_by_kind`; `manifest.py` says why one constant
    cannot hold both)."""
    return kv_bytes_per_token_by_kind(hf, bytes_per_elem)["full"]


# -- 5. the rehearsal's shape ----------------------------------------------
def rehearsal_config(mc, tp: int):
    """A rehearsal checks control flow on the CPU, not speed: the tiny
    layer-group widths, which keep every code path of the family."""
    from production_stack_tpu.models import config as mcfg

    return dataclasses.replace(
        mcfg.TINY_GROUPS_DEBUG, name=mc.name,
        rms_norm_eps=mc.rms_norm_eps, max_model_len=mc.max_model_len,
    )


# -- 6. the guard -----------------------------------------------------------
def check(config: dict, mc) -> None:
    """Refuse where the file and the program's ModelConfig disagree on
    what the counts and the reference rest on."""
    kinds = _kinds(config)
    want = {
        "hidden_size": (mc.hidden_size, config["hidden_size"]),
        "dense width": (mc.intermediate_size, config["intermediate_size"]),
        "expert width": (mc.moe_intermediate_size,
                         config["moe_intermediate_size"]),
        "layers": (mc.num_layers, config["num_hidden_layers"]),
        "q heads": (mc.num_heads, config["num_attention_heads"]),
        "qk head dim": (mc.head_dim, config["head_dim"]),
        "v head dim": (mc.v_dim, config["v_head_dim"]),
        "vocabulary": (mc.vocab_size, config["vocab_size"]),
        "router width": (mc.router_experts, config["router_experts"]),
        "experts held": (mc.local_experts if mc.router_experts else 0,
                         config["n_routed_experts"]),
        "experts a token": (mc.num_experts_per_tok,
                            config["num_experts_per_tok"]),
        "layer pattern": (list(mc.layer_kinds),
                          list(config["hybrid_layer_pattern"])),
        "dense layers": (mc.dense_layers,
                         config["moe_layer_freq"].index(1)),
        "kv heads": ([k.num_kv_heads for k in mc.attn_kinds],
                     [kinds["full"]["nkv"], kinds["window"]["nkv"]]),
        "windows": ([k.window for k in mc.attn_kinds],
                    [None, config["sliding_window"]]),
        "sinks": ([k.sink for k in mc.attn_kinds],
                  [bool(config["add_full_attention_sink_bias"]),
                   bool(config["add_swa_attention_sink_bias"])]),
        "rotary dims": (mc.rope_dim, int(
            config["head_dim"] * config["partial_rotary_factor"]) // 2 * 2),
        "v scale": (mc.v_scale, config["attention_value_scale"]),
    }
    wrong = {k: v for k, v in want.items() if v[0] != v[1]}
    if wrong:
        raise SystemExit(
            "the program's ModelConfig and the configuration's file "
            "disagree (program, file): " + ", ".join(
                f"{k} {a!r} != {b!r}" for k, (a, b) in wrong.items())
            + ": the mimo_v2 family would count and check other weights "
            "than are served")
    if not mc.layer_groups or mc.qkv_bias or mc.tie_word_embeddings:
        raise SystemExit(
            "the mimo_v2 family covers a stack of layer groups without "
            "biases and with an untied head; the program's ModelConfig "
            f"has layer_groups={mc.layer_groups}, qkv_bias={mc.qkv_bias}, "
            f"tie_word_embeddings={mc.tie_word_embeddings}")
