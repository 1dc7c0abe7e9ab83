"""The laguna family: poolside's Laguna-XS.2 (33.4B-A3B) decoder.

`model_type: laguna` (source: the published config.json). The only file
of the benchmark that knows this parameter tree and these equations;
`manifest.py` says what a family file gives and how it is found. THE
EQUATIONS, layer l, with t(l) = `layer_types[l]` (full_attention or
sliding_attention) and H(l) = `num_attention_heads_per_layer[l]` (48 on
full layers, 64 on window layers); 8 kv heads, head width 128, no
biases, RMSNorm eps `rms_norm_eps`, pre-norm residual blocks, untied
head:

1. x = RMSNorm(h); q = x W_q as H(l) heads, k = x W_k and v = x W_v as
   8 heads.
2. Rotary by layer type (`rope_parameters[t]`), half-split. Window
   layers: theta 1e4, all 128 dims of q and k, plain. Full layers: theta
   5e5, the first 64 dims of each head (`partial_rotary_factor` 0.5;
   dims 64-127 unrotated), YaRN over those 64 dims: `factor` 64,
   `original_max_position_embeddings` 4,096, `beta_fast` 64, `beta_slow`
   1, the inverse frequencies blended between interpolation (/ factor)
   and extrapolation by the linear ramp between the two correction dims,
   and `attention_factor` 1.41589 (= 0.1 ln 64 + 1) MULTIPLYING COS AND
   SIN: only the rotated half of a dot product carries its square. (Not
   the softmax scale times mscale squared, which is DeepSeek's reading
   and the xing4 family's.)
3. o_i = softmax_j(q_i . k_j / sqrt(128)) v_j, causal; window layers
   over keys i - 512 < j <= i (`sliding_window`), no sink; GQA groups of
   H(l) / 8.
4. Output gate (`gating: true`): g = sigmoid(x W_g), W_g 2,048 x H(l),
   one scalar a head and row from the NORMED layer input; o_head <-
   g_head o_head; then h <- h + concat(o) W_o.
5. x = RMSNorm(h). Layer 0 (`mlp_layer_types[0]` = dense): SwiGLU of
   width 8,192. Layers >= 1: p = softmax(x W_r) over 256 experts in
   float32; the 8 largest; weights p_e / sum_chosen p * 2.5
   (`moe_routed_scaling_factor`, on the experts' output:
   `moe_apply_router_weight_on_input` false); y = sum_e w_e SwiGLU_e(x)
   (width 512) + SwiGLU_shared(x) (width 512, ungated); h <- h + y.
6. Final RMSNorm, head over 100,352 ids.

ASSUMED (no key of the config says; the configuration's file lists each
under `assumed` with its ground): (a) the gate's activation (sigmoid),
its input (the normed layer input) and its place (after attention,
before W_o): the head-wise gate of arXiv:2505.06708, which the key's two
spellings (`true` here, "per-head" in the sibling Laguna-S-2.1) name;
(b) softmax scoring with renormalised top-8: the config's keys are the
Qwen-MoE family's and it has none of `scoring_func`, `topk_method`,
`n_group`; (c) SiLU, no q/k norm, no gate on the shared expert;
half-split rotary, as the program has it (a fixed permutation of weight
columns away from an interleaved form; the weights are random).

Imports jax inside its functions only: `run.py` loads a family for its
counts and imports no jax.
"""

from __future__ import annotations

import dataclasses

VOCAB_SLICES = 8
QUERY_BLOCK = 512
TYPES = ("full_attention", "sliding_attention")   # kind 0, kind 1
# what the seeded tree scales the routed experts' down projections by;
# `init_params` says why
EXPERT_DOWN_GAIN = 0.1


# -- 1. the config.json the program reads ----------------------------------
def hf_config(config: dict) -> dict:
    """The published keys as they are (the harness takes its own out)."""
    return dict(config)


def _segments(mc):
    """(kind, routed, count) runs of alike layers, as the program's tree
    stacks them (`ModelConfig.segments`)."""
    return [(k, r, c) for k, r, c, _ in mc.segments()]


# -- 2. the weights ---------------------------------------------------------
def init_params(mc, key, dtype):
    """All weights from the key, one run of alike layers at a time and
    layer by layer inside it; the output gate, the router and the shared
    expert are random like every matrix, so that a dropped one shows in
    the reference check.

    THE SCALES. As `families/mimo_v2.py` scales them, and for its
    reasons: every matrix has entries of ONE standard deviation, hidden
    ** -0.5, and the embedding's rows unit variance an entry, so that a
    row's token stays the largest single term of its stream and shows in
    its routing. The gate's logits are then of unit size and a head's
    gate lies in 0.27-0.73 for two rows of three: a gate dropped (1) or
    misplaced moves every head's output by half. One thing is this
    family's, as it is xing4's: the ROUTED experts' down projections
    carry EXPERT_DOWN_GAIN (the shared expert, which every row passes
    and no routing decides, keeps the common deviation). Top-k routing
    is discontinuous: where the 8th and 9th score lie closer than
    bfloat16's rounding of the stream, served path and float32
    reference choose differently, at any scale of the router. Here all
    256 experts are held, so EVERY such flip is seen (MiMo's rank sees
    one in 16), the scores of 256 experts lie twice as close at the
    cut as xing4's 64, and a chosen expert weighs 0.2-0.5 (top-8
    renormalised, times 2.5) where MiMo's weighs 0.125. Reckoned from
    xing4's CPU emulation at published widths (PERF.md, Findings PR 33:
    gain 0.1, 726 windows, worst position 0.048 of 0.1, worst mean
    0.0122 of 0.03): about 1.3 times its flips, each 0.6 of its size;
    what the chip read is in PERF.md, Findings PR 43."""
    import jax
    import jax.numpy as jnp

    h, v = mc.hidden_size, mc.vocab_size
    dk, dv = mc.head_dim, mc.v_dim

    def w(k, shape, std=h ** -0.5):
        return (jax.random.normal(k, shape, jnp.float32)
                * std).astype(dtype)

    def stack(k, kind, routed, count):
        ak = mc.kinds[kind]
        nq, nkv = ak.num_heads, ak.num_kv_heads
        shapes = {
            "wq": (h, nq * dk), "wk": (h, nkv * dk),
            "wv": (h, nkv * dv), "wo": (nq * dv, h),
        }
        if mc.head_gate:
            shapes["w_head_gate"] = (h, nq)
        if routed:
            e, f = mc.local_experts, mc.moe_intermediate_size
            fs = f * mc.shared_experts
            shapes |= {
                "router": (h, mc.router_experts),
                "w_gate": (e, h, f), "w_up": (e, h, f),
                "w_down": (e, f, h),
            }
            if fs:
                shapes |= {"ws_gate": (h, fs), "ws_up": (h, fs),
                           "ws_down": (fs, h)}
        else:
            i = mc.intermediate_size
            shapes |= {"w_gate": (h, i), "w_up": (h, i),
                       "w_down": (i, h)}
        down = ("w_down",) if routed else ()

        def one_layer(k):
            ks = jax.random.split(k, len(shapes))
            lp = {name: w(ks[j], s, h ** -0.5 * (
                EXPERT_DOWN_GAIN if name in down else 1.0))
                for j, (name, s) in enumerate(sorted(shapes.items()))}
            lp["attn_norm"] = jnp.ones((h,), dtype)
            lp["mlp_norm"] = jnp.ones((h,), dtype)
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
def _inv_freq(rot: int, theta: float, yarn):
    """The rotary frequencies of `rot` dims: plain, or YaRN's blend as
    the public implementation of `rope_type: yarn` computes it."""
    import math

    import jax.numpy as jnp

    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    if yarn is None:
        return inv

    def correction_dim(rotations):
        return rot * math.log(yarn.original_max_position / (
            rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), rot - 1)
    # 0 where a dim keeps its frequency (extrapolation), 1 where it
    # takes it divided by the factor (interpolation)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return inv / yarn.factor * ramp + inv * (1.0 - ramp)


def forward_logprobs(cfg, params, token_ids, rows):
    """log-softmax over the vocabulary at `rows` of a full forward pass
    over `token_ids` (t,). Everything float32: no kernel, no cache, no
    batching, dense masks. Departures from a textbook loop, all to fit
    beside the serving cache: each run of alike layers is walked by
    `lax.scan` over its stacked weights with the layer's bf16 weights
    upcast inside the step; the experts are upcast and applied ONE AT A
    TIME (a whole expert layer in float32 is 3.2 GB); attention runs
    over blocks of QUERY_BLOCK query rows against all keys; the head is
    applied to the asked rows only, in vocabulary slices."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    t = token_ids.shape[0]
    dk, dv = cfg.head_dim, cfg.v_dim
    eps = cfg.rms_norm_eps
    qb = min(t, QUERY_BLOCK)
    n_blocks = -(-t // qb)
    pos = jnp.arange(t)

    def rms(x, w):
        n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return n * w.astype(f32)

    def rope(x, ak):
        """The first `rotary_dim` dims of every head of x (t, heads, dk)
        rotated half-split at the kind's frequencies, cos and sin times
        the kind's factor; the rest as they are."""
        rot = ak.rotary_dim
        fr = pos.astype(f32)[:, None] * _inv_freq(
            rot, ak.rope_theta, ak.rope_yarn)[None, :]
        factor = 1.0 if ak.rope_factor is None else ak.rope_factor
        cos = (jnp.cos(fr) * factor)[:, None, :]
        sin = (jnp.sin(fr) * factor)[:, None, :]
        x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)

    def attend(q, k, v, ak):
        """q (t, nq, dk) over k, v (t, nkv, .), by blocks of query rows."""
        nq, nkv = ak.num_heads, ak.num_kv_heads
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
            return jnp.einsum("tkgs,skd->tkgd", jax.nn.softmax(s, -1), v)

        out = jax.lax.map(block, (qp, starts))
        return out.reshape(n_blocks * qb, nq, dv)[:t]

    def swiglu(x, g, u, d):
        return (jax.nn.silu(x @ g.astype(f32)) * (x @ u.astype(f32))
                ) @ d.astype(f32)

    def routed(x, lp):
        logits = jnp.dot(x, lp["router"].astype(f32),
                         precision=jax.lax.Precision.HIGHEST)
        p = jax.nn.softmax(logits, -1)
        _, chosen = jax.lax.top_k(p, cfg.num_experts_per_tok)
        w = jnp.take_along_axis(p, chosen, 1)
        if cfg.router_renorm:
            w = w / jnp.sum(w, -1, keepdims=True)
        w = w * cfg.routed_scaling

        def expert(acc, args):
            e, wg, wu, wd = args
            w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)
            return acc + w_e[:, None] * swiglu(x, wg, wu, wd), None

        out, _ = jax.lax.scan(
            expert, jnp.zeros_like(x),
            (jnp.arange(cfg.router_experts), lp["w_gate"], lp["w_up"],
             lp["w_down"]))
        if cfg.shared_experts:
            out = out + swiglu(x, lp["ws_gate"], lp["ws_up"],
                               lp["ws_down"])
        return out

    h = params["embed"][token_ids].astype(f32)
    for stack, (kind, is_routed, _) in zip(params["segments"],
                                           _segments(cfg)):
        ak = cfg.kinds[kind]

        def layer(h, lp, ak=ak, is_routed=is_routed):
            nq, nkv = ak.num_heads, ak.num_kv_heads
            x = rms(h, lp["attn_norm"])
            q = rope((x @ lp["wq"].astype(f32)).reshape(t, nq, dk), ak)
            k = rope((x @ lp["wk"].astype(f32)).reshape(t, nkv, dk), ak)
            v = (x @ lp["wv"].astype(f32)).reshape(t, nkv, dv)
            o = attend(q, k, v, ak)
            if cfg.head_gate:
                gate = jax.nn.sigmoid(x @ lp["w_head_gate"].astype(f32))
                o = o * gate[:, :, None]
            h = h + o.reshape(t, nq * dv) @ lp["wo"].astype(f32)
            x = rms(h, lp["mlp_norm"])
            if is_routed:
                return h + routed(x, lp), None
            return h + swiglu(x, lp["w_gate"], lp["w_up"],
                              lp["w_down"]), None

        h, _ = jax.lax.scan(layer, h, stack)
    h = rms(h, params["final_norm"])[rows]
    lm = (params["embed"].T if cfg.tie_word_embeddings
          else params["lm_head"])
    vocab = lm.shape[1]
    step = -(-vocab // VOCAB_SLICES)
    logits = jnp.concatenate([
        h @ lm[:, i:i + step].astype(f32) for i in range(0, vocab, step)
    ], -1)
    return jax.nn.log_softmax(logits, -1)


# -- 4. the counts: bytes and operations a step needs ----------------------
# Kept with the benchmark so that no PR that claims a gain can change how
# a share of a peak is counted. Inputs are a configuration file's dict.
def _kinds(hf: dict) -> dict[str, dict]:
    """Per attention kind: its layers here and its query heads."""
    out = {}
    for name, t in zip(("full", "window"), TYPES):
        at = [i for i, lt in enumerate(hf["layer_types"]) if lt == t]
        out[name] = {
            "layers": len(at),
            "nq": (hf["num_attention_heads_per_layer"][at[0]] if at
                   else hf["num_attention_heads"]),
        }
    return out


def attention_params(hf: dict, kind: str) -> int:
    """q, k, v, o of one layer of `kind`, and its output gate."""
    h, d = hf["hidden_size"], hf["head_dim"]
    nq, nkv = _kinds(hf)[kind]["nq"], hf["num_key_value_heads"]
    return (h * nq * d + 2 * h * nkv * d + nq * d * h
            + (h * nq if hf.get("gating") else 0))


def expert_params(hf: dict) -> int:
    """One expert: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of one expert's weights: what a step reads for each routed
    expert that has at least one row."""
    return expert_params(hf) * bytes_per_param


def expert_flops_per_row(hf: dict) -> int:
    """Multiply-adds x 2 of one (row, expert) pair."""
    return 2 * expert_params(hf)


def layer_params(hf: dict, index: int) -> int:
    """Parameters of layer `index`: its attention kind's projections and
    gate, the two norms, and either the dense MLP or the router, the
    routed experts and the shared one."""
    h = hf["hidden_size"]
    kind = ("window" if hf["layer_types"][index] == TYPES[1] else "full")
    n = attention_params(hf, kind) + 2 * h
    if hf["mlp_layer_types"][index] == "dense":
        return n + 3 * h * hf["intermediate_size"]
    return (n + h * hf["num_experts"]
            + hf["num_experts"] * expert_params(hf)
            + 3 * h * hf["shared_expert_intermediate_size"])


def total_params(hf: dict) -> int:
    h, v = hf["hidden_size"], hf["vocab_size"]
    embed = v * h * (1 if hf.get("tie_word_embeddings") else 2)
    return sum(layer_params(hf, i)
               for i in range(hf["num_hidden_layers"])) + embed + h


def layer_stack_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of EVERY weight the layer stack holds (all 256 experts of
    each routed layer, whether a step reads them or not); neither
    embedding nor lm_head. A decode step reads the experts that have
    rows, so a share of these bytes would overstate a step's."""
    return sum(layer_params(hf, i)
               for i in range(hf["num_hidden_layers"])) * bytes_per_param


def kv_bytes_per_token_by_kind(hf: dict, bytes_per_elem: int = 2) -> dict:
    """KV bytes one context token takes in ALL the layers of each kind
    (K and V at 128 lanes each: stored as they are)."""
    per_layer = 2 * hf["num_key_value_heads"] * hf["head_dim"]
    return {kind: k["layers"] * per_layer * bytes_per_elem
            for kind, k in _kinds(hf).items()}


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
    widths of this family's shape, which keep every code path of it."""
    from production_stack_tpu.models import config as mcfg

    return dataclasses.replace(
        mcfg.TINY_LAGUNA_DEBUG, name=mc.name,
        rms_norm_eps=mc.rms_norm_eps, max_model_len=mc.max_model_len,
    )


# -- 6. the guard -----------------------------------------------------------
def check(config: dict, mc) -> None:
    """Refuse where the file and the program's ModelConfig disagree on
    what the counts and the reference rest on, and where the file is not
    what this family's `assumed` covers."""
    import math

    kinds = _kinds(config)
    rp = config["rope_parameters"]
    d = config["head_dim"]

    def rotary(t):
        r = int(d * rp[t].get("partial_rotary_factor", 1.0))
        return r - r % 2

    def yarn(t):
        p = rp[t]
        if p.get("rope_type", "default") != "yarn":
            return None, None
        return ((float(p["factor"]),
                 p["original_max_position_embeddings"],
                 float(p["beta_fast"]), float(p["beta_slow"])),
                float(p.get("attention_factor")
                      or 0.1 * math.log(p["factor"]) + 1.0))

    def of_mc(ak):
        y = ak.rope_yarn
        return (y and (y.factor, y.original_max_position, y.beta_fast,
                       y.beta_slow), ak.rope_factor)

    present = [t for t in TYPES if t in config["layer_types"]]
    mk = mc.kinds
    want = {
        "hidden_size": (mc.hidden_size, config["hidden_size"]),
        "dense width": (mc.intermediate_size, config["intermediate_size"]),
        "expert width": (mc.moe_intermediate_size,
                         config["moe_intermediate_size"]),
        "layers": (mc.num_layers, config["num_hidden_layers"]),
        "head dim": ((mc.head_dim, mc.v_dim), (d, d)),
        "vocabulary": (mc.vocab_size, config["vocab_size"]),
        "layer pattern": ([present[k] for k in mc.layer_kinds],
                          list(config["layer_types"])),
        "q heads": ([k.num_heads for k in mk],
                    [kinds[n]["nq"] for n, t in zip(("full", "window"),
                                                    TYPES) if t in present]),
        "kv heads": ([k.num_kv_heads for k in mk],
                     [config["num_key_value_heads"]] * len(present)),
        "windows": ([k.window for k in mk],
                    [config["sliding_window"] if t == TYPES[1] else None
                     for t in present]),
        "rope theta": ([k.rope_theta for k in mk],
                       [float(rp[t]["rope_theta"]) for t in present]),
        "rotary dims": ([k.rotary_dim for k in mk],
                        [rotary(t) for t in present]),
        "yarn": ([of_mc(k) for k in mk], [yarn(t) for t in present]),
        "output gate": (mc.head_gate, config.get("gating") in (
            True, "per-head")),
        "router width": (mc.router_experts, config["num_experts"]),
        "experts held": (mc.local_experts if mc.router_experts else 0,
                         config["num_experts"]),
        "experts a token": (mc.num_experts_per_tok,
                            config["num_experts_per_tok"]),
        "shared experts": (
            mc.shared_experts * mc.moe_intermediate_size,
            config["shared_expert_intermediate_size"]),
        "scaling factor": (mc.routed_scaling,
                           float(config["moe_routed_scaling_factor"])),
        "routing": ((mc.router_scoring, mc.router_bias, mc.router_renorm),
                    ("softmax", False, True)),
        "dense layers": (mc.dense_layers,
                         config["mlp_layer_types"].index("sparse")),
    }
    wrong = {k: v for k, v in want.items() if v[0] != v[1]}
    if wrong:
        raise SystemExit(
            "the program's ModelConfig and the configuration's file "
            "disagree (program, file): " + ", ".join(
                f"{k} {a!r} != {b!r}" for k, (a, b) in wrong.items())
            + ": the laguna family would count and check other weights "
            "than are served")
    if (not mc.layer_groups or mc.qkv_bias or mc.tie_word_embeddings
            or any(k.sink or k.latent_dim for k in mk)
            or mc.hc_mult != 1 or mc.v_scale != 1.0):
        raise SystemExit(
            "the laguna family covers a stack of layer groups without "
            "biases, sinks, latent kinds or residual streams and with an "
            "untied head; the program's ModelConfig has layer_groups="
            f"{mc.layer_groups}, qkv_bias={mc.qkv_bias}, "
            f"tie_word_embeddings={mc.tie_word_embeddings}, kinds={mk}, "
            f"hc_mult={mc.hc_mult}, v_scale={mc.v_scale}")
