"""The nemotron_h family: NVIDIA's Nemotron-3-Super (120B-A12B) decoder.

`model_type: nemotron_h` (source: the published config.json). The only
file of the benchmark that knows this parameter tree and these
equations; `manifest.py` says what a family file gives and how it is
found. THE EQUATIONS. Every block is x <- x + F(RMSNorm_w(x, eps
`layer_norm_epsilon`)) with ONE F, chosen by the block's letter in
`hybrid_override_pattern`; after the last block a final RMSNorm and an
untied head.

- "M", Mamba-2 (`mamba_num_heads` H, `mamba_head_dim` P, d_inner = H P,
  `n_groups` G, `ssm_state_size` N, `conv_kernel` K, C = d_inner + 2 G
  N): [z | xBC | dt] = u W_in (no bias); xBC <- silu(conv(xBC) + b),
  conv_t = sum_k w[k] xBC[t - (K - 1) + k], depthwise and causal (rows
  before the sequence are zero); xBC = [x (H, P) | B (G, N) | C (G, N)],
  head h using group h // (H / G); d = softplus(dt + dt_bias), A =
  -exp(A_log), a scalar a head; S_t = exp(d_t A) S_{t-1} + d_t x_t (x)
  B_t with S (H, P, N), S_{-1} = 0; y_t = S_t C_t + D x_t; y <-
  RMSNorm_w(y silu(z)), the statistics over each of the G groups of
  d_inner / G lanes separately; F = y W_out.
- "*", attention: GQA, `num_attention_heads` query heads over
  `num_key_value_heads` kv heads of `head_dim`, no biases, causal,
  scale head_dim ** -0.5, NO positional encoding.
- "E", routed experts at a latent width: s = sigmoid(u W_r) over
  `router_experts` outputs in float32; the `num_experts_per_tok` largest
  of s + e_score_correction_bias; weights s_e / sum_chosen s x
  `routed_scaling_factor`; v = u W_a (hidden -> `moe_latent_size`); an
  expert is relu(v W1_e)^2 W2_e (no gate matrix); F = (sum_e w_e
  expert_e(v)) W_b + relu(u W1_s)^2 W2_s, the shared expert of
  `moe_shared_expert_intermediate_size` on the full hidden state. Of the
  router's experts this rank holds `n_routed_experts` (the slice of
  `ep_rank` of `ep_size`); what the others would add is left out here as
  in the program.

ASSUMED (the configuration's file lists each under `assumed`): no
positional encoding in attention although `rope_theta` and
`partial_rotary_factor` stand in the file (the Nemotron-H reports' form:
the state-space layers carry order); the router reads u and not v (the
LatentMoE description's form); the MTP module is left out.

Imports jax inside its functions only: `run.py` loads a family for its
counts and imports no jax.
"""

from __future__ import annotations

import dataclasses

VOCAB_SLICES = 4
QUERY_BLOCK = 512
OWN_KEYS = ("router_experts",)
# what the seeded tree scales the routed experts' down projections by;
# `init_params` says why
EXPERT_DOWN_GAIN = 0.1


# -- 1. the config.json the program reads ----------------------------------
def hf_config(config: dict) -> dict:
    """The published keys, with `n_routed_experts` back at the router's
    width: the program is told the experts it holds by ep_size/ep_rank."""
    out = {k: v for k, v in config.items() if k not in OWN_KEYS}
    out["n_routed_experts"] = config["router_experts"]
    return out


# -- 2. the weights ---------------------------------------------------------
def _block_shapes(mc, letter: str) -> dict:
    h = mc.hidden_size
    if letter == "M":
        return {"w_in": (h, mc.ssm_inner + mc.ssm_conv_dim + mc.ssm_heads),
                "conv_w": (mc.ssm_conv, mc.ssm_conv_dim),
                "conv_b": (mc.ssm_conv_dim,),
                "w_out": (mc.ssm_inner, h)}
    if letter == "*":
        ak = mc.kinds[0]
        return {"wq": (h, ak.num_heads * mc.head_dim),
                "wk": (h, ak.num_kv_heads * mc.head_dim),
                "wv": (h, ak.num_kv_heads * mc.v_dim),
                "wo": (ak.num_heads * mc.v_dim, h)}
    e, f, lat = mc.local_experts, mc.moe_intermediate_size, mc.expert_width
    fs = f * mc.shared_experts
    return {"router": (h, mc.router_experts),
            "w_lat_in": (h, lat), "w_lat_out": (lat, h),
            "w_up": (e, lat, f), "w_down": (e, f, lat),
            "ws_up": (h, fs), "ws_down": (fs, h)}


def init_params(mc, key, dtype):
    """All weights from the key, one unit of `mc.units()` at a time and
    layer by layer inside it.

    THE SCALES. Every matrix at ONE standard deviation, hidden ** -0.5,
    and the embedding's rows at unit variance an entry, as
    `families/mimo_v2.py` scales them and for its reasons (a row's token
    stays the largest single term of its stream). The mixer's own
    parameters as Mamba-2 initialises them: A_log = log U(1, 16),
    dt_bias the inverse softplus of a log-uniform step in [0.001, 0.1]
    (`time_step_min` / `time_step_max`), D ones, norms ones; the
    convolution's taps at K ** -0.5 and its bias at 0.1, the router's
    selection bias at 0.1. The ROUTED experts' down projections carry
    EXPERT_DOWN_GAIN, as xing4's and laguna's do: top-22 routing over
    512 scores is discontinuous, and where the 22nd and 23rd lie closer
    than bfloat16's rounding of the stream the served path and the
    float32 reference choose differently; the gain bounds what one such
    flip moves (PERF.md, Findings PR 45 has the table it was read
    from)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    h, v = mc.hidden_size, mc.vocab_size

    def w(k, shape, std):
        return (jax.random.normal(k, shape, f32) * std).astype(dtype)

    def stack(k, unit, count):
        def one_layer(k):
            out = []
            for letter, kl in zip(unit, jax.random.split(k, len(unit))):
                shapes = _block_shapes(mc, letter)
                ks = jax.random.split(kl, len(shapes) + 3)
                lp = {}
                for j, (name, s) in enumerate(sorted(shapes.items())):
                    std = h ** -0.5
                    if name == "conv_w":
                        std = mc.ssm_conv ** -0.5
                    elif name == "conv_b":
                        std = 0.1
                    elif name == "w_down":
                        std *= EXPERT_DOWN_GAIN
                    lp[name] = w(ks[j], s, std)
                lp["norm"] = jnp.ones((h,), dtype)
                if letter == "M":
                    nh = mc.ssm_heads
                    step = jnp.exp(jax.random.uniform(
                        ks[-1], (nh,), f32, jnp.log(1e-3), jnp.log(1e-1)))
                    lp |= {
                        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                        "A_log": jnp.log(jax.random.uniform(
                            ks[-2], (nh,), f32, 1.0, 16.0)),
                        "D": jnp.ones((nh,), f32),
                        "ssm_norm": jnp.ones((mc.ssm_inner,), dtype),
                    }
                if letter == "E":
                    lp["router_bias"] = 0.1 * jax.random.normal(
                        ks[-3], (mc.router_experts,), f32)
                out.append(lp)
            return out

        return jax.lax.map(one_layer, jax.random.split(k, count))

    units = mc.units()
    k_embed, k_head, *k_units = jax.random.split(key, 2 + len(units))
    return {
        "embed": w(k_embed, (v, h), 1.0),
        "segments": [stack(k, unit, count)
                     for k, (unit, count, _, _) in zip(k_units, units)],
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": w(k_head, (h, v), h ** -0.5),
    }


# -- 3. the plain reference -------------------------------------------------
def forward_logprobs(cfg, params, token_ids, rows):
    """log-softmax over the vocabulary at `rows` of a full forward pass
    over `token_ids` (t,). Everything float32: no kernel, no cache, no
    chunking: the recurrence is a sequential `lax.scan` over the tokens.
    Departures from a textbook loop, all to fit beside the serving
    cache: each unit is walked by `lax.scan` over its stacked weights
    with a layer's bf16 weights upcast inside the step; the experts are
    upcast and applied ONE AT A TIME; attention runs over blocks of
    QUERY_BLOCK query rows against all keys; the head is applied to the
    asked rows only, in vocabulary slices."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    t = token_ids.shape[0]
    eps = cfg.rms_norm_eps
    pos = jnp.arange(t)

    def rms(x, w):
        n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return n * w.astype(f32)

    def relu2(x):
        return jnp.square(jax.nn.relu(x))

    def mamba(u, lp):
        nh, p = cfg.ssm_heads, cfg.ssm_head_dim
        g, n, k = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv
        d, c = nh * p, cfg.ssm_conv_dim
        zxd = u @ lp["w_in"].astype(f32)
        z, xbc, dt = zxd[:, :d], zxd[:, d:d + c], zxd[:, d + c:]
        padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
        cw = lp["conv_w"].astype(f32)
        xbc = jax.nn.silu(sum(padded[j:j + t] * cw[j] for j in range(k))
                          + lp["conv_b"].astype(f32))
        x = xbc[:, :d].reshape(t, nh, p)
        b = jnp.repeat(xbc[:, d:d + g * n].reshape(t, g, n), nh // g, 1)
        cm = jnp.repeat(xbc[:, d + g * n:].reshape(t, g, n), nh // g, 1)
        delta = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))
        a = -jnp.exp(lp["A_log"].astype(f32))

        def token(s, inp):
            x_t, b_t, c_t, d_t = inp
            s = (jnp.exp(d_t * a)[:, None, None] * s
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return s, jnp.sum(s * c_t[:, None, :], -1)

        _, y = jax.lax.scan(token, jnp.zeros((nh, p, n), f32),
                            (x, b, cm, delta))
        y = (y + lp["D"].astype(f32)[:, None] * x).reshape(t, d)
        y = (y * jax.nn.silu(z)).reshape(t, g, d // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return (y.reshape(t, d) * lp["ssm_norm"].astype(f32)
                ) @ lp["w_out"].astype(f32)

    def attention(u, lp):
        ak = cfg.kinds[0]
        nq, nkv, dk, dv = (ak.num_heads, ak.num_kv_heads, cfg.head_dim,
                           cfg.v_dim)
        grp = nq // nkv
        qb = min(t, QUERY_BLOCK)
        n_blocks = -(-t // qb)
        q = (u @ lp["wq"].astype(f32)).reshape(t, nkv, grp, dk)
        k = (u @ lp["wk"].astype(f32)).reshape(t, nkv, dk)
        v = (u @ lp["wv"].astype(f32)).reshape(t, nkv, dv)
        qp = jnp.pad(q, ((0, n_blocks * qb - t), (0, 0), (0, 0), (0, 0))
                     ).reshape(n_blocks, qb, nkv, grp, dk)

        def block(args):
            qblk, start = args
            mask = pos[None, :] <= (start + jnp.arange(qb))[:, None]
            s = jnp.einsum("tkgd,skd->tkgs", qblk, k) * dk ** -0.5
            s = jnp.where(mask[:, None, None, :], s, -1e30)
            return jnp.einsum("tkgs,skd->tkgd", jax.nn.softmax(s, -1), v)

        o = jax.lax.map(block, (qp, jnp.arange(n_blocks) * qb))
        return o.reshape(n_blocks * qb, nq * dv)[:t] @ lp["wo"].astype(f32)

    def experts(u, lp):
        logits = jnp.dot(u, lp["router"].astype(f32),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(s + lp["router_bias"].astype(f32),
                                  cfg.num_experts_per_tok)
        w = jnp.take_along_axis(s, chosen, 1)
        w = w / jnp.sum(w, -1, keepdims=True) * cfg.routed_scaling
        lat = u @ lp["w_lat_in"].astype(f32)
        first = cfg.ep_rank * cfg.local_experts

        def expert(acc, args):
            e, w1, w2 = args
            w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)
            return acc + w_e[:, None] * (
                relu2(lat @ w1.astype(f32)) @ w2.astype(f32)), None

        out, _ = jax.lax.scan(
            expert, jnp.zeros_like(lat),
            (first + jnp.arange(cfg.local_experts), lp["w_up"],
             lp["w_down"]))
        return (out @ lp["w_lat_out"].astype(f32)
                + relu2(u @ lp["ws_up"].astype(f32))
                @ lp["ws_down"].astype(f32))

    fns = {"M": mamba, "*": attention, "E": experts}
    h = params["embed"][token_ids].astype(f32)
    for blocks, (unit, _, _, _) in zip(params["segments"], cfg.units()):
        def layers(h, lps, unit=unit):
            for letter, lp in zip(unit, lps):
                h = h + fns[letter](rms(h, lp["norm"]), lp)
            return h, None

        h, _ = jax.lax.scan(layers, h, blocks)
    h = rms(h, params["final_norm"])[rows]
    lm = params["lm_head"]
    vocab = lm.shape[1]
    step = -(-vocab // VOCAB_SLICES)
    logits = jnp.concatenate([
        h @ lm[:, i:i + step].astype(f32) for i in range(0, vocab, step)
    ], -1)
    return jax.nn.log_softmax(logits, -1)


# -- 4. the counts: bytes and operations a step needs ----------------------
# Kept with the benchmark so that no PR that claims a gain can change how
# a share of a peak is counted. Inputs are a configuration file's dict.
def mamba_params(hf: dict) -> int:
    """One "M" block: W_in, the convolution and its bias, dt_bias, A_log,
    D, the gated norm, W_out (its input norm is counted by
    `layer_params`)."""
    h = hf["hidden_size"]
    nh, p = hf["mamba_num_heads"], hf["mamba_head_dim"]
    d = nh * p
    c = d + 2 * hf["n_groups"] * hf["ssm_state_size"]
    return (h * (d + c + nh) + (hf["conv_kernel"] + 1) * c + 3 * nh + d
            + d * h)


def attention_params(hf: dict) -> int:
    h, d = hf["hidden_size"], hf["head_dim"]
    nq, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    return 2 * h * nq * d + 2 * h * nkv * d


def expert_params(hf: dict) -> int:
    """One routed expert: up and down at the latent width."""
    return 2 * hf["moe_latent_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of one expert's weights: what a step reads for each routed
    expert that has at least one row."""
    return expert_params(hf) * bytes_per_param


def expert_flops_per_row(hf: dict) -> int:
    """Multiply-adds x 2 of one (row, expert) pair."""
    return 2 * expert_params(hf)


def layer_params(hf: dict, index: int) -> int:
    """Parameters of block `index` HELD HERE (the experts of this rank),
    its norm counted."""
    h = hf["hidden_size"]
    letter = hf["hybrid_override_pattern"][index]
    if letter == "M":
        return h + mamba_params(hf)
    if letter == "*":
        return h + attention_params(hf)
    router = hf.get("router_experts", hf["n_routed_experts"])
    return (h + h * router + router + 2 * h * hf["moe_latent_size"]
            + hf["n_routed_experts"] * expert_params(hf)
            + 2 * h * hf["moe_shared_expert_intermediate_size"]
            * hf["n_shared_experts"])


def total_params(hf: dict) -> int:
    h, v = hf["hidden_size"], hf["vocab_size"]
    return sum(layer_params(hf, i)
               for i in range(hf["num_hidden_layers"])) + 2 * v * h + h


def layer_stack_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of EVERY weight the layer stack holds here; neither
    embedding nor lm_head. A decode step reads the experts that have
    rows, so a share of these bytes would overstate a step's."""
    return sum(layer_params(hf, i)
               for i in range(hf["num_hidden_layers"])) * bytes_per_param


def kv_bytes_per_token(hf: dict, bytes_per_elem: int = 2) -> int:
    """K and V of the attention blocks a context token."""
    return (hf["hybrid_override_pattern"].count("*") * 2
            * hf["num_key_value_heads"] * hf["head_dim"] * bytes_per_elem)


def state_bytes_per_seq(hf: dict, conv_bytes_per_elem: int = 2) -> int:
    """Bytes of recurrent state a sequence holds in all the "M" blocks:
    S (H, P, N) in float32 and the convolution's K - 1 rows of C lanes.
    What a state slot takes, what a snapshot takes, and what a decode
    step reads and writes a lane."""
    nh, p, n = (hf["mamba_num_heads"], hf["mamba_head_dim"],
                hf["ssm_state_size"])
    c = nh * p + 2 * hf["n_groups"] * n
    return hf["hybrid_override_pattern"].count("M") * (
        nh * p * n * 4 + (hf["conv_kernel"] - 1) * c * conv_bytes_per_elem)


# -- 5. the rehearsal's shape ----------------------------------------------
def rehearsal_config(mc, tp: int):
    """A rehearsal checks control flow on the CPU, not speed: the tiny
    widths of this family's shape, which keep every code path of it (G >
    1 with several heads a group, 4 taps, a chunk shorter than the
    prompts, latent != hidden, relu squared, top-k > 1 under an ep_size
    > 1, the EM...* pattern)."""
    from production_stack_tpu.models import config as mcfg

    return dataclasses.replace(
        mcfg.TINY_NEMOTRON_DEBUG, name=mc.name,
        rms_norm_eps=mc.rms_norm_eps, max_model_len=mc.max_model_len,
    )


# -- 6. the guard -----------------------------------------------------------
def check(config: dict, mc) -> None:
    """Refuse where the file and the program's ModelConfig disagree on
    what the counts and the reference rest on."""
    ak = mc.kinds[0] if mc.attn_kinds else None
    want = {
        "hidden_size": (mc.hidden_size, config["hidden_size"]),
        "layers": (mc.num_layers, config["num_hidden_layers"]),
        "pattern": (mc.block_pattern, config["hybrid_override_pattern"]),
        "vocabulary": (mc.vocab_size, config["vocab_size"]),
        "heads": ((ak.num_heads, ak.num_kv_heads, mc.head_dim, mc.v_dim),
                  (config["num_attention_heads"],
                   config["num_key_value_heads"], config["head_dim"],
                   config["head_dim"])),
        "positional encoding": (mc.rope, False),
        "mixer": ((mc.ssm_heads, mc.ssm_head_dim, mc.ssm_groups,
                   mc.ssm_state, mc.ssm_conv),
                  (config["mamba_num_heads"], config["mamba_head_dim"],
                   config["n_groups"], config["ssm_state_size"],
                   config["conv_kernel"])),
        "expert widths": ((mc.moe_latent_size, mc.moe_intermediate_size,
                           mc.shared_experts * mc.moe_intermediate_size),
                          (config["moe_latent_size"],
                           config["moe_intermediate_size"],
                           config["moe_shared_expert_intermediate_size"]
                           * config["n_shared_experts"])),
        "router width": (mc.router_experts, config["router_experts"]),
        "experts held": (mc.local_experts, config["n_routed_experts"]),
        "rank": ((mc.ep_rank, mc.ep_size),
                 (config["ep_rank"], config["ep_size"])),
        "experts a token": (mc.num_experts_per_tok,
                            config["num_experts_per_tok"]),
        "scaling factor": (mc.routed_scaling,
                           float(config["routed_scaling_factor"])),
        "routing": ((mc.router_scoring, mc.router_bias, mc.router_renorm),
                    ("sigmoid", True, True)),
        "mlp": ((mc.hidden_act, mc.mlp_gated), ("relu2", False)),
        "parameters": (mc.num_params(), total_params(config)),
        "state a sequence": (mc.state_bytes_per_seq(),
                             state_bytes_per_seq(config)),
    }
    wrong = {k: v for k, v in want.items() if v[0] != v[1]}
    if wrong:
        raise SystemExit(
            "the program's ModelConfig and the configuration's file "
            "disagree (program, file): " + ", ".join(
                f"{k} {a!r} != {b!r}" for k, (a, b) in wrong.items())
            + ": the nemotron_h family would count and check other "
            "weights than are served")
    if mc.tie_word_embeddings or mc.qkv_bias or mc.hc_mult != 1:
        raise SystemExit(
            "the nemotron_h family covers a stack of single-sublayer "
            "blocks with an untied head, no biases and one residual "
            f"stream; the program's ModelConfig has tie_word_embeddings="
            f"{mc.tie_word_embeddings}, qkv_bias={mc.qkv_bias}, "
            f"hc_mult={mc.hc_mult}")
