"""The kimi_linear family: Moonshot's Kimi-Linear-48B-A3B decoder.

`model_type: kimi_linear` (source: the published config.json; the
mechanism is Kimi Delta Attention, arXiv:2510.26692, on the gated delta
rule of arXiv:2412.06464). The only file of the benchmark that knows
this parameter tree and these equations; `manifest.py` says what a
family file gives and how it is found. THE EQUATIONS. Hidden size d,
RMSNorm eps `rms_norm_eps`, no biases. A published layer is two blocks,
each x <- x + F(RMSNorm_w(x)): a mixer, then a feed-forward part; after
the last layer a final RMSNorm and an untied head. `linear_attn_config`
numbers layers from 1: `kda_layers` have a KDA mixer, `full_attn_layers`
latent attention; layers 1..`first_k_dense_replace` a dense MLP, every
later one routed experts.

- KDA (H = `linear_attn_config.num_heads` heads, K = V = its `head_dim`,
  `short_conv_kernel_size` taps). u the normed row: q, k, v = u W (d ->
  H K, H K, H V), f_a = u W_fa, g_a = u W_ga (d -> K each), b = u W_beta
  (d -> H): six projections, held side by side as ONE matrix W_in = [v |
  k | q | f_a | g_a | b]; each lane of [v | k | q]
  through its own causal depthwise convolution over the sequence's last
  taps - 1 rows (rows before the sequence are zero), then silu; per head
  q <- q / sqrt(|q|^2 + 1e-6) K^-1/2, k <- k / sqrt(|k|^2 + 1e-6). g =
  -exp(A_log_h) softplus(f_a W_fb + dt_bias), a K-vector a head (d
  -> K -> H K); beta = sigmoid(b). S (K, V) a head,
  float32, S_0 = 0: St = Diag(exp g_t) S_{t-1}; S_t = St + beta_t k_t
  (v_t - St^T k_t)^T; o_t = S_t^T q_t. y = RMSNorm_w(o_t) over a head's
  V dims (one weight of V for all heads) x sigmoid(g_a W_gb) (d ->
  K -> H V); F = y W_o. No positional encoding.
- latent attention without positions (`mla_use_nope`; `kv_lora_rank`
  lat, `qk_nope_head_dim` + `qk_rope_head_dim` a head, `v_head_dim`,
  `q_lora_rank` null): q = u W_q directly; [c_kv; k_r] = u W_dkv, c =
  RMSNorm_w(c_kv); a head's key [c W_uk; k_r] with k_r NOT rotated and
  shared by all heads, value c W_uv; scale (nope + rope)^-1/2; causal.
  THIS reference computes that un-absorbed form from the full sequence;
  the program serves it absorbed, from one cached row [c; k_r] a token.
- dense MLP: SwiGLU d -> `intermediate_size` -> d.
- routed experts: s = sigmoid(u W_r) over `router_experts` outputs in
  float32; the `num_experts_per_token` largest of s +
  e_score_correction_bias; weights s_e / sum_chosen s x
  `routed_scaling_factor`; an expert is SwiGLU d -> `moe_intermediate_
  size` -> d; plus `num_shared_experts` shared ones on every row. Of the
  router's experts this rank holds `num_experts` (the slice of `ep_rank`
  of `ep_size`); what the others would add is left out here as in the
  program.

ASSUMED (the configuration's file lists each under `assumed`): the ranks
of the two low-rank maps (= the KDA head_dim), convolutions without
bias, the order conv -> silu -> norm, the norm's 1e-6 and the selection
bias are the published modelling code's, not keys; `head_dim` 72 and
`num_key_value_heads` 32 at the file's top level are read by nothing.

Imports jax inside its functions only: `run.py` loads a family for its
counts and imports no jax.
"""

from __future__ import annotations

import dataclasses

VOCAB_SLICES = 4
OWN_KEYS = ("router_experts",)
# lanes a cached row takes on the chip: 512 latent + 64 shared key dims
# are stored as 640 (the 128-lane tile; `ModelRunner._k_store_dim`)
K_STORE_LANES = 640
# what the seeded tree scales the routed experts' down projections by;
# `init_params` says why
EXPERT_DOWN_GAIN = 0.1


# -- 1. the config.json the program reads ----------------------------------
def hf_config(config: dict) -> dict:
    """The published keys, with `num_experts` back at the router's
    width: the program is told the experts it holds by ep_size/ep_rank."""
    out = {k: v for k, v in config.items() if k not in OWN_KEYS}
    out["num_experts"] = config["router_experts"]
    return out


# -- 2. the weights ---------------------------------------------------------
def _block_shapes(mc, letter: str) -> dict:
    h = mc.hidden_size
    if letter == "K":
        nh, vd, kd = mc.ssm_heads, mc.ssm_head_dim, mc.ssm_state
        # the row's six projections side by side, as the program
        # holds them: [v | k | q | f_a | g_a | beta]
        return {"w_in": (h, mc.ssm_conv_dim + 2 * kd + nh),
                "conv_w": (mc.ssm_conv, mc.ssm_conv_dim),
                "w_fb": (kd, nh * kd), "w_gb": (kd, nh * vd),
                "w_o": (nh * vd, h)}
    if letter == "*":
        ak = mc.kinds[0]
        nq, lat, rot = ak.num_heads, ak.latent_dim, ak.rotary_dim
        return {"wq": (h, nq * mc.head_dim),
                "w_dkv": (h, lat + rot),
                "w_ukv": (lat, nq * (mc.head_dim - rot + mc.v_dim)),
                "wo": (nq * mc.v_dim, h)}
    if letter == "-":
        i = mc.intermediate_size
        return {"w_gate": (h, i), "w_up": (h, i), "w_down": (i, h)}
    e, f = mc.local_experts, mc.moe_intermediate_size
    fs = f * mc.shared_experts
    return {"router": (h, mc.router_experts),
            "w_gate": (e, h, f), "w_up": (e, h, f), "w_down": (e, f, h),
            "ws_gate": (h, fs), "ws_up": (h, fs), "ws_down": (fs, h)}


def init_params(mc, key, dtype):
    """All weights from the key, one unit of `mc.tree_units()` (here a
    stack a kind of block: K, -, *, E) at a time and layer by layer
    inside it.

    THE SCALES. Every matrix at ONE standard deviation, hidden ** -0.5,
    and the embedding's rows at unit variance an entry, as the other
    layer-group families scale them and for their reasons (a row's token
    stays the largest single term of its stream). Norms ones; the
    convolutions' taps at taps ** -0.5; the router's selection bias at
    0.1. The KDA mixer's own parameters as the gated delta rule's
    published initialisation has them, which is Mamba-2's: A_log = log
    U(1, 16) a head, dt_bias the inverse softplus of a log-uniform step
    in [0.001, 0.1] a key channel. The ROUTED experts' down projections
    carry EXPERT_DOWN_GAIN, as xing4's, laguna's and nemotron's do:
    top-8 routing over 256 scores is discontinuous, and where the 8th
    and 9th lie closer than bfloat16's rounding of the stream the served
    path and the float32 reference choose differently; a chosen expert
    weighs ~0.3 here (renormalised top-8 times 2.446), and the gain
    bounds what one such flip moves (PERF.md, Findings PR 51)."""
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
                    elif name == "w_down" and letter == "E":
                        std *= EXPERT_DOWN_GAIN
                    lp[name] = w(ks[j], s, std)
                lp["norm"] = jnp.ones((h,), dtype)
                if letter == "K":
                    nh, kd = mc.ssm_heads, mc.ssm_state
                    step = jnp.exp(jax.random.uniform(
                        ks[-1], (nh * kd,), f32, jnp.log(1e-3),
                        jnp.log(1e-1)))
                    lp |= {
                        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                        "A_log": jnp.log(jax.random.uniform(
                            ks[-2], (nh,), f32, 1.0, 16.0)),
                        "o_norm": jnp.ones((mc.ssm_head_dim,), dtype),
                    }
                if letter == "*":
                    lp["kv_norm"] = jnp.ones(
                        (mc.kinds[0].latent_dim,), dtype)
                if letter == "E":
                    lp["router_bias"] = 0.1 * jax.random.normal(
                        ks[-3], (mc.router_experts,), f32)
                out.append(lp)
            return out

        return jax.lax.map(one_layer, jax.random.split(k, count))

    units = mc.tree_units()
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
    chunking: the delta rule is a sequential `lax.scan` over the tokens,
    latent attention un-absorbed (every head's keys and values made from
    the latent rows of the full sequence) under a dense mask. Departures
    from a textbook loop, all to fit beside the serving cache: a block's
    bf16 weights are upcast where they are used; the experts are upcast
    and applied ONE AT A TIME; the head is applied to the asked rows
    only, in vocabulary slices."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    t = token_ids.shape[0]
    eps = cfg.rms_norm_eps
    pos = jnp.arange(t)

    def rms(x, w):
        n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return n * w.astype(f32)

    def swiglu(x, g, u, d):
        return (jax.nn.silu(x @ g.astype(f32)) * (x @ u.astype(f32))
                ) @ d.astype(f32)

    def unit_norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def kda(u, lp):
        nh, vd, kd, taps = (cfg.ssm_heads, cfg.ssm_head_dim,
                            cfg.ssm_state, cfg.ssm_conv)
        d, c = nh * vd, cfg.ssm_conv_dim
        proj = u @ lp["w_in"].astype(f32)
        vkq, f_a, g_a, b = (proj[:, :c], proj[:, c:c + kd],
                            proj[:, c + kd:c + 2 * kd], proj[:, c + 2 * kd:])
        padded = jnp.pad(vkq, ((taps - 1, 0), (0, 0)))
        cw = lp["conv_w"].astype(f32)
        vkq = jax.nn.silu(
            sum(padded[j:j + t] * cw[j] for j in range(taps)))
        v = vkq[:, :d].reshape(t, nh, vd)
        k = unit_norm(vkq[:, d:d + nh * kd].reshape(t, nh, kd))
        q = unit_norm(vkq[:, d + nh * kd:].reshape(t, nh, kd)) * kd ** -0.5
        g = -jnp.exp(lp["A_log"].astype(f32))[:, None] * jax.nn.softplus(
            (f_a @ lp["w_fb"].astype(f32)
             + lp["dt_bias"].astype(f32)).reshape(t, nh, kd))
        beta = jax.nn.sigmoid(b)

        def token(s, inp):
            q_t, k_t, v_t, g_t, b_t = inp
            s = jnp.exp(g_t)[:, :, None] * s
            u_t = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
            s = s + k_t[:, :, None] * u_t[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        _, o = jax.lax.scan(token, jnp.zeros((nh, kd, vd), f32),
                            (q, k, v, g, beta))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        o = (o * lp["o_norm"].astype(f32)).reshape(t, d)
        gate = g_a @ lp["w_gb"].astype(f32)
        return (o * jax.nn.sigmoid(gate)) @ lp["w_o"].astype(f32)

    def attention(u, lp):
        ak = cfg.kinds[0]
        nq, dk, dv = ak.num_heads, cfg.head_dim, cfg.v_dim
        lat, nope = ak.latent_dim, cfg.head_dim - ak.rotary_dim
        q = (u @ lp["wq"].astype(f32)).reshape(t, nq, dk)
        ckv = u @ lp["w_dkv"].astype(f32)
        c, k_r = rms(ckv[:, :lat], lp["kv_norm"]), ckv[:, lat:]
        kv = (c @ lp["w_ukv"].astype(f32)).reshape(t, nq, nope + dv)
        s = (jnp.einsum("thd,shd->ths", q[..., :nope], kv[..., :nope])
             + jnp.einsum("thd,sd->ths", q[..., nope:], k_r)) * dk ** -0.5
        s = jnp.where((pos[None, :] <= pos[:, None])[:, None, :], s, -1e30)
        o = jnp.einsum("ths,shd->thd", jax.nn.softmax(s, -1),
                       kv[..., nope:])
        return o.reshape(t, nq * dv) @ lp["wo"].astype(f32)

    def dense(u, lp):
        return swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"])

    def experts(u, lp):
        logits = jnp.dot(u, lp["router"].astype(f32),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(s + lp["router_bias"].astype(f32),
                                  cfg.num_experts_per_tok)
        w = jnp.take_along_axis(s, chosen, 1)
        if cfg.router_renorm:
            w = w / jnp.sum(w, -1, keepdims=True)
        w = w * cfg.routed_scaling
        first = cfg.ep_rank * cfg.local_experts
        stack, layer = lp["experts"]

        def expert(acc, e):
            # one expert of one layer out of the whole stacks at a time
            wg, wu, wd = (stack[n][layer, e]
                          for n in ("w_gate", "w_up", "w_down"))
            w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
            return acc + w_e[:, None] * swiglu(u, wg, wu, wd), None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                              jnp.arange(cfg.local_experts))
        return out + swiglu(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])

    fns = {"K": kda, "*": attention, "-": dense, "E": experts}
    # the tree holds one stack a kind of block; the blocks run in the
    # pattern's order, each the next of its kind
    stacks = {unit: seg[0] for seg, (unit, _, _, _) in zip(
        params["segments"], cfg.tree_units())}
    seen = dict.fromkeys(stacks, 0)
    h = params["embed"][token_ids].astype(f32)
    routed = ("w_gate", "w_up", "w_down")
    for letter in cfg.block_pattern:
        stack, i = stacks[letter], seen[letter]
        seen[letter] += 1
        lp = {n: a[i] for n, a in stack.items()
              if letter != "E" or n not in routed}
        if letter == "E":
            lp["experts"] = (stack, i)
        h = h + fns[letter](rms(h, lp["norm"]), lp)
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
def kda_params(hf: dict) -> int:
    """One KDA mixer: the three projections, their convolutions, the two
    low-rank maps, dt_bias, A_log, W_beta, the output norm, W_o (its
    input norm is counted by `layer_params`)."""
    h, lin = hf["hidden_size"], hf["linear_attn_config"]
    nh, dim, taps = (lin["num_heads"], lin["head_dim"],
                     lin["short_conv_kernel_size"])
    width = nh * dim
    return ((h + taps) * 3 * width + 2 * (h * dim + dim * width) + width
            + nh + h * nh + dim + width * h)


def latent_params(hf: dict) -> int:
    h, nq = hf["hidden_size"], hf["num_attention_heads"]
    lat, rot = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    nope, dv = hf["qk_nope_head_dim"], hf["v_head_dim"]
    return (h * nq * (nope + rot) + h * (lat + rot) + lat
            + lat * nq * (nope + dv) + nq * dv * h)


def expert_params(hf: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of one expert's weights: what a step reads for each routed
    expert that has at least one row."""
    return expert_params(hf) * bytes_per_param


def expert_flops_per_row(hf: dict) -> int:
    """Multiply-adds x 2 of one (row, expert) pair."""
    return 2 * expert_params(hf)


def layer_params(hf: dict, layer: int) -> int:
    """Parameters of published layer `layer` (numbered from 1) HELD HERE
    (the experts of this rank): the mixer and the feed-forward part,
    each with its norm."""
    h = hf["hidden_size"]
    mixer = (kda_params(hf) if layer in hf["linear_attn_config"][
        "kda_layers"] else latent_params(hf))
    if layer <= hf["first_k_dense_replace"]:
        ffn = 3 * h * hf["intermediate_size"]
    else:
        router = hf.get("router_experts", hf["num_experts"])
        ffn = (h * router + router
               + (hf["num_experts"] + hf["num_shared_experts"])
               * expert_params(hf))
    return 2 * h + mixer + ffn


def _stack_params(hf: dict) -> int:
    return sum(layer_params(hf, i)
               for i in range(1, hf["num_hidden_layers"] + 1))


def total_params(hf: dict) -> int:
    h, v = hf["hidden_size"], hf["vocab_size"]
    return _stack_params(hf) + 2 * v * h + h


def layer_stack_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of EVERY weight the layer stack holds here; neither
    embedding nor lm_head. A decode step reads the experts that have
    rows, so a share of these bytes would overstate a step's."""
    return _stack_params(hf) * bytes_per_param


def kv_bytes_per_token(hf: dict, bytes_per_elem: int = 2) -> int:
    """The latent layers' cached rows a context token, AS STORED."""
    return (len(hf["linear_attn_config"]["full_attn_layers"])
            * K_STORE_LANES * bytes_per_elem)


def state_layers(hf: dict) -> int:
    return len(hf["linear_attn_config"]["kda_layers"])


def state_bytes_per_seq(hf: dict, conv_bytes_per_elem: int = 2) -> int:
    """Bytes of recurrent state a sequence holds in all the KDA layers:
    S (H, K, V) in float32 and the convolutions' taps - 1 rows of 3 H
    dim lanes. What a state slot takes, what a snapshot takes, and what
    a decode step reads and writes a lane."""
    lin = hf["linear_attn_config"]
    nh, dim = lin["num_heads"], lin["head_dim"]
    return state_layers(hf) * (
        nh * dim * dim * 4 + (lin["short_conv_kernel_size"] - 1)
        * 3 * nh * dim * conv_bytes_per_elem)


def state_update_bytes_per_lane(hf: dict) -> int:
    """Bytes the decode update of ONE lane and layer has to move: the
    float32 state read and written once (the row's q, k, v, decay and
    the convolution's tail, 2% of it, are left out)."""
    lin = hf["linear_attn_config"]
    return 2 * lin["num_heads"] * lin["head_dim"] ** 2 * 4


# -- 5. the rehearsal's shape ----------------------------------------------
def rehearsal_config(mc, tp: int):
    """A rehearsal checks control flow on the CPU, not speed: the tiny
    widths of this family's shape, which keep every code path of it
    (several heads, K != V, 4 taps, a chunk shorter than the prompts,
    both mixer kinds, the dense layer, top-k > 1 under an ep_size > 1)."""
    from production_stack_tpu.models import config as mcfg

    return dataclasses.replace(
        mcfg.TINY_KIMI_DEBUG, name=mc.name,
        rms_norm_eps=mc.rms_norm_eps, max_model_len=mc.max_model_len,
    )


# -- 6. the guard -----------------------------------------------------------
def check(config: dict, mc) -> None:
    """Refuse where the file and the program's ModelConfig disagree on
    what the counts and the reference rest on."""
    ak = mc.kinds[0] if mc.attn_kinds else None
    lin = config["linear_attn_config"]
    layers = range(1, config["num_hidden_layers"] + 1)
    pattern = "".join(
        ("K" if i in lin["kda_layers"] else "*")
        + ("-" if i <= config["first_k_dense_replace"] else "E")
        for i in layers)
    want = {
        "hidden_size": (mc.hidden_size, config["hidden_size"]),
        "pattern": (mc.block_pattern, pattern),
        "vocabulary": (mc.vocab_size, config["vocab_size"]),
        "latent attention": (
            (ak.num_heads, ak.latent_dim, ak.rotary_dim, mc.head_dim,
             mc.v_dim, mc.q_lora_rank),
            (config["num_attention_heads"], config["kv_lora_rank"],
             config["qk_rope_head_dim"],
             config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
             config["v_head_dim"], 0)),
        "positional encoding": (mc.rope, False),
        "mixer": ((mc.ssm_heads, mc.ssm_head_dim, mc.ssm_state,
                   mc.ssm_conv),
                  (lin["num_heads"], lin["head_dim"], lin["head_dim"],
                   lin["short_conv_kernel_size"])),
        "mlp widths": ((mc.intermediate_size, mc.moe_intermediate_size,
                        mc.shared_experts),
                       (config["intermediate_size"],
                        config["moe_intermediate_size"],
                        config["num_shared_experts"])),
        "router width": (mc.router_experts, config["router_experts"]),
        "experts held": (mc.local_experts, config["num_experts"]),
        "rank": ((mc.ep_rank, mc.ep_size),
                 (config["ep_rank"], config["ep_size"])),
        "experts a token": (mc.num_experts_per_tok,
                            config["num_experts_per_token"]),
        "scaling factor": (mc.routed_scaling,
                           float(config["routed_scaling_factor"])),
        "routing": ((mc.router_scoring, mc.router_bias, mc.router_renorm),
                    ("sigmoid", True, True)),
        "mlp": ((mc.hidden_act, mc.mlp_gated), ("silu", True)),
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
            + ": the kimi_linear family would count and check other "
            "weights than are served")
    if mc.tie_word_embeddings or mc.qkv_bias or mc.hc_mult != 1:
        raise SystemExit(
            "the kimi_linear family covers a stack of single-sublayer "
            "blocks with an untied head, no biases and one residual "
            f"stream; the program's ModelConfig has tie_word_embeddings="
            f"{mc.tie_word_embeddings}, qkv_bias={mc.qkv_bias}, "
            f"hc_mult={mc.hc_mult}")
