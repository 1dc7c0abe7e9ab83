"""The xing4 family: Xing4.0-29B-A4B's decoder.

`model_type: xing4_0` (source: the published config.json). The only file
of the benchmark that knows this parameter tree and these equations;
`manifest.py` says what a family file gives and how it is found. THE
EQUATIONS, from the config's keys and the published descriptions those
keys name; h = `hidden_size`, n = `hc_mult` = 4 residual streams X
(n, h) a token, RMSNorm eps `rms_norm_eps`, no biases, untied head:

1. Residual path (manifold-constrained hyper-connections,
   arXiv:2512.24880; `hc_mult`, `hc_sinkhorn_iters`, `hc_eps`,
   `mhc_h_res_clamp_min/max`). Each sublayer F (a layer has two, F_attn
   and F_mlp) has its own phi (n h, 2n + n^2), alpha (3,), b (2n + n^2).
   xt = RMSNorm(vec(X)) without a weight; [pre; post; res] = alpha *
   (xt phi) + b, part by part; H_pre = sigmoid(pre) (n,), H_post = 2
   sigmoid(post) (n,), H_res = SK(exp(clamp(res, -30, 30))) (n, n), SK
   repeating 20 times: every row over its sum + hc_eps, then every
   column over its sum + hc_eps. u = H_pre X (h,), y = F(RMSNorm_w(u)),
   X' = H_res X + H_post^T y. In float32.
2. Latent attention (DeepSeek-V2/V3's MLA; `q_lora_rank` 768,
   `kv_lora_rank` 512, `qk_nope_head_dim` 128, `qk_rope_head_dim` 64,
   `v_head_dim` 128, 32 heads). c_q = RMSNorm(x W_dq); a head's q = c_q
   W_uq = [q_nope (128); q_rope (64)]. [c_kv (512); k_r (64)] = x W_dkv;
   c = RMSNorm(c_kv); a head's [k_nope; v] = c W_ukv. Scores (q_nope .
   k_nope + rope(q_rope) . rope(k_r)) * s, s = 192^-1/2 * m^2, m = 0.1
   `mscale_all_dim` ln(`factor`) + 1 = 1.4159; causal softmax; (sum p v)
   W_o (4096 -> h). Rotary with YaRN (`factor` 64, original 4,096,
   `beta_fast` 32, `beta_slow` 1, theta 10,000): DeepSeek-V3's blend of
   inv_freq and inv_freq / factor by the linear ramp between the two
   correction dims; cos and sin times m(mscale) / m(mscale_all_dim) = 1.
   THIS reference computes the un-absorbed form above from the full
   sequence; the program serves it absorbed, from one cached row
   [c; rope(k_r)] a token.
3. `first_k_dense_replace` 2: layers 0-1 a SwiGLU of width 9,216; then
   64 routed experts of width 1,024 and one shared one. sigma =
   sigmoid(x W_g) in float32; chosen = top-4 of sigma + bias
   (`noaux_tc`; `n_group` = `topk_group` = 1: no group limit); weights
   sigma_e / sum_chosen sigma * 2.0 (`norm_topk_prob`,
   `routed_scaling_factor`); output sum w_e E_e(x) + E_shared(x).
4. Final RMSNorm of the streams' sum, head over 131,072 ids.

ASSUMED (no key of the config says; the configuration's file lists them
under `assumed`): the streams start as n copies of the embedding and are
summed before the final norm; rows are normalised before columns;
half-split rotary, as the program has it (a fixed permutation of weight
columns away from the interleaved form; the weights are random). LEFT
OUT: the one MTP module (`num_nextn_predict_layers`), which does not
enter the main model's logits.

`ep_size` is a PUBLISHED key of this model (1: every expert on every
rank) and passes through as it is; the mimo_v2 family uses the same name
for its deployment's share, under a key of its own file.

Imports jax inside its functions only: `run.py` loads a family for its
counts and imports no jax.
"""

from __future__ import annotations

import dataclasses

VOCAB_SLICES = 8
QUERY_BLOCK = 512
# score elements a head of one attention block may hold (block rows x
# keys): 32 heads of float32 scores stay under ~270 MB at any length
SCORE_BUDGET = 1 << 21
# lanes a cached row takes on the chip: 512 latent + 64 rotary dims are
# stored as 640 (the 128-lane tile; `ModelRunner._k_store_dim`)
K_STORE_LANES = 640


# -- 1. the config.json the program reads ----------------------------------
def hf_config(config: dict) -> dict:
    """The published keys as they are (the harness takes its own out)."""
    return dict(config)


def _segments(mc):
    """(routed, count) runs of alike layers, as the program's tree
    stacks them (`ModelConfig.segments`)."""
    return [(r, c) for _, r, c, _ in mc.segments()]


# -- 2. the weights ---------------------------------------------------------
# what the seeded tree is scaled by; `init_params` says why
HC_ALPHA = (1.0, 1.0, 0.5)
HC_B_STD = 0.5
HC_RES_DIAGONAL = 2.0
EXPERT_DOWN_GAIN = 0.1


def init_params(mc, key, dtype):
    """All weights from the key, one run of alike layers at a time and
    layer by layer inside it; the selection bias, the mixing's alpha, b
    and phi and the shared expert non-zero, so that a dropped one shows
    in the reference check.

    THE SCALES. As `families/mimo_v2.py` scales them, and for its
    reasons: every matrix has entries of ONE standard deviation, hidden
    ** -0.5, and the embedding's rows unit variance an entry, so that a
    row's token stays the largest single term of its streams and shows
    in its routing. Three things are this family's, each read on the
    CPU at published widths before the first chip run (PERF.md,
    Findings PR 33; the configuration's `assumed.weights` has the
    numbers):
    - the ROUTED experts' down projections carry EXPERT_DOWN_GAIN (the
      shared expert, which every row passes and no routing decides,
      keeps the common deviation): a chosen expert weighs ~0.5 here
      (top-4 of 64, renormalised, times the scaling factor 2) where
      MiMo's weighs ~0.125, so ONE routing flip between the served
      path's bfloat16 streams and the float32 reference moves a
      position four times as far at equal scales. My CPU emulation,
      PR 33 (the program's forward pass in bf16 against this reference,
      eight-position windows held to `reference.py`'s 0.1 / 0.03): gain
      0.5 fails 34 of 249 windows (worst position 0.162), 0.15 none of
      363 (0.058), 0.1 none of 726 (worst position 0.048, worst window
      mean 0.0122) and, as served here, none of 484 (0.026 / 0.011);
      every matrix rounded to e4m3 fails 121 of 121 at either gain, by
      the mean;
    - phi has the deviation of its fan-in, (n h) ** -0.5, so that the
      raw mixing logits are of unit size; alpha = HC_ALPHA times (1 +
      0.1 N), b = HC_B_STD N with HC_RES_DIAGONAL added on H_res's
      diagonal (the published initialisation keeps H_res near the
      identity: a stream stays mostly its own). At this size the 20
      Sinkhorn iterations leave H_res doubly stochastic to ~1e-5;
    - the q and kv bottlenecks' norms are ones, as every norm."""
    import jax
    import jax.numpy as jnp

    h, v = mc.hidden_size, mc.vocab_size
    nq, dk, dv = mc.num_heads, mc.head_dim, mc.v_dim
    n = mc.hc_mult
    ak = mc.attn_kinds[0]
    lat, rot, r = ak.latent_dim, mc.rope_dim, mc.q_lora_rank
    f = mc.moe_intermediate_size

    def w(k, shape, std=h ** -0.5):
        return (jax.random.normal(k, shape, jnp.float32)
                * std).astype(dtype)

    def stack(k, routed, count):
        shapes = {
            "w_dq": (h, r), "w_uq": (r, nq * dk),
            "w_dkv": (h, lat + rot),
            "w_ukv": (lat, nq * (dk - rot + dv)), "wo": (nq * dv, h),
        }
        if routed:
            e = mc.local_experts
            shapes |= {
                "router": (h, mc.router_experts),
                "w_gate": (e, h, f), "w_up": (e, h, f),
                "w_down": (e, f, h),
                "ws_gate": (h, f * mc.shared_experts),
                "ws_up": (h, f * mc.shared_experts),
                "ws_down": (f * mc.shared_experts, h),
            }
        else:
            i = mc.intermediate_size
            shapes |= {"w_gate": (h, i), "w_up": (h, i),
                       "w_down": (i, h)}
        down = ("w_down",) if routed else ()

        def one_layer(k):
            ks = jax.random.split(k, len(shapes) + 7)
            lp = {name: w(ks[j], s, h ** -0.5 * (
                EXPERT_DOWN_GAIN if name in down else 1.0))
                for j, (name, s) in enumerate(sorted(shapes.items()))}
            lp["attn_norm"] = jnp.ones((h,), dtype)
            lp["mlp_norm"] = jnp.ones((h,), dtype)
            lp["q_norm"] = jnp.ones((r,), dtype)
            lp["kv_norm"] = jnp.ones((lat,), dtype)
            if routed and mc.router_bias:
                lp["router_bias"] = 0.1 * jax.random.normal(
                    ks[-1], (mc.router_experts,), jnp.float32)
            for j, sub in enumerate(("attn", "mlp")):
                k_phi, k_a, k_b = ks[-2 - 3 * j], ks[-3 - 3 * j], ks[-4 - 3 * j]
                lp[f"hc_{sub}_phi"] = w(
                    k_phi, (n * h, 2 * n + n * n), (n * h) ** -0.5)
                lp[f"hc_{sub}_alpha"] = jnp.asarray(HC_ALPHA) * (
                    1.0 + 0.1 * jax.random.normal(k_a, (3,), jnp.float32))
                b = HC_B_STD * jax.random.normal(
                    k_b, (2 * n + n * n,), jnp.float32)
                lp[f"hc_{sub}_b"] = b.at[2 * n:].add(
                    HC_RES_DIAGONAL * jnp.eye(n).reshape(-1))
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

    A copy of `tests/reference_model.py::xing4_forward` (the original
    stays where the program's own tests use it): no kernel, no cache, no
    batching, a dense mask, latent attention UN-absorbed (every head's
    keys and values are made from the latent rows of the full sequence).
    Departures from a textbook loop, all so that 18k tokens fit beside
    the served weights: each run of alike layers is walked by `lax.scan`
    over its stacked weights with the layer's bf16 weights upcast inside
    the step; everything that is a function of one row (the mixing, the
    projections, the MLP, the experts, ONE expert upcast at a time) runs
    over blocks of rows; attention runs over blocks of query rows against
    all keys, the block sized so that its scores stay under
    SCORE_BUDGET; the head is applied to the asked rows only, in
    vocabulary slices."""
    import math

    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    t = token_ids.shape[0]
    n = cfg.hc_mult
    nq, dk, dv = cfg.num_heads, cfg.head_dim, cfg.v_dim
    rot = cfg.rope_dim
    nope = dk - rot
    ak = cfg.attn_kinds[0]
    lat = ak.latent_dim
    eps = cfg.rms_norm_eps
    qb = min(t, QUERY_BLOCK, max(8, 1 << int(math.log2(SCORE_BUDGET / t))))
    n_blocks = -(-t // qb)
    t_pad = n_blocks * qb
    pos = jnp.arange(t_pad)

    # YaRN's frequencies, the softmax scale, cos / sin
    scale = dk ** -0.5
    inv = 1.0 / (ak.rope_theta ** (jnp.arange(0, rot, 2, dtype=f32) / rot))
    cs = 1.0
    y = cfg.rope_yarn
    if y is not None:
        def corr(rotations):
            return rot * math.log(y.original_max_position / (
                rotations * 2 * math.pi)) / (2 * math.log(ak.rope_theta))

        low = max(math.floor(corr(y.beta_fast)), 0)
        high = min(math.ceil(corr(y.beta_slow)), rot - 1)
        ramp = jnp.clip((jnp.arange(rot // 2, dtype=f32) - low)
                        / max(high - low, 0.001), 0.0, 1.0)
        inv = inv / y.factor * ramp + inv * (1.0 - ramp)

        def m(s):
            return 0.1 * s * math.log(y.factor) + 1.0 if y.factor > 1 else 1.0

        cs = m(y.mscale) / m(y.mscale_all_dim)
        if y.mscale_all_dim:
            scale *= m(y.mscale_all_dim) ** 2

    def rope(x, p):  # (rows, heads, rot) at positions p (rows,)
        fr = p.astype(f32)[:, None] * inv[None, :]
        cos, sin = (jnp.cos(fr) * cs)[:, None], (jnp.sin(fr) * cs)[:, None]
        x1, x2 = x[..., :rot // 2], x[..., rot // 2:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def rms(x, w=None):
        out = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return out if w is None else out * w.astype(f32)

    def by_blocks(fn, *arrays):
        """fn over blocks of qb rows of every array; its results put
        back together along the rows."""
        out = jax.lax.map(lambda a: fn(*a), tuple(
            a.reshape(n_blocks, qb, *a.shape[1:]) for a in arrays))
        return jax.tree.map(
            lambda a: a.reshape(t_pad, *a.shape[2:]), out)

    def mix(X, lp, sub):
        """The mixing matrices of a block X (rows, n, h)."""
        v = X.reshape(X.shape[0], n * X.shape[-1])
        proj = rms(v) @ lp[f"hc_{sub}_phi"].astype(f32)
        a, b = lp[f"hc_{sub}_alpha"], lp[f"hc_{sub}_b"]
        pre = jax.nn.sigmoid(a[0] * proj[:, :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * proj[:, n:2 * n] + b[n:2 * n])
        res = (a[2] * proj[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
        res = jnp.exp(jnp.clip(res, *cfg.hc_res_clamp))
        for _ in range(cfg.hc_sinkhorn_iters):
            res = res / (jnp.sum(res, -1, keepdims=True) + cfg.hc_eps)
            res = res / (jnp.sum(res, -2, keepdims=True) + cfg.hc_eps)
        return pre, post, res

    def sublayer(X, lp, sub, norm, fn):
        """One block of rows through a sublayer: X' = H_res X + H_post^T
        F(RMSNorm_w(H_pre X))."""
        if n == 1:
            return X + fn(rms(X[:, 0], lp[norm]))[:, None]
        pre, post, res = mix(X, lp, sub)
        out = fn(rms(jnp.einsum("tj,tjh->th", pre, X), lp[norm]))
        return (jnp.einsum("tij,tjh->tih", res, X)
                + post[:, :, None] * out[:, None, :])

    def swiglu(x, g, u, d):
        return (jax.nn.silu(x @ g.astype(f32)) * (x @ u.astype(f32))
                ) @ d.astype(f32)

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

    def attention_layer(X, lp):
        """The attention sublayer over all rows: the latent rows of the
        whole sequence first, every head's keys and values from them,
        then block after block of query rows."""
        def latent_rows(Xb, p):
            u = Xb[:, 0] if n == 1 else jnp.einsum(
                "tj,tjh->th", mix(Xb, lp, "attn")[0], Xb)
            ckv = rms(u, lp["attn_norm"]) @ lp["w_dkv"].astype(f32)
            return (rms(ckv[:, :lat], lp["kv_norm"]),
                    rope(ckv[:, None, lat:], p)[:, 0])

        c, k_r = by_blocks(latent_rows, X, pos)
        kv = (c @ lp["w_ukv"].astype(f32)).reshape(t_pad, nq, nope + dv)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        def block(Xb, p):
            def attend(x):
                q = (rms(x @ lp["w_dq"].astype(f32), lp["q_norm"])
                     @ lp["w_uq"].astype(f32)).reshape(qb, nq, dk)
                s = (jnp.einsum("thd,shd->ths", q[..., :nope], k_nope)
                     + jnp.einsum("thd,sd->ths", rope(q[..., nope:], p),
                                  k_r)) * scale
                s = jnp.where((pos[None, :] <= p[:, None])[:, None, :],
                              s, -1e30)
                o = jnp.einsum("ths,shd->thd", jax.nn.softmax(s, -1), v)
                return o.reshape(qb, nq * dv) @ lp["wo"].astype(f32)

            return sublayer(Xb, lp, "attn", "attn_norm", attend)

        return by_blocks(block, X, pos)

    emb = params["embed"][token_ids].astype(f32)
    emb = jnp.pad(emb, ((0, t_pad - t), (0, 0)))
    X = jnp.broadcast_to(emb[:, None, :], (t_pad, n, emb.shape[-1]))
    for stack, (is_routed, _) in zip(params["segments"], _segments(cfg)):
        def layer(X, lp, is_routed=is_routed):
            X = attention_layer(X, lp)
            mlp = (lambda x: routed(x, lp)) if is_routed else (
                lambda x: swiglu(x, lp["w_gate"], lp["w_up"],
                                 lp["w_down"]))
            return by_blocks(
                lambda Xb: sublayer(Xb, lp, "mlp", "mlp_norm", mlp),
                X), None

        X, _ = jax.lax.scan(layer, X, stack)
    h = rms(jnp.sum(X[rows], 1), params["final_norm"])
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
def attention_params(hf: dict) -> int:
    """One layer's attention: W_dq, its norm, W_uq, W_dkv, its norm,
    W_ukv, W_o."""
    h, nq = hf["hidden_size"], hf["num_attention_heads"]
    r, lat = hf["q_lora_rank"], hf["kv_lora_rank"]
    nope, rot, dv = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                     hf["v_head_dim"])
    return (h * r + r + r * nq * (nope + rot) + h * (lat + rot) + lat
            + lat * nq * (nope + dv) + nq * dv * h)


def mixing_params(hf: dict) -> int:
    """One layer's hyper-connection parameters: phi, alpha and b of its
    two sublayers."""
    n = hf["hc_mult"]
    k = 2 * n + n * n
    return 2 * (n * hf["hidden_size"] * k + 3 + k)


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
    """Parameters of layer `index`: attention, the two norms, the
    mixing, and either the dense MLP or the router (with its selection
    bias), the routed experts and the shared ones."""
    h = hf["hidden_size"]
    count = attention_params(hf) + 2 * h + mixing_params(hf)
    if index < hf["first_k_dense_replace"]:
        return count + 3 * h * hf["intermediate_size"]
    e = hf["n_routed_experts"]
    router = h * e + (e if hf.get("topk_method") == "noaux_tc" else 0)
    return count + router + (e + hf["n_shared_experts"]) * expert_params(hf)


def total_params(hf: dict) -> int:
    h, v = hf["hidden_size"], hf["vocab_size"]
    embed = v * h * (1 if hf.get("tie_word_embeddings") else 2)
    return sum(layer_params(hf, i)
               for i in range(hf["num_hidden_layers"])) + embed + h


def layer_stack_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of EVERY weight the layer stack holds (all 64 experts of
    each routed layer, whether a step reads them or not); neither
    embedding nor lm_head. A decode step reads the experts that have
    rows: this family's cell reports `moe_expert_roofline_share` over
    the counters instead of a share of these bytes."""
    return sum(layer_params(hf, i)
               for i in range(hf["num_hidden_layers"])) * bytes_per_param


def kv_bytes_per_token_by_kind(hf: dict, bytes_per_elem: int = 2) -> dict:
    """Cache bytes one context token takes in ALL the layers, at the
    width the chip's cache stores a latent row (K_STORE_LANES; there is
    no V array)."""
    return {"latent": hf["num_hidden_layers"] * K_STORE_LANES
            * bytes_per_elem}


def kv_bytes_per_token(hf: dict, bytes_per_elem: int = 2) -> int:
    return kv_bytes_per_token_by_kind(hf, bytes_per_elem)["latent"]


# -- 5. the rehearsal's shape ----------------------------------------------
def rehearsal_config(mc, tp: int):
    """A rehearsal checks control flow on the CPU, not speed: the tiny
    latent widths, which keep every code path of the family."""
    from production_stack_tpu.models import config as mcfg

    return dataclasses.replace(
        mcfg.TINY_LATENT_DEBUG, name=mc.name,
        rms_norm_eps=mc.rms_norm_eps, max_model_len=mc.max_model_len,
    )


# -- 6. the guard -----------------------------------------------------------
def check(config: dict, mc) -> None:
    """Refuse where the file and the program's ModelConfig disagree on
    what the counts and the reference rest on."""
    rs = config["rope_scaling"]
    y = mc.rope_yarn
    kinds = mc.attn_kinds
    want = {
        "hidden_size": (mc.hidden_size, config["hidden_size"]),
        "dense width": (mc.intermediate_size, config["intermediate_size"]),
        "expert width": (mc.moe_intermediate_size,
                         config["moe_intermediate_size"]),
        "layers": (mc.num_layers, config["num_hidden_layers"]),
        "q heads": (mc.num_heads, config["num_attention_heads"]),
        "qk head dim": (mc.head_dim, config["qk_nope_head_dim"]
                        + config["qk_rope_head_dim"]),
        "rotary dims": (mc.rope_dim, config["qk_rope_head_dim"]),
        "v head dim": (mc.v_dim, config["v_head_dim"]),
        "q bottleneck": (mc.q_lora_rank, config["q_lora_rank"]),
        "latent dims": ([k.latent_dim for k in kinds],
                        [config["kv_lora_rank"]]),
        "vocabulary": (mc.vocab_size, config["vocab_size"]),
        "router width": (mc.router_experts, config["n_routed_experts"]),
        "experts held": (mc.local_experts if mc.router_experts else 0,
                         config["n_routed_experts"] // config["ep_size"]),
        "experts a token": (mc.num_experts_per_tok,
                            config["num_experts_per_tok"]),
        "shared experts": (mc.shared_experts, config["n_shared_experts"]),
        "scaling factor": (mc.routed_scaling,
                           float(config["routed_scaling_factor"])),
        "dense layers": (mc.dense_layers, config["first_k_dense_replace"]),
        "streams": (mc.hc_mult, config["hc_mult"]),
        "sinkhorn": ((mc.hc_sinkhorn_iters, mc.hc_eps, mc.hc_res_clamp),
                     (config["hc_sinkhorn_iters"], config["hc_eps"],
                      (float(config["mhc_h_res_clamp_min"]),
                       float(config["mhc_h_res_clamp_max"])))),
        "rope theta": ([k.rope_theta for k in kinds],
                       [float(config["rope_theta"])]),
        "yarn": (y and (y.factor, y.original_max_position, y.beta_fast,
                        y.beta_slow, y.mscale, y.mscale_all_dim),
                 (float(rs["factor"]),
                  rs["original_max_position_embeddings"],
                  float(rs["beta_fast"]), float(rs["beta_slow"]),
                  float(rs["mscale"]), float(rs["mscale_all_dim"]))),
    }
    wrong = {k: v for k, v in want.items() if v[0] != v[1]}
    if wrong:
        raise SystemExit(
            "the program's ModelConfig and the configuration's file "
            "disagree (program, file): " + ", ".join(
                f"{k} {a!r} != {b!r}" for k, (a, b) in wrong.items())
            + ": the xing4 family would count and check other weights "
            "than are served")
    if not mc.layer_groups or mc.qkv_bias or mc.tie_word_embeddings:
        raise SystemExit(
            "the xing4 family covers a stack of layer groups without "
            "biases and with an untied head; the program's ModelConfig "
            f"has layer_groups={mc.layer_groups}, qkv_bias={mc.qkv_bias}, "
            f"tie_word_embeddings={mc.tie_word_embeddings}")
