"""A decoder whose layers come in GROUPS, over one paged KV cache per kind.

`models/llama.py` serves a stack of alike layers under one `lax.scan`.
This module serves the stacks `ModelConfig.attn_kinds` describes: full
and window attention layers side by side, each kind with its own kv
heads, QUERY heads (so wq, wo and the GQA group follow the kind), rope
theta, rotary dims, YaRN and factor on cos and sin, window and (where
the config says so) a learned sink in the softmax; a per-head output
gate (`cfg.head_gate`: sigmoid of a linear on the normed layer input,
one scalar a head and row, on the attention output before W_o); q/k
heads wider than v heads, rotary on the leading dims of a head only, V
scaled before the cache; leading dense layers and then
a routed expert layer over the experts this engine holds
(`ops/moe.routed_experts`, told its slice by `cfg.ep_rank/ep_size`),
with a scaling factor and shared experts beside them where the config
has them; a LATENT attention kind (`AttnKind.latent_dim`), whose cache
holds one row a token that every head reads as key and as value; and
HYPER-CONNECTIONS (`cfg.hc_mult` residual streams a token).
Nothing here branches on a model's name: the fields select the code.

And stacks of SINGLE-sublayer blocks (`cfg.block_pattern`,
`forward_blocks`): a layer is x + F(RMSNorm(x)) with ONE F, a
state-space mixer (`ops/ssm.py`), a gated delta-rule linear-attention
mixer (`ops/kda.py`), attention (plain, or latent where kind 0 is:
`_latent_qkv`, shared with `forward`), routed experts (at a latent
width with no gate matrix, or gated at the model's own) beside a shared
expert, or a dense MLP. Runs of a
repeating UNIT of unlike blocks ("EM" x 5) go under one `lax.scan`
(`cfg.units()`), so a program traces a unit once; where a pattern has
fewer KINDS of block than its best cover has bodies (K-KEKE*EKE: four
against eight) ONE scan walks the blocks in the pattern's order and
runs the body of each block's letter, every block taking its parameters from
its letter's stack (`cfg.switched`, `cfg.tree_units()`), so a program
traces each kind once. In a turn of that scan the kinds that stand
once come first and a stack of matrices goes in as the chip holds it
(`_stored_turned`): neither changes what is computed, and each took a
copy off the device that the turn or the program made for nothing
(PERF.md, Findings PR 52). The recurrent state of
the mixers is a third member of the K-side cache pytree: "ssm": {"s",
"conv"}, a slot a sequence, and "smap", the block manager's maps from a
block to state slots (`engine/block_manager.StateBlockManager`), from
which `ssm.plan_rows` finds every row's sequence: no program ships
anything for them.

Latent attention (DeepSeek-V2's MLA), served ABSORBED. With x a normed
row: c_q = RMSNorm(x W_dq), a head's q = c_q W_uq = [q_nope; q_rope]
(q = x W_q directly where `q_lora_rank` is 0; rope the identity where
the model has no positional encoding);
[c_kv; k_r] = x W_dkv, c = RMSNorm(c_kv); the cache row is [c;
rope(k_r)], one for all heads. A head's key and value would be
[k_nope; v] = c W_ukv; instead the query is taken through the key
up-projection, q_lat = q_nope W_uk^T, and scored against the cached row
itself: (q_lat . c + rope(q_rope) . rope(k_r)) * scale. The output
sum_j p_j c_j leaves `latent_dim` wide and goes through W_uv, then W_o.

Hyper-connections (manifold-constrained, arXiv:2512.24880), n streams
X (n, h) a token, each sublayer F with parameters phi (n h, 2n + n^2),
alpha (3,), b (2n + n^2,) of its own: xt = RMSNorm(vec(X)) without a
weight; [pre; post; res] = alpha * (xt phi) + b by parts; H_pre =
sigmoid(pre), H_post = 2 sigmoid(post), H_res = SK(exp(clamp(res))),
SK repeating `hc_sinkhorn_iters` times: rows over their sum + eps, then
columns over theirs. u = H_pre X, y = F(RMSNorm_w(u)), X' = H_res X +
H_post^T y. The streams start as n copies of the embedding and are
summed before the final norm; the mixing runs in float32. The carry is
streams-major, (n, rows, h), so that its last two dims tile as every
other activation's do.

Why a module beside llama.py and not llama.py grown: the layer body
differs in every line that touches a shape (two head widths, a cache per
kind, a write slot per cache group, a spec handed to the attention
callback, expert statistics in the carry) while LoRA, the pipeline
phase loop and the tensor-parallel sharding rules hang on llama.py's
`decoder_layer` as it is. Growing it would put a branch on every one of
those lines for every dense model; the parts that ARE shared (norms,
rope, SwiGLU, the cache-write idiom, the attention callback) are the
functions both import.

Design:
- params["segments"] holds one stacked tree per RUN of alike layers
  (`cfg.segments()`: layer 0, the five window layers, the full layer of
  a one-period cut), walked by one `lax.scan` each, so a program traces
  one layer per run and its size does not grow with the depth of a run.
- the KV cache is a pytree: k_cache = {"g": one (L_kind, nkv, slots,
  d_k) array per kind, "map": int32 block map, "stats": int32
  counters}, v_cache = {"g": (..., d_v) arrays}. Kind 0's block table
  is THE table every program ships. A windowed kind's cache is a
  smaller pool of its own; its table is kind 0's mapped through "map"
  (primary block id -> this pool's block id, 0 = the null block: not
  resident), on the device, so no program ships a second table or a
  second set of write slots (engine/block_manager.WindowedBlockManager
  keeps the map; the runner uploads it when it changed). The runner
  maps a program's tables ONCE, where the program unpacks its constants
  (`ModelRunner._map_tables`: a round's tables and map are fixed at its
  dispatch), and an attention call says only whether its kind walks the
  mapped ones (`AttnSpec.mapped`); this module maps the rows' write
  slots, once a forward.
- "stats" accumulates the routed layers' counters on the device through
  every layer and fused step of ONE program, which starts it at zero
  (`ModelRunner._enter_caches`); the runner takes it off what the
  program returns and copies it out beside the round's tokens.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.ops import kda, ssm
from production_stack_tpu.ops.cache_write import plan_rows
from production_stack_tpu.ops.cache_write import write_kv as scatter_kv
from production_stack_tpu.ops.expert_ffn import activation
from production_stack_tpu.ops.layers import (
    apply_rope,
    rms_norm,
    rope_cos_sin,
    swiglu,
)
from production_stack_tpu.ops.moe import routed_experts
from production_stack_tpu.ops.sinkhorn import sinkhorn

F32 = jnp.float32

# kc["stats"]: pairs routed, pairs whose expert is held here, local
# experts with at least one row — summed over routed layers and steps
N_STATS = 3


class AttnSpec(NamedTuple):
    """What an attention call of one layer kind needs beyond q and the
    caches; the runner's attention callbacks take it as `spec`."""
    window: int | None
    sink: jax.Array | None       # (nq,) float32 logits
    mapped: bool                 # the windowed cache group's kind: it
                                 # walks the program's MAPPED tables
    latent_v: int | None = None  # a latent kind: the cached row's
                                 # leading lanes are the value, and
                                 # there is no V cache (vc is None)


def mapped_kind(cfg: ModelConfig) -> int | None:
    """The windowed kind (its cache group is the mapped pool), after
    checking what this module and the block manager can hold: a full
    kind first (its table is every sequence's table), and at most one
    windowed kind."""
    windowed = [i for i, k in enumerate(cfg.attn_kinds) if k.window]
    if cfg.attn_kinds[0].window or len(windowed) > 1:
        raise ValueError(
            f"model {cfg.name}: layer groups need kind 0 to attend the "
            "full context and at most one windowed kind, got windows "
            f"{[k.window for k in cfg.attn_kinds]}"
        )
    return windowed[0] if windowed else None


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype = jnp.bfloat16
) -> dict:
    """Random-init parameters; sinks, the router's selection bias and
    the hyper-connections' alpha, b and phi non-zero, so that dropping
    one shows against the reference."""
    if cfg.block_pattern:
        return _init_blocks(cfg, key, dtype)
    h, v = cfg.hidden_size, cfg.vocab_size
    dk, dv = cfg.head_dim, cfg.v_dim
    keys = iter(jax.random.split(key, 16 * len(cfg.segments()) + 4))
    # what PR 33 added draws from a stream of its own, so that a model
    # without it keeps the weights its seed gave it before; PR 43's gate
    # from a third
    more = iter(jax.random.split(
        jax.random.fold_in(key, 33), 16 * len(cfg.segments())))
    gates = iter(jax.random.split(
        jax.random.fold_in(key, 43), len(cfg.segments())))

    def w(shape, fan_in, keys=keys):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    segments = []
    for kind, routed, c, _ in cfg.segments():
        ak = cfg.kinds[kind]
        nq, nkv, rot = ak.num_heads, ak.num_kv_heads, ak.rotary_dim
        lp = {
            "attn_norm": jnp.ones((c, h), dtype),
            "mlp_norm": jnp.ones((c, h), dtype),
        }
        if ak.latent_dim:
            r, lat = cfg.q_lora_rank, ak.latent_dim
            lp |= {
                "w_dq": w((c, h, r), h, more),
                "q_norm": jnp.ones((c, r), dtype),
                "w_uq": w((c, r, nq * dk), r, more),
            } if r else {"wq": w((c, h, nq * dk), h, more)}
            lp |= {
                "w_dkv": w((c, h, lat + rot), h, more),
                "kv_norm": jnp.ones((c, lat), dtype),
                "w_ukv": w((c, lat, nq * (dk - rot + dv)), lat, more),
            }
        else:
            lp |= {
                "wq": w((c, h, nq * dk), h),
                "wk": w((c, h, nkv * dk), h),
                "wv": w((c, h, nkv * dv), h),
            }
        lp["wo"] = w((c, nq * dv, h), nq * dv)
        if cfg.head_gate:
            lp["w_head_gate"] = w((c, h, nq), h, gates)
        if cfg.hc_mult > 1:
            n = cfg.hc_mult
            for sub in ("attn", "mlp"):
                lp[f"hc_{sub}_phi"] = w(
                    (c, n * h, 2 * n + n * n), n * h, more)
                lp[f"hc_{sub}_alpha"] = 1.0 + 0.1 * jax.random.normal(
                    next(more), (c, 3), F32)
                lp[f"hc_{sub}_b"] = 0.5 * jax.random.normal(
                    next(more), (c, 2 * n + n * n), F32)
        if ak.sink:
            lp["sink"] = jax.random.normal(
                next(keys), (c, nq), jnp.float32)
        if cfg.qkv_bias:
            lp["bq"] = w((c, nq * dk), 4)
            lp["bk"] = w((c, nkv * dk), 4)
            lp["bv"] = w((c, nkv * dv), 4)
        if routed:
            e, f = cfg.local_experts, cfg.moe_intermediate_size
            lp["router"] = w((c, h, cfg.router_experts), h)
            if cfg.router_bias:
                lp["router_bias"] = 0.1 * jax.random.normal(
                    next(keys), (c, cfg.router_experts), jnp.float32)
            lp["w_gate"] = w((c, e, h, f), h)
            lp["w_up"] = w((c, e, h, f), h)
            lp["w_down"] = w((c, e, f, h), f)
            if cfg.shared_experts:
                fs = f * cfg.shared_experts
                lp["ws_gate"] = w((c, h, fs), h, more)
                lp["ws_up"] = w((c, h, fs), h, more)
                lp["ws_down"] = w((c, fs, h), fs, more)
        else:
            i = cfg.intermediate_size
            lp["w_gate"] = w((c, h, i), h)
            lp["w_up"] = w((c, h, i), h)
            lp["w_down"] = w((c, i, h), i)
        segments.append(lp)
    params = {
        "embed": w((v, h), h),
        "segments": segments,
        "final_norm": jnp.ones((h,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((h, v), h)
    return params


def _init_blocks(cfg: ModelConfig, key: jax.Array, dtype) -> dict:
    """`init_params` for a stack of single-sublayer blocks: per unit of
    `cfg.tree_units()` a list with one stacked tree a letter. The mixer's
    own parameters as Mamba-2 initialises them (A_log = log U(1, 16),
    dt_bias the inverse softplus of a log-uniform step in [1e-3, 1e-1],
    D ones); the convolution's bias and the router's selection bias
    non-zero, so that dropping one shows against the reference."""
    h, v = cfg.hidden_size, cfg.vocab_size
    keys = iter(jax.random.split(
        key, 16 * sum(len(u[0]) for u in cfg.tree_units()) + 4))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(dtype)

    def block(letter, c):
        lp = {"norm": jnp.ones((c, h), dtype)}
        if letter == "M":
            d, cd, nh = cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_heads
            step = jnp.exp(jax.random.uniform(
                next(keys), (c, nh), F32, jnp.log(1e-3), jnp.log(1e-1)))
            return lp | {
                "w_in": w((c, h, d + cd + nh), h),
                "conv_w": w((c, cfg.ssm_conv, cd), cfg.ssm_conv),
                "conv_b": w((c, cd), 100),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (c, nh), F32, 1.0, 16.0)),
                "D": jnp.ones((c, nh), F32),
                "ssm_norm": jnp.ones((c, d), dtype),
                "w_out": w((c, d, h), d),
            }
        if letter == "K":
            # the gated delta rule's published initialisation
            nh, vd, kd = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
            step = jnp.exp(jax.random.uniform(
                next(keys), (c, nh * kd), F32, jnp.log(1e-3),
                jnp.log(1e-1)))
            return lp | {
                # [v | k | q | f_a | g_a | beta]: one product a row
                "w_in": w((c, h, cfg.ssm_conv_dim + 2 * kd + nh), h),
                "conv_w": w((c, cfg.ssm_conv, cfg.ssm_conv_dim),
                            cfg.ssm_conv),
                "w_fb": w((c, kd, nh * kd), kd),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (c, nh), F32, 1.0, 16.0)),
                "w_gb": w((c, kd, nh * vd), kd),
                "o_norm": jnp.ones((c, vd), dtype),
                "w_o": w((c, nh * vd, h), nh * vd),
            }
        if letter == "-":
            i = cfg.intermediate_size
            lp |= {"w_up": w((c, h, i), h), "w_down": w((c, i, h), i)}
            if cfg.mlp_gated:
                lp["w_gate"] = w((c, h, i), h)
            return lp
        if letter == "*" and cfg.kinds[0].latent_dim:
            ak = cfg.kinds[0]
            nq, lat, rot = ak.num_heads, ak.latent_dim, ak.rotary_dim
            r, dk = cfg.q_lora_rank, cfg.head_dim
            q = {"w_dq": w((c, h, r), h), "q_norm": jnp.ones((c, r), dtype),
                 "w_uq": w((c, r, nq * dk), r)} if r else {
                     "wq": w((c, h, nq * dk), h)}
            return lp | q | {
                "w_dkv": w((c, h, lat + rot), h),
                "kv_norm": jnp.ones((c, lat), dtype),
                "w_ukv": w((c, lat, nq * (dk - rot + cfg.v_dim)), lat),
                "wo": w((c, nq * cfg.v_dim, h), nq * cfg.v_dim),
            }
        if letter == "*":
            ak = cfg.kinds[0]
            nq, nkv = ak.num_heads, ak.num_kv_heads
            return lp | {
                "wq": w((c, h, nq * cfg.head_dim), h),
                "wk": w((c, h, nkv * cfg.head_dim), h),
                "wv": w((c, h, nkv * cfg.v_dim), h),
                "wo": w((c, nq * cfg.v_dim, h), nq * cfg.v_dim),
            }
        e, f, lat = (cfg.local_experts, cfg.moe_intermediate_size,
                     cfg.expert_width)
        lp |= {"router": w((c, h, cfg.router_experts), h),
               "w_up": w((c, e, lat, f), lat),
               "w_down": w((c, e, f, lat), f)}
        if cfg.router_bias:
            lp["router_bias"] = 0.1 * jax.random.normal(
                next(keys), (c, cfg.router_experts), F32)
        if cfg.mlp_gated:
            lp["w_gate"] = w((c, e, lat, f), lat)
        if cfg.moe_latent_size:
            lp["w_lat_in"] = w((c, h, lat), h)
            lp["w_lat_out"] = w((c, lat, h), lat)
        if cfg.shared_experts:
            fs = f * cfg.shared_experts
            lp |= {"ws_up": w((c, h, fs), h), "ws_down": w((c, fs, h), fs)}
            if cfg.mlp_gated:
                lp["ws_gate"] = w((c, h, fs), h)
        return lp

    params = {
        "embed": w((v, h), h),
        "segments": [[block(letter, c) for letter in unit]
                     for unit, c, _, _ in cfg.tree_units()],
        "final_norm": jnp.ones((h,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((h, v), h)
    return params


# a routed run's expert weights: kept out of the scan's per-layer slices
# and handed to the expert layer as whole stacks with the layer's index
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def hc_mix(cfg, x, phi, alpha, b):
    """The mixing matrices of one sublayer from the streams `x` (n,
    rows, h): (H_pre (n, rows), H_post (n, rows), H_res (n, n, rows)),
    float32, rows on the minor axis so that the small matrices of all
    rows fill whole vector tiles. H_res[i, j] weighs stream j in new
    stream i and is doubly stochastic up to `hc_eps`."""
    n, rows, h = x.shape
    xf = x.astype(F32)
    ms = jnp.mean(xf * xf, axis=(0, 2))                    # (rows,)
    xt = xf * jax.lax.rsqrt(ms + cfg.rms_norm_eps)[None, :, None]
    proj = jnp.einsum(
        "jrh,jhk->kr", xt, phi.astype(F32).reshape(n, h, -1),
        precision=jax.lax.Precision.HIGHEST,
    )                                                      # (2n + n^2, rows)
    a, b = alpha.astype(F32), b.astype(F32)[:, None]
    pre = jax.nn.sigmoid(a[0] * proj[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * proj[n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(a[2] * proj[2 * n:] + b[2 * n:],
                           *cfg.hc_res_clamp)).reshape(n, n, rows)
    res = sinkhorn(res, cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return pre, post, res


def _sublayer(cfg, x, lp, sub, fn):
    """x + fn(x) on one residual stream; with hyper-connections
    (`cfg.hc_mult` > 1, x the (n, rows, h) streams) fn reads a learned
    mixture of the streams and its result is spread back over them
    (the module docstring has the equations). `fn` takes (rows, h) and
    gives (its (rows, h) result in the model's dtype, whatever else it
    has to hand on), which comes back beside the new x."""
    if cfg.hc_mult == 1:
        y, aux = fn(x)
        return x + y, aux
    with jax.named_scope("hc_mix"):
        pre, post, res = hc_mix(
            cfg, x, lp[f"hc_{sub}_phi"], lp[f"hc_{sub}_alpha"],
            lp[f"hc_{sub}_b"])
        xf = x.astype(F32)
        u = jnp.sum(pre[:, :, None] * xf, axis=0).astype(x.dtype)
    y, aux = fn(u)
    with jax.named_scope("hc_mix"):
        out = (jnp.sum(res[:, :, :, None] * xf[None], axis=1)
               + post[:, :, None] * y.astype(F32)[None])
        return out.astype(x.dtype), aux


def _latent_qkv(cfg, ak, x, lp, kc, l, write_slots, cos, sin, dtype):
    """Latent attention's cache write and absorbed query: -> (q (n, nq,
    latent + rope) in `dtype`, kc with the rows' [c; rope(k_r)] written
    at `write_slots`, W_uv (latent, nq, d_v)). `cos` None: no positional
    encoding, the shared key dims are cached as projected."""
    n = x.shape[0]
    ak = cfg.kind_of(ak)
    nq, dk, dv = ak.num_heads, cfg.head_dim, cfg.v_dim
    lat, rot = ak.latent_dim, ak.rotary_dim
    nope = dk - rot
    if cfg.q_lora_rank:
        cq = rms_norm(
            jnp.dot(x, lp["w_dq"], preferred_element_type=F32).astype(dtype),
            lp["q_norm"], cfg.rms_norm_eps)
        q = jnp.dot(cq, lp["w_uq"], preferred_element_type=F32)
    else:
        q = jnp.dot(x, lp["wq"], preferred_element_type=F32)
    q = q.astype(dtype).reshape(n, nq, dk)
    ckv = jnp.dot(x, lp["w_dkv"], preferred_element_type=F32).astype(dtype)
    c = rms_norm(ckv[:, :lat], lp["kv_norm"], cfg.rms_norm_eps)
    q_rope, k_rope = q[..., nope:], ckv[:, None, lat:]
    if cos is not None:
        q_rope, k_rope = apply_rope(q_rope, k_rope, cos, sin)
    w_ukv = lp["w_ukv"].reshape(lat, nq, nope + dv)
    q_lat = jnp.einsum(
        "nhd,lhd->nhl", q[..., :nope], w_ukv[..., :nope],
        preferred_element_type=F32).astype(dtype)
    row = jnp.concatenate([c, k_rope[:, 0]], axis=-1).astype(kc.dtype)
    if kc.shape[-1] > lat + rot:
        # stored wider (zero lanes up to the kernel's 128-lane tile)
        row = jnp.pad(row, ((0, 0), (0, kc.shape[-1] - lat - rot)))
    kc = kc.at[l, 0, write_slots].set(row)
    return (jnp.concatenate([q_lat, q_rope], axis=-1), kc,
            w_ukv[..., nope:])


def _layer(cfg, kind, routed, h, kc, vc, stats, lp, l, *, cos, sin,
           write_slots, real, attn_fn, write_kv, dtype,
           experts=None, stack_index=None):
    """One layer of `kind` over n rows: llama.decoder_layer's shape
    (K/V written at `write_slots` BEFORE attn_fn runs) with this
    family's widths. `kc`/`vc` are the kind's own cache arrays (`vc`
    None for a latent kind) and `l` indexes them. `real` (n,) bool: the
    rows that are tokens; the rest (padding, idle lanes, lanes a device
    stop froze) write the null block's slot 0. `h` is (n, hidden), or
    the (hc_mult, n, hidden) streams under hyper-connections."""
    ak = cfg.kinds[kind]
    n = h.shape[-2]
    nq, nkv = ak.num_heads, ak.num_kv_heads
    dk, dv = cfg.head_dim, cfg.v_dim

    def proj(x, name, bias):
        out = jnp.dot(x, lp[name], preferred_element_type=jnp.float32)
        return out + lp[bias].astype(jnp.float32) if cfg.qkv_bias else out

    def attention(u, kc=kc, vc=vc):
        x = rms_norm(u, lp["attn_norm"], cfg.rms_norm_eps,
                     cfg.norm_weight_offset)
        w_uv = None
        if ak.latent_dim:
            q, kc, w_uv = _latent_qkv(
                cfg, ak, x, lp, kc, l, write_slots, cos, sin, dtype)
        else:
            q = proj(x, "wq", "bq").astype(dtype).reshape(n, nq, dk)
            k = proj(x, "wk", "bk").astype(dtype).reshape(n, nkv, dk)
            v = proj(x, "wv", "bv")
            if cfg.v_scale != 1.0:
                v = v * cfg.v_scale
            v = v.astype(dtype).reshape(n, nkv, dv)
            q, k = apply_rope(q, k, cos, sin)
            kc, vc = write_kv(kc, vc, l, write_slots, k, v)

        spec = AttnSpec(
            window=ak.window,
            sink=lp["sink"] if ak.sink else None,
            mapped=bool(ak.window),
            latent_v=ak.latent_dim or None,
        )
        attn_out = attn_fn(q, l, kc, vc, spec)  # (n, nq, d_v | latent)
        # the paged kernels store a segment's rows into their output
        # tile and leave the tile's other rows as the tile held them:
        # now and then not a number (on the chip: tokens 0 and NaN
        # log-probabilities after rounds with padded rows, PR 28). In a
        # stack of alike layers such a row stays its own. Here it would
        # reach real rows: through the routed layer's row matrices (0 x
        # NaN), and through the null block it writes, which a windowed
        # lane reads, masked, for the pages it let go
        attn_out = jnp.where(real[:, None, None], attn_out, 0)
        if cfg.head_gate:
            with jax.named_scope("attn_gate"):
                # one scalar a head and row, from the normed layer
                # input, in float32 (rows that are no tokens are zero
                # above, and their x is finite: nothing to mask again)
                gate = jax.nn.sigmoid(jnp.dot(
                    x, lp["w_head_gate"], preferred_element_type=F32))
                attn_out = attn_out.astype(F32) * gate[:, :, None]
        if w_uv is not None:
            attn_out = jnp.einsum(
                "nhl,lhd->nhd", attn_out.astype(dtype), w_uv,
                preferred_element_type=F32)
        return jnp.dot(
            attn_out.reshape(n, nq * dv).astype(dtype), lp["wo"],
            preferred_element_type=jnp.float32,
        ).astype(dtype), (kc, vc)

    def mlp(u):
        x = rms_norm(u, lp["mlp_norm"], cfg.rms_norm_eps,
                     cfg.norm_weight_offset)
        if not routed:
            return swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"],
                          act=cfg.hidden_act), 0
        # rows that are no tokens keep no pair
        y, st = routed_experts(
            x, lp["router"], lp.get("router_bias"),
            *(experts[name] for name in EXPERT_STACKS),
            stack_index=stack_index,
            top_k=cfg.num_experts_per_tok,
            first_expert=cfg.ep_rank * cfg.local_experts,
            scoring=cfg.router_scoring, renorm=cfg.router_renorm,
            scale=cfg.routed_scaling, valid=real,
        )
        if cfg.shared_experts:
            with jax.named_scope("shared_expert"):
                y = y + swiglu(
                    x, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                    act=cfg.hidden_act).astype(F32)
        return y.astype(dtype), st

    h, (kc, vc) = _sublayer(cfg, h, lp, "attn", attention)
    h, st = _sublayer(cfg, h, lp, "mlp", mlp)
    if routed:
        stats = stats + st
    return h, kc, vc, stats


def forward(
    cfg: ModelConfig,
    params: dict,
    token_ids: jax.Array,   # (n,) int32
    positions: jax.Array,   # (n,) int32
    k_cache: dict,          # {"g": per-kind arrays, "map", "stats"}
    v_cache: dict,          # {"g": per-kind arrays; None for a latent
                            # kind, whose rows are keys and values}
    write_slots: jax.Array,  # (n,) int32 slots in KIND 0's pool
    attn_fn,                # attn_fn(q, l, kc, vc, spec) -> (n, nq, d_v),
                            # nq the layer kind's query heads
    logits_rows: jax.Array,
    lora: dict | None = None,
    lora_slots: jax.Array | None = None,
    return_hidden: bool = False,
    *,
    block_size: int,
    write_kv=scatter_kv,    # the layers' cache write (ops/cache_write.py)
    state_rows: tuple[int, int, int] | None = None,  # a model with
    # recurrent state: the program's shape as `ssm.plan_rows` takes it
    # (lanes, rows a lane at most, trailing one-token rows)
):
    """llama.forward's contract over a cache group per kind; returns
    (logits[r, V] fp32, k_cache, v_cache)."""
    assert lora is None, "LoRA is refused at start-up for layer groups"
    if cfg.block_pattern:
        return forward_blocks(
            cfg, params, token_ids, positions, k_cache, v_cache,
            write_slots, attn_fn, logits_rows, return_hidden,
            block_size=block_size, write_kv=write_kv,
            state_rows=state_rows)
    dtype = params["embed"].dtype
    block_map = k_cache["map"]
    kg, vg = list(k_cache["g"]), list(v_cache["g"])
    stats = k_cache["stats"]
    # a token's row never writes slot 0 (the null block's)
    real = write_slots > 0
    # a windowed kind's slot for a row: the row's primary block mapped
    # into that pool, same offset in the block
    mapped_slots = None
    if any(ak.window for ak in cfg.attn_kinds):
        mapped_slots = (
            block_map[write_slots // block_size] * block_size
            + write_slots % block_size
        )
    rope = [rope_cos_sin(positions, ak.rotary_dim, ak.rope_theta,
                         ak.rope_yarn, ak.rope_factor)
            for ak in cfg.kinds]

    h = params["embed"][token_ids].astype(dtype)
    if cfg.embed_scale != 1.0:
        h = (h.astype(jnp.float32) * cfg.embed_scale).astype(dtype)
    if cfg.hc_mult > 1:
        # the streams start as copies of the embedding
        h = jnp.broadcast_to(h, (cfg.hc_mult, *h.shape))

    with jax.named_scope("layers"):
        for lp_stack, (kind, routed, count, l0) in zip(
            params["segments"], cfg.segments()
        ):
            ak = cfg.attn_kinds[kind]
            cos, sin = rope[kind]
            slots = mapped_slots if ak.window is not None else write_slots
            if not ak.latent_dim:
                # once a segment, not once a layer (`write_kv` reads it)
                slots = plan_rows(slots, kg[kind])

            experts = None
            if routed:
                experts = {n: lp_stack[n] for n in EXPERT_STACKS}
                lp_stack = {n: a for n, a in lp_stack.items()
                            if n not in EXPERT_STACKS}

            def body(carry, xs, kind=kind, routed=routed, cos=cos,
                     sin=sin, slots=slots, experts=experts):
                h, kc, vc, st = carry
                lp, l, c = xs
                h, kc, vc, st = _layer(
                    cfg, kind, routed, h, kc, vc, st, lp, l,
                    cos=cos, sin=sin, write_slots=slots,
                    real=real, attn_fn=attn_fn, write_kv=write_kv,
                    dtype=dtype, experts=experts, stack_index=c,
                )
                return (h, kc, vc, st), None

            (h, kg[kind], vg[kind], stats), _ = jax.lax.scan(
                body, (h, kg[kind], vg[kind], stats),
                (lp_stack, l0 + jnp.arange(count), jnp.arange(count)),
            )

    k_cache = {"g": tuple(kg), "map": block_map, "stats": stats}
    v_cache = {"g": tuple(vg)}
    return _head(cfg, params, h, logits_rows, return_hidden, k_cache,
                 v_cache)


def _head(cfg, params, h, logits_rows, return_hidden, k_cache, v_cache):
    """The final norm and the head over the asked rows."""
    dtype = params["embed"].dtype
    if cfg.hc_mult > 1:
        # and are summed before the final norm: the head's rows only
        h = jnp.sum(
            h[:, logits_rows].astype(F32), axis=0).astype(dtype)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps,
                 cfg.norm_weight_offset)
    h_sel = h if cfg.hc_mult > 1 else h[logits_rows]
    if return_hidden:
        return h_sel.astype(jnp.float32), k_cache, v_cache
    lm_head = (
        params["embed"].T if cfg.tie_word_embeddings
        else params["lm_head"]
    )
    with jax.named_scope("lm_head"):
        logits = jnp.dot(h_sel, lm_head, preferred_element_type=jnp.float32)
    return logits, k_cache, v_cache


def _mlp_ungated(x, w_up, w_down, act: str):
    """act(x W_up) W_down, float32 out."""
    a = activation(act, jnp.dot(x, w_up, preferred_element_type=F32))
    return jnp.dot(a.astype(x.dtype), w_down, preferred_element_type=F32)


LANES = 128  # the minor tile of the chip's memory


def _stored_turned(a) -> bool:
    """Whether the chip holds the stack of matrices `a` (L, m, n) with m
    and not n as its minor dimension: it does where n would be padded to
    the tile and m would not (the KDA in-projection, 2304 x 12576: 12576
    is 98.25 tiles). A loop carries its operands in the default order,
    so handed such a stack as it is written the compiler copies the
    whole of it into that order once a program (232 MB: 0.75 ms a decode
    round, and as much memory; my chip runs, PRs 51 and 52)."""
    return a.ndim == 3 and a.shape[2] % LANES != 0 and (
        a.shape[1] % LANES == 0)


def forward_blocks(cfg, params, token_ids, positions, k_cache, v_cache,
                   write_slots, attn_fn, logits_rows, return_hidden, *,
                   block_size, write_kv, state_rows):
    """`forward` for a stack of single-sublayer blocks
    (`cfg.block_pattern`): one attention cache group (plain or
    latent), the state group `k_cache["ssm"]` and the maps
    `k_cache["smap"]` beside it."""
    assert state_rows is not None or not cfg.ssm_layers, (
        "a program that reaches a state-space layer says its rows' shape")
    dtype = params["embed"].dtype
    ak = cfg.kinds[0]
    nq, nkv, dk, dv = ak.num_heads, ak.num_kv_heads, cfg.head_dim, cfg.v_dim
    kc, vc = k_cache["g"][0], v_cache["g"][0]
    stats, state = k_cache["stats"], k_cache.get("ssm")
    real = write_slots > 0
    n = token_ids.shape[0]
    plan = None
    if cfg.ssm_layers:
        plan = ssm.plan_rows(write_slots, positions, k_cache["smap"],
                             block_size, *state_rows)
    slots = write_slots if ak.latent_dim else plan_rows(write_slots, kc)
    cos = sin = None
    if cfg.rope:
        cos, sin = rope_cos_sin(positions, ak.rotary_dim, ak.rope_theta,
                                ak.rope_yarn, ak.rope_factor)
    spec = AttnSpec(window=None, sink=None, mapped=False,
                    latent_v=ak.latent_dim or None)
    act = cfg.hidden_act

    def latent_attention(x, lp, l, kc, vc):
        q, kc, w_uv = _latent_qkv(
            cfg, ak, x, lp, kc, l, slots, cos, sin, dtype)
        out = jnp.where(real[:, None, None],
                        attn_fn(q, l, kc, vc, spec), 0)
        out = jnp.einsum("nhl,lhd->nhd", out.astype(dtype), w_uv,
                         preferred_element_type=F32)
        return jnp.dot(out.reshape(n, nq * dv).astype(dtype), lp["wo"],
                       preferred_element_type=F32).astype(dtype), kc, vc

    def attention(x, lp, l, kc, vc):
        q = jnp.dot(x, lp["wq"], preferred_element_type=F32).astype(
            dtype).reshape(n, nq, dk)
        k = jnp.dot(x, lp["wk"], preferred_element_type=F32).astype(
            dtype).reshape(n, nkv, dk)
        v = jnp.dot(x, lp["wv"], preferred_element_type=F32).astype(
            dtype).reshape(n, nkv, dv)
        if cfg.rope:
            q, k = apply_rope(q, k, cos, sin)
        kc, vc = write_kv(kc, vc, l, slots, k, v)
        # rows that are no tokens come back as the tile held them
        # (`_layer` has the story): zero before they meet a matrix
        out = jnp.where(real[:, None, None],
                        attn_fn(q, l, kc, vc, spec), 0)
        return jnp.dot(out.reshape(n, nq * dv).astype(dtype), lp["wo"],
                       preferred_element_type=F32).astype(dtype), kc, vc

    def mlp(x, lp, pre):
        """A dense MLP (`pre` "w_") or the shared expert ("ws_"), gated
        or not, float32 out."""
        if cfg.mlp_gated:
            return swiglu(x, lp[pre + "gate"], lp[pre + "up"],
                          lp[pre + "down"], act=act).astype(F32)
        return _mlp_ungated(x, lp[pre + "up"], lp[pre + "down"], act)

    def experts(x, lp, stacks, i):
        lat = x
        if cfg.moe_latent_size:
            lat = jnp.dot(x, lp["w_lat_in"],
                          preferred_element_type=F32).astype(dtype)
        y, st = routed_experts(
            x, lp["router"], lp.get("router_bias"),
            stacks.get("w_gate"), stacks["w_up"], stacks["w_down"],
            stack_index=i, top_k=cfg.num_experts_per_tok,
            first_expert=cfg.ep_rank * cfg.local_experts,
            scoring=cfg.router_scoring, renorm=cfg.router_renorm,
            scale=cfg.routed_scaling, valid=real, act=act,
            expert_x=lat if cfg.moe_latent_size else None,
        )
        if cfg.moe_latent_size:
            y = jnp.dot(y.astype(dtype), lp["w_lat_out"],
                        preferred_element_type=F32)
        if cfg.shared_experts:
            with jax.named_scope("shared_expert"):
                y = y + mlp(x, lp, "ws_")
        return y.astype(dtype), st

    def block(letter, lp, stack, i, l, carry):
        """One block: `lp` its own parameters, `stack` its experts'
        whole stacks and `i` its index in them, `l` its layer in the
        attention cache group or the state group."""
        h, kc, vc, st, state = carry
        x = rms_norm(h, lp["norm"], cfg.rms_norm_eps,
                     cfg.norm_weight_offset)
        if letter in "MK":
            # one state group, whichever recurrence
            f, state = (ssm if letter == "M" else kda).mixer(
                cfg, x, lp, state, l, plan)
        elif letter == "*":
            f, kc, vc = (latent_attention if ak.latent_dim
                         else attention)(x, lp, l, kc, vc)
        elif letter == "-":
            f = mlp(x, lp, "w_").astype(dtype)
        else:
            f, s = experts(x, lp, stack, i)
            st = st + s
        return h + f, kc, vc, st, state

    def split(letter, lp):
        """(what a scan or an index slices, the experts' stacks, which
        stay whole)."""
        if letter != "E":
            return lp, None
        return ({k: a for k, a in lp.items() if k not in EXPERT_STACKS},
                {k: lp[k] for k in EXPERT_STACKS if k in lp})

    h = params["embed"][token_ids].astype(dtype)
    if cfg.embed_scale != 1.0:
        h = (h.astype(F32) * cfg.embed_scale).astype(dtype)
    if cfg.switched:
        # ONE scan over the blocks in the pattern's order; each runs
        # its letter's body with its index in that letter's stack,
        # which is also its layer in its cache or state group, and a
        # program traces each kind ONCE
        letters = [u for u, _, _, _ in cfg.tree_units()]
        parts = [split(c, seg[0]) for c, seg in zip(
            letters, params["segments"])]
        seen: dict[str, int] = {}
        at = []
        for c in cfg.block_pattern:
            at.append((letters.index(c), seen.get(c, 0)))
            seen[c] = seen.get(c, 0) + 1

        # what of the carry (h, kc, vc, stats, state) a kind touches
        touches = {"M": (0, 4), "K": (0, 4), "*": (0, 1, 2), "E": (0, 3),
                   "-": (0,)}

        def branch(n, c, sliced, stack):
            """`run(carry, kind, i)`: block kind `n` (letter `c`) at
            index `i` of its stack where `kind` is `n`, else nothing."""
            idx = touches[c]

            def take(carry):
                return tuple(carry[j] for j in idx)

            def put(carry, part):
                full = list(carry)
                for j, p in zip(idx, part):
                    full[j] = p
                return tuple(full)

            def on_part(lp, i, carry, part):
                return take(block(c, lp, stack, i, i, put(carry, part)))

            if cfg.block_pattern.count(c) == 1:
                # a kind that stands once: its weights are sliced here,
                # statically, and it runs under a `cond` on what it
                # touches. (As a loop its weights, which do not vary in
                # it, were prefetched in the pattern's scan: copied at
                # every block, ten times a step; my chip run, PR 51.)
                lp = jax.tree.map(lambda a: a[0], sliced)

                def run(carry, kind, i):
                    return put(carry, jax.lax.cond(
                        kind == n,
                        lambda p: on_part(lp, jnp.int32(0), carry, p),
                        lambda p: p, take(carry)))
                return run

            # a stack of matrices goes into the loop as the chip holds
            # it: a transpose that is a bitcast there. The block's own
            # slice is turned back, and the matrix product takes that
            # as its dimension numbers
            turned = jax.tree.map(_stored_turned, sliced)
            stored = jax.tree.map(
                lambda a, tr: jnp.swapaxes(a, 1, 2) if tr else a,
                sliced, turned)

            def run(carry, kind, i):
                # a loop of one turn or none carries the state pool in
                # place, where a `cond` copied it (1.1 GB a block: a
                # step 27 ms for 3). The index rides the turn's counter:
                # one that does not vary in the loop is hoisted out of
                # it with the slices of the weights
                return put(carry, jax.lax.fori_loop(
                    0, (kind == n).astype(jnp.int32),
                    lambda t, p: on_part(
                        jax.tree.map(
                            lambda a, tr: a[i + t].T if tr else a[i + t],
                            stored, turned), i + t,
                        carry, p),
                    take(carry)))
            return run

        # the kinds that stand once come first in a turn. Behind the
        # other kinds' loops the compiler had the time to fetch their
        # largest matrix (the latent block's `wo`, 19 MB) into VMEM
        # ahead of the `cond`: in every turn, ten times a step for the
        # one that uses it (0.15 ms of a 3.7 ms decode step). First in
        # the turn there is nothing to hide a fetch behind, and it is
        # left to the branch that is taken
        branches = [branch(n, c, *part) for n, (c, part) in sorted(
            enumerate(zip(letters, parts)),
            key=lambda e: cfg.block_pattern.count(e[1][0]) != 1)]

        def body(carry, xs):
            for run in branches:
                carry = run(carry, xs[0], xs[1])
            return carry, None

        with jax.named_scope("layers"):
            (h, kc, vc, stats, state), _ = jax.lax.scan(
                body, (h, kc, vc, stats, state),
                jnp.asarray(at, jnp.int32))
        return _tail(cfg, params, h, logits_rows, return_hidden, k_cache,
                     kc, vc, stats, state)
    with jax.named_scope("layers"):
        for blocks, (unit, count, a0, m0) in zip(
                params["segments"], cfg.units()):
            n_attn = unit.count("*")
            n_ssm = unit.count("M") + unit.count("K")
            sliced, stacks = zip(*(split(c, lp)
                                   for c, lp in zip(unit, blocks)))

            def body(carry, xs, unit=unit, stacks=stacks, a0=a0, m0=m0,
                     n_attn=n_attn, n_ssm=n_ssm):
                lps, i = xs
                seen = {"*": 0, "M": 0}
                for letter, lp, stack in zip(unit, lps, stacks):
                    kind = "M" if letter in "MK" else letter
                    l = None
                    if kind in seen:
                        l = (a0 + i * n_attn if kind == "*"
                             else m0 + i * n_ssm) + seen[kind]
                        seen[kind] += 1
                    carry = block(letter, lp, stack, i, l, carry)
                return carry, None

            (h, kc, vc, stats, state), _ = jax.lax.scan(
                body, (h, kc, vc, stats, state),
                (list(sliced), jnp.arange(count)))
    return _tail(cfg, params, h, logits_rows, return_hidden, k_cache, kc,
                 vc, stats, state)


def _tail(cfg, params, h, logits_rows, return_hidden, k_cache, kc, vc,
          stats, state):
    """`forward_blocks`' way out: the caches put back, then the head."""
    k_cache = {**k_cache, "g": (kc,), "stats": stats}
    if state is not None:
        k_cache["ssm"] = state
    return _head(cfg, params, h, logits_rows, return_hidden, k_cache,
                 {"g": (vc,)})
