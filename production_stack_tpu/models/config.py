"""Model architecture configs for the decoder families the engine serves.

One config dataclass covers Llama 2/3, Mistral, Qwen2 (qkv bias), Mixtral
(MoE), Phi-3 (fused qkv/gate_up), Gemma (GeGLU + zero-centered norms +
scaled embeddings), TinyLlama variants, and stacks of LAYER GROUPS
(`attn_kinds` / `layer_kinds`: window and full attention layers with
their own kv heads, rope theta and caches, a routed expert layer that is
told which experts it holds; models/layer_groups.py) — the family the reference stack's tutorials deploy (Llama-3.1-8B in
reference: tutorials/08-benchmark-multi-round-qa-multi-gpu.md, opt-125m-sized
configs for CI-scale tests).

Presets are resolvable by name so the engine can run weight-free (random init)
for benchmarks and tests; `from_hf_config` maps a HuggingFace config.json so
real checkpoints load when present on disk.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
from dataclasses import dataclass

from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)


@dataclass(frozen=True)
class AttnKind:
    """One kind of attention layer in a stack of layer groups. Each kind
    has its own KV cache arrays (a cache group, engine/model_runner.py)."""
    num_kv_heads: int
    rope_theta: float
    window: int | None = None  # keys j with q_pos - window < j <= q_pos
    sink: bool = False         # a learned per-q-head logit in the
                               # softmax denominator (nothing added to
                               # the numerator)
    # latent attention (DeepSeek-V2's MLA): the cache holds ONE row a
    # token, `latent_dim` normed latent dims and then the rotary key
    # dims, read by every q head as the key (all of it) and as the
    # value (its first `latent_dim` dims): one array a layer, no V
    # array. 0 = keys and values of their own
    latent_dim: int = 0
    # what a kind may have of its own and otherwise takes from the model
    # (`ModelConfig.kinds` fills a None in): its query heads (wq, wo and
    # the GQA group follow them; the kv heads are the kind's anyway),
    # the leading dims of a head that rotate, and YaRN on those
    num_heads: int | None = None
    rotary_dim: int | None = None
    rope_yarn: YarnScaling | None = None
    # multiplies cos and sin (HF `rope_parameters.attention_factor`, as
    # the public YaRN implementation of that key reads it: only the
    # rotated dims of a dot product carry its square). None = YaRN's
    # mscale / mscale_all_dim ratio, 1 without YaRN. NOT the softmax
    # scale's mscale squared (`ModelConfig.attn_scale`, DeepSeek's
    # reading), which is the model's and covers every dim
    rope_factor: float | None = None


@dataclass(frozen=True)
class YarnScaling:
    """`rope_scaling` of `type: yarn` as DeepSeek-V3's modelling code
    reads it (ops/layers.rope_cos_sin, yarn_mscale)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_model_len: int = 8192
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    qkv_bias: bool = False  # Qwen2-style attention bias
    # family knobs beyond the Llama defaults:
    hidden_act: str = "silu"  # "gelu_tanh" for the Gemma family
    norm_weight_offset: float = 0.0  # Gemma stores RMSNorm w zero-centered
    embed_scale: float = 1.0  # Gemma scales embeddings by sqrt(hidden)
    # sliding-window attention (Phi-3-mini, Mistral-v0.1): each token
    # attends to at most this many predecessors; None = full context.
    # Both attention paths mask it; the paged kernels' page walk
    # (ops/pallas_attention._walk) starts at the window's first KV
    # block, so earlier pages never stream in. ONE window for every
    # layer; a stack that mixes windows says so in `attn_kinds`
    sliding_window: int | None = None
    # MoE (Mixtral family): 0 experts = dense MLP. capacity_factor 0
    # selects the exact all-experts einsum path; > 0 the GShard
    # static-capacity dispatch (ops/moe.py)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_capacity_factor: float = 0.0
    # -- layer groups (models/layer_groups.py). Empty `attn_kinds` = a
    # stack of alike layers described by the scalar fields above
    # (models/llama.py). Otherwise layer i is of kind
    # attn_kinds[layer_kinds[i]]; `num_kv_heads`, `rope_theta` and
    # `sliding_window` above are then kind 0's and nothing reads them.
    attn_kinds: tuple[AttnKind, ...] = ()
    layer_kinds: tuple[int, ...] = ()
    # q/k head width is `head_dim`; v (and the attention output) may be
    # narrower, and rotary may cover only the leading dims of q and k
    v_head_dim: int | None = None   # None = head_dim
    rotary_dim: int | None = None   # None = head_dim
    v_scale: float = 1.0            # multiplies V before the cache
    # routed experts (ops/moe.routed_experts): `router_experts` is the
    # router's width — every expert of the deployment — of which this
    # engine holds the contiguous slice of expert-parallel rank
    # `ep_rank` of `ep_size`: the ONE place that says which experts
    # are here. 0 = no routed layer. The first `dense_layers` layers
    # keep a dense MLP of width `intermediate_size`; expert width is
    # `moe_intermediate_size`
    router_experts: int = 0
    router_scoring: str = "softmax"  # or "sigmoid"
    router_bias: bool = False        # selection by score + learned bias
    router_renorm: bool = True       # weights / their sum over chosen
    moe_intermediate_size: int = 0
    dense_layers: int = 0
    ep_rank: int = 0
    ep_size: int = 1
    # beside the routed experts: `shared_experts` experts of the routed
    # width that every row passes (one SwiGLU of that many widths), and
    # a factor on the routed sum's weights
    shared_experts: int = 0
    routed_scaling: float = 1.0
    # latent attention (a kind with `latent_dim`): q goes through a
    # normed bottleneck of `q_lora_rank` dims (0: projected directly);
    # a head is `head_dim` = nope dims then `rotary_dim` rotary dims,
    # and `v_head_dim` out
    q_lora_rank: int = 0
    rope_yarn: YarnScaling | None = None
    # hyper-connections (arXiv:2512.24880): `hc_mult` residual streams a
    # token, each sublayer reading a learned mixture of them and writing
    # back through a Sinkhorn-projected matrix. 1 = one plain stream
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple[float, float] = (-30.0, 30.0)
    # a looped stack (arXiv:2510.25741): the SAME `num_layers` layers
    # run `ut_steps` times a token, the final norm closing every pass;
    # each pass writes and reads K/V of its own (cache layer
    # t * num_layers + l), so the cache holds `cache_layers` layers.
    # `sandwich_norm`: a second RMSNorm on each sublayer's OUTPUT,
    # before the residual add. `exit_gate`: a learned scalar gate a
    # pass (sigmoid of a linear on the normed hidden state); served at
    # a threshold of 1, where no token leaves early, it is only read
    # out as a counter (tpu:loop_exit_mass). 1 / False = a stack that
    # runs once, as every other model here
    ut_steps: int = 1
    sandwich_norm: bool = False
    exit_gate: bool = False
    # a per-head output gate in a stack of layer groups (the head-wise
    # gate of arXiv:2505.06708): g = sigmoid(x W_g), one scalar a query
    # head and row from the normed layer input, multiplies that head's
    # attention output before W_o
    head_gate: bool = False
    # -- a stack of SINGLE-sublayer blocks (models/layer_groups.py,
    # `forward_blocks`): each layer is x + F(RMSNorm(x)) with ONE F, by
    # its letter in `block_pattern`: "M" a state-space (Mamba-2) mixer,
    # "K" a gated delta-rule linear-attention (KDA) mixer, "*" attention
    # (kind 0 of `attn_kinds`, latent where the kind says so;
    # `layer_kinds` then lists the attention blocks only), "E" routed
    # experts, "-" a dense MLP of `intermediate_size`. A letter a BLOCK:
    # `num_layers` letters where a published layer is one block, twice
    # as many where it is a mixer and a feed-forward part. "" = every
    # layer is attention followed by an MLP, as everywhere else
    block_pattern: str = ""
    rope: bool = True   # False: attention without positional encoding
    # the state-space mixer: `ssm_heads` heads of `ssm_head_dim`, B and
    # C in `ssm_groups` groups of `ssm_state` dims, a causal depthwise
    # convolution of `ssm_conv` taps over [x | B | C], the scan computed
    # in chunks of `ssm_chunk` rows. A sequence carries, a layer, the
    # (heads, head_dim, state) float32 state and the convolution's last
    # `ssm_conv - 1` rows: a STATE SLOT, not pages. The KDA mixer
    # (ops/kda.py) fills the same slot and is sized by the same fields:
    # `ssm_heads` heads with a (`ssm_state` key dims, `ssm_head_dim`
    # value dims) matrix state each, `ssm_groups` = `ssm_heads`, the
    # convolutions over [v | k | q] where Mamba-2 has [x | B | C]
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # routed experts at a LATENT width: rows go hidden -> latent before
    # the experts and back after their weighted sum (the router and the
    # shared expert read the hidden state). 0 = experts at hidden width
    moe_latent_size: int = 0
    # False: an MLP (dense, expert, shared) is up -> act -> down with no
    # gate matrix; `hidden_act` "relu2" is relu squared
    mlp_gated: bool = True

    def __post_init__(self):
        if self.ut_steps < 1 or (self.ut_steps > 1 and self.attn_kinds):
            raise ValueError(
                f"model {self.name}: ut_steps={self.ut_steps} needs a "
                "stack of alike layers run at least once (a looped stack "
                "of layer groups has no code path)"
            )
        pattern = self.block_pattern
        if pattern and (
            len(pattern) % self.num_layers or set(pattern) - set("MK*E-")
            or not self.attn_kinds
            or (self.ssm_layers and not (self.ssm_heads and self.ssm_state))
            or ("K" in pattern and self.ssm_groups != self.ssm_heads)
            or ("E" in pattern and not self.router_experts)
            or ("-" in pattern and not self.intermediate_size)
            or self.hc_mult > 1 or self.head_gate or self.dense_layers
        ):
            raise ValueError(
                f"model {self.name}: block_pattern {pattern!r} must give "
                f"one of M, K, *, E, - for each block of {self.num_layers} "
                "layers (as many blocks a layer throughout) of a stack of "
                "layer groups, with the state sizes "
                "for an M or a K (a K head has keys of its own: "
                "ssm_groups = ssm_heads), routed experts for an E and "
                "intermediate_size for a - (no residual streams, head "
                "gate or `dense_layers` there: a - says where a dense "
                "MLP stands)"
            )
        if "M" in pattern and "K" in pattern:
            raise ValueError(
                f"model {self.name}: block_pattern {pattern!r} has "
                "state-space (M) and delta-rule (K) mixers in one stack, "
                "which is not served: the state group holds one "
                "recurrence's slots")
        if self.attn_kinds:
            if len(self.layer_kinds) != (
                pattern.count("*") if pattern else self.num_layers
            ) or not all(
                0 <= k < len(self.attn_kinds) for k in self.layer_kinds
            ):
                raise ValueError(
                    f"model {self.name}: layer_kinds must give one of "
                    f"{len(self.attn_kinds)} attn_kinds for each of "
                    f"{self.num_layers} layers, got {self.layer_kinds}"
                )
            for ak in self.attn_kinds:
                y = ak.rope_yarn
                if y is not None and y.mscale_all_dim and y != self.rope_yarn:
                    raise ValueError(
                        f"model {self.name}: a kind's YaRN with "
                        f"mscale_all_dim={y.mscale_all_dim} would scale "
                        "that kind's softmax; the softmax scale is one a "
                        "model (`attn_scale` reads the model's rope_yarn)"
                    )
        elif self.head_gate:
            raise ValueError(
                f"model {self.name}: head_gate needs a stack of layer "
                "groups (models/llama.py has no output gate)"
            )
        if self.router_experts and (
            self.router_experts % self.ep_size
            or not 0 <= self.ep_rank < self.ep_size
        ):
            raise ValueError(
                f"model {self.name}: {self.router_experts} routed experts "
                f"do not divide over ep_size={self.ep_size} "
                f"(ep_rank={self.ep_rank})"
            )

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def cache_layers(self) -> int:
        """Layers of K/V a token holds in a cache of alike layers: one a
        layer and pass. The ONE count that whatever sizes, allocates or
        moves that cache reads."""
        return self.num_layers * self.ut_steps

    @property
    def layer_groups(self) -> bool:
        """True for a stack described by `attn_kinds` (served by
        models/layer_groups.py, one cache group per kind)."""
        return bool(self.attn_kinds)

    @property
    def v_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def rope_dim(self) -> int:
        return self.rotary_dim or self.head_dim

    def kind_of(self, ak: AttnKind) -> AttnKind:
        """A kind as the code reads it: every field it leaves to the
        model (None) filled in with the model's."""
        return dataclasses.replace(
            ak,
            num_heads=ak.num_heads or self.num_heads,
            rotary_dim=ak.rotary_dim or self.rope_dim,
            rope_yarn=ak.rope_yarn or self.rope_yarn,
        )

    @functools.cached_property
    def kinds(self) -> tuple[AttnKind, ...]:
        """`attn_kinds`, each as `kind_of` gives it."""
        return tuple(self.kind_of(ak) for ak in self.attn_kinds)

    @property
    def attn_scale(self) -> float:
        """The softmax scale: head_dim ** -0.5, times YaRN's mscale
        squared where the config scales every dim (`mscale_all_dim`)."""
        scale = self.head_dim ** -0.5
        y = self.rope_yarn
        if y is not None and y.mscale_all_dim:
            from production_stack_tpu.ops.layers import yarn_mscale

            scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
        return scale

    @property
    def local_experts(self) -> int:
        """Routed experts held here: ranks hold contiguous slices."""
        return self.router_experts // self.ep_size

    def segments(self) -> tuple[tuple[int, bool, int, int], ...]:
        """The stack as runs of alike layers: (kind, routed, count,
        index of the run's first layer WITHIN its kind's cache group).
        A program traces one layer per run, so its size grows with the
        runs, not the layers."""
        runs: list[list] = []
        seen = [0] * len(self.attn_kinds)
        for i, kind in enumerate(self.layer_kinds):
            routed = bool(self.router_experts) and i >= self.dense_layers
            if runs and runs[-1][0] == kind and runs[-1][1] == routed:
                runs[-1][2] += 1
            else:
                runs.append([kind, routed, 1, seen[kind]])
            seen[kind] += 1
        return tuple(tuple(r) for r in runs)

    def units(self) -> tuple[tuple[str, int, int, int], ...]:
        """A stack of single-sublayer blocks as runs of a repeating
        UNIT of unlike blocks: (unit, count, index of the run's first
        "*" within the attention cache group, the same for its first
        "M" or "K" within the state group). One `lax.scan` walks a run, so a
        program traces each unit once: the cover with the fewest traced
        bodies (EMEMEMEMEM* = "EM" x 5 and "*": two units, three
        bodies)."""
        p, n = self.block_pattern, len(self.block_pattern)
        best: list[tuple[int, tuple]] = [(0, ())] * (n + 1)
        for i in range(n - 1, -1, -1):
            cands = []
            for size in range(1, n - i + 1):
                unit, reps = p[i:i + size], 1
                while p[i + reps * size:i + (reps + 1) * size] == unit:
                    reps += 1
                for r in range(1, reps + 1):
                    cost, rest = best[i + r * size]
                    cands.append((size + cost, -r, ((unit, r),) + rest))
            cost, _, cover = min(cands)
            best[i] = (cost, cover)
        runs, n_attn, n_ssm = [], 0, 0
        for unit, count in best[0][1]:
            runs.append((unit, count, n_attn, n_ssm))
            n_attn += count * unit.count("*")
            n_ssm += count * (unit.count("M") + unit.count("K"))
        return tuple(runs)

    @functools.cached_property
    def switched(self) -> bool:
        """True where the pattern has fewer kinds of block than its best
        cover has bodies (K-KEKE*EKE: 4 against 8). The blocks then run
        under ONE scan that runs the body of the block's letter, each
        from its letter's stack at its own index, and a program traces
        each kind once: size, trace time and compile time follow the kinds,
        not the order the pattern puts them in."""
        return len(set(self.block_pattern)) < sum(
            len(u) for u, _, _, _ in self.units())

    def tree_units(self) -> tuple[tuple[str, int, int, int], ...]:
        """How the parameter tree stacks the blocks: `units()`, or where
        `switched` one single-letter unit a letter, in the order of
        first occurrence, all its blocks stacked."""
        if not self.switched:
            return self.units()
        p = self.block_pattern
        return tuple((c, p.count(c), 0, 0) for c in dict.fromkeys(p))

    @property
    def ssm_layers(self) -> int:
        """Layers that carry a recurrent state: a slot of the state
        group a sequence, whichever recurrence fills it."""
        return self.block_pattern.count("M") + self.block_pattern.count("K")

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Lanes the convolution runs over: [x | B | C]."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def state_bytes_per_seq(self, conv_itemsize: int = 2) -> int:
        """Bytes of recurrent state a sequence holds in all its
        state-space layers: the float32 state and the convolution's
        tail (what a state slot, and a snapshot, takes)."""
        return self.ssm_layers * (
            self.ssm_heads * self.ssm_head_dim * self.ssm_state * 4
            + (self.ssm_conv - 1) * self.ssm_conv_dim * conv_itemsize)

    @property
    def expert_width(self) -> int:
        """The width the routed experts read and write."""
        return self.moe_latent_size or self.hidden_size

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    def block_params(self, letter: str) -> int:
        """Every weight HELD here of one single-sublayer block (its norm
        counted): exact, the budget of blocks, slots and snapshots
        rests on it."""
        h = self.hidden_size
        if letter == "M":
            d, heads = self.ssm_inner, self.ssm_heads
            return (h + h * (d + self.ssm_conv_dim + heads)
                    + (self.ssm_conv + 1) * self.ssm_conv_dim
                    + 3 * heads + d + d * h)
        if letter == "K":
            d, kd = self.ssm_inner, self.ssm_heads * self.ssm_state
            rank = self.ssm_state
            return (h + h * self.ssm_conv_dim
                    + self.ssm_conv * self.ssm_conv_dim
                    + h * rank + rank * kd + kd + self.ssm_heads
                    + h * self.ssm_heads
                    + h * rank + rank * d + self.ssm_head_dim + d * h)
        if letter == "*":
            ak = self.kinds[0]
            nq, out = ak.num_heads, ak.num_heads * self.v_dim * h
            if ak.latent_dim:
                lat, r = ak.latent_dim, self.q_lora_rank
                nope = self.head_dim - ak.rotary_dim
                q = (h * r + r + r * nq * self.head_dim if r
                     else h * nq * self.head_dim)
                return (h + q + h * (lat + ak.rotary_dim) + lat
                        + lat * nq * (nope + self.v_dim) + out)
            return (h + h * nq * self.head_dim
                    + h * ak.num_kv_heads * (self.head_dim + self.v_dim)
                    + out)
        mats = 3 if self.mlp_gated else 2
        if letter == "-":
            return h + mats * h * self.intermediate_size
        w, f = self.expert_width, self.moe_intermediate_size
        return (h + h * self.router_experts
                + (self.router_experts if self.router_bias else 0)
                + (2 * h * w if self.moe_latent_size else 0)
                + self.local_experts * mats * w * f
                + self.shared_experts * mats * h * f)

    def num_params(self) -> int:
        """Parameter count (for memory budgeting): exact for a stack of
        layer groups, approximate for a stack of alike layers."""
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        if self.block_pattern:
            return (v * h * (1 if self.tie_word_embeddings else 2) + h
                    + sum(self.block_params(c) for c in self.block_pattern))
        if self.layer_groups:
            # every weight HELD here: the local experts only
            total = v * h * (1 if self.tie_word_embeddings else 2) + h
            for li, kind in enumerate(self.layer_kinds):
                ak = self.kinds[kind]
                nq, rot = ak.num_heads, ak.rotary_dim
                if ak.latent_dim:
                    r, nope = self.q_lora_rank, self.head_dim - rot
                    total += (
                        (h * r + r + r * nq * self.head_dim if r
                         else h * nq * self.head_dim)
                        + h * (ak.latent_dim + rot)
                        + ak.latent_dim
                        + ak.latent_dim * nq * (nope + self.v_dim)
                    )
                else:
                    total += (
                        h * nq * self.head_dim
                        + h * ak.num_kv_heads * (self.head_dim + self.v_dim)
                    )
                total += (
                    nq * self.v_dim * h
                    + 2 * h
                    + (nq if ak.sink else 0)
                    + (h * nq if self.head_gate else 0)
                )
                if self.hc_mult > 1:
                    n = self.hc_mult
                    total += 2 * (n * h * (2 * n + n * n) + 2 * n + n * n + 3)
                if self.router_experts and li >= self.dense_layers:
                    total += (
                        h * self.router_experts
                        + (self.router_experts if self.router_bias else 0)
                        + (self.local_experts + self.shared_experts)
                        * 3 * h * self.moe_intermediate_size
                    )
                else:
                    total += 3 * h * i
            return total
        mlp = 3 * h * i * max(1, self.num_experts)
        if self.is_moe:
            mlp += h * self.num_experts  # router
        per_layer = (
            h * self.q_size
            + 2 * h * self.kv_size
            + self.q_size * h
            + mlp
            + (4 if self.sandwich_norm else 2) * h
        )
        embed = v * h * (1 if self.tie_word_embeddings else 2)
        # a looped stack's passes share the weights: counted once
        gate = h + 1 if self.exit_gate else 0
        return self.num_layers * per_layer + embed + h + gate


# -- Presets ---------------------------------------------------------------
# Architecture hyper-parameters are public knowledge (HF config.json files).

_PRESETS: dict[str, ModelConfig] = {}


def _register(cfg: ModelConfig) -> ModelConfig:
    _PRESETS[cfg.name] = cfg
    return cfg


TINY_DEBUG = _register(
    ModelConfig(
        name="pst-tiny-debug",
        vocab_size=384,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_model_len=256,
        rope_theta=10000.0,
        tie_word_embeddings=True,
    )
)

# same tiny dims with headroom past 512-token prompts: the shared-KV-
# cache e2e serves a 512-token cross-engine prefix (tests/
# test_cache_server.py) which TINY_DEBUG's 256 ceiling cannot hold
TINY_CTX1K_DEBUG = _register(
    dataclasses.replace(
        TINY_DEBUG,
        name="pst-tiny-ctx1k-debug",
        max_model_len=1024,
    )
)

# tiny widths with a LONG logical context: CPU tests drive the
# long-prefill ring lane (tests/test_long_context_serving.py), deep
# logical chains, and the tier-overflow path without big-model compute
TINY_CTX64K_DEBUG = _register(
    dataclasses.replace(
        TINY_DEBUG,
        name="pst-tiny-ctx64k-debug",
        max_model_len=65536,
    )
)

# the tiny dims as a looped stack: two layers run three times a token
# with K/V of its own a pass (six cache layers), the output norms and
# the exit gate (models/llama.py's pass loop; tests/test_ouro_loop.py)
TINY_LOOP_DEBUG = _register(
    dataclasses.replace(
        TINY_DEBUG,
        name="pst-tiny-loop-debug",
        tie_word_embeddings=False,
        rms_norm_eps=1e-6,
        ut_steps=3,
        sandwich_norm=True,
        exit_gate=True,
    )
)

TINY_MOE_DEBUG = _register(
    dataclasses.replace(
        TINY_DEBUG,
        name="pst-tiny-moe-debug",
        num_kv_heads=4,  # ep tests shard experts one-per-chip at tp=4
        num_experts=4,
        num_experts_per_tok=2,
    )
)

# a stack of layer groups at tiny widths that keep every code path of
# models/layer_groups.py: full and window layers with different kv
# heads, q/k heads wider than v heads, rotary on a third of the dims,
# a sink on the window kind, V scaled, a leading dense layer, then 16
# sigmoid-routed experts (selection bias, renormalised) of which rank 0
# of 4 holds 4; window 8, so contexts of 40+ run far past it
TINY_GROUPS_DEBUG = _register(
    ModelConfig(
        name="pst-tiny-groups-debug",
        vocab_size=384,
        hidden_size=64,
        intermediate_size=128,
        num_layers=4,
        num_heads=4,
        num_kv_heads=1,
        head_dim=24,
        max_model_len=256,
        rope_theta=1e7,
        attn_kinds=(
            AttnKind(num_kv_heads=1, rope_theta=1e7),
            AttnKind(num_kv_heads=2, rope_theta=1e4, window=8, sink=True),
        ),
        layer_kinds=(0, 1, 1, 0),
        v_head_dim=16,
        rotary_dim=8,
        v_scale=0.707,
        router_experts=16,
        num_experts_per_tok=4,
        router_scoring="sigmoid",
        router_bias=True,
        moe_intermediate_size=32,
        dense_layers=1,
        ep_rank=0,
        ep_size=4,
    )
)

# latent attention, hyper-connections and a shared expert at tiny
# widths that keep every code path they add to models/layer_groups.py:
# a 32-dim latent row + 8 rotary dims read as key and value, q through
# a 24-dim bottleneck, YaRN past an original 64 positions (with the
# softmax scale's mscale squared), four residual streams, one leading
# dense layer, then 16 sigmoid-routed experts (all held here) with a
# scaling factor of 2 beside one shared expert
TINY_LATENT_DEBUG = _register(
    ModelConfig(
        name="pst-tiny-latent-debug",
        vocab_size=384,
        hidden_size=64,
        intermediate_size=128,
        num_layers=4,
        num_heads=4,
        num_kv_heads=1,
        head_dim=24,
        max_model_len=256,
        rope_theta=1e4,
        rms_norm_eps=1e-6,
        attn_kinds=(AttnKind(num_kv_heads=1, rope_theta=1e4,
                             latent_dim=32),),
        layer_kinds=(0, 0, 0, 0),
        v_head_dim=16,
        rotary_dim=8,
        q_lora_rank=24,
        rope_yarn=YarnScaling(factor=4.0, original_max_position=64,
                              mscale=1.0, mscale_all_dim=1.0),
        hc_mult=4,
        router_experts=16,
        num_experts_per_tok=4,
        router_scoring="sigmoid",
        router_bias=True,
        moe_intermediate_size=32,
        dense_layers=1,
        shared_experts=1,
        routed_scaling=2.0,
    )
)

# a stack shaped like published layers 0-4 of a `model_type: laguna`
# model at tiny widths that keep every code path that family adds to
# models/layer_groups.py: a full kind of 6 query heads and a window kind
# of 8 over the same 2 kv heads (GQA groups 3 and 4 in one program), half
# of a head rotated on the full kind (YaRN past 64 original positions at
# a theta of 100, so that two of its four frequencies lie on the ramp;
# cos and sin times 0.1 ln 4 + 1) against the whole head on the window
# kind, the per-head output gate, a leading dense layer, then 16
# softmax-routed experts (top-4, renormalised, scaling 2.5; all held
# here) beside one shared expert; window 12 = three blocks of 4
TINY_LAGUNA_DEBUG = _register(
    ModelConfig(
        name="pst-tiny-laguna-debug",
        vocab_size=384,
        hidden_size=64,
        intermediate_size=128,
        num_layers=5,
        num_heads=6,
        num_kv_heads=2,
        head_dim=16,
        max_model_len=256,
        rope_theta=100.0,
        rms_norm_eps=1e-6,
        attn_kinds=(
            AttnKind(num_kv_heads=2, rope_theta=100.0, num_heads=6,
                     rotary_dim=8,
                     rope_yarn=YarnScaling(factor=4.0,
                                           original_max_position=64,
                                           beta_fast=8.0),
                     rope_factor=0.1 * math.log(4.0) + 1.0),
            AttnKind(num_kv_heads=2, rope_theta=1e4, window=12,
                     num_heads=8, rotary_dim=16),
        ),
        layer_kinds=(0, 1, 1, 1, 0),
        rotary_dim=8,
        head_gate=True,
        router_experts=16,
        num_experts_per_tok=4,
        router_scoring="softmax",
        moe_intermediate_size=32,
        dense_layers=1,
        shared_experts=1,
        routed_scaling=2.5,
    )
)

# a stack of single-sublayer blocks at tiny widths that keep every code
# path of `layer_groups.forward_blocks`: the pattern EMEMEM* (one unit
# "EM" three times and an attention block), a state-space mixer of 8
# heads of 4 in 2 groups (4 heads a group) with a state of 8, a
# convolution of 4 taps and chunks of 8 rows (shorter than the prompts),
# attention without positional encoding, 16 sigmoid-routed experts
# (top-4 of score + bias, renormalised, times 2.5; rank 0 of 2 holds 8)
# of width 24 at a latent width of 16 under a hidden width of 32, relu
# squared with no gate matrix, a shared expert of two widths
TINY_NEMOTRON_DEBUG = _register(
    ModelConfig(
        name="pst-tiny-nemotron-debug",
        vocab_size=384,
        hidden_size=32,
        intermediate_size=0,
        num_layers=7,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_model_len=256,
        rope_theta=1e4,
        hidden_act="relu2",
        mlp_gated=False,
        attn_kinds=(AttnKind(num_kv_heads=2, rope_theta=1e4),),
        layer_kinds=(0,),
        block_pattern="EMEMEM*",
        rope=False,
        ssm_heads=8,
        ssm_head_dim=8,
        ssm_groups=2,
        ssm_state=8,
        ssm_conv=4,
        ssm_chunk=8,
        moe_latent_size=16,
        router_experts=16,
        num_experts_per_tok=4,
        router_scoring="sigmoid",
        router_bias=True,
        moe_intermediate_size=24,
        ep_rank=0,
        ep_size=2,
        shared_experts=2,
        routed_scaling=2.5,
    )
)

# published layers 1-5 of a `model_type: kimi_linear` model as ten
# single-sublayer blocks (K-KEKE*EKE: KDA + dense MLP, then KDA, KDA,
# latent attention, KDA each with routed experts) at tiny widths that
# keep every code path that family adds: KDA mixers of 4 heads with 8
# key dims and 16 value dims (K != V), 4 taps, chunks of 8 rows (shorter
# than the prompts); latent attention without positional encoding, a
# 32-dim latent row + 8 shared key dims that are not rotated, the query
# projected directly; a gated dense MLP; 16 sigmoid-routed gated experts
# (top-4 of score + bias, renormalised, times 2.446; rank 0 of 2 holds
# 8) beside one shared expert
TINY_KIMI_DEBUG = _register(
    ModelConfig(
        name="pst-tiny-kimi-debug",
        vocab_size=384,
        hidden_size=32,
        intermediate_size=64,
        num_layers=5,
        num_heads=4,
        num_kv_heads=1,
        head_dim=24,
        max_model_len=256,
        rope_theta=1e4,
        attn_kinds=(AttnKind(num_kv_heads=1, rope_theta=1e4,
                             latent_dim=32),),
        layer_kinds=(0,),
        v_head_dim=16,
        rotary_dim=8,
        block_pattern="K-KEKE*EKE",
        rope=False,
        ssm_heads=4,
        ssm_head_dim=16,
        ssm_groups=4,
        ssm_state=8,
        ssm_conv=4,
        ssm_chunk=8,
        router_experts=16,
        num_experts_per_tok=4,
        router_scoring="sigmoid",
        router_bias=True,
        moe_intermediate_size=24,
        ep_rank=0,
        ep_size=2,
        shared_experts=1,
        routed_scaling=2.446,
    )
)

# CI-scale stand-in for facebook/opt-125m in the reference's test configs:
# same order of magnitude, Llama-class architecture.
SMALL_125M = _register(
    ModelConfig(
        name="pst-small-125m",
        vocab_size=32000,
        hidden_size=768,
        intermediate_size=2048,
        num_layers=12,
        num_heads=12,
        num_kv_heads=12,
        head_dim=64,
        max_model_len=2048,
        rope_theta=10000.0,
    )
)

LLAMA_3_2_1B = _register(
    ModelConfig(
        name="llama-3.2-1b",
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        max_model_len=131072,
        rope_theta=500000.0,
        tie_word_embeddings=True,
    )
)

LLAMA_3_2_3B = _register(
    ModelConfig(
        name="llama-3.2-3b",
        vocab_size=128256,
        hidden_size=3072,
        intermediate_size=8192,
        num_layers=28,
        num_heads=24,
        num_kv_heads=8,
        head_dim=128,
        max_model_len=131072,
        rope_theta=500000.0,
        tie_word_embeddings=True,
    )
)

LLAMA_3_8B = _register(
    ModelConfig(
        name="llama-3-8b",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        max_model_len=8192,
        rope_theta=500000.0,
    )
)

LLAMA_3_1_8B = _register(
    dataclasses.replace(LLAMA_3_8B, name="llama-3.1-8b", max_model_len=131072)
)

MISTRAL_7B = _register(
    ModelConfig(
        name="mistral-7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        max_model_len=32768,
        rope_theta=1000000.0,
    )
)

QWEN2_7B = _register(
    ModelConfig(
        name="qwen2-7b",
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        max_model_len=32768,
        rope_theta=1000000.0,
        qkv_bias=True,
    )
)

MIXTRAL_8X7B = _register(
    ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        max_model_len=32768,
        rope_theta=1000000.0,
        num_experts=8,
        num_experts_per_tok=2,
    )
)


def from_hf_config(path: str, name: str | None = None) -> ModelConfig:
    """Build a ModelConfig from a HuggingFace `config.json` on local disk."""
    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    by_type = {"mimo_v2": _from_mimo_v2, "xing4_0": _from_xing4,
               "ouro": _from_ouro, "laguna": _from_laguna,
               "nemotron_h": _from_nemotron_h,
               "kimi_linear": _from_kimi_linear}
    if hf.get("model_type") in by_type:
        return by_type[hf["model_type"]](hf, name or os.path.basename(
            os.path.normpath(path)))
    arch = (hf.get("architectures") or ["?"])[0]
    if arch not in (
        "LlamaForCausalLM",
        "MistralForCausalLM",
        "Qwen2ForCausalLM",
        "MixtralForCausalLM",
        "Phi3ForCausalLM",
        "GemmaForCausalLM",
    ):
        raise ValueError(f"unsupported architecture {arch!r} at {path}")
    num_heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // num_heads
    gemma = arch == "GemmaForCausalLM"
    max_len = hf.get("max_position_embeddings", 8192)
    window = hf.get("sliding_window")
    # Qwen2-family configs ship a sliding_window value alongside
    # use_sliding_window=false; a window >= max_position_embeddings is
    # also a no-op mask that would only cost us the paged-attention path.
    if not hf.get("use_sliding_window", True):
        window = None
    # HF Qwen2 slides only layers >= max_window_layers; the shipped
    # default (== num_hidden_layers) means NO layer slides. A stack of
    # mixed windows is a layer-group config (`attn_kinds`), which the
    # checkpoint loader (models/weights.py) does not map yet: all-full
    # when no layer slides, else keep the window for every layer (the
    # majority behavior) and say so.
    mwl = hf.get("max_window_layers")
    if window and mwl is not None:
        if mwl >= hf["num_hidden_layers"]:
            window = None
        elif mwl > 0:
            logger.warning(
                "max_window_layers=%d < num_hidden_layers=%d: applying "
                "sliding_window=%d to ALL layers (mixed windows are a "
                "layer-group config, attn_kinds, which the checkpoint "
                "loader does not map); first %d layers will differ "
                "from HF",
                mwl, hf["num_hidden_layers"], window, mwl,
            )
    if window and window >= max_len:
        window = None
    act = hf.get("hidden_act") or hf.get("hidden_activation") or "silu"
    if act in ("gelu_pytorch_tanh", "gelu_new", "gelu"):
        act = "gelu_tanh"
    return ModelConfig(
        name=name or os.path.basename(os.path.normpath(path)),
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=num_heads,
        num_kv_heads=hf.get("num_key_value_heads", num_heads),
        head_dim=head_dim,
        max_model_len=max_len,
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=(
            True if gemma else hf.get("tie_word_embeddings", False)
        ),
        qkv_bias=(arch == "Qwen2ForCausalLM"),
        hidden_act=act if gemma else "silu",
        norm_weight_offset=1.0 if gemma else 0.0,
        embed_scale=float(hf["hidden_size"]) ** 0.5 if gemma else 1.0,
        sliding_window=int(window) if window else None,
        num_experts=hf.get("num_local_experts", 0),
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
    )


def _from_mimo_v2(hf: dict, name: str) -> ModelConfig:
    """`model_type: mimo_v2` (MiMo-V2-Flash / V2.5 text decoder): full
    and window attention layers by `hybrid_layer_pattern` (0 full, 1
    window), each kind with its own kv heads and rope theta, a learned
    sink where the config says so; qk and v head dims apart, rotary on
    the leading `partial_rotary_factor` of the qk dims, V scaled;
    leading dense layers by `moe_layer_freq`, then sigmoid-scored
    routed experts chosen by score + bias (`noaux_tc`); shared experts
    and a routed scaling factor where the file has them (MiMo's own
    leaves them at none / 1).

    `n_routed_experts` is the ROUTER's width. `ep_size` / `ep_rank`
    (not published keys: a deployment's) say which contiguous slice of
    the experts this engine holds; absent = all of them."""
    L = hf["num_hidden_layers"]
    pattern = list(hf["hybrid_layer_pattern"])
    freq = list(hf.get("moe_layer_freq") or [0] * L)
    dense_layers = freq.index(1) if 1 in freq else L
    if (len(pattern) != L or len(freq) != L
            or any(f != 1 for f in freq[dense_layers:])):
        raise ValueError(
            f"{name}: hybrid_layer_pattern and moe_layer_freq must have "
            f"num_hidden_layers={L} entries, dense layers leading"
        )
    _refuse_unserved(hf, name)
    if (hf.get("swa_head_dim", hf["head_dim"]) != hf["head_dim"]
            or hf.get("swa_v_head_dim", hf["v_head_dim"])
            != hf["v_head_dim"]
            or hf.get("swa_num_attention_heads",
                      hf["num_attention_heads"])
            != hf["num_attention_heads"]):
        raise ValueError(
            f"{name}: window layers with other q heads or head dims "
            "than the full layers are not served"
        )
    window = hf.get("sliding_window") or hf.get("sliding_window_size")
    kinds = (
        AttnKind(
            num_kv_heads=hf["num_key_value_heads"],
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            sink=bool(hf.get("add_full_attention_sink_bias", False)),
        ),
        AttnKind(
            num_kv_heads=hf.get("swa_num_key_value_heads",
                                hf["num_key_value_heads"]),
            rope_theta=float(hf.get("swa_rope_theta",
                                    hf.get("rope_theta", 10000.0))),
            window=int(window),
            sink=bool(hf.get("add_swa_attention_sink_bias", False)),
        ),
    )
    head_dim = hf["head_dim"]
    # the rotary dims are the leading int(head_dim * factor), as the
    # published modelling code takes them (192 * 0.334 -> 64)
    rotary = int(head_dim * hf.get("partial_rotary_factor", 1.0))
    routed = dense_layers < L
    return ModelConfig(
        name=name,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=kinds[0].num_kv_heads,
        head_dim=head_dim,
        max_model_len=hf.get("max_position_embeddings", 8192),
        rope_theta=kinds[0].rope_theta,
        rms_norm_eps=hf.get("layernorm_epsilon",
                            hf.get("rms_norm_eps", 1e-5)),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        attn_kinds=kinds,
        layer_kinds=tuple(int(p) for p in pattern),
        v_head_dim=hf["v_head_dim"],
        rotary_dim=rotary - rotary % 2,
        v_scale=float(hf.get("attention_value_scale") or 1.0),
        dense_layers=dense_layers,
        **_routed_fields(hf, routed),
    )


def _refuse_unserved(hf: dict, name: str) -> None:
    for key, want in (("n_group", (None, 1)), ("topk_group", (None, 1)),
                      ("attention_bias", (None, False)),
                      ("hidden_act", (None, "silu"))):
        if hf.get(key) not in want:
            raise ValueError(
                f"{name}: {key}={hf.get(key)!r} is not served "
                "(group-limited routing and attention biases have no "
                "code path yet)"
            )


def _routed_fields(hf: dict, routed: bool) -> dict:
    """The routed expert layer's fields from the DeepSeek-V3 style keys
    both layer-group families publish."""
    return dict(
        router_experts=hf["n_routed_experts"] if routed else 0,
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        router_scoring=hf.get("scoring_func", "softmax"),
        router_bias=hf.get("topk_method") == "noaux_tc",
        router_renorm=bool(hf.get("norm_topk_prob", True)),
        moe_intermediate_size=hf.get("moe_intermediate_size", 0),
        ep_rank=int(hf.get("ep_rank", 0)),
        ep_size=int(hf.get("ep_size", 1)),
        shared_experts=int(hf.get("n_shared_experts") or 0),
        routed_scaling=float(hf.get("routed_scaling_factor") or 1.0),
    )


def _from_xing4(hf: dict, name: str) -> ModelConfig:
    """`model_type: xing4_0` (Xing4.0-29B-A4B): every layer latent
    attention (DeepSeek-V2's MLA: `q_lora_rank`, `kv_lora_rank`,
    `qk_nope_head_dim` + `qk_rope_head_dim` a head, `v_head_dim` out)
    under YaRN-scaled rotary; `hc_mult` residual streams mixed by
    Sinkhorn-projected matrices (manifold-constrained hyper-
    connections); `first_k_dense_replace` leading dense layers, then
    sigmoid-scored routed experts (`noaux_tc`) with a scaling factor
    beside `n_shared_experts` shared ones. The one MTP module
    (`num_nextn_predict_layers`) is not part of the main model's
    logits and is not built."""
    L = hf["num_hidden_layers"]
    _refuse_unserved(hf, name)
    if hf.get("moe_layer_freq", 1) != 1:
        raise ValueError(
            f"{name}: moe_layer_freq={hf['moe_layer_freq']!r} is not "
            "served (every layer after the dense ones is routed)")
    dense_layers = min(int(hf.get("first_k_dense_replace", 0)), L)
    rs = hf.get("rope_scaling") or {}
    kind = rs.get("type") or rs.get("rope_type") or "default"
    if kind not in ("default", "yarn"):
        raise ValueError(f"{name}: rope_scaling type {kind!r} is not served")
    yarn = None
    if kind == "yarn":
        yarn = YarnScaling(
            factor=float(rs["factor"]),
            original_max_position=int(
                rs["original_max_position_embeddings"]),
            beta_fast=float(rs.get("beta_fast", 32)),
            beta_slow=float(rs.get("beta_slow", 1)),
            mscale=float(rs.get("mscale", 1)),
            mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
        )
    theta = float(hf.get("rope_theta", 10000.0))
    ak = AttnKind(num_kv_heads=1, rope_theta=theta,
                  latent_dim=hf["kv_lora_rank"])
    return ModelConfig(
        name=name,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=1,
        head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        max_model_len=hf.get("max_position_embeddings", 8192),
        rope_theta=theta,
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        attn_kinds=(ak,),
        layer_kinds=(0,) * L,
        v_head_dim=hf["v_head_dim"],
        rotary_dim=hf["qk_rope_head_dim"],
        q_lora_rank=hf["q_lora_rank"],
        rope_yarn=yarn,
        hc_mult=int(hf.get("hc_mult", 1)),
        hc_sinkhorn_iters=int(hf.get("hc_sinkhorn_iters", 20)),
        hc_eps=float(hf.get("hc_eps", 1e-6)),
        hc_res_clamp=(float(hf.get("mhc_h_res_clamp_min", -30)),
                      float(hf.get("mhc_h_res_clamp_max", 30))),
        dense_layers=dense_layers,
        **_routed_fields(hf, dense_layers < L),
    )


def _from_laguna(hf: dict, name: str) -> ModelConfig:
    """`model_type: laguna` (poolside's Laguna family): full and window
    attention layers by `layer_types`, each kind with its own QUERY
    heads (`num_attention_heads_per_layer`) over the same kv heads, and
    its own rotary scheme (`rope_parameters[layer type]`: theta, the
    rotated share of a head, YaRN and the factor on cos and sin); a
    per-head output gate (`gating`); dense or routed MLP by
    `mlp_layer_types`, the routed ones softmax-scored over `num_experts`
    with renormalised top-k and `moe_routed_scaling_factor`, beside a
    shared expert of `shared_expert_intermediate_size`.

    The three per-layer lists are a third spelling of segments; they
    become kinds (0 = full, 1 = window) and `layer_kinds`. What has no
    code path is refused by name."""
    L = hf["num_hidden_layers"]
    types = list(hf.get("layer_types") or ["full_attention"] * L)
    heads = list(hf.get("num_attention_heads_per_layer")
                 or [hf["num_attention_heads"]] * L)
    mlps = list(hf.get("mlp_layer_types") or (
        ["sparse" if hf.get("num_experts") else "dense"] * L))
    if not len(types) == len(heads) == len(mlps) == L:
        raise ValueError(
            f"{name}: layer_types, num_attention_heads_per_layer and "
            f"mlp_layer_types must have num_hidden_layers={L} entries")
    names = ("full_attention", "sliding_attention")
    if set(types) - set(names):
        raise ValueError(
            f"{name}: layer_types {sorted(set(types) - set(names))} are "
            "not served (full_attention and sliding_attention are)")
    if types[0] != "full_attention":
        raise ValueError(
            f"{name}: a window layer first is not served: the first "
            "layer's kind owns the block table every sequence ships, and "
            "that has to be the kind that keeps every token")
    window = hf.get("sliding_window")
    if "sliding_attention" in types and not isinstance(window, int):
        raise ValueError(
            f"{name}: sliding_window={window!r} is not served (one "
            "window for every sliding_attention layer; more than one "
            "windowed kind has no code path)")
    gating = hf.get("gating")
    gate_types = set(hf.get("gating_types") or ())
    if gating not in (None, False, True, "per-head") or (
            gate_types - {"per_head"}):
        raise ValueError(
            f"{name}: gating={gating!r} / gating_types="
            f"{sorted(gate_types)} is not served (the per-head output "
            "gate, `true` or \"per-head\", is; an element-wise gate has "
            "no code path)")
    for key, want in (("attention_bias", (None, False)),
                      ("hidden_act", (None, "silu")),
                      ("use_qk_norm", (None, False)),
                      ("qk_norm", (None, False)),
                      ("moe_apply_router_weight_on_input", (None, False)),
                      ("moe_router_logit_softcapping", (None, 0)),
                      ("n_group", (None, 1)), ("topk_group", (None, 1))):
        if hf.get(key) not in want:
            raise ValueError(
                f"{name}: {key}={hf.get(key)!r} is not served for "
                "model_type laguna")
    head_dim = hf.get("head_dim") or (
        hf["hidden_size"] // hf["num_attention_heads"])
    rope = hf.get("rope_parameters") or {}

    def kind(type_name: str) -> AttnKind:
        per_layer = {h for t, h in zip(types, heads) if t == type_name}
        if len(per_layer) != 1:
            raise ValueError(
                f"{name}: {type_name} layers with query heads "
                f"{sorted(per_layer)}: one count a layer type is served")
        rp = rope.get(type_name) or {}
        rtype = rp.get("rope_type") or rp.get("type") or "default"
        if rtype not in ("default", "yarn"):
            raise ValueError(
                f"{name}: rope_type {rtype!r} of {type_name} is not "
                "served")
        yarn = factor = None
        if rtype == "yarn":
            yarn = YarnScaling(
                factor=float(rp["factor"]),
                original_max_position=int(
                    rp["original_max_position_embeddings"]),
                beta_fast=float(rp.get("beta_fast", 32)),
                beta_slow=float(rp.get("beta_slow", 1)),
            )
            # HF's reading: the given factor, else 0.1 ln(factor) + 1
            from production_stack_tpu.ops.layers import yarn_mscale

            factor = float(rp.get("attention_factor")
                           or yarn_mscale(yarn.factor, 1.0))
        rotary = int(head_dim * float(rp.get(
            "partial_rotary_factor", hf.get("partial_rotary_factor", 1.0))))
        return AttnKind(
            num_kv_heads=hf.get("num_key_value_heads",
                                hf["num_attention_heads"]),
            rope_theta=float(rp.get("rope_theta",
                                    hf.get("rope_theta", 10000.0))),
            window=window if type_name == "sliding_attention" else None,
            num_heads=per_layer.pop(),
            rotary_dim=rotary - rotary % 2,
            rope_yarn=yarn,
            rope_factor=factor,
        )

    kinds = tuple(kind(t) for t in names if t in types)
    if set(mlps) - {"dense", "sparse"}:
        raise ValueError(
            f"{name}: mlp_layer_types {sorted(set(mlps))} are not served")
    dense_layers = mlps.index("sparse") if "sparse" in mlps else L
    if "dense" in mlps[dense_layers:]:
        raise ValueError(
            f"{name}: a dense MLP after a routed one is not served "
            "(dense layers lead)")
    routed = dense_layers < L
    f = hf.get("moe_intermediate_size", 0)
    shared = hf.get("shared_expert_intermediate_size") or 0
    if routed and shared % f:
        raise ValueError(
            f"{name}: shared_expert_intermediate_size={shared} is no "
            f"multiple of moe_intermediate_size={f}")
    return ModelConfig(
        name=name,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=kinds[0].num_kv_heads,
        head_dim=head_dim,
        max_model_len=hf.get("max_position_embeddings", 8192),
        rope_theta=kinds[0].rope_theta,
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        attn_kinds=kinds,
        layer_kinds=tuple(names.index(t) for t in types),
        head_gate=bool(gating),
        dense_layers=dense_layers,
        router_experts=hf["num_experts"] if routed else 0,
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        router_scoring="softmax",
        router_renorm=bool(hf.get("norm_topk_prob", True)),
        moe_intermediate_size=f,
        ep_rank=int(hf.get("ep_rank", 0)),
        ep_size=int(hf.get("ep_size", 1)),
        shared_experts=shared // f if routed else 0,
        routed_scaling=float(hf.get("moe_routed_scaling_factor") or 1.0),
    )


def _from_nemotron_h(hf: dict, name: str) -> ModelConfig:
    """`model_type: nemotron_h` (the Nemotron-H / Nemotron-3 hybrids):
    `hybrid_override_pattern` gives each layer ONE sublayer, "M" a
    Mamba-2 mixer (`mamba_num_heads` x `mamba_head_dim`, `n_groups`,
    `ssm_state_size`, `conv_kernel`, `chunk_size`), "*" GQA attention
    without positional encoding, "E" sigmoid-scored routed experts
    chosen by score + bias (`n_routed_experts`, `num_experts_per_tok`,
    renormalised, times `routed_scaling_factor`) at `moe_latent_size`
    beside a shared expert of `moe_shared_expert_intermediate_size` on
    the hidden state; every MLP is up -> relu squared -> down
    (`mlp_hidden_act: relu2`, no gate matrix). The MTP module
    (`num_nextn_predict_layers`) is not part of the main model's logits
    and is not built. `ep_size` / `ep_rank` (a deployment's keys) say
    which contiguous slice of the experts this engine holds. What has
    no code path is refused by name."""
    pattern = str(hf["hybrid_override_pattern"])
    L = hf["num_hidden_layers"]
    if len(pattern) != L:
        raise ValueError(
            f"{name}: hybrid_override_pattern has {len(pattern)} letters "
            f"for num_hidden_layers={L}")
    if set(pattern) - set("M*E"):
        raise ValueError(
            f"{name}: hybrid_override_pattern letters "
            f"{sorted(set(pattern) - set('M*E'))} are not served (M, * "
            "and E are; a plain-MLP block, \"-\", has no code path)")
    for key, want in (("n_group", (None, 1)), ("topk_group", (None, 1)),
                      ("attention_bias", (None, False)),
                      ("mamba_proj_bias", (None, False)),
                      ("use_bias", (None, False)),
                      ("mlp_bias", (None, False)),
                      ("moe_shared_expert_overlap", (None, False)),
                      ("use_conv_bias", (None, True)),
                      ("mamba_hidden_act", (None, "silu")),
                      ("mlp_hidden_act", ("relu2",)),
                      ("norm_topk_prob", (None, True)),
                      ("sliding_window", (None,))):
        if hf.get(key) not in want:
            raise ValueError(
                f"{name}: {key}={hf.get(key)!r} is not served for "
                "model_type nemotron_h")
    heads, p_dim = hf["mamba_num_heads"], hf["mamba_head_dim"]
    if heads * p_dim != hf.get("expand", 2) * hf["hidden_size"] or (
            heads % hf["n_groups"]):
        raise ValueError(
            f"{name}: mamba_num_heads={heads} x mamba_head_dim={p_dim} "
            f"must be expand={hf.get('expand', 2)} x hidden_size and a "
            f"multiple of n_groups={hf['n_groups']}")
    routed = "E" in pattern
    f = hf.get("moe_intermediate_size", 0)
    shared = (hf.get("moe_shared_expert_intermediate_size") or 0) * int(
        hf.get("n_shared_experts") or 0)
    if routed and shared % f:
        raise ValueError(
            f"{name}: moe_shared_expert_intermediate_size={shared} is no "
            f"multiple of moe_intermediate_size={f}")
    num_heads = hf["num_attention_heads"]
    theta = float(hf.get("rope_theta", 10000.0))
    kv = hf.get("num_key_value_heads", num_heads)
    return ModelConfig(
        name=name,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf.get("intermediate_size", 0),
        num_layers=L,
        num_heads=num_heads,
        num_kv_heads=kv,
        head_dim=hf.get("head_dim") or hf["hidden_size"] // num_heads,
        max_model_len=hf.get("max_position_embeddings", 8192),
        rope_theta=theta,
        rms_norm_eps=hf.get("layer_norm_epsilon",
                            hf.get("norm_eps", 1e-5)),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        hidden_act="relu2",
        mlp_gated=False,
        attn_kinds=(AttnKind(num_kv_heads=kv, rope_theta=theta),),
        layer_kinds=(0,) * pattern.count("*"),
        block_pattern=pattern,
        rope=False,
        ssm_heads=heads,
        ssm_head_dim=p_dim,
        ssm_groups=hf["n_groups"],
        ssm_state=hf["ssm_state_size"],
        ssm_conv=hf.get("conv_kernel", 4),
        ssm_chunk=hf.get("chunk_size", 128),
        moe_latent_size=int(hf.get("moe_latent_size") or 0),
        router_experts=hf["n_routed_experts"] if routed else 0,
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        router_scoring="sigmoid",
        router_bias=True,
        router_renorm=True,
        moe_intermediate_size=f,
        ep_rank=int(hf.get("ep_rank", 0)),
        ep_size=int(hf.get("ep_size", 1)),
        shared_experts=shared // f if routed else 0,
        routed_scaling=float(hf.get("routed_scaling_factor") or 1.0),
    )


def _from_kimi_linear(hf: dict, name: str) -> ModelConfig:
    """`model_type: kimi_linear` (Kimi-Linear, arXiv:2510.26692): every
    published layer is a mixer and a feed-forward part, each a block of
    its own here. `linear_attn_config` numbers the layers from 1:
    `kda_layers` take a KDA mixer ("K": `num_heads` heads of `head_dim`
    keys and values, convolutions of `short_conv_kernel_size` taps),
    `full_attn_layers` latent attention ("*": `kv_lora_rank`,
    `qk_nope_head_dim` + `qk_rope_head_dim` a head, `v_head_dim` out, the
    query projected directly where `q_lora_rank` is null, the shared key
    dims NOT rotated under `mla_use_nope`). The first
    `first_k_dense_replace` layers keep a dense SwiGLU ("-"), the rest
    sigmoid-scored routed experts ("E": `num_experts`,
    `num_experts_per_token`, `moe_renormalize`, `routed_scaling_factor`,
    selection by score + bias as the family's gate carries it) beside
    `num_shared_experts` shared ones. `head_dim` and
    `num_key_value_heads` at the file's top level are read by nothing.
    `ep_size` / `ep_rank` (a deployment's keys) say which contiguous
    slice of the experts this engine holds. What has no code path is
    refused by name."""
    L = hf["num_hidden_layers"]
    lin = hf["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda & full or kda | full != set(range(1, L + 1)):
        raise ValueError(
            f"{name}: linear_attn_config's kda_layers and "
            f"full_attn_layers must share out layers 1..{L} (numbered "
            "from 1)")
    if not full:
        raise ValueError(
            f"{name}: a stack without a full_attn_layers entry is not "
            "served (the block table every sequence ships is the "
            "attention cache group's)")
    for key, want in (("num_expert_group", (None, 1)),
                      ("topk_group", (None, 1)),
                      ("num_nextn_predict_layers", (None, 0)),
                      ("moe_layer_freq", (None, 1)),
                      ("hidden_act", (None, "silu")),
                      ("moe_router_activation_func", ("sigmoid",)),
                      ("q_lora_rank", (None, 0)),
                      ("attention_bias", (None, False))):
        if hf.get(key) not in want:
            raise ValueError(
                f"{name}: {key}={hf.get(key)!r} is not served for "
                "model_type kimi_linear (group-limited routing, an MTP "
                "module, dense layers among routed ones and a query "
                "bottleneck have no tested code path there)")
    if not hf.get("mla_use_nope", False) or hf.get("rope_scaling"):
        raise ValueError(
            f"{name}: mla_use_nope={hf.get('mla_use_nope')!r} with "
            f"rope_scaling={hf.get('rope_scaling')!r} is not served for "
            "model_type kimi_linear (latent attention in a stack of "
            "single-sublayer blocks runs without positional encoding)")
    dense = min(int(hf.get("first_k_dense_replace", 0)), L)
    routed = dense < L
    pattern = "".join(
        ("K" if i in kda else "*") + ("-" if i <= dense else "E")
        for i in range(1, L + 1))
    theta = float(hf.get("rope_theta", 10000.0))
    heads, dim = lin["num_heads"], lin["head_dim"]
    return ModelConfig(
        name=name,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=1,
        head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        max_model_len=hf.get("model_max_length",
                             hf.get("max_position_embeddings", 8192)),
        rope_theta=theta,
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        attn_kinds=(AttnKind(num_kv_heads=1, rope_theta=theta,
                             latent_dim=hf["kv_lora_rank"]),),
        layer_kinds=(0,) * len(full),
        v_head_dim=hf["v_head_dim"],
        rotary_dim=hf["qk_rope_head_dim"],
        block_pattern=pattern,
        rope=False,
        ssm_heads=heads,
        ssm_head_dim=dim,
        ssm_groups=heads,
        ssm_state=dim,
        ssm_conv=lin.get("short_conv_kernel_size", 4),
        # the chunked form's chunk: not a key of the config. 16 by the
        # chip (two lanes of 256 rows at 32 heads of 128 x 128: 1.57 ms
        # a layer against 1.80 at 32 and 2.21 at 64; PERF.md, Findings
        # PR 51): the (chunk, chunk, keys) decays are elementwise work
        ssm_chunk=16,
        router_experts=hf["num_experts"] if routed else 0,
        num_experts_per_tok=hf.get("num_experts_per_token", 2),
        router_scoring="sigmoid",
        router_bias=True,
        router_renorm=bool(hf.get("moe_renormalize", True)),
        moe_intermediate_size=hf.get("moe_intermediate_size", 0),
        ep_rank=int(hf.get("ep_rank", 0)),
        ep_size=int(hf.get("ep_size", 1)),
        shared_experts=int(hf.get("num_shared_experts") or 0) if routed
        else 0,
        routed_scaling=float(hf.get("routed_scaling_factor") or 1.0),
    )


def _from_ouro(hf: dict, name: str) -> ModelConfig:
    """`model_type: ouro` (Ouro-1.4B / 2.6B, a looped language model):
    a Llama-class stack of full multi-head attention and SwiGLU layers
    that runs `total_ut_steps` times a token over the same weights, each
    pass with K/V of its own, a second norm on every sublayer's output,
    the final norm after every pass, and a scalar exit gate a pass.
    Served at `early_exit_threshold` 1 (the published value): every
    token runs every pass. A lower threshold lets single rows of a
    batch leave the loop early, which no step program can do."""
    threshold = hf.get("early_exit_threshold", 1)
    if threshold is not None and float(threshold) < 1.0:
        raise ValueError(
            f"{name}: early_exit_threshold={threshold!r} is not served: "
            "a row would leave the looped stack early while its batch "
            "goes on (only a threshold of 1, every pass for every "
            "token, has a code path)"
        )
    if hf.get("use_sliding_window"):
        raise ValueError(
            f"{name}: use_sliding_window=true is not served for "
            "model_type ouro (the published model attends over the "
            "full context in every layer)"
        )
    L = hf["num_hidden_layers"]
    kinds = hf.get("layer_types") or []
    if any(k != "full_attention" for k in kinds[:L]):
        raise ValueError(
            f"{name}: layer_types other than full_attention are not "
            f"served for model_type ouro, got {sorted(set(kinds[:L]))}"
        )
    for key, want in (("rope_scaling", (None,)),
                      ("attention_bias", (None, False)),
                      ("hidden_act", (None, "silu"))):
        if hf.get(key) not in want:
            raise ValueError(
                f"{name}: {key}={hf.get(key)!r} is not served for "
                "model_type ouro"
            )
    num_heads = hf["num_attention_heads"]
    return ModelConfig(
        name=name,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=L,
        num_heads=num_heads,
        num_kv_heads=hf.get("num_key_value_heads", num_heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // num_heads,
        max_model_len=hf.get("max_position_embeddings", 8192),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        ut_steps=int(hf.get("total_ut_steps", 1)),
        sandwich_norm=True,
        exit_gate=True,
    )


def get_model_config(model: str) -> ModelConfig:
    """Resolve a model: preset name, local HF checkpoint directory, or an
    HF id already present in the local HF cache (zero-egress)."""
    if model in _PRESETS:
        return _PRESETS[model]
    if os.path.isdir(model) and os.path.exists(
        os.path.join(model, "config.json")
    ):
        return from_hf_config(model)
    from production_stack_tpu.models.weights import resolve_model_dir

    d = resolve_model_dir(model)
    if d is not None:
        return from_hf_config(d, name=model)
    raise ValueError(
        f"unknown model {model!r} (not a preset, local checkpoint dir, or "
        f"cached HF id); known presets: {sorted(_PRESETS)}"
    )


def list_presets() -> list[str]:
    return sorted(_PRESETS)
