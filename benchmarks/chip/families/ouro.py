"""The ouro family: a LOOPED Llama-class decoder (Ouro-1.4B / 2.6B,
arXiv 2510.25741, `model_type: ouro`).

`L` alike layers of full multi-head attention and SwiGLU run
`T = total_ut_steps` times a token over the SAME weights:

    h = E[token]
    for t in 0 .. T-1:
        for l in 0 .. L-1:
            a = Attn_l(RMS(h; g1_l))        # q, k rotated over the whole
                                            # head; this pass's OWN keys
                                            # and values (a cache would
                                            # hold them at t * L + l)
            h = h + RMS(a; g2_l)            # "sandwich": a second norm on
                                            # the sublayer's output
            x = RMS(h; g3_l)
            h = h + RMS(W_down (silu(W_gate x) * (W_up x)); g4_l)
        h = RMS(h; g_final)                 # closes EVERY pass
        lambda_t = sigmoid(w_exit . h + b_exit)
    logits = h W_head                       # of the last pass

No biases on any projection. At `early_exit_threshold` 1 (published) no
token leaves early, so the gate moves no logit: the program only reads
it out as a counter, and `exit_distribution` here is the plain form of
what that counter sums.

The only file of the benchmark that knows this parameter tree and these
equations; `manifest.py` says what a family file gives and how it is
found. Imports jax inside its functions only.

THE SEEDED WEIGHTS. Every matrix is N(0, 1) / sqrt(fan_in) as in the
dense family. What differs, and why:
- embedding rows have unit variance (the dense family's have 1 /
  hidden): a sublayer's normed output has RMS `g2` whatever its input,
  so rows of RMS 0.02 would be lost under the first sublayer and every
  token would look alike;
- the input gains `g1`, `g3` and the final gain are 1 + 0.25 N(0, 1),
  the OUTPUT gains `g2`, `g4` are OUT_GAIN (0.25) times that: all seeded
  away from 1, so that a dropped or swapped norm shows, and the output
  gains below 1 so that one sublayer adds a quarter of a unit of RMS to
  a residual stream of 1 to 1.6;
- the head is HEAD_GAIN (0.5) / sqrt(hidden): logits of deviation 0.5.
  Why these two scales: through 4 x 12 layer applications in bfloat16
  the served log-probabilities have to stay inside the harness's
  tolerances (0.1 a position, 0.03 a prompt's mean), on seeds the
  driver draws anew. The program's forward in bfloat16 against this
  reference at the published widths (on the CPU, seven seeds; PERF.md,
  Findings PR 38) read, worst position / worst mean: 0.041 / 0.022 with
  output gains 0.5 and a head of 1; 0.036 / 0.019 with 0.25 and 1;
  0.018 / 0.0096 with 0.25 and 0.5, three times of room under the
  mean's limit, as the dense family has (0.033 / 0.012 on the chip).
  The differences scale with the logits' deviation (a chosen token is
  the one whose rounding fell highest), so the head's scale halves
  them; a wrong computation still moves a chosen token's
  log-probability by whole units (tests/chip_benchmark/
  test_chipbench_ouro.py drops each norm);
- the gate's weights are N(0, 1) / sqrt(hidden) and its bias GATE_BIAS
  (-1): non-zero, a gate of about 0.3 a pass.
"""

from __future__ import annotations

import dataclasses

VOCAB_SLICES = 8
OUT_GAIN = 0.25
HEAD_GAIN = 0.5
GAIN_SPREAD = 0.25
GATE_BIAS = -1.0
# norms the tests may leave out of the reference, to show that the
# comparison sees each of them (`forward_logprobs(drop=...)`)
NORMS = ("g2", "g4", "pass_norm")


# -- 1. the config.json the program reads ----------------------------------
def hf_config(config: dict) -> dict:
    """The published keys (the family has no key of its own)."""
    return dict(config)


# -- 2. the weights ---------------------------------------------------------
def init_params(mc, key, dtype):
    """All weights from the key, layer by layer (the looped stack's
    weights once: the passes share them); see the module's note on the
    scales."""
    import jax
    import jax.numpy as jnp

    h, i, v = mc.hidden_size, mc.intermediate_size, mc.vocab_size

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def gain(k, mean=1.0):
        return (mean * (1.0 + GAIN_SPREAD * jax.random.normal(
            k, (h,), jnp.float32))).astype(dtype)

    shapes = {
        "wq": ((h, mc.q_size), h), "wk": ((h, mc.kv_size), h),
        "wv": ((h, mc.kv_size), h), "wo": ((mc.q_size, h), mc.q_size),
        "w_gate": ((h, i), h), "w_up": ((h, i), h), "w_down": ((i, h), i),
    }
    gains = {"attn_norm": 1.0, "attn_out_norm": OUT_GAIN,
             "mlp_norm": 1.0, "mlp_out_norm": OUT_GAIN}

    def one_layer(k):
        ks = jax.random.split(k, len(shapes) + len(gains))
        lp = {n: w(ks[j], s, f) for j, (n, (s, f)) in
              enumerate(sorted(shapes.items()))}
        for j, (n, mean) in enumerate(sorted(gains.items())):
            lp[n] = gain(ks[len(shapes) + j], mean)
        return lp

    k_embed, k_head, k_layers, k_final, k_gate = jax.random.split(key, 5)
    return {
        "embed": jax.random.normal(k_embed, (v, h), jnp.float32).astype(
            dtype),
        "layers": jax.lax.map(
            one_layer, jax.random.split(k_layers, mc.num_layers)),
        "final_norm": gain(k_final),
        "exit_gate_w": w(k_gate, (h,), h),
        "exit_gate_b": jnp.asarray(GATE_BIAS, dtype),
        "lm_head": (jax.random.normal(k_head, (h, v), jnp.float32)
                    * HEAD_GAIN * h ** -0.5).astype(dtype),
    }


# -- 3. the plain reference -------------------------------------------------
def _passes(cfg, params, token_ids, *, drop=(), shared_cache_from=None):
    """The hidden state after each pass's closing norm, (T, t, hidden)
    float32: a full forward over `token_ids` (t,), a dense causal mask,
    no cache, each pass attending over the keys and values it computed
    itself.

    Two departures from a textbook loop, as in the dense family: the
    layers are walked by `lax.scan` over the stacked weights, each
    layer's bf16 weights upcast inside the step.

    For the tests only: `drop` leaves norms of NORMS out altogether;
    `shared_cache_from=c` is what ONE cache slot a layer, shared among
    the passes, would give a prompt computed in two chunks [0, c) and
    [c, t): when the second chunk runs, the rows of the first hold what
    its LAST pass wrote, in every pass."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    t = token_ids.shape[0]
    nq, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    pos = jnp.arange(t, dtype=f32)
    half = d // 2
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(half, dtype=f32) * 2.0 / d))
    freqs = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(freqs)[:, None, :], jnp.sin(freqs)[:, None, :]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def rms(x, g, name=None):
        if name in drop:
            return x
        n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return n * g.astype(f32)

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def layer(h, xs):
        lp, k_old, v_old = xs
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        x = rms(h, lp["attn_norm"])
        q = rope((x @ lp["wq"]).reshape(t, nq, d))
        k = rope((x @ lp["wk"]).reshape(t, nkv, d))
        v = (x @ lp["wv"]).reshape(t, nkv, d)
        if k_old is not None:
            first = (jnp.arange(t) < shared_cache_from)[:, None, None]
            k, v = jnp.where(first, k_old, k), jnp.where(first, v_old, v)
        qg = q.reshape(t, nkv, nq // nkv, d)
        s = jnp.einsum("tkgd,skd->tkgs", qg, k) * (d ** -0.5)
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        o = jnp.einsum("tkgs,skd->tkgd", jax.nn.softmax(s, -1), v)
        h = h + rms(o.reshape(t, nq * d) @ lp["wo"],
                    lp["attn_out_norm"], "g2")
        x = rms(h, lp["mlp_norm"])
        m = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])
             ) @ lp["w_down"]
        return h + rms(m, lp["mlp_out_norm"], "g4"), (k, v)

    def run(old):
        h = params["embed"][token_ids].astype(f32)
        out, kvs = [], []
        for p in range(cfg.ut_steps):
            h, kv = jax.lax.scan(
                layer, h, (params["layers"], *(old or (None, None))))
            # (dropping "pass_norm" drops the norm BETWEEN passes; the
            # last pass's is the model's norm before the head)
            h = rms(h, params["final_norm"],
                    "pass_norm" if p < cfg.ut_steps - 1 else None)
            out.append(h)
            kvs.append(kv)
        return jnp.stack(out), kvs

    if shared_cache_from is None:
        return run(None)[0]
    # causal: rows < c of a plain run are what the first chunk gave, and
    # its last pass's keys and values are what the shared slots hold
    # when the second chunk runs
    first, kvs = run(None)
    second = (jnp.arange(t) >= shared_cache_from)[None, :, None]
    return jnp.where(second, run(kvs[-1])[0], first)


def forward_logprobs(cfg, params, token_ids, rows, *, drop=(),
                     shared_cache_from=None):
    """log-softmax over the vocabulary at `rows` of a full forward pass
    over `token_ids` (t,): the LAST pass's normed state through the
    head, applied to the asked rows only, in vocabulary slices.
    Everything float32."""
    import jax
    import jax.numpy as jnp

    h = _passes(cfg, params, token_ids, drop=drop,
                shared_cache_from=shared_cache_from)[-1]
    h = h[rows]
    lm = params["lm_head"]
    v = lm.shape[1]
    step = -(-v // VOCAB_SLICES)
    logits = jnp.concatenate([
        h @ lm[:, i:i + step].astype(jnp.float32)
        for i in range(0, v, step)], -1)
    return jax.nn.log_softmax(logits, -1)


def exit_distribution(cfg, params, token_ids, rows):
    """(T, r): the probability that row r leaves after pass t under the
    gate, `lambda_t prod_{s<t} (1 - lambda_s)`, the last pass taking the
    rest. What the program's `tpu:loop_exit_mass` sums over its sampled
    rows."""
    import jax
    import jax.numpy as jnp

    h = _passes(cfg, params, token_ids)[:, rows]
    lam = jax.nn.sigmoid(
        h @ params["exit_gate_w"].astype(jnp.float32)
        + params["exit_gate_b"].astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(lam * before)[:-1], before[-1:]])


# -- 4. the counts: bytes and operations a step needs ----------------------
# Kept with the benchmark so that no PR that claims a gain can change how
# a share of a peak is counted. Inputs are a configuration file's dict.
def _dims(hf: dict) -> tuple[int, int, int, int, int, int, int, int]:
    h = hf["hidden_size"]
    nq = hf["num_attention_heads"]
    return (h, hf["intermediate_size"], hf["num_hidden_layers"], nq,
            hf.get("num_key_value_heads", nq),
            hf.get("head_dim") or h // nq, hf["vocab_size"],
            hf["total_ut_steps"])


def layer_params(hf: dict) -> int:
    """Parameters of one decoder layer: q, k, v, o, gate, up, down and
    the four gains; no bias."""
    h, i, _, nq, nkv, d, _, _ = _dims(hf)
    return h * nq * d + 2 * h * nkv * d + nq * d * h + 3 * h * i + 4 * h


def total_params(hf: dict) -> int:
    """Held weights: the stack ONCE (its passes share it), embedding
    and head (untied), the final gain, the gate."""
    h, _, layers, _, _, _, v, _ = _dims(hf)
    return layers * layer_params(hf) + 2 * v * h + h + h + 1


def layer_stack_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of weights ONE pass through the layer stack reads: every
    layer matrix and gain; neither embedding nor head. A token's step
    reads them `total_ut_steps` times (`step_weight_bytes`)."""
    return hf["num_hidden_layers"] * layer_params(hf) * bytes_per_param


def step_weight_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes of weights ONE decode step must read: the stack once a
    pass, the final gain and the gate, and the head once."""
    h, _, _, _, _, _, v, passes = _dims(hf)
    return (passes * layer_stack_bytes(hf, bytes_per_param)
            + (h * v + 2 * h + 1) * bytes_per_param)


def kv_bytes_per_token(hf: dict, bytes_per_elem: int = 2) -> int:
    """K and V of every layer AND pass: a pass reads and writes cache
    layers of its own, so a token's context is walked T x L times a
    step."""
    _, _, layers, _, nkv, d, _, passes = _dims(hf)
    return 2 * layers * passes * nkv * d * bytes_per_elem


# -- 5. the rehearsal's shape ----------------------------------------------
def rehearsal_config(mc, tp: int):
    """The tiny debug widths as a looped stack: the passes, the output
    norms and the gate are what select code paths in this family."""
    from production_stack_tpu.models import config as mcfg

    tiny = mcfg.TINY_DEBUG
    return dataclasses.replace(
        tiny, name=mc.name, num_kv_heads=max(tiny.num_heads, tp),
        rms_norm_eps=mc.rms_norm_eps, rope_theta=mc.rope_theta,
        tie_word_embeddings=False, max_model_len=mc.max_model_len,
        ut_steps=mc.ut_steps, sandwich_norm=mc.sandwich_norm,
        exit_gate=mc.exit_gate,
    )


# -- 6. the guard -----------------------------------------------------------
def check(config: dict, mc) -> None:
    """Refuse where the file and the program's ModelConfig disagree on
    what the counts and the reference rest on. First of all the loop: a
    program whose ModelConfig has no `ut_steps`, or another count than
    the file's `total_ut_steps`, would serve the file as a stack that
    runs once."""
    want = config["total_ut_steps"]
    got = getattr(mc, "ut_steps", None)
    if got != want:
        raise SystemExit(
            f"the configuration's file says total_ut_steps={want}, the "
            f"program's ModelConfig has ut_steps={got!r}: the program "
            "would not run the layer stack as often as the ouro family "
            "counts and its reference computes")
    if not (getattr(mc, "sandwich_norm", False)
            and getattr(mc, "exit_gate", False)):
        raise SystemExit(
            "the ouro family's layers norm each sublayer's output and "
            "the model carries an exit gate; the program's ModelConfig "
            f"has sandwich_norm={getattr(mc, 'sandwich_norm', None)!r}, "
            f"exit_gate={getattr(mc, 'exit_gate', None)!r}")
    served = (mc.hidden_size, mc.intermediate_size, mc.num_layers,
              mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.vocab_size,
              mc.ut_steps)
    if served != _dims(config):
        raise SystemExit(
            f"the configuration's file gives the shapes {_dims(config)}, "
            f"the program's ModelConfig {served}: the ouro family would "
            "count other weights than are served")
    if (mc.qkv_bias or mc.is_moe or mc.sliding_window or mc.layer_groups
            or mc.tie_word_embeddings or mc.hidden_act != "silu"):
        raise SystemExit(
            "the ouro family covers a looped SwiGLU decoder of alike "
            "full-attention layers without biases and with an untied "
            f"head; the program's ModelConfig has qkv_bias={mc.qkv_bias}, "
            f"num_experts={mc.num_experts}, sliding_window="
            f"{mc.sliding_window}, tie_word_embeddings="
            f"{mc.tie_word_embeddings}, hidden_act={mc.hidden_act!r}")
