"""The plain reference: a Llama-class decoder's forward pass in float32.

A copy of `tests/reference_model.py::dense_forward` (the original stays
where the program's own tests use it), made float32 throughout under
`jax.default_matmul_precision("highest")`: no kernel, no cache, no
batching, a dense causal mask over the whole sequence. It follows the
published architectures (Mistral, Qwen2: RMSNorm, rotary embedding on
half-split head dims, grouped-query attention, SwiGLU, untied lm_head,
and q/k/v biases where the configuration has them). One departure from a
textbook loop: the layers are walked by `lax.scan` over the stacked
weights and each layer's bf16 weights are upcast inside the step, so
only one layer's float32 copy lives beside the serving cache, and the
lm_head is applied to the asked rows only, in vocabulary slices.

`teacher_forced_logprobs` is what `correct` is decided on: for a prompt
and the tokens the served path generated after it, the reference's
log-probability of each generated token given everything before it.

TOLERANCE. The served path computes in bfloat16 (8 bits of precision,
relative rounding 2**-8 = 0.4%) with float32 accumulation, so its logits
over 14-16 layers of random weights carry an absolute error of a few
hundredths against float32. Measured on the chip at published widths
(PERF.md, Findings, PR 23; 27 runs, 2 prompts of 8 tokens each), the
served chosen-token log-probabilities differ from this reference by at
most 0.033 at any position (mistral-7b-l16; 0.023 on qwen2-7b-l14), and
by at most 0.012 in the mean over a prompt's 8 positions.
LOGPROB_ATOL = 0.1 is three times the worst position seen and far below
what a wrong computation gives: a dropped q/k/v bias, a wrong GQA
grouping, a wrong rope theta or a missing layer changes the chosen
tokens' log-probabilities by whole units (under random weights the
distribution over 32k-152k ids is nearly flat, so an unrelated
computation scores a chosen token near -log(vocab) = -10 to -12).
MEAN_ATOL = 0.03 on the mean absolute difference is 2.4 times the worst
mean seen; 8-bit floating-point weights or activations (4 bits of
precision, 16 times bfloat16's rounding) would pass it several times
over. It is not tight enough to tell int8 weights with per-channel
scales from bfloat16, which err about as much; PERF.md lists that.
"""

from __future__ import annotations

LOGPROB_ATOL = 0.1
MEAN_ATOL = 0.03
VOCAB_SLICES = 8


def _forward_logprobs(cfg, params, token_ids, rows):
    """log-softmax over the vocabulary at `rows` of a full forward pass
    over `token_ids` (t,). Everything float32, precision highest."""
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

    def rms(x, w):
        n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return n * (w.astype(f32) + cfg.norm_weight_offset)

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def layer(h, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        x = rms(h, lp["attn_norm"])
        q, k, v = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
        if cfg.qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = rope(q.reshape(t, nq, d))
        k = rope(k.reshape(t, nkv, d))
        v = v.reshape(t, nkv, d)
        qg = q.reshape(t, nkv, nq // nkv, d)
        s = jnp.einsum("tkgd,skd->tkgs", qg, k) * (d ** -0.5)
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        o = jnp.einsum("tkgs,skd->tkgd", jax.nn.softmax(s, -1), v)
        h = h + o.reshape(t, nq * d) @ lp["wo"]
        x = rms(h, lp["mlp_norm"])
        h = h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])
                 ) @ lp["w_down"]
        return h, None

    h = params["embed"][token_ids].astype(f32) * cfg.embed_scale
    h, _ = jax.lax.scan(layer, h, params["layers"])
    h = rms(h, params["final_norm"])[rows]
    lm = (params["embed"].T if cfg.tie_word_embeddings
          else params["lm_head"])
    v = lm.shape[1]
    step = -(-v // VOCAB_SLICES)
    logits = jnp.concatenate([
        h @ lm[:, i:i + step].astype(f32) for i in range(0, v, step)
    ], -1)
    return jax.nn.log_softmax(logits, -1)


def teacher_forced_logprobs(cfg, params, prompt_ids, generated_ids):
    """The reference's log-probability of each generated token, given
    the prompt and the generated tokens before it. Plain floats."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if cfg.is_moe or cfg.sliding_window or cfg.hidden_act != "silu":
        raise NotImplementedError(
            "the reference covers dense SwiGLU decoders with full "
            "attention; add the mechanism here with its configuration")
    ids = np.asarray(list(prompt_ids) + list(generated_ids), np.int32)
    n_p, n_g = len(prompt_ids), len(generated_ids)
    rows = np.arange(n_p - 1, n_p - 1 + n_g, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        lp = jax.jit(_forward_logprobs, static_argnums=0)(
            cfg, params, jnp.asarray(ids), jnp.asarray(rows))
    lp = np.asarray(lp)
    return [float(lp[i, g]) for i, g in enumerate(generated_ids)]


def compare(served: list[float], reference: list[float]) -> dict:
    """The decision: every position within LOGPROB_ATOL and the mean
    absolute difference within MEAN_ATOL."""
    diffs = [abs(a - b) for a, b in zip(served, reference)]
    ok = (len(served) == len(reference) and len(diffs) > 0
          and max(diffs) <= LOGPROB_ATOL
          and sum(diffs) / len(diffs) <= MEAN_ATOL)
    return {"ok": bool(ok), "max_abs_diff": max(diffs) if diffs else None,
            "mean_abs_diff": sum(diffs) / len(diffs) if diffs else None,
            "n": len(diffs)}
