"""The plain reference, as far as it is common to every architecture.

The forward pass itself is the family's (`forward_logprobs` of the file
under `families/` that the cell's configuration names: float32, no
kernel, no cache, no batching, nothing of the program imported). Here
is what every family shares: the ids and the rows asked for, the jit
under `jax.default_matmul_precision("highest")`, the comparison that
decides `correct`, and its tolerances with their reasons.

`teacher_forced_logprobs` is what `correct` is decided on: for a prompt
and the tokens the served path generated after it, the family's
reference log-probability of each generated token given everything
before it.

TOLERANCE. The served path computes in bfloat16 (8 bits of precision,
relative rounding 2**-8 = 0.4%) with float32 accumulation, so its logits
over 14-16 layers of random weights carry an absolute error of a few
hundredths against float32. The measurements below are the dense
family's, the only one served so far; a family that is added reads its
own before it relies on these limits. Measured on the chip at published
widths (PERF.md, Findings, PR 23; 27 runs, 2 prompts of 8 tokens each),
the served chosen-token log-probabilities differ from the dense
reference by at most 0.033 at any position (mistral-7b-l16; 0.023 on
qwen2-7b-l14), and by at most 0.012 in the mean over a prompt's 8
positions.
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


def teacher_forced_logprobs(family, cfg, params, prompt_ids,
                            generated_ids):
    """The family's reference log-probability of each generated token,
    given the prompt and the generated tokens before it. Plain floats."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ids = np.asarray(list(prompt_ids) + list(generated_ids), np.int32)
    n_p, n_g = len(prompt_ids), len(generated_ids)
    rows = np.arange(n_p - 1, n_p - 1 + n_g, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        lp = jax.jit(family.forward_logprobs, static_argnums=0)(
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
