"""Batched token sampler, jit-compiled with static shapes.

TPU-first design: instead of a per-request Python loop, sampling is one fused
XLA program over the whole decode batch. Temperature / top-k / top-p are
per-row vectors; randomness is per-row counter-based PRNG keys so results are
reproducible regardless of batch composition.

Top-k/top-p operate within a static TOP_CAP-candidate window (`lax.top_k`),
which avoids a full 128k-vocab sort on the MXU-unfriendly sort path. greedy
rows use the exact full-vocab argmax. TOP_CAP bounds the effective top_k; for
top_p the residual probability mass outside the top-64 of an LLM softmax is
negligible, and vLLM's TPU backend makes the same trade.

The window is built only where a row reads it: `lax.top_k`, the temperature
scaling, the top-k / top-p / min-p masks, the Gumbel noise and the gather sit
in one branch of ONE `lax.cond` on "does any row of this call sample", a
scalar computed on the device from the call's own `temperature`. A call whose
rows are all greedy takes the argmax and skips the rest (on a v5e the sort of
f32[32, 152064] is ~0.8 ms of a ~10 ms decode step); a call with one sampling
row runs all of it for all rows, and every row gets the token it always got.
The host counts both kinds of call (`tpu:sampler_steps`,
`tpu:sampler_window_steps`). Do not `vmap` this function: a mapped `cond`
becomes a `select` and both sides run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TOP_CAP = 64


@functools.partial(jax.jit, static_argnames=("top_cap",))
def sample_tokens(
    logits: jax.Array,  # (b, vocab) float32
    temperature: jax.Array,  # (b,) float32; 0 => greedy
    top_p: jax.Array,  # (b,) float32 in (0, 1]
    top_k: jax.Array,  # (b,) int32; <=0 => disabled
    key_data: jax.Array,  # (b, 2) uint32 per-row PRNG key data
    min_p: jax.Array | None = None,  # (b,) float32 in [0, 1]; 0 => off
    top_cap: int = TOP_CAP,
) -> jax.Array:
    """Sample one token per row. Returns (b,) int32."""
    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    greedy = temperature <= 0.0

    def window() -> jax.Array:
        vals, idxs = jax.lax.top_k(logits, top_cap)  # (b, cap) desc order
        temp = jnp.maximum(temperature, 1e-6)[:, None]
        scaled = vals / temp

        # top-k mask within the candidate window
        ranks = jnp.arange(top_cap)[None, :]
        k = jnp.where(top_k[:, None] <= 0, top_cap, top_k[:, None])
        keep_k = ranks < jnp.minimum(k, top_cap)

        # top-p (nucleus) mask: keep the smallest prefix with cumprob >=
        # top_p, i.e. keep entries whose *preceding* cumulative mass is
        # < top_p.
        probs = jax.nn.softmax(jnp.where(keep_k, scaled, -jnp.inf), axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_p = (cum - probs) < top_p[:, None]

        keep = keep_k & keep_p
        if min_p is not None:
            # min-p (vLLM min_p role): drop candidates whose post-temperature
            # probability is below min_p * max_prob. Row 0 of the descending
            # top-k IS the max-prob candidate.
            keep = keep & (probs >= min_p[:, None] * probs[:, 0:1])
        keep = keep.at[:, 0].set(True)  # never mask the argmax candidate
        masked = jnp.where(keep, scaled, -jnp.inf)

        def row_gumbel(kd):
            return jax.random.gumbel(
                jax.random.wrap_key_data(kd, impl="threefry2x32"), (top_cap,)
            )

        gumbel = jax.vmap(row_gumbel)(key_data)
        choice = jnp.argmax(masked + gumbel, axis=-1)  # (b,)
        sampled_ids = jnp.take_along_axis(
            idxs, choice[:, None], axis=-1
        ).squeeze(-1).astype(jnp.int32)
        return jnp.where(greedy, greedy_ids, sampled_ids)

    # the branch not taken does not run: a scalar predicate stays control
    # flow on the chip (tests/test_tpu_aot_compile.py reads the compiled text)
    return jax.lax.cond(jnp.all(greedy), lambda: greedy_ids, window)


def apply_penalties(
    logits: jax.Array,  # (b, vocab) float32
    output_mask: jax.Array,  # (b, vocab) bool: token appeared in output
    output_counts: jax.Array,  # (b, vocab) float32: occurrences in output
    presence: jax.Array,  # (b,)
    frequency: jax.Array,  # (b,)
    repetition: jax.Array,  # (b,)
) -> jax.Array:
    """OpenAI-style presence/frequency + HF-style repetition penalties."""
    logits = logits - presence[:, None] * output_mask
    logits = logits - frequency[:, None] * output_counts
    rep = repetition[:, None]
    penalized = jnp.where(logits > 0, logits / rep, logits * rep)
    return jnp.where(output_mask, penalized, logits)


# device-side stop masks (elastic fused decode): the pad token a frozen
# lane's sampled slot is pinned to. 0 is safe — the host consumes only
# the per-lane valid counts, never the pinned slots.
STOP_PAD_TOKEN = 0

# unified ragged dispatch: the sentinel a NON-prefill lane's sampled
# first-token slot is pinned to inside the lane-typed round (negative —
# can never collide with a real token id, unlike STOP_PAD_TOKEN whose
# slots are guarded by valid counts instead). Hosts must only consume
# rows where the value is >= 0, and the engine asserts exactly that.
RAGGED_IDLE_TOKEN = -1


def stop_hit(
    tokens: jax.Array,  # (b,) int32 just-sampled tokens
    eos_ids: jax.Array,  # (b,) int32 per-lane EOS (-1 = ignore_eos/none)
    stop_ids: jax.Array | None,  # (b, cap) int32 padded with -1, or None
) -> jax.Array:
    """Per-lane bool: the sampled token is that lane's EOS or one of
    its stop_token_ids. Shared by the fused decode scan so the device
    check can never drift from one copy of the semantics; the
    min_tokens/max_tokens gates are applied by the caller (they depend
    on the scan's per-lane append counters, not on the token). -1
    sentinels never match (token ids are non-negative)."""
    hit = tokens == eos_ids
    if stop_ids is not None:
        hit = hit | jnp.any(tokens[:, None] == stop_ids, axis=1)
    return hit


LOGPROB_CAP = 20  # static top-N bucket; hosts slice to the requested N


def token_logprobs(
    logits: jax.Array,  # (b, vocab) float32 — post-penalty model logits
    tokens: jax.Array,  # (b,) int32 chosen tokens
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-row chosen-token logprob + top-LOGPROB_CAP alternatives.

    Computed from log_softmax of the raw (pre-temperature) logits — the
    model's distribution, matching vLLM's logprobs semantics. Returns
    (chosen (b,), top_vals (b, CAP), top_ids (b, CAP) int32)."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    chosen = jnp.take_along_axis(
        lp, tokens[:, None].astype(jnp.int32), axis=-1
    )[:, 0]
    top_vals, top_ids = jax.lax.top_k(lp, LOGPROB_CAP)
    return chosen, top_vals, top_ids.astype(jnp.int32)
