"""Sampler unit tests."""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.sampler import TOP_CAP, sample_tokens
from production_stack_tpu.engine.sampling_params import SamplingParams


def run(logits, temp, top_p=1.0, top_k=-1, key=(0, 0)):
    b = logits.shape[0]
    return np.asarray(
        sample_tokens(
            logits.astype(np.float32),
            np.full((b,), temp, np.float32),
            np.full((b,), top_p, np.float32),
            np.full((b,), top_k, np.int32),
            np.tile(np.asarray(key, np.uint32), (b, 1)),
        )
    )


def test_greedy_is_argmax():
    rng = np.random.RandomState(0)
    logits = rng.randn(4, 1000)
    out = run(logits, temp=0.0)
    assert (out == logits.argmax(-1)).all()


def test_top_k_1_is_argmax():
    rng = np.random.RandomState(1)
    logits = rng.randn(4, 1000)
    out = run(logits, temp=1.0, top_k=1)
    assert (out == logits.argmax(-1)).all()


def test_top_p_tiny_is_argmax():
    rng = np.random.RandomState(2)
    logits = rng.randn(4, 1000)
    out = run(logits, temp=1.0, top_p=1e-6)
    assert (out == logits.argmax(-1)).all()


def test_sampling_respects_top_k():
    rng = np.random.RandomState(3)
    logits = rng.randn(1, 1000)
    top5 = set(np.argsort(logits[0])[-5:])
    for step in range(50):
        out = run(logits, temp=2.0, top_k=5, key=(7, step))
        assert out[0] in top5


def test_same_key_is_deterministic():
    rng = np.random.RandomState(4)
    logits = rng.randn(2, 500)
    a = run(logits, temp=1.0, key=(42, 3))
    b = run(logits, temp=1.0, key=(42, 3))
    assert (a == b).all()


def test_different_keys_vary():
    rng = np.random.RandomState(5)
    logits = np.zeros((1, 100))  # uniform -> sampling must move around
    seen = {run(logits, 1.0, key=(9, s))[0] for s in range(30)}
    assert len(seen) > 5


def test_mixed_greedy_and_sampled_rows():
    rng = np.random.RandomState(6)
    logits = rng.randn(3, 200).astype(np.float32)
    temps = np.asarray([0.0, 1.0, 0.0], np.float32)
    out = np.asarray(
        sample_tokens(
            logits,
            temps,
            np.ones((3,), np.float32),
            np.full((3,), -1, np.int32),
            np.tile(np.asarray([1, 2], np.uint32), (3, 1)),
        )
    )
    assert out[0] == logits[0].argmax()
    assert out[2] == logits[2].argmax()


def run_minp(logits, temp, min_p, key=(0, 0)):
    b = logits.shape[0]
    return np.asarray(
        sample_tokens(
            logits.astype(np.float32),
            np.full((b,), temp, np.float32),
            np.ones((b,), np.float32),
            np.full((b,), -1, np.int32),
            np.tile(np.asarray(key, np.uint32), (b, 1)),
            min_p=np.full((b,), min_p, np.float32),
        )
    )


def test_min_p_one_is_argmax():
    """min_p=1.0 keeps only candidates at max_prob -> argmax for any
    temperature (vLLM min_p semantics: threshold = min_p * max_prob)."""
    rng = np.random.RandomState(3)
    logits = rng.randn(4, 1000) * 3
    for key in [(0, i) for i in range(8)]:
        out = run_minp(logits, temp=1.0, min_p=1.0, key=key)
        assert (out == logits.argmax(-1)).all()


def test_min_p_zero_matches_disabled():
    """min_p=0 must be bit-identical to not passing min_p at all."""
    rng = np.random.RandomState(4)
    logits = rng.randn(4, 1000)
    for key in [(5, i) for i in range(8)]:
        a = run(logits, temp=0.8, key=key)
        b = run_minp(logits, temp=0.8, min_p=0.0, key=key)
        assert (a == b).all()


def test_min_p_filters_tail():
    """With one dominant token and a high min_p, samples never come
    from the tail."""
    logits = np.full((2, 100), 0.0, np.float32)
    logits[:, 7] = 6.0  # dominant
    logits[:, 8] = 5.0  # survives min_p=0.2 (prob ratio e^-1 ~ 0.37)
    seen = set()
    for i in range(32):
        out = run_minp(logits, temp=1.0, min_p=0.2, key=(9, i))
        seen.update(out.tolist())
    assert seen <= {7, 8}, seen


# -- the candidate window runs only where a row samples -----------------
# `sample_tokens` builds its window (lax.top_k over the vocabulary, the
# masks, the noise) inside one branch of a lax.cond; below is the
# straight-line function it replaced, kept as the reference every mix of
# rows is held to, token for token.


@functools.partial(jax.jit, static_argnames=("top_cap",))
def straight_line_sample_tokens(logits, temperature, top_p, top_k,
                                key_data, min_p=None, top_cap=TOP_CAP):
    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    vals, idxs = jax.lax.top_k(logits, top_cap)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = vals / temp
    ranks = jnp.arange(top_cap)[None, :]
    k = jnp.where(top_k[:, None] <= 0, top_cap, top_k[:, None])
    keep_k = ranks < jnp.minimum(k, top_cap)
    probs = jax.nn.softmax(jnp.where(keep_k, scaled, -jnp.inf), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = (cum - probs) < top_p[:, None]
    keep = keep_k & keep_p
    if min_p is not None:
        keep = keep & (probs >= min_p[:, None] * probs[:, 0:1])
    keep = keep.at[:, 0].set(True)
    masked = jnp.where(keep, scaled, -jnp.inf)

    def row_gumbel(kd):
        return jax.random.gumbel(
            jax.random.wrap_key_data(kd, impl="threefry2x32"), (top_cap,)
        )

    gumbel = jax.vmap(row_gumbel)(key_data)
    choice = jnp.argmax(masked + gumbel, axis=-1)
    sampled_ids = jnp.take_along_axis(
        idxs, choice[:, None], axis=-1
    ).squeeze(-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy_ids, sampled_ids)


ROWS = 6
MIXES = {
    "all_greedy": dict(temps=[0.0] * ROWS),
    "all_sampled": dict(temps=[0.7, 1.0, 1.3, 2.0, 0.2, 1.0]),
    "one_sampled_among_greedy": dict(temps=[0.0, 0.0, 0.0, 0.9, 0.0, 0.0]),
    "greedy_first_and_last": dict(temps=[0.0, 1.1, 0.6, 0.8, 1.0, 0.0]),
    "top_k_on": dict(temps=[1.0, 0.0, 1.5, 1.0, 0.0, 0.8],
                     top_ks=[5, 5, 1, 40, -1, 200]),
    "top_p_on": dict(temps=[1.0, 0.0, 1.5, 1.0, 0.0, 0.8],
                     top_ps=[0.9, 0.5, 0.3, 1e-6, 1.0, 0.99]),
    "min_p_on": dict(temps=[1.0, 0.0, 1.5, 1.0, 0.0, 0.8],
                     min_ps=[0.1, 0.5, 0.0, 1.0, 0.3, 0.05]),
    "all_filters_on": dict(temps=[1.0, 0.0, 1.5, 1.0, 0.0, 0.8],
                           top_ks=[8, -1, 3, 50, 2, 64],
                           top_ps=[0.9, 1.0, 0.7, 0.95, 0.5, 0.8],
                           min_ps=[0.0, 0.2, 0.05, 0.1, 0.0, 0.02]),
}


def _mix_args(mix, seed):
    rng = np.random.RandomState(seed)
    spec = MIXES[mix]
    args = (
        (rng.randn(ROWS, 777) * 2.5).astype(np.float32),
        np.asarray(spec["temps"], np.float32),
        np.asarray(spec.get("top_ps", [1.0] * ROWS), np.float32),
        np.asarray(spec.get("top_ks", [-1] * ROWS), np.int32),
        rng.randint(0, 2**31, size=(ROWS, 2)).astype(np.uint32),
    )
    kwargs = {}
    if "min_ps" in spec:
        kwargs["min_p"] = np.asarray(spec["min_ps"], np.float32)
    return args, kwargs


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_tokens_are_those_of_the_straight_line_sampler(mix, seed):
    args, kwargs = _mix_args(mix, seed)
    want = np.asarray(straight_line_sample_tokens(*args, **kwargs))
    got = np.asarray(sample_tokens(*args, **kwargs))
    assert got.dtype == want.dtype and got.shape == want.shape
    assert (got == want).all(), (got, want)
    greedy_rows = args[1] <= 0.0
    assert (got[greedy_rows] == args[0].argmax(-1)[greedy_rows]).all()


def _primitives(jaxpr, inside_cond=False):
    """(primitive name, is it inside a cond's branch) of every equation,
    sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside_cond
        inner = inside_cond or eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub, inner)


@pytest.mark.parametrize("with_min_p", [False, True])
def test_one_cond_and_the_top_k_is_inside_it(with_min_p):
    args, kwargs = _mix_args("min_p_on" if with_min_p else "top_k_on", 0)
    prims = list(_primitives(
        jax.make_jaxpr(sample_tokens)(*args, **kwargs).jaxpr))
    assert [name for name, _ in prims].count("cond") == 1
    top_ks = [inside for name, inside in prims if name == "top_k"]
    assert top_ks == [True]
    # what a greedy round still pays for sits outside the branch
    assert ("argmax", False) in prims
    # and the noise, the other cost that no greedy row reads, does not
    assert all(inside for name, inside in prims
               if name in ("threefry2x32", "random_bits", "cumsum"))


def test_an_all_greedy_call_does_not_run_the_window(monkeypatch):
    """The branch is taken by the program, on the value of `temperature`:
    a callback beside the top_k fires only in a call that has a sampling
    row."""
    hits = []
    top_k = jax.lax.top_k

    def counted_top_k(*a, **kw):
        jax.debug.callback(lambda: hits.append(1))
        return top_k(*a, **kw)

    monkeypatch.setattr(jax.lax, "top_k", counted_top_k)
    # a function of its own: jit caches traces by the function it wraps
    fn = jax.jit(lambda *a: sample_tokens.__wrapped__(*a))
    for mix, want in (("all_greedy", []), ("one_sampled_among_greedy", [1]),
                      ("all_greedy", [1]), ("all_sampled", [1, 1])):
        fn(*_mix_args(mix, 0)[0]).block_until_ready()
        jax.effects_barrier()
        assert hits == want, mix


# -- the step programs, on the CPU at the tiny debug widths ---------------

ROUND_MIXES = {
    "greedy_rows": {"a": 0.0, "b": 0.0, "c": 0.0},
    "sampled_rows": {"a": 0.8, "b": 1.2, "c": 0.5},
    "both": {"a": 0.0, "b": 0.9, "c": 0.0},
}


def _serve(engine, temps):
    """Requests "a" and "b" from the start, "c" while they decode (its
    prefill rides a lane-typed round); {id: tokens}, and what the rounds
    added to the runner's (sampler steps, window steps)."""
    rs = np.random.RandomState(7)
    prompts = {rid: rs.randint(0, 384, size=n).tolist()
               for rid, n in (("a", 11), ("b", 5), ("c", 19))}

    def add(rid):
        engine.add_request(
            rid, prompt_token_ids=prompts[rid],
            sampling_params=SamplingParams(
                max_tokens=13, temperature=temps[rid], top_p=0.9,
                top_k=40, seed=5, ignore_eos=True),
        )

    before = list(engine.runner.sampler_steps)
    add("a")
    add("b")
    outs, steps = {}, 0
    while engine.has_unfinished() or "c" not in outs:
        if steps == 2:
            add("c")
        for o in engine.step():
            if o.finished:
                outs[o.request_id] = list(o.token_ids)
        steps += 1
        assert steps < 500, "engine wedged"
    return outs, [now - was for now, was in
                  zip(engine.runner.sampler_steps, before)]


@pytest.fixture(scope="module")
def served():
    """{mix: (tokens, counter deltas)} of one engine with the sampler as
    it is, and the tokens of one whose programs were built over the
    straight-line sampler."""
    from production_stack_tpu.engine import llm_engine, sampler

    def engine():
        return llm_engine.LLMEngine(EngineConfig(
            model="pst-tiny-debug", tokenizer="byte", dtype="float32",
            cache_dtype="float32", block_size=8, num_kv_blocks=192,
            max_num_seqs=4, max_prefill_chunk=8, num_scheduler_steps=4,
            seed=0,
        ))

    e = engine()
    change = {mix: _serve(e, temps) for mix, temps in ROUND_MIXES.items()}
    # the step builders import the sampler when they build a program
    patched = [(sampler, "sample_tokens"), (llm_engine, "sample_tokens")]
    with pytest.MonkeyPatch.context() as mp:
        for module, name in patched:
            mp.setattr(module, name, straight_line_sample_tokens)
        e = engine()
        parent = {mix: _serve(e, temps)[0]
                  for mix, temps in ROUND_MIXES.items()}
    return change, parent


@pytest.mark.parametrize("mix", sorted(ROUND_MIXES))
def test_step_programs_return_the_straight_line_samplers_tokens(
        served, mix):
    change, parent = served
    tokens, _ = change[mix]
    assert tokens.keys() == {"a", "b", "c"}
    assert all(len(t) == 13 for t in tokens.values())
    assert tokens == parent[mix]


@pytest.mark.parametrize("mix", sorted(ROUND_MIXES))
def test_sampler_steps_count_the_rounds_that_build_the_window(
        served, mix):
    """tpu:sampler_steps / tpu:sampler_window_steps: every evaluation of
    the sampler by a dispatched round, and those whose rows held a
    temperature > 0."""
    _, (steps, window) = served[0][mix]
    assert steps > 0
    if mix == "greedy_rows":
        assert window == 0
    elif mix == "sampled_rows":
        assert window == steps
    else:
        assert 0 < window < steps


# -- the benchmark's name for the window, against a recorded trace ---------
# The sampler's top-k as the profiler printed it in a traced run of the
# parent of PR 36 (`qwen2-7b-l14.chat-sys2k`, seed 3600000601, TPU v5 lite;
# `custom-call.60 f32[32,64]`, 0.225 s of 5 s): the fused decode scan's, and
# a lane-typed round's first-token rows.
RECORDED_TOPK = [
    '%custom-call.60 = (f32[32,64]{1,0:T(8,128)S(1)}, s32[32,64]{1,0:T(8,128)'
    'S(1)}) custom-call(f32[32,152064]{1,0:T(8,128)S(1)} %fusion.188), '
    'custom_call_target="TopK", called_computations={%compare-greater-than.1'
    '.clone.clone.clone.clone.clone.clone.clone.clone.clone.clone}',
    '%custom-call.106 = (f32[32,64]{1,0:T(8,128)S(1)}, s32[32,64]{1,0:T(8,128)'
    'S(1)}) custom-call(f32[32,152064]{1,0:T(8,128)S(1)} %fusion.483), '
    'custom_call_target="TopK", called_computations={%compare-greater-than.2'
    '.clone.clone.clone.clone.clone.clone.clone.clone.clone.clone}',
]
# what the change's trace has in its place, and neighbours that are not the
# window: the branch's wrapper, a Pallas kernel, the log-probabilities' top 20
RECORDED_OTHERS = [
    '%conditional.7 = (s32[32]{0:T(128)}) conditional(s32[]{:T(128)} '
    '%convert_element_type.239, (f32[32,152064]{1,0:T(8,128)S(1)}, '
    'f32[32]{0:T(128)S(1)}, s32[32]{0:T(128)S(1)}',
    '%ragged_paged_attention.6 = bf16[32,28,128]{2,1,0:T(8,128)(2,1)} '
    'custom-call(s32[]{:T(128)} %layer), custom_call_target="tpu_custom_call"',
    '%custom-call.3 = (f32[32,20]{1,0:T(8,128)}, s32[32,20]{1,0:T(8,128)}) '
    'custom-call(f32[32,152064]{1,0:T(8,128)} %fusion.9), '
    'custom_call_target="TopK"',
    '%fusion.188 = f32[32,152064]{1,0:T(8,128)S(1)} fusion(bf16[32,3584] %x)',
]


@pytest.mark.parametrize("mix", ["serve", "batch"])
def test_the_benchmarks_metric_names_the_recorded_top_k(mix):
    path = os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "chip",
        "layer_metrics", f"sampler_topk_op_share.{mix}.json")
    with open(path) as f:
        spec = json.load(f)
    assert spec["reader"] == "trace_op_share"
    pat = re.compile(spec["ops"])
    assert f"f32[32,{TOP_CAP}]" in RECORDED_TOPK[0]
    assert all(pat.search(text) for text in RECORDED_TOPK)
    assert not any(pat.search(text) for text in RECORDED_OTHERS)
