"""Multi-step decode (--num-scheduler-steps): K fused on-device
decode+sample iterations per dispatch must be BIT-IDENTICAL to K single
steps — greedy and stochastic — because the per-iteration sampling keys
are the same (seed, generated_len + i) the single-step path uses.

Role: one device->host fetch per K tokens instead of per token (vLLM
multi-step scheduling / MaxText on-device sampling loop); cost of the
fetch on an attached chip: not measured."""

from __future__ import annotations

import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams


def _engine(k_steps=1, **kw):
    cfg = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=8, num_kv_blocks=64,
        max_num_seqs=3, max_prefill_chunk=16, seed=0,
        num_scheduler_steps=k_steps,
    )
    cfg.update(kw)
    return LLMEngine(EngineConfig(**cfg))


PROMPTS = [
    list(range(1, 12)),
    [50, 60, 70, 80, 90],
    [7, 8, 9, 10, 11, 12, 13, 14, 15],
]


@pytest.mark.parametrize("k", [4, 8])
def test_greedy_parity_vs_single_step(k):
    sp = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    single = [o.token_ids for o in _engine(1).generate(PROMPTS, sp)]
    multi = [o.token_ids for o in _engine(k).generate(PROMPTS, sp)]
    assert multi == single


def test_sampled_parity_vs_single_step():
    sp = SamplingParams(max_tokens=9, temperature=0.8, top_p=0.9, seed=7,
                        ignore_eos=True)
    single = [o.token_ids for o in _engine(1).generate(PROMPTS, sp)]
    multi = [o.token_ids for o in _engine(4).generate(PROMPTS, sp)]
    assert multi == single


def test_max_tokens_not_multiple_of_k():
    """Stop conditions land mid-dispatch; overshoot must be discarded."""
    sp = SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)
    outs = _engine(4).generate(PROMPTS, sp)
    assert all(len(o.token_ids) == 5 for o in outs)


def test_eos_mid_dispatch():
    """A sequence hitting EOS inside a multi-step window stops there."""
    sp1 = SamplingParams(max_tokens=12, temperature=0.0)
    single = [o.token_ids for o in _engine(1).generate(PROMPTS, sp1)]
    multi = [o.token_ids for o in _engine(4).generate(PROMPTS, sp1)]
    assert multi == single


def test_penalties_on_device_parity():
    """Penalty token counts ride on device through the multi-step scan;
    outputs must match the single-step host-penalty engine exactly."""
    sp = SamplingParams(max_tokens=6, temperature=0.7, seed=3,
                        repetition_penalty=1.3, ignore_eos=True)
    single = [o.token_ids for o in _engine(1).generate(PROMPTS, sp)]
    multi = [o.token_ids for o in _engine(8).generate(PROMPTS, sp)]
    assert multi == single


def test_mixed_sampling_batch():
    """Greedy + sampled sequences share one multi-step dispatch."""
    eng = _engine(4)
    sps = [
        SamplingParams(max_tokens=7, temperature=0.0, ignore_eos=True),
        SamplingParams(max_tokens=7, temperature=1.0, seed=11,
                       ignore_eos=True),
        SamplingParams(max_tokens=3, temperature=0.0, ignore_eos=True),
    ]
    outs = [
        eng.generate([p], sp)[0].token_ids
        for p, sp in zip(PROMPTS, sps)
    ]
    want = [
        _engine(1).generate([p], sp)[0].token_ids
        for p, sp in zip(PROMPTS, sps)
    ]
    assert outs == want


def test_rejects_k_above_block_size():
    """Validated at BOOT: a mid-serving failure would kill the step-loop
    thread and hang all in-flight requests."""
    with pytest.raises(ValueError, match="block_size"):
        _engine(16)  # block_size 8


def test_tp_multistep_parity():
    """Multi-step under tensor parallelism matches tp=1. Under a mesh
    nothing is staged, so no round starts at a fetch either: what
    refuses a stage refuses the early start, with no list of its own
    (tests/test_early_dispatch.py)."""
    sp = SamplingParams(max_tokens=14, temperature=0.0, ignore_eos=True)
    one, mesh = _engine(4), _engine(4, tensor_parallel_size=2)
    base = [o.token_ids for o in one.generate(PROMPTS[:2], sp)]
    tp = [o.token_ids for o in mesh.generate(PROMPTS[:2], sp)]
    assert tp == base
    assert one._early_dispatch_total >= 2
    assert (mesh._staged_hits_total, mesh._early_dispatch_total) == (0, 0)


def test_streaming_deltas_cover_all_tokens():
    """Multi-step appends K tokens before one output is built; the
    drained delta must carry ALL of them (review finding: last-token-only
    deltas streamed 1/K of the text)."""
    eng = _engine(4)
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    rid = "stream-1"
    eng.add_request(rid, prompt_token_ids=PROMPTS[0], sampling_params=sp)
    deltas, ids = [], []
    while True:
        outs = eng.step()
        for o in outs:
            deltas.append(o.delta_text)
            ids.extend(o.new_token_ids)
        if outs and outs[-1].finished:
            final = outs[-1]
            break
    assert ids == final.token_ids
    assert "".join(deltas) == final.text


_PREFETCH_SAMPLING = {
    "greedy": [dict(temperature=0.0)] * 3,
    # the staged rounds must derive the same (seed, generated_len + i)
    # keys as the unstaged ones
    "sampled": [dict(temperature=0.9, top_p=0.9, seed=7)] * 3,
    "mixed": [
        dict(temperature=0.0),
        dict(temperature=0.8, seed=3),
        dict(temperature=0.8, top_p=0.9, min_p=0.05, seed=9),
    ],
}


def _prefetch_engine(prefetch, **overrides):
    kw = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=8, num_kv_blocks=128,
        max_num_seqs=4, max_prefill_chunk=32,
        num_scheduler_steps=4, prefetch_decode=prefetch, seed=0,
    )
    kw.update(overrides)
    return LLMEngine(EngineConfig(**kw))


@pytest.mark.parametrize("sampling", list(_PREFETCH_SAMPLING))
def test_prefetch_decode_parity_and_hits(sampling):
    """Speculative h2d prefetch (stage_decode_multi): streams must be
    bit-identical with prefetch on vs off, and in a steady fused run
    the staged buffer must actually get consumed: several rounds are
    dispatched from a stage."""
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 384, size=n).tolist() for n in (9, 17, 30)]
    sps = [SamplingParams(max_tokens=24, ignore_eos=True, **kw)
           for kw in _PREFETCH_SAMPLING[sampling]]
    e_on = _prefetch_engine(True)
    out_on = [o.token_ids for o in e_on.generate(prompts, sps)]
    e_off = _prefetch_engine(False)
    out_off = [o.token_ids for o in e_off.generate(prompts, sps)]
    assert out_on == out_off
    assert all(len(t) == 24 for t in out_on)
    assert e_on._staged_hits_total >= 3
    assert e_off._staged_hits_total == 0


@pytest.mark.parametrize("device_stop", [True, False])
def test_prefetch_survives_mid_stream_admission(device_stop):
    """A new arrival between rounds invalidates the staged prediction
    (lane set changes): the stage is dropped or refused as a counted
    miss, never dispatched, and the engine stays bit-identical to the
    unprefetched engine."""
    sp = SamplingParams(max_tokens=20, temperature=0.0, ignore_eos=True)

    def run(e):
        outs = {}
        e.add_request("a", prompt_token_ids=list(range(1, 12)),
                      sampling_params=sp)
        steps = 0
        while e.has_unfinished() or steps == 0:
            for o in e.step():
                if o.finished:
                    outs[o.request_id] = o.token_ids
            steps += 1
            if steps == 3:  # mid-decode admission breaks the lane set
                # the stage of the next round is outstanding: uploaded,
                # or dispatched already at this step's fetch
                assert (e._staged_decode is not None
                        or e._inflight is not None) == e._prefetch_decode
                hits = e._staged_hits_total
                e.add_request("b", prompt_token_ids=list(range(30, 45)),
                              sampling_params=sp)
            if steps == 4:
                # the round behind the admission did not take the stage
                assert e._staged_hits_total == hits
        return outs

    a = run(_prefetch_engine(True, device_stop=device_stop))
    b = run(_prefetch_engine(False, device_stop=device_stop))
    assert a == b and set(a) == {"a", "b"}


def test_stage_invalidated_by_block_free_epoch():
    """Any block free() between stage and consume must invalidate the
    staged buffer (code-review r5: freed block ids can be re-handed to
    another sequence, so a same-length table could silently reference
    someone else's KV). The epoch rides the fingerprint."""
    eng = _prefetch_engine(True, max_num_seqs=2)
    sp = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
    eng.add_request("a", prompt_token_ids=list(range(1, 12)),
                    sampling_params=sp)
    stage = eng.runner.stage_decode_multi

    def stage_then_free(*a, **kw):
        # simulate a table free (abort/preempt of some other sequence)
        # after the stage took its fingerprint
        eng.block_manager.free_epoch += 1
        return stage(*a, **kw)

    eng.runner.stage_decode_multi = stage_then_free
    outs = []
    while eng.has_unfinished():
        for o in eng.step():
            if o.finished:
                outs.append(o.token_ids)
    assert eng._staged_misses_total > 0
    assert eng._staged_hits_total == 0  # every stage was invalidated
    assert len(outs) == 1 and len(outs[0]) == 24
