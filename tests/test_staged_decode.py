"""The staged round of `prefetch_decode` (the default, and what every
benchmark cell runs): while fused round N executes, round N+1's packed
inputs are uploaded, and when the prediction holds round N+1 is
dispatched chained on round N's on-device tokens. It only changes WHEN
the host uploads, never what the device computes, so every stream must
be bit-identical to `prefetch_decode=False` — with a stage outstanding
when a stop fires, a request is aborted, or a lane nears its bounds.

Greedy / sampled parity with counted hits and the mid-generation
arrival live in tests/test_multistep.py."""

import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams


def make_engine(prefetch: bool, **overrides) -> LLMEngine:
    kwargs = dict(
        model="pst-tiny-debug",
        tokenizer="byte",
        dtype="float32",
        cache_dtype="float32",
        block_size=8,
        num_kv_blocks=128,
        max_num_seqs=4,
        max_prefill_chunk=16,
        num_scheduler_steps=4,
        prefetch_decode=prefetch,
        seed=0,
    )
    kwargs.update(overrides)
    return LLMEngine(EngineConfig(**kwargs))


def _prompts():
    rng = np.random.RandomState(11)
    return [rng.randint(0, 384, size=n).tolist() for n in (5, 19, 11)]


def run(engine, prompts, sp):
    return [o.token_ids for o in engine.generate(prompts, sp)]


def _count_dispatches(eng):
    """Count decode_multi dispatches (device rounds)."""
    box = {"n": 0}
    orig = eng.runner.decode_multi

    def counting(*a, **kw):
        box["n"] += 1
        return orig(*a, **kw)

    eng.runner.decode_multi = counting
    return box


def _step_until_staged(eng) -> None:
    """Step until a stage is outstanding: uploaded and waiting for the
    next step, or (where its prediction held at the fetch's return)
    dispatched already and in flight (tests/test_early_dispatch.py)."""
    for _ in range(20):
        eng.step()
        if eng._staged_decode is not None or eng._inflight is not None:
            return
    raise AssertionError("no round was staged in 20 steps")


@pytest.mark.parametrize("max_tokens", [16, 40])
def test_staged_with_eos_active(max_tokens):
    """Normal chat traffic (EOS active, no ignore_eos): the stage
    carries the stop masks advanced by K, and a lane that stops breaks
    the fingerprint instead of being dispatched on a pad token."""
    sp = SamplingParams(max_tokens=max_tokens, temperature=0.0)
    eng = make_engine(True)
    out_on = run(eng, _prompts(), sp)
    assert out_on == run(make_engine(False), _prompts(), sp)
    if max_tokens == 40:
        assert eng._staged_hits_total >= 3


def test_staged_with_stop_token_ids():
    """stop_token_ids do not refuse the stage: with a stop token the
    greedy run never emits, generations run to max_tokens on staged
    rounds."""
    base = run(make_engine(False), _prompts(),
               SamplingParams(max_tokens=32, temperature=0.0,
                              ignore_eos=True))
    never = next(t for t in range(384)
                 if all(t not in ids for ids in base))
    sp = SamplingParams(max_tokens=32, temperature=0.0,
                        ignore_eos=True, stop_token_ids=[never])
    eng = make_engine(True)
    out_on = run(eng, _prompts(), sp)
    assert out_on == run(make_engine(False), _prompts(), sp)
    assert all(len(t) == 32 for t in out_on)
    assert eng._staged_hits_total >= 3


def _stop_inside_a_round():
    """(stream, index, token, params): a stop token whose FIRST
    occurrence in a stream is well into it, so that several staged
    rounds are dispatched before it fires."""
    probe = run(make_engine(False), _prompts(),
                SamplingParams(max_tokens=32, temperature=0.0,
                               ignore_eos=True))
    s, i = next((s, i) for i in range(24, 13, -1)
                for s, ids in enumerate(probe)
                if ids.index(ids[i]) == i)
    tok = probe[s][i]
    # max_tokens far enough off that the round in which the stop fires
    # still stages its successor
    return s, i, tok, SamplingParams(
        max_tokens=48, temperature=0.0, ignore_eos=True,
        stop_token_ids=[tok])


@pytest.mark.parametrize("device_stop", [True, False])
def test_stop_fires_inside_a_staged_round(device_stop):
    """A stop token that FIRES inside a round that was dispatched from
    a stage, with the next stage already uploaded: the stream ends at
    exactly the unstaged engine's token (on the device under device
    stops, discarded on the host without them), the outstanding stage
    is refused and counted, and the other lanes go on unharmed."""
    s, i, stop_tok, sp = _stop_inside_a_round()
    eng = make_engine(True, device_stop=device_stop)
    out_on = run(eng, _prompts(), sp)
    out_off = run(make_engine(False, device_stop=device_stop),
                  _prompts(), sp)
    assert out_on == out_off
    assert out_on[s][-1] == stop_tok and len(out_on[s]) == i + 1
    assert eng._staged_hits_total >= 1
    assert eng._staged_misses_total >= 1


def test_staging_adds_no_device_round_and_no_overshoot():
    """A stage is an upload, not a dispatch: without device stops the
    staged engine runs exactly the unstaged engine's device rounds and
    discards exactly its overshoot tokens."""
    *_, sp = _stop_inside_a_round()
    eng_off = make_engine(False, device_stop=False)
    n_off = _count_dispatches(eng_off)
    out_off = run(eng_off, _prompts(), sp)
    eng_on = make_engine(True, device_stop=False)
    n_on = _count_dispatches(eng_on)
    out_on = run(eng_on, _prompts(), sp)
    assert out_on == out_off
    assert n_on["n"] == n_off["n"]
    assert (eng_on._decode_overshoot_tokens_total
            == eng_off._decode_overshoot_tokens_total)
    assert eng_off._decode_overshoot_tokens_total > 0
    assert eng_on._staged_hits_total >= 1


def test_penalties_refuse_the_stage():
    """The chained program carries no penalty state: rounds with
    penalties are never staged."""
    sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True,
                        repetition_penalty=1.3)
    eng = make_engine(True)
    staged = []
    orig = eng.runner.stage_decode_multi
    eng.runner.stage_decode_multi = (
        lambda *a, **kw: staged.append(1) or orig(*a, **kw))
    assert run(eng, _prompts(), sp) == run(
        make_engine(False), _prompts(), sp)
    assert staged == [] and eng._staged_hits_total == 0


def test_abort_with_a_stage_outstanding_no_spurious_output():
    """Aborting one request while the next round of its batch is staged
    must not emit a finished output for it, inflate
    requests_finished_total, or dispatch the stage built for its lane."""
    eng = make_engine(True)
    sp = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    prompts = _prompts()
    eng.add_request("keep", prompt_token_ids=prompts[0],
                    sampling_params=sp)
    eng.add_request("gone", prompt_token_ids=prompts[1],
                    sampling_params=sp)
    _step_until_staged(eng)
    hits = eng._staged_hits_total
    assert eng.abort_request("gone")
    outs = list(eng.step())
    assert eng._staged_hits_total == hits  # the stale stage was refused
    while eng.has_unfinished():
        outs.extend(eng.step())
    finished = [o for o in outs if o.finished]
    assert [o.request_id for o in finished] == ["keep"]
    assert eng.stats().requests_finished_total == 1
    alone = make_engine(False)
    assert finished[0].token_ids == run(alone, [prompts[0]], sp)[0]


def test_abort_all_with_a_stage_outstanding_drains():
    """When EVERY request is aborted with a stage outstanding, nothing
    is left to step, nothing is emitted, and the stale stage is never
    dispatched for whoever comes next."""
    eng = make_engine(True)
    sp = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    prompts = _prompts()
    eng.add_request("only", prompt_token_ids=prompts[0],
                    sampling_params=sp)
    _step_until_staged(eng)
    eng.abort_request("only")
    # a round that holds the aborted lane may be on the device: it is
    # fetched (and its tokens dropped) before anything else happens
    assert eng.has_unfinished() == (eng._inflight is not None)
    assert [o.request_id for o in eng.step()] == []
    assert not eng.has_unfinished()
    hits = eng._staged_hits_total
    eng.add_request("next", prompt_token_ids=prompts[2],
                    sampling_params=sp)
    outs = list(eng.step())  # the admission drops the stale stage
    assert eng._staged_decode is None and eng._staged_hits_total == hits
    while eng.has_unfinished():
        outs.extend(eng.step())
    (final,) = [o for o in outs if o.finished]
    assert final.token_ids == run(make_engine(False), [prompts[2]], sp)[0]


def test_staging_respects_max_model_len():
    """A lane within 2K tokens of the context limit is not staged past
    it, and the stream ends where the unstaged one does."""
    sp = SamplingParams(max_tokens=200, temperature=0.0, ignore_eos=True)
    prompts = [_prompts()[0]]
    eng = make_engine(True, max_model_len=48)
    out_on = run(eng, prompts, sp)
    assert out_on == run(make_engine(False, max_model_len=48), prompts, sp)
    assert len(out_on[0]) == 48 - len(prompts[0])
    assert eng._staged_hits_total >= 1
    # and no round starts at a fetch past it either: the early starts
    # are the stages, and none is left in flight at the limit
    assert 1 <= eng._early_dispatch_total <= eng._staged_hits_total
    assert eng._inflight is None
