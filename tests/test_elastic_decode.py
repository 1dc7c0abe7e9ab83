"""Fused decode with device-side stop masks: tokens must stay
BIT-IDENTICAL to the serial single-step path while lanes finish MID-ROUND on device — EOS, stop_token_ids, and
max_tokens freeze the lane inside the fused scan (pinned pad slot,
KV writes to the trash slot, penalty/DFA state frozen) and the host
applies exactly the per-lane valid counts instead of discarding
overshoot after the fetch.

Role: a fixed-trip K round samples slots past a lane's stop; device
stops remove the waste (a round whose lanes all ended exits early),
and this suite pins the parity bar every prior perf PR met."""

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


# -- (a) EOS mid-round -------------------------------------------------------
def test_eos_mid_round_parity_and_zero_overshoot():
    """Lanes hitting EOS inside the fused window freeze ON DEVICE: the
    stream is bit-identical to the serial path and the host discards
    nothing (the fixed-trip control discards the overshoot instead)."""
    sp = SamplingParams(max_tokens=12, temperature=0.0)
    single = [o.token_ids for o in _engine(1).generate(PROMPTS, sp)]
    dev = _engine(4)
    multi = [o.token_ids for o in dev.generate(PROMPTS, sp)]
    assert multi == single
    assert dev._decode_overshoot_tokens_total == 0
    # at least one round ended with every lane frozen before the trip
    # count -> the device loop exited early instead of paying the tail
    assert dev._decode_early_exit_rounds_total > 0

    ctl = _engine(4, device_stop=False)
    control = [o.token_ids for o in ctl.generate(PROMPTS, sp)]
    assert control == single
    # the control DID sample past the stops and threw the slots away
    assert ctl._decode_overshoot_tokens_total > 0
    assert ctl._decode_early_exit_rounds_total == 0


# -- (b) stop_token_ids mid-round --------------------------------------------
def test_stop_token_ids_mid_round_parity():
    """A per-request stop id landing mid-window freezes the lane at the
    stop token (which IS appended, matching check_stop)."""
    learn = SamplingParams(max_tokens=12, temperature=0.0,
                           ignore_eos=True)
    stream = _engine(1).generate(PROMPTS, learn)[0].token_ids
    sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True,
                        stop_token_ids=[stream[5]])
    single = [o.token_ids for o in _engine(1).generate(PROMPTS, sp)]
    dev = _engine(4)
    multi = [o.token_ids for o in dev.generate(PROMPTS, sp)]
    assert multi == single
    # stopped ON the stop token (appended, then frozen), mid-stream
    assert single[0][-1] == stream[5] and len(single[0]) < 12
    assert dev._decode_overshoot_tokens_total == 0


def test_min_tokens_gates_device_stops():
    """min_tokens defers EOS/stop-id stops on device exactly like
    check_stop's host gate."""
    sp = SamplingParams(max_tokens=12, temperature=0.0, min_tokens=6)
    single = [o.token_ids for o in _engine(1).generate(PROMPTS, sp)]
    multi = [o.token_ids for o in _engine(4).generate(PROMPTS, sp)]
    assert multi == single


# -- (c) max_tokens expiring mid-round ---------------------------------------
def test_max_tokens_mid_round_parity():
    """The remaining-budget countdown freezes a lane whose max_tokens
    expires inside the window; lane budgets differ so freezes happen on
    different iterations of the same dispatch."""
    sps = [
        SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True),
        SamplingParams(max_tokens=11, temperature=0.0, ignore_eos=True),
        SamplingParams(max_tokens=7, temperature=0.8, seed=3,
                       ignore_eos=True),
    ]
    single = [o.token_ids for o in _engine(1).generate(PROMPTS, sps)]
    dev = _engine(4)
    multi = [o.token_ids for o in dev.generate(PROMPTS, sps)]
    assert multi == single
    assert [len(t) for t in multi] == [5, 11, 7]
    assert dev._decode_overshoot_tokens_total == 0


# -- (d) penalties + done-mask interplay -------------------------------------
def test_penalties_frozen_lane_stops_updating_counts():
    """A frozen lane must stop updating its on-device penalty counts —
    its pinned pad slots are not generated output. Lanes freeze at
    different iterations while penalized neighbours keep sampling."""
    sps = [
        SamplingParams(max_tokens=3, temperature=0.7, seed=3,
                       repetition_penalty=1.3, ignore_eos=True),
        SamplingParams(max_tokens=9, temperature=0.7, seed=3,
                       presence_penalty=0.5, frequency_penalty=0.2,
                       ignore_eos=True),
        SamplingParams(max_tokens=7, temperature=0.0,
                       repetition_penalty=1.2, ignore_eos=True),
    ]
    single = [o.token_ids for o in _engine(1).generate(PROMPTS, sps)]
    multi = [o.token_ids for o in _engine(8).generate(PROMPTS, sps)]
    assert multi == single


def test_logprobs_ride_device_stop_fetch():
    """Logprob arrays share the single fetch with the valid counts;
    entries past a lane's freeze point must never be emitted."""
    sp = SamplingParams(max_tokens=7, temperature=0.0, logprobs=3)
    single = _engine(1).generate(PROMPTS, sp)
    multi = _engine(4).generate(PROMPTS, sp)
    for s, m in zip(single, multi):
        assert m.token_ids == s.token_ids
        assert len(m.logprobs) == len(s.logprobs)
        for a, b in zip(s.logprobs, m.logprobs):
            assert a["token_id"] == b["token_id"]
            assert abs(a["logprob"] - b["logprob"]) < 1e-4


# -- (e) guided-decoding lanes -----------------------------------------------
def test_guided_lanes_with_device_stops():
    """Guided lanes ride the fused scan with stop masks: a frozen
    lane's DFA state stops stepping, and host-side guided completion
    (choice exhausted) still resolves as before."""
    sps = [
        SamplingParams(max_tokens=10, temperature=0.0,
                       guided_choice=["hello", "goodbye"]),
        SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True),
        SamplingParams(max_tokens=10, temperature=0.0),
    ]
    single = [o.token_ids for o in _engine(1).generate(PROMPTS, sps)]
    multi = [o.token_ids for o in _engine(4).generate(PROMPTS, sps)]
    assert multi == single


# -- (f) staging and accounting under device stops --------------------------
def test_prefetch_staging_hits_with_device_stops():
    """The h2d-prefetch stage carries the advanced stop countdowns; in
    a steady fused run the staged buffer must actually be consumed
    (hits > 0) and streams stay bit-identical to the unprefetched
    engine."""
    def eng(prefetch):
        return _engine(
            4, num_kv_blocks=128, max_num_seqs=3,
            prefetch_decode=prefetch,
        )

    sp = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
    e_on = eng(True)
    out_on = [o.token_ids for o in e_on.generate(PROMPTS, sp)]
    e_off = eng(False)
    out_off = [o.token_ids for o in e_off.generate(PROMPTS, sp)]
    assert out_on == out_off
    assert e_on._staged_hits_total > 0


def test_decode_round_counters_at_the_one_round_size():
    """Every round is `num_scheduler_steps` long: 8 decode tokens after
    the prefill's first are two rounds of 4, nothing discarded."""
    eng = _engine(4)
    sp = SamplingParams(max_tokens=9, temperature=0.0, ignore_eos=True)
    eng.generate(PROMPTS[:1], sp)
    s = eng.stats()
    assert s.decode_rounds_total == 2
    assert s.decode_overshoot_tokens_total == 0


def test_stop_strings_still_resolve_on_host():
    """Stop STRINGS cannot run on device (text matching): the lane
    overshoots on device and the host discards — outputs identical to
    the serial path, overshoot counted."""
    learn = SamplingParams(max_tokens=12, temperature=0.0,
                           ignore_eos=True)
    text = _engine(1).generate(PROMPTS, learn)[0].text
    needle = text[2:4]
    sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True,
                        stop=[needle])
    single = _engine(1).generate(PROMPTS, sp)
    dev = _engine(4)
    multi = dev.generate(PROMPTS, sp)
    assert [o.text for o in multi] == [o.text for o in single]
    assert [o.token_ids for o in multi] == [
        o.token_ids for o in single
    ]


@pytest.mark.parametrize("k", [4, 8])
def test_stochastic_parity_with_device_stops(k):
    """Sampled streams (per-iteration keys (seed, generated_len + i))
    stay bit-identical under freezing lanes at any K."""
    sp = SamplingParams(max_tokens=9, temperature=0.8, top_p=0.9,
                        seed=7)
    single = [o.token_ids for o in _engine(1).generate(PROMPTS, sp)]
    multi = [o.token_ids for o in _engine(k).generate(PROMPTS, sp)]
    assert multi == single


def test_valid_counts_are_exact():
    """The dispatch's per-lane valid counts equal the tokens the host
    actually applies — no row past a freeze is ever consumed (probe the
    runner directly)."""
    eng = _engine(4)
    sp = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    outs = eng.generate(PROMPTS, sp)
    assert all(len(o.token_ids) == 6 for o in outs)
    # 5 decode tokens after prefill: a 4-round then a (budget-frozen)
    # round where every lane's valid count is 1 or 2 depending on the
    # adaptive bucket; either way generated == applied exactly
    assert eng._decode_overshoot_tokens_total == 0
