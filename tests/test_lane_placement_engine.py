"""Decode sequences seated by the prefix they hold: the engine's half.

`ModelRunner.decode_lanes` (`model_runner.place_lanes`,
tests/test_lane_placement.py) gives each decode sequence of a round a
lane; everything the pack ships a lane goes there and everything that
comes back a lane is read there. A row's result does not depend on its
lane, so every stream must be what it is with sequence i in lane i —
forced here by patching that one function, as nothing in the program
switches it off — under every sampling feature that ships something a
lane, through staged rounds, and beside the prefill rows of a lane-typed
round.
"""

from __future__ import annotations

import numpy as np
import pytest

from production_stack_tpu.engine import model_runner
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.ops import pallas_attention as pa

BS = 8
B = 16      # two row blocks


@pytest.fixture(autouse=True)
def small_kv_block(monkeypatch):
    """A KV block of two pages, so that a prefix of five pages is a run
    (tests/test_shared_run.py)."""
    monkeypatch.setattr(pa, "_kv_block_pages", lambda *_: 2)
    for f in (pa.ragged_paged_attention, pa.paged_prefill_attention):
        f.clear_cache()
    yield
    for f in (pa.ragged_paged_attention, pa.paged_prefill_attention):
        f.clear_cache()


def _engine(**kw) -> LLMEngine:
    cfg = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=BS, num_kv_blocks=256,
        max_num_seqs=B, max_prefill_chunk=64, seed=0,
        num_scheduler_steps=4, attention_impl="pallas",
    )
    cfg.update(kw)
    return LLMEngine(EngineConfig(**cfg))


def _lane_i_for_sequence_i(monkeypatch) -> None:
    monkeypatch.setattr(
        model_runner, "place_lanes",
        lambda pages, b, least: np.arange(len(pages), dtype=np.int32))


def _watch_lanes(e: LLMEngine) -> list[list[int]]:
    """Every map the engine asks for."""
    seen: list[list[int]] = []
    orig = e.runner.decode_lanes

    def watched(tables):
        lanes = orig(tables)
        seen.append(lanes.tolist())
        return lanes

    e.runner.decode_lanes = watched
    return seen


def _two_prefixes(sizes):
    """Prompts over two prefixes of 5.5 pages, taking turns: A B A B .."""
    rng = np.random.RandomState(4)
    prefixes = [rng.randint(1, 300, size=44).tolist() for _ in range(2)]
    return prefixes, [prefixes[i % 2] + rng.randint(1, 300, size=n).tolist()
                      for i, n in enumerate(sizes)]


def _prime(e: LLMEngine, prefixes) -> None:
    """Each prefix enters the prefix cache: later prompts share its
    pages."""
    one = SamplingParams(max_tokens=1, temperature=0.0)
    for p in prefixes:
        e.generate([p + [7]], one)


def _finals(e: LLMEngine, arrivals) -> dict:
    """Drive `e` with (step, id, prompt, sampling) arrivals; the final
    output of each request as (tokens, finish reason, logprobs)."""
    outs, steps = {}, 0
    pending = sorted(arrivals, key=lambda a: a[0])
    while pending or e.has_unfinished():
        while pending and pending[0][0] <= steps:
            _, rid, prompt, sp = pending.pop(0)
            e.add_request(rid, prompt_token_ids=prompt, sampling_params=sp)
        for o in e.step():
            if o.finished:
                outs[o.request_id] = (
                    o.token_ids, o.finish_reason, o.logprobs)
        steps += 1
        assert steps < 800, "engine wedged"
    return outs


def _sampling_mix(stop_token: int) -> list[SamplingParams]:
    """One of everything that ships an array a lane; `max_tokens` that
    end the lanes in different rounds."""
    return [
        SamplingParams(max_tokens=21, temperature=0.0, ignore_eos=True),
        SamplingParams(max_tokens=9, temperature=0.8, top_p=0.9, seed=11,
                       ignore_eos=True),
        SamplingParams(max_tokens=30, temperature=0.0, ignore_eos=True,
                       presence_penalty=0.6, frequency_penalty=0.4,
                       repetition_penalty=1.2),
        SamplingParams(max_tokens=14, temperature=0.0, ignore_eos=True,
                       logprobs=3),
        SamplingParams(max_tokens=26, temperature=0.7, seed=5, top_k=40,
                       min_p=0.02, ignore_eos=True,
                       stop_token_ids=[stop_token, 301, 302]),
        SamplingParams(max_tokens=17, temperature=0.0, ignore_eos=True,
                       logit_bias={5: 4.0, 9: -3.0}),
        SamplingParams(max_tokens=12, temperature=0.0),
    ]


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_every_stream_is_what_it_is_with_sequence_i_in_lane_i(
        impl, monkeypatch):
    """Seven requests over two cached prefixes, arriving A B A B ..: with
    each prefix's lanes in a row block of their own, the tokens, finish
    reasons and log-probabilities of lane i for sequence i."""
    prefixes, prompts = _two_prefixes([5, 9, 3, 12, 7, 4, 10])
    # a stop token that request 4 meets on its way: its tenth, alone
    unstopped = _sampling_mix(300)[4]
    tenth = _engine(attention_impl=impl).generate(
        [prompts[4]], unstopped)[0].token_ids[9]
    sps = _sampling_mix(tenth)
    arrivals = [(0, f"r{i}", p, sp)
                for i, (p, sp) in enumerate(zip(prompts, sps))]

    def run():
        e = _engine(attention_impl=impl)
        _prime(e, prefixes)
        seen = _watch_lanes(e)
        return e, seen, _finals(e, arrivals)

    e, seen, placed = run()
    assert len(placed) == len(sps)
    # A's lanes in row block 0, B's in row block 1, idle lanes between
    assert [0, 8, 1, 9, 2, 10, 3] in seen
    # the mix really ends lanes in different rounds, one by a stop token
    assert len({len(t) for t, _, _ in placed.values()}) >= 4
    assert placed["r4"][1] == "stop" and len(placed["r4"][0]) <= 10
    assert placed["r3"][2] is not None and len(placed["r3"][2]) == 14
    _lane_i_for_sequence_i(monkeypatch)
    e0, seen0, arrival = run()
    assert all(m == list(range(len(m))) for m in seen0)
    assert placed == arrival
    if impl == "pallas":
        # and that is what the seats are for: where the order of arrival
        # shares nothing but by luck, the placed lanes share their prefix
        assert e.runner.attn_lane_tokens[0] == e0.runner.attn_lane_tokens[0]
        assert e.runner.attn_lane_tokens[1] > (
            2 * e0.runner.attn_lane_tokens[1])
        assert e.runner.attn_lane_tokens[1] > (
            0.4 * e.runner.attn_lane_tokens[0])
    else:
        assert e.runner.attn_lane_tokens[1] == 0


def test_a_staged_decode_round_over_placed_lanes_is_a_hit(monkeypatch):
    """The stage of round N+1 is laid out over round N's lanes, where
    the chained tokens sit: with the lanes placed, the staged rounds hit
    as often as with sequence i in lane i, and give the same tokens."""
    sp = SamplingParams(max_tokens=33, temperature=0.8, seed=3,
                        ignore_eos=True)
    prefixes, prompts = _two_prefixes([5, 9, 3, 12])

    def run():
        e = _engine()
        _prime(e, prefixes)
        seen = _watch_lanes(e)
        outs = [o.token_ids for o in e.generate(prompts, sp)]
        return e, seen, outs

    e, seen, placed = run()
    assert [0, 8, 1, 9] in seen
    assert e._staged_hits_total >= 4
    # a stage laid out over other lanes than the round's is no hit:
    # the next round packs and uploads for itself
    e.add_request("late", prompt_token_ids=prompts[0], sampling_params=sp)
    e.callers_waiting = 1  # somebody at the lock: the staged round
    # waits for the next step and does not start at this one's fetch
    early = e._early_dispatch_total
    assert early >= 4
    for _ in range(20):
        e.step()
        if e._staged_decode is not None:
            break
    assert e._staged_decode is not None and e._inflight is None
    assert e._early_dispatch_total == early
    hits, misses = e._staged_hits_total, e._staged_misses_total
    e._staged_decode["lanes"] = e._staged_decode["lanes"] + 1
    e.step()
    assert (e._staged_hits_total, e._staged_misses_total) == (
        hits, misses + 1)
    e.callers_waiting = 0
    while e.has_unfinished():
        e.step()

    _lane_i_for_sequence_i(monkeypatch)
    e0, _, arrival = run()
    assert placed == arrival
    assert e0._staged_hits_total >= 4


def test_a_staged_lane_typed_round_over_placed_lanes_is_a_hit(monkeypatch):
    """Four lanes decode over two prefixes while a cold prompt's chunks
    ride beside them: the staged lane-typed rounds hit, the decode
    sequences sit by their prefix in them, and every request (the cold
    one too) gets the tokens of lane i for sequence i."""
    sp = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
    prefixes, prompts = _two_prefixes([5, 9, 3, 12])
    cold = np.random.RandomState(9).randint(1, 300, size=37).tolist()
    arrivals = [(0, f"r{i}", p, sp) for i, p in enumerate(prompts)]
    arrivals.append((3, "cold", cold, sp))

    def run():
        e = _engine(max_prefill_chunk=8, ragged_dispatch=True)
        _prime(e, prefixes)
        seen = _watch_lanes(e)
        return e, seen, _finals(e, arrivals)

    e, seen, placed = run()
    assert e._ragged_rounds_total > 0
    assert e._ragged_staged_hits_total > 0
    assert [0, 8, 1, 9] in seen
    _lane_i_for_sequence_i(monkeypatch)
    e0, _, arrival = run()
    assert placed == arrival
    assert e0._ragged_staged_hits_total == e._ragged_staged_hits_total


@pytest.mark.parametrize("kernel", [True, False], ids=["rows", "composed"])
def test_the_prefill_rows_of_a_lane_typed_round_are_untouched(kernel):
    """The map moves the decode half of a lane-typed round's buffer and
    the decode lanes of its header; the prefill pack and the prefill
    lanes' header are byte for byte those of lane i for sequence i."""
    r = _engine(
        attention_impl="pallas" if kernel else "xla",
        ragged_dispatch=True, max_prefill_chunk=16).runner
    assert r.ragged_kernel == kernel
    n, c_pad, k = 3, 64, 4
    tables = [[5, 6, 7, 20], [8, 9, 10, 21], [5, 6, 7, 22]]
    dec = dict(
        positions=[30, 27, 29], block_tables=tables,
        context_lens=[31, 28, 30], temps=np.zeros((n,), np.float32),
        top_ps=np.ones((n,), np.float32),
        top_ks=np.full((n,), -1, np.int32),
        keys=np.zeros((n, 2), np.uint32),
        stop=(np.full((n,), -1, np.int32), np.zeros((n,), np.int32),
              np.asarray([9, 5, 7], np.int32), None),
    )
    pf = ([[3, 4, 5, 6, 7], [8, 9, 10]], [0, 8], [[40, 41], [42, 43]],
          [5, 11], None)
    fill = r._fill_ragged_rows_pack if kernel else r._fill_ragged_pack

    def packed(lanes):
        return fill(*pf, c_pad, False, [11, 12, 13], dec["positions"],
                    tables, dec["context_lens"], k, dec["temps"],
                    dec["top_ps"], dec["top_ks"], dec["keys"],
                    stop=dec["stop"], pf_budgets=[0, 4], lanes=lanes)

    lanes = r.decode_lanes(tables)
    assert lanes.tolist() == [0, 8, 1]
    *dims, plain = packed(None)
    *dims_p, placed = packed(lanes)
    assert dims == dims_p
    if kernel:
        s_cap = r._rows_lane_cap()
        meta_n, pf_n, dec_n = r._ragged_rows_pack_sizes(
            *dims, B, c_pad, False, stop_cap=0)
    else:
        s_cap = dims[0]
        meta_n, pf_n, dec_n = r._ragged_pack_sizes(
            *dims, B, c_pad, False, stop_cap=0)
    assert len(plain) == len(placed) == meta_n + pf_n + dec_n
    np.testing.assert_array_equal(
        placed[meta_n:meta_n + pf_n], plain[meta_n:meta_n + pf_n])
    header = placed[:meta_n].reshape(3, s_cap + B)
    header0 = plain[:meta_n].reshape(3, s_cap + B)
    np.testing.assert_array_equal(header[:, :s_cap], header0[:, :s_cap])
    # the decode lanes of the header: type, K and budget at each seat
    want = np.zeros((3, B), np.int32)
    want[:, lanes] = [[model_runner.RAGGED_LANE_DECODE] * n, [k] * n,
                      [9, 5, 7]]
    np.testing.assert_array_equal(header[:, s_cap:], want)
    assert header0[:, s_cap:s_cap + n].tolist() == want[:, lanes].tolist()
    # and the decode pack differs, as it must
    assert (placed[meta_n + pf_n:] != plain[meta_n + pf_n:]).any()
