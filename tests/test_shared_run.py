"""Decode lanes that share a prefix: the model runner's half.

A round's pack finds, per row block of RAGGED_TQ decode lanes, the
leading keys that every lane with a sequence reads from the same pages
(`ModelRunner._shared_runs`) and ships them beside the tables; the ragged
kernel walks them once for the block (tests/test_pallas_attention.py has
the kernel's half). Here: what the pack finds in a table, that a staged
round carries its own, that tokens do not depend on who shares a block,
and what the counters count.
"""

from __future__ import annotations

import numpy as np
import pytest
from prometheus_client import CollectorRegistry, generate_latest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.metrics import EngineMetrics
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.ops import pallas_attention as pa

BS = 8
KV_BLOCK = 2 * BS       # keys, forced: the tiny widths would give 512


@pytest.fixture(autouse=True)
def small_kv_block(monkeypatch):
    """A KV block of two pages, so that a prefix of a few pages is a
    run; the kernels are jitted on shapes, not on the block's size."""
    monkeypatch.setattr(pa, "_kv_block_pages", lambda *_: 2)
    for f in (pa.ragged_paged_attention, pa.paged_prefill_attention):
        f.clear_cache()
    yield
    for f in (pa.ragged_paged_attention, pa.paged_prefill_attention):
        f.clear_cache()


def engine(**kw):
    cfg = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=BS, num_kv_blocks=192,
        max_num_seqs=4, max_prefill_chunk=64, seed=0,
        num_scheduler_steps=4, attention_impl="pallas",
    )
    cfg.update(kw)
    return LLMEngine(EngineConfig(**cfg))


@pytest.fixture(scope="module")
def runner():
    # made before the first test's own patch: it sizes its counters'
    # KV block when it is made
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "_kv_block_pages", lambda *_: 2)
        return engine(max_num_seqs=12).runner


def table(*rows, pages=8):
    out = np.zeros((len(rows), pages), np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out


PREFIX = [7, 3, 9, 4, 11]

_RUN_CASES = {
    # name: (table rows, contexts, expected [keys, lane] a row block)
    "common-run": (
        [PREFIX + [20], PREFIX + [21, 22], PREFIX + [23]],
        [44, 50, 41], [[40, 0]]),
    "diverges-at-page-3": (
        [PREFIX + [20], [7, 3, 9, 30, 31, 32], PREFIX + [23]],
        [44, 44, 44], [[24, 0]]),
    "a-lane-shorter-than-the-run": (
        [PREFIX + [20], PREFIX[:3], PREFIX + [23]],
        [44, 19, 44], [[18, 0]]),
    "one-live-lane": ([PREFIX + [20], [], []], [44, 0, 0], [[0, 0]]),
    "no-live-lane": ([[], []], [0, 0], [[0, 0]]),
    "nothing-in-common": (
        [[1, 2, 3], [4, 5, 6]], [20, 20], [[0, 0]]),
    # the run is addressed through the first lane that holds a sequence
    "idle-lanes-first": (
        [[], [], PREFIX + [20], [], PREFIX + [21]],
        [0, 0, 44, 0, 47], [[40, 2]]),
    # lanes 0-7 share, lanes 8-10 hold other prompts, lane 11 is idle
    "two-row-blocks": (
        [PREFIX + [20 + i] for i in range(8)]
        + [[50, 51, 52], [50, 51, 53], [50, 51], []],
        [44] * 8 + [24, 24, 15, 0], [[40, 0], [14, 8]]),
    "tables-identical-to-the-last-page": (
        [PREFIX, PREFIX], [40, 40], [[39, 0]]),
}


@pytest.mark.parametrize("name", list(_RUN_CASES))
def test_shared_runs_of_a_packed_table(runner, name):
    rows, ctx, want = _RUN_CASES[name]
    got = runner._shared_runs(table(*rows), np.asarray(ctx, np.int32))
    assert got.dtype == np.int32
    assert got[:, 0].tolist() == [w[0] for w in want]
    for (keys, lane), (_, want_lane) in zip(got.tolist(), want):
        if keys:
            assert lane == want_lane


def test_no_shared_run_under_a_mesh(runner, monkeypatch):
    rows, ctx, _ = _RUN_CASES["common-run"]
    monkeypatch.setattr(runner, "mesh", object())
    assert not runner._shared_runs(
        table(*rows), np.asarray(ctx, np.int32)).any()


def _pack_args(n, ctx):
    return dict(
        temps=np.zeros((n,), np.float32), top_ps=np.ones((n,), np.float32),
        top_ks=np.full((n,), -1, np.int32),
        keys=np.zeros((n, 2), np.uint32), context_lens=ctx,
        positions=[c - 1 for c in ctx])


def test_the_pack_and_a_staged_round_carry_their_own_runs(runner):
    """The round's buffer holds the runs of ITS tables and contexts: a
    staged next round, packed from the contexts it predicts, carries
    its own, in the buffer and beside it for the dispatch's counters."""
    tables = [PREFIX + [20, 21], PREFIX[:4] + [30, 31, 32]]
    layout, _ = runner._decode_pack_layout(12, 64, True)
    at, shape = layout["shared_run"]
    assert shape == (2, 2)
    packed = runner._fill_decode_pack(
        64, True, None, block_tables=tables, **_pack_args(2, [37, 36]))
    assert packed[at:at + 4].tolist() == [32, 0, 0, 8]
    assert runner._packed_runs.tolist() == [[32, 0], [0, 8]]
    # the prediction: both lanes four tokens on, the same tables
    c_pad, dev, runs = runner.stage_decode_multi(
        block_tables=tables, steps=4, **_pack_args(2, [41, 40]))
    assert runs.tolist() == [[32, 0], [0, 8]]
    assert np.asarray(dev)[at:at + 4].tolist() == [32, 0, 0, 8]
    # a lane that is still inside the run holds it back for both
    packed = runner._fill_decode_pack(
        64, True, None, block_tables=tables, **_pack_args(2, [37, 20]))
    assert packed[at:at + 2].tolist() == [19, 0]


def _prompts():
    rng = np.random.RandomState(4)
    prefix = rng.randint(1, 300, size=44).tolist()
    return [prefix + rng.randint(1, 300, size=n).tolist()
            for n in (5, 9, 3)]


@pytest.mark.parametrize("sp", [
    SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True),
    SamplingParams(max_tokens=10, temperature=0.8, seed=11, top_p=0.9,
                   ignore_eos=True),
], ids=["greedy", "sampled"])
def test_tokens_do_not_depend_on_who_shares_the_block(sp):
    """Three requests over one cached prefix decode, together in one row
    block over a shared run, the tokens each decodes alone."""
    prompts = _prompts()
    alone = []
    for p in prompts:
        e = engine()
        alone.append(e.generate([p], sp)[0].token_ids)
        assert e.runner.attn_lane_tokens[1] == 0
    e = engine()
    # the prefix enters the cache with the first request; the others
    # hit it and share its pages
    e.generate([prompts[0]], SamplingParams(max_tokens=1, temperature=0.0))
    outs = e.generate(prompts, sp)
    assert [o.token_ids for o in outs] == alone
    lane, shared = e.runner.attn_lane_tokens
    assert 0 < shared < lane
    assert e.stats().attn_lane_tokens == (lane, shared)


def test_decode_rows_of_a_lane_typed_round_share_too():
    """Two lanes decode over the cached prefix while a third prompt's
    chunks ride beside them in lane-typed rounds: the decode rows of
    those rounds go through the shared run as well, and every request
    gets the tokens it gets alone."""
    sp = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
    prompts = _prompts()
    cold = np.random.RandomState(9).randint(1, 300, size=30).tolist()
    kw = dict(max_prefill_chunk=8, ragged_dispatch=True)
    alone = [engine(**kw).generate([p], sp)[0].token_ids
             for p in (*prompts[:2], cold)]
    e = engine(**kw)
    e.generate([prompts[0]], SamplingParams(max_tokens=1, temperature=0.0))
    arrivals = [(0, 0, prompts[0]), (0, 1, prompts[1]), (4, 2, cold)]
    outs, steps, shared_in_ragged = {}, 0, 0
    while arrivals or e.has_unfinished():
        while arrivals and arrivals[0][0] <= steps:
            _, rid, prompt = arrivals.pop(0)
            e.add_request(str(rid), prompt_token_ids=prompt,
                          sampling_params=sp)
        before = e.runner.attn_lane_tokens[1]
        for o in e.step():
            if o.finished:
                outs[int(o.request_id)] = o.token_ids
        if e.last_step_kind == "ragged":
            shared_in_ragged += e.runner.attn_lane_tokens[1] - before
        steps += 1
        assert steps < 500, "engine wedged"
    assert [outs[i] for i in range(3)] == alone
    assert e._ragged_rounds_total > 0
    assert shared_in_ragged > 0


def test_the_three_counters_with_and_without_a_shared_run(runner):
    """tpu:attn_context_tokens counts what the walk streams (a run once
    a row block and step), tpu:attn_lane_context_tokens every lane's
    context, tpu:attn_shared_context_tokens the lane-tokens a shared
    pass served; the run cut down to the KV block as the kernel cuts
    it."""
    def counted(fn):
        before = (runner.attn_context_tokens[0], *runner.attn_lane_tokens)
        fn()
        after = (runner.attn_context_tokens[0], *runner.attn_lane_tokens)
        return tuple(a - b for a, b in zip(after, before))

    ctx, k = [44, 50, 41], 4
    lanes = sum(c + i for c in ctx for i in range(k))
    assert counted(lambda: runner._note_attn_context(ctx, k)) == (
        lanes, lanes, 0)
    runs = np.asarray([[40, 0], [0, 8]], np.int32)
    cut = 40 // KV_BLOCK * KV_BLOCK
    assert counted(
        lambda: runner._note_attn_context(ctx, k, [30], runs=runs)
    ) == (lanes + 30 - k * 2 * cut, lanes + 30, k * 3 * cut)
    # nine lanes: eight in the first row block, one alone in the second
    runs = np.asarray([[40, 0], [40, 8]], np.int32)
    ctx = [44] * 9
    lanes = sum(c + i for c in ctx for i in range(k))
    assert counted(
        lambda: runner._note_attn_context(ctx, k, runs=runs)
    ) == (lanes - k * 7 * cut, lanes, k * 8 * cut)

    reg = CollectorRegistry()
    metrics = EngineMetrics("m", registry=reg)
    snap = engine().stats()
    snap.attn_lane_tokens = (700, 300)
    metrics.update_from_snapshot(snap)
    text = generate_latest(reg).decode()
    for name, value in (("attn_lane_context_tokens", 700.0),
                        ("attn_shared_context_tokens", 300.0)):
        assert f'tpu:{name}_total{{model_name="m"}} {value}' in text, name
