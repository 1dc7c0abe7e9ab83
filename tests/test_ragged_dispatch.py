"""Unified ragged prefill+decode dispatch: ONE lane-typed engine round
(prefill-chunk lanes + fused decode lanes in a single device program)
must give the split alternating path's (`--no-ragged-dispatch`) tokens,
exactly, and its logical KV (see _assert_kv_close) across the mixed
matrix: cold multi-chunk prefills riding beside decoding lanes, device
stops firing mid-round, min_tokens gates, penalties, guided lanes,
LoRA slots, and staged-prefetch hits.

Role: the decode aggregate sits at ~16% of the HBM roofline (PERF.md)
and the split prefill/decode dispatch paths are the structural cause —
the interleave throttle and the admission-K clamp exist only because a
round could serve one side at a time. The ragged round dissolves both:
this suite pins the token/KV parity bar every prior perf PR met, plus
the NEW scheduling contract (a waiting prefill claims a lane in the
very next round, with no interleave-streak wait and no K clamp for
in-round prefill work).
"""

from __future__ import annotations

import numpy as np
import pytest

from production_stack_tpu.engine.block_manager import BlockManager
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.engine.scheduler import (
    Scheduler,
    SchedulerConfig,
)
from production_stack_tpu.engine.sequence import Sequence


def _engine(ragged, k=4, **kw):
    cfg = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=8, num_kv_blocks=192,
        max_num_seqs=3, max_prefill_chunk=8, seed=0,
        num_scheduler_steps=k, ragged_dispatch=ragged,
    )
    cfg.update(kw)
    return LLMEngine(EngineConfig(**cfg))


SHORT = [1, 2, 3, 4, 5]
MED = [50, 60, 70, 80, 90, 91, 92]
LONG = list(range(1, 30))  # 4 chunks at max_prefill_chunk=8


def _run_staggered(engine, arrivals, sps):
    """Drive the engine with requests arriving at given step indices —
    the shape that actually produces MIXED rounds (a cold prompt's
    chunks riding beside already-decoding lanes). Returns
    {request_id: (token_ids, logprobs)} finals."""
    outs: dict = {}
    pending = sorted(arrivals, key=lambda a: a[0])
    steps = 0
    while pending or engine.has_unfinished():
        while pending and pending[0][0] <= steps:
            _, rid, prompt = pending.pop(0)
            sp = sps[rid] if isinstance(sps, dict) else sps
            engine.add_request(
                rid, prompt_token_ids=prompt, sampling_params=sp
            )
        for o in engine.step():
            if o.finished:
                outs[o.request_id] = (o.token_ids, o.logprobs)
        steps += 1
        assert steps < 3000, "engine wedged"
    return outs


def _cached_kv_by_hash(engine):
    """Logical KV state: cached-block hash -> (k_block, v_block) —
    layout-agnostic (the two modes legitimately allocate different
    physical block ids under different round orders)."""
    k = np.asarray(engine.runner.k_cache)
    v = np.asarray(engine.runner.v_cache)
    bs = engine.block_manager.block_size
    return {
        h: (k[:, :, bid * bs : (bid + 1) * bs],
            v[:, :, bid * bs : (bid + 1) * bs])
        for h, bid in engine.block_manager.cached_blocks.items()
    }


def _assert_kv_close(c_a, c_b):
    """Logical KV of two engines that ran DIFFERENT programs over the
    same tokens: same cached hashes, K and V equal to float32 rounding.
    Not bit equality: the two engines batch a token's row with
    different neighbours (a mixed round's [prefill | decode] rows, a
    row-count bucket against a (group, chunk) grid), and XLA's CPU
    matmuls block a different row count differently — layer 0's K/V
    (no matmul over mixed rows behind them yet) are bit-equal, deeper
    layers differ by ~1e-6 (PERF.md, Findings PR 25). Kernel against
    kernel stays bit-exact in tests/test_pallas_attention.py."""
    assert set(c_a) == set(c_b) and c_a, "cached hash sets differ"
    for h in c_a:
        for x, y in zip(c_a[h], c_b[h]):
            np.testing.assert_array_equal(x[0], y[0])  # layer 0
            np.testing.assert_allclose(x, y, rtol=2e-5, atol=2e-5)


def _assert_parity(arrivals, sps, k=4, engine_kw=None, check_kv=True):
    """Run the staggered workload under ragged and split engines;
    assert token streams identical and logical KV equal to rounding.
    Returns the ragged engine for counter assertions."""
    kw = engine_kw or {}
    e_r = _engine(True, k=k, **kw)
    out_r = _run_staggered(e_r, arrivals, sps)
    e_s = _engine(False, k=k, **kw)
    out_s = _run_staggered(e_s, arrivals, sps)
    assert {r: t for r, (t, _) in out_r.items()} == {
        r: t for r, (t, _) in out_s.items()
    }
    if check_kv:
        _assert_kv_close(_cached_kv_by_hash(e_r), _cached_kv_by_hash(e_s))
    return e_r, out_r, out_s


# -- (a) the headline mixed round: cold multi-chunk prefill + decode ---------
def test_cold_multichunk_prefill_beside_decode_parity():
    """A 4-chunk cold prompt arrives while another lane decodes: its
    chunks ride as prefill lanes of the SAME rounds the decode lane
    keeps stepping in — the alternating split path's tokens and
    logical KV."""
    sp = SamplingParams(max_tokens=16, temperature=0.0, ignore_eos=True)
    e_r, _, _ = _assert_parity(
        [(0, "a", SHORT), (2, "b", LONG)], sp,
    )
    assert e_r._ragged_rounds_total > 0
    # the lane-mix histogram saw at least one mixed round
    assert any(
        key.startswith("p") for key in e_r._ragged_lane_mix_hist
    )


def test_burst_admission_packs_prefill_lanes():
    """Two cold prompts + one decoding lane: both prompts' chunks pack
    into the prefill side of one lane-typed round."""
    sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    e_r, _, _ = _assert_parity(
        [(0, "a", SHORT), (2, "b", LONG), (2, "c", MED)], sp,
    )
    assert e_r._ragged_rounds_total > 0


# -- (b) device stops firing mid-round ---------------------------------------
def test_eos_mid_round_in_ragged_rounds():
    """EOS freezes a decode lane inside a MIXED round's fused scan:
    streams identical to the split path, zero host-discarded
    overshoot."""
    sp = SamplingParams(max_tokens=12, temperature=0.0)
    e_r, _, _ = _assert_parity(
        [(0, "a", SHORT), (1, "b", LONG), (1, "c", MED)], sp,
        check_kv=False,  # finished seqs free their tables; compare tokens
    )
    assert e_r._decode_overshoot_tokens_total == 0


def test_stop_token_ids_and_min_tokens_mid_round():
    """Per-request stop ids + min_tokens gates ride the ragged round's
    decode half unchanged from the elastic path."""
    learn = SamplingParams(max_tokens=12, temperature=0.0,
                           ignore_eos=True)
    stream = _engine(False, k=1).generate([SHORT], learn)[0].token_ids
    sps = {
        "a": SamplingParams(max_tokens=12, temperature=0.0,
                            ignore_eos=True,
                            stop_token_ids=[stream[5]]),
        "b": SamplingParams(max_tokens=12, temperature=0.0,
                            min_tokens=6),
        "c": SamplingParams(max_tokens=9, temperature=0.8, seed=7,
                            top_p=0.9, ignore_eos=True),
    }
    _assert_parity(
        [(0, "a", SHORT), (2, "b", LONG), (2, "c", MED)], sps,
        check_kv=False,
    )


def test_max_tokens_budgets_expire_mid_round():
    """Different per-lane budgets freeze decode lanes on different
    iterations of the same mixed round."""
    sps = {
        "a": SamplingParams(max_tokens=5, temperature=0.0,
                            ignore_eos=True),
        "b": SamplingParams(max_tokens=11, temperature=0.0,
                            ignore_eos=True),
        "c": SamplingParams(max_tokens=7, temperature=0.8, seed=3,
                            ignore_eos=True),
    }
    _, out_r, _ = _assert_parity(
        [(0, "a", SHORT), (1, "b", LONG), (2, "c", MED)], sps,
        check_kv=False,
    )
    assert [len(out_r[r][0]) for r in ("a", "b", "c")] == [5, 11, 7]


# -- (c) penalties / logprobs / guided / LoRA lanes --------------------------
def test_penalties_ride_ragged_rounds():
    """Penalty token counts stay on device through the mixed round's
    scan; frozen lanes stop updating them."""
    sps = {
        "a": SamplingParams(max_tokens=9, temperature=0.7, seed=3,
                            repetition_penalty=1.3, ignore_eos=True),
        "b": SamplingParams(max_tokens=9, temperature=0.7, seed=3,
                            presence_penalty=0.5, frequency_penalty=0.2,
                            ignore_eos=True),
        "c": SamplingParams(max_tokens=7, temperature=0.0,
                            ignore_eos=True),
    }
    _assert_parity(
        [(0, "a", SHORT), (2, "b", LONG), (2, "c", MED)], sps,
        check_kv=False,
    )


def test_logprobs_ride_ragged_rounds():
    """Logprob arrays share the mixed round's fetch; entries match the
    split path lane for lane."""
    sp = SamplingParams(max_tokens=7, temperature=0.0, logprobs=3)
    _, out_r, out_s = _assert_parity(
        [(0, "a", SHORT), (2, "b", LONG)], sp, check_kv=False,
    )
    for rid in out_r:
        lp_r, lp_s = out_r[rid][1], out_s[rid][1]
        assert len(lp_r) == len(lp_s)
        for a, b in zip(lp_r, lp_s):
            assert a["token_id"] == b["token_id"]
            assert abs(a["logprob"] - b["logprob"]) < 1e-4


def test_guided_lanes_ride_ragged_rounds():
    """A guided decode lane's device DFA tables ride the mixed round;
    near-budget steering still falls back (split execution) with
    identical outputs."""
    sps = {
        "a": SamplingParams(max_tokens=10, temperature=0.0,
                            guided_choice=["hello", "goodbye"]),
        "b": SamplingParams(max_tokens=10, temperature=0.0,
                            ignore_eos=True),
    }
    _assert_parity(
        [(0, "a", SHORT), (2, "b", LONG)], sps, check_kv=False,
    )


def test_lora_lanes_ride_ragged_rounds():
    """Prefill and decode lanes carry independent LoRA slot vectors
    through the ONE fused program."""
    import os
    import tempfile

    from production_stack_tpu.engine.lora import save_adapter_npz

    mc = EngineConfig(model="pst-tiny-debug").model_config()
    rng = np.random.RandomState(11)
    L, h = mc.num_layers, mc.hidden_size
    adapter = {"scaling": np.float32(0.5)}
    for t, (din, dout) in {
        "wq": (h, mc.q_size), "wo": (mc.q_size, h),
    }.items():
        adapter[f"{t}_A"] = (
            rng.randn(L, din, 4).astype(np.float32) * 0.05
        )
        adapter[f"{t}_B"] = (
            rng.randn(L, 4, dout).astype(np.float32) * 0.05
        )
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "adapter.npz")
        save_adapter_npz(path, adapter)

        def eng(ragged):
            e = _engine(ragged, enable_lora=True, max_loras=2,
                        max_lora_rank=8)
            e.load_lora("ad1", path)
            return e

        sp = SamplingParams(max_tokens=8, temperature=0.0,
                            ignore_eos=True)
        # "b" comes while "a" has rounds left (a round that starts at
        # its predecessor's fetch is not joined by a later arrival)
        arrivals = [(0, "a", SHORT), (1, "b", LONG)]

        def run(ragged):
            e = eng(ragged)
            outs = {}
            pending = list(arrivals)
            steps = 0
            while pending or e.has_unfinished():
                while pending and pending[0][0] <= steps:
                    _, rid, prompt = pending.pop(0)
                    e.add_request(
                        rid, prompt_token_ids=prompt,
                        sampling_params=sp,
                        lora_name="ad1" if rid == "b" else None,
                    )
                for o in e.step():
                    if o.finished:
                        outs[o.request_id] = o.token_ids
                steps += 1
            return e, outs

        e_r, out_r = run(True)
        _, out_s = run(False)
        assert out_r == out_s
        assert e_r._ragged_rounds_total > 0


# -- (d) staged-prefetch hits -------------------------------------------------
def test_staged_ragged_prefetch_hits_and_parity():
    """The predicted next lane-typed round's packed buffer is uploaded
    ahead and actually consumed (hits > 0) in a steady mixed run, with
    streams identical to the unprefetched engine."""
    sp = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    long_prompt = list(range(1, 60))
    arrivals = [(0, "a", SHORT), (3, "b", long_prompt)]

    def run(prefetch):
        e = _engine(True, max_num_seqs=2, num_kv_blocks=256,
                    prefetch_decode=prefetch)
        return e, _run_staggered(e, arrivals, sp)

    e_on, out_on = run(True)
    e_off, out_off = run(False)
    assert {r: t for r, (t, _) in out_on.items()} == {
        r: t for r, (t, _) in out_off.items()
    }
    assert e_on._ragged_staged_hits_total > 0
    assert e_off._ragged_staged_hits_total == 0


def test_stale_ragged_stage_is_counted_miss_not_error():
    """Fix audit: a staged buffer whose lane mix / layout no longer
    matches the dispatch must be a COUNTED staging miss (rebuild +
    serial upload), never a dispatch error. Runner-level: hand
    ragged_dispatch a staged handle of the wrong total length."""
    e = _engine(True, max_num_seqs=2, num_kv_blocks=256)
    r = e.runner
    import jax.numpy as jnp

    temps = np.zeros((2,), np.float32)
    top_ps = np.ones((2,), np.float32)
    top_ks = np.full((2,), -1, np.int32)
    keys = np.zeros((2, 2), np.uint32)
    table = list(range(100, 104))
    pf_table = list(range(104, 108))
    # a "staged" handle with the right bucket key but a WRONG length
    # (e.g. built before a stop-cap / lane-mix change)
    c_pad = r._ctx_bucket(16 + 3)
    s_pad, t_pad, pc_pad = 1, r._prefill_bucket(4), r._ctx_bucket(16)
    bogus = ((("ragged", s_pad, t_pad, pc_pad, c_pad)),
             jnp.zeros((7,), jnp.int32))
    chain = jnp.zeros((2,), jnp.int32)  # device tokens => chained path
    out = r.ragged_dispatch(
        [[1, 2, 3, 4]], [12], [pf_table], [16],
        chain, [15, 15], [table, table], [16, 16], 4,
        temps, top_ps, top_ks, keys,
        staged=bogus,
    )
    assert out[0].shape[0] == s_pad  # dispatched fine on a fresh pack


def test_drain_contract_and_stats():
    """drain_ragged_observations empties the deque; the stats snapshot
    carries the ragged counters for /metrics."""
    sp = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    e = _engine(True)
    _run_staggered(e, [(0, "a", SHORT), (2, "b", LONG)], sp)
    obs = e.drain_ragged_observations()
    assert obs and all(n >= 1 for n in obs)
    assert e.drain_ragged_observations() == []
    s = e.stats()
    assert s.ragged_rounds_total == len(obs)
    assert s.ragged_prefill_lanes_total >= len(obs)
    assert s.ragged_decode_lanes_total >= len(obs)


# -- (e) the scheduling contract ---------------------------------------------
def _sched(ragged, **kw):
    bm = BlockManager(kw.pop("num_blocks", 64), kw.pop("block_size", 4))
    cfg = SchedulerConfig(
        max_num_seqs=kw.pop("max_num_seqs", 4),
        max_prefill_chunk=kw.pop("max_prefill_chunk", 8),
        max_model_len=kw.pop("max_model_len", 128),
        ragged_dispatch=ragged,
        **kw,
    )
    return Scheduler(cfg, bm)


def _mkseq(rid, n_prompt, **kw):
    return Sequence(
        rid, list(range(1, n_prompt + 1)), SamplingParams(**kw), None
    )


def test_waiting_prefill_joins_next_ragged_round_no_interleave_wait():
    """THE acceptance contract: under ragged_dispatch a newly arrived
    prompt's chunks are scheduled in every consecutive round beside the
    decode batch — never parked behind the decode-interleave streak.
    The split control alternates (its rounds are prefill XOR decode)."""
    sched = _sched(True)
    a = _mkseq("a", 4, max_tokens=64, ignore_eos=True)
    sched.add_seq(a)
    out = sched.schedule()
    assert [w.seq.request_id for w in out.prefills] == ["a"]
    a.num_computed_tokens = 4
    a.append_token(7)  # prefill done, decode-ready

    # a 3-chunk prompt arrives while `a` decodes
    b = _mkseq("b", 24, max_tokens=8, ignore_eos=True)
    sched.add_seq(b)
    chunks_seen = 0
    for _ in range(3):
        out = sched.schedule()
        # EVERY round is mixed: b's next chunk AND a's decode lane
        assert out.is_ragged
        assert [w.seq.request_id for w in out.prefills] == ["b"]
        assert [s.request_id for s in out.decode.seqs] == ["a"]
        w = out.prefills[0]
        b.num_computed_tokens += w.chunk_len
        chunks_seen += 1
        a.append_token(9)  # decode applied
    assert chunks_seen == 3 and b.prefill_done is False or True

    # split control: the same shape alternates prefill/decode rounds
    sched2 = _sched(False)
    a2 = _mkseq("a", 4, max_tokens=64, ignore_eos=True)
    sched2.add_seq(a2)
    out = sched2.schedule()
    a2.num_computed_tokens = 4
    a2.append_token(7)
    b2 = _mkseq("b", 24, max_tokens=8, ignore_eos=True)
    sched2.add_seq(b2)
    kinds = []
    for _ in range(4):
        out = sched2.schedule()
        assert not out.is_ragged
        if out.prefills:
            kinds.append("p")
            b2.num_computed_tokens += out.prefills[0].chunk_len
        elif out.decode is not None:
            kinds.append("d")
            a2.append_token(9)
    assert "d" in kinds and "p" in kinds  # the alternation ragged removes


def test_ragged_engine_gates():
    """Engine-level gating: ragged is off under --no-ragged-dispatch,
    on otherwise; the scheduler flag follows."""
    e = _engine(True)
    assert e._ragged_dispatch and e.scheduler.config.ragged_dispatch
    e = _engine(False)
    assert not e._ragged_dispatch
    assert not e.scheduler.config.ragged_dispatch


# -- (f) single-kernel ragged paged attention (PR 11) ------------------------
# The Pallas path now serves ANY lane mix with ONE ragged_paged_
# attention launch (decode rows + prefill q-tiles share the grid) and
# keys the packed-prefill/ragged program variants on padded ROW-count
# buckets. These tests pin the engine-level parity, the one-launch
# contract, and the variant-space shrink vs the PR 7 lane-mix grid.

def test_single_kernel_mixed_round_parity():
    """Kernel-mode ragged engine vs kernel-mode split engine (both
    attention_impl=pallas, interpret on CPU): identical tokens, equal
    logical KV through mixed rounds with device stops."""
    sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    e_r, _, _ = _assert_parity(
        [(0, "a", SHORT), (2, "b", LONG), (3, "c", MED)], sp,
        engine_kw=dict(attention_impl="pallas"),
    )
    assert e_r.runner.ragged_kernel
    assert e_r._ragged_rounds_total > 0


def test_single_kernel_exotic_sampling_parity():
    """Penalties, logprobs, and stop ids all ride the fused rows
    round's shared decode core: kernel-mode ragged vs kernel-mode
    split, token streams and logprob entries identical."""
    learn = SamplingParams(max_tokens=10, temperature=0.0,
                           ignore_eos=True)
    stream = _engine(False, k=1).generate([SHORT], learn)[0].token_ids
    sps = {
        "a": SamplingParams(max_tokens=10, temperature=0.7, seed=3,
                            repetition_penalty=1.3, ignore_eos=True),
        "b": SamplingParams(max_tokens=8, temperature=0.0, logprobs=2,
                            ignore_eos=True),
        "c": SamplingParams(max_tokens=10, temperature=0.0,
                            ignore_eos=True,
                            stop_token_ids=[stream[4]]),
    }
    _, out_r, out_s = _assert_parity(
        [(0, "a", SHORT), (2, "b", LONG), (2, "c", MED)], sps,
        engine_kw=dict(attention_impl="pallas"), check_kv=False,
    )
    lp_r, lp_s = out_r["b"][1], out_s["b"][1]
    assert len(lp_r) == len(lp_s) > 0
    for x, y in zip(lp_r, lp_s):
        assert x["token_id"] == y["token_id"]
        assert abs(x["logprob"] - y["logprob"]) < 1e-4


def _mixed_dispatch(runner, n_pf, chunk_len, k=4, total_len=16):
    """Drive one mixed ragged_dispatch on a fresh runner: n_pf prefill
    lanes, each mid-prefill with `chunk_len` tokens of a `total_len`
    prompt, beside a full decode batch (trash tables at the top of
    the pool, the precompile pattern). Fixing total_len across mixes
    keeps the prefill ctx bucket constant so only the LANE MIX varies
    between calls."""
    b = runner.config.max_num_seqs
    bs = runner.block_size
    nb = runner.num_blocks
    temps = np.zeros((b,), np.float32)
    top_ps = np.ones((b,), np.float32)
    top_ks = np.full((b,), -1, np.int32)
    keys = np.zeros((b, 2), np.uint32)
    c_pad = runner._ctx_bucket(16 + k - 1)
    npages = c_pad // bs
    dec_table = list(range(nb - npages, nb))
    pf_pages = runner._ctx_bucket(total_len) // bs
    pf_tabs = [
        list(range(nb - npages - (i + 1) * pf_pages,
                   nb - npages - i * pf_pages))
        for i in range(n_pf)
    ]
    ctx = c_pad - (k - 1)
    out = runner.ragged_dispatch(
        [[1] * chunk_len] * n_pf,
        [total_len - chunk_len] * n_pf, pf_tabs,
        [total_len] * n_pf,
        [1] * b, [ctx - 1] * b, [dec_table] * b, [ctx] * b, k,
        temps, top_ps, top_ks, keys,
    )
    import jax
    jax.block_until_ready(out)


def test_single_kernel_one_launch_per_lane_mix():
    """THE acceptance contract: under the single kernel, a mixed
    round's traced program contains a LANE-COUNT-INDEPENDENT number of
    ragged kernel launches (one per layer for the fused step-0
    forward, one per layer inside the decode loop) and ZERO launches
    of the lone-chunk prefill kernel."""
    from production_stack_tpu.ops import pallas_attention as pa

    import jax

    def launches(n_pf):
        e = _engine(True, attention_impl="pallas", num_kv_blocks=256)
        # the kernel entries are themselves jitted and jax's trace
        # cache is process-global: clear it so each program's launch
        # count is measured fresh, not deduped against a prior engine
        jax.clear_caches()
        pa.reset_launch_counts()
        _mixed_dispatch(e.runner, n_pf, chunk_len=4)
        return pa.launch_counts()

    l1 = launches(1)
    l2 = launches(4)
    # layers run under lax.scan, so the traced program holds exactly
    # TWO ragged launches — the fused step-0 forward's and the decode
    # loop body's — regardless of the lane mix
    assert l1 == l2 == {"ragged": 2, "prefill": 0}


def test_single_kernel_variant_space_shrinks():
    """Precompile-variant acceptance: lane mixes that pack to the same
    row bucket share ONE program under the single kernel, so both the
    live lane-mix matrix and precompile_ragged compile strictly fewer
    ragged variants than the (group, chunk) grid of the lane-mix
    programs, which the XLA path still runs."""
    # live matrix: (lanes x chunk_len) mixes — lane-mix keys
    # (s_pad, t_pad, ...) = 4 variants, rows keys r_pad = 3
    mixes = [(1, 4), (2, 4), (1, 12), (2, 12)]

    def impl(rows):
        return "pallas" if rows else "xla"

    def variants(rows):
        e = _engine(True, attention_impl=impl(rows), num_kv_blocks=256,
                    max_prefill_chunk=16)
        for n_pf, clen in mixes:
            _mixed_dispatch(e.runner, n_pf, clen)
        return len(e.runner._ragged_fns)

    n_rows = variants(True)
    n_mix = variants(False)
    assert n_rows < n_mix, (n_rows, n_mix)
    assert (n_rows, n_mix) == (3, 4)

    # the split packed-prefill path collapses the same way: its
    # program keys on (r_pad, pc_pad) instead of (s_pad, t_pad, c_pad)
    def pf_variants(rows):
        e = _engine(True, attention_impl=impl(rows), num_kv_blocks=256,
                    max_prefill_chunk=16)
        r = e.runner
        nb = r.num_blocks
        pgs = r._ctx_bucket(16) // r.block_size
        for n_pf, clen in mixes:
            tabs = [
                list(range(nb - (i + 1) * pgs, nb - i * pgs))
                for i in range(n_pf)
            ]
            out = r.prefill_batch(
                [[1] * clen] * n_pf, [16 - clen] * n_pf, tabs,
                [16] * n_pf,
            )
            import jax
            jax.block_until_ready(out)
        return len(r._prefill_batch_fns)

    assert pf_variants(True) < pf_variants(False)

    # precompile grid: with a uniform warm chunk the per-(ctx, k)
    # group dedupe is 1:1, so the warm pass never compiles MORE —
    # the precompile_serving group grid (multiple chunk buckets) is
    # where the row-bucket dedupe strictly shrinks, pinned above
    def precompiled(rows):
        e = _engine(True, attention_impl=impl(rows), num_kv_blocks=256,
                    max_prefill_chunk=16, max_prefill_seqs=4)
        e.runner.precompile_ragged(
            [16], [4], max_groups=4, chunk_len=16,
        )
        return len(e.runner._ragged_fns)

    assert precompiled(True) <= precompiled(False)


def test_single_kernel_staged_prefetch_hits_and_parity():
    """The h2d-prefetched next-round buffer (rows layout) is consumed
    under the single kernel (hits > 0) with streams identical to the
    unprefetched kernel-mode engine."""
    sp = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    long_prompt = list(range(1, 60))
    arrivals = [(0, "a", SHORT), (3, "b", long_prompt)]

    def run(prefetch):
        e = _engine(True, attention_impl="pallas", max_num_seqs=2,
                    num_kv_blocks=256, prefetch_decode=prefetch)
        return e, _run_staggered(e, arrivals, sp)

    e_on, out_on = run(True)
    e_off, out_off = run(False)
    assert {r: t for r, (t, _) in out_on.items()} == {
        r: t for r, (t, _) in out_off.items()
    }
    assert e_on._ragged_staged_hits_total > 0


def test_compile_events_counted_and_in_stats():
    """Compile-count observability: every program-variant build ticks
    the runner counter, rides the stats snapshot (-> tpu:compile_
    events_total), and distinguishes kernel-mode builder kinds."""
    sp = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    e = _engine(True, attention_impl="pallas")
    _run_staggered(e, [(0, "a", SHORT), (1, "b", LONG)], sp)
    assert e.runner.compile_events_total > 0
    assert "ragged_rows" in e.runner.compile_events
    s = e.stats()
    assert s.compile_events_total == e.runner.compile_events_total
    assert s.compile_events == e.runner.compile_events
    # the counter is a monotonic total: re-running an already-warmed
    # workload shape adds nothing
    _run_staggered(e, [(0, "d", SHORT)], sp)
    before = e.runner.compile_events_total
    _run_staggered(e, [(0, "e", SHORT)], sp)
    assert e.runner.compile_events_total == before


def test_stochastic_parity_in_mixed_rounds():
    """Sampled streams (per-iteration keys (seed, generated_len + i))
    stay bit-identical through lane-typed rounds."""
    sp = SamplingParams(max_tokens=9, temperature=0.8, top_p=0.9,
                        seed=7, ignore_eos=True)
    _assert_parity(
        [(0, "a", SHORT), (2, "b", LONG), (3, "c", MED)], sp,
        check_kv=False,
    )


# -- (g) lanes that hold no sequence are zero-row segments (PR 29) -----------
# The decode pack ships a lane without a sequence with context 0 and the
# step programs make it a ZERO-row segment of the attention walk (no KV
# block read, a zero row out), where it was a one-row segment over one
# key of the null page (context 1). Nothing a live lane computes may
# move, under any sampling feature, dispatch mode or stop mode, and no
# program may be added or re-keyed.

def _ship_idle_lanes_as_before(monkeypatch):
    """The parent's packing: idle lanes with context 1 (the programs
    then give them a one-row segment, as every lane had)."""
    from production_stack_tpu.engine.model_runner import ModelRunner

    fill = ModelRunner._fill_decode_pack

    def fill_ones(self, c_pad, chained, token_ids, positions, *a, **kw):
        packed = fill(self, c_pad, chained, token_ids, positions, *a, **kw)
        b = self.config.max_num_seqs
        layout, _ = self._decode_pack_layout(b, c_pad, chained)
        at, _ = layout["ctx"]  # the fields before it are the same ones
        assert not packed[at + len(positions): at + b].any()
        packed[at + len(positions): at + b] = 1
        return packed

    monkeypatch.setattr(ModelRunner, "_fill_decode_pack", fill_ones)


@pytest.mark.parametrize("device_stop, mode", [
    (True, "staged"), (False, "staged"), (True, "plain"),
])
def test_five_live_of_32_lanes_as_zero_row_segments(
        monkeypatch, device_stop, mode):
    """5 sequences on a 32-lane kernel-mode engine, through fused decode
    rounds and lane-typed rounds (a cold 4-chunk prompt joins decoding
    lanes), with penalties, log-probabilities, a stop id that freezes a
    lane mid-round and budgets that end inside a round: tokens and
    log-probabilities equal the parent's packing, and so do the kernel
    launches traced and the keys of the programs built."""
    import jax
    from production_stack_tpu.ops import pallas_attention as pa

    learn = SamplingParams(max_tokens=10, temperature=0.0,
                           ignore_eos=True)
    stream = _engine(False, k=1).generate([MED], learn)[0].token_ids
    sps = {
        "a": SamplingParams(max_tokens=13, temperature=0.7, seed=3,
                            repetition_penalty=1.3, ignore_eos=True),
        "b": SamplingParams(max_tokens=9, temperature=0.0, logprobs=2,
                            ignore_eos=True),
        "c": SamplingParams(max_tokens=12, temperature=0.0,
                            ignore_eos=True,
                            stop_token_ids=[stream[5]]),
        "d": SamplingParams(max_tokens=11, temperature=0.0,
                            ignore_eos=True),
        "e": SamplingParams(max_tokens=7, temperature=0.9, top_p=0.8,
                            seed=11, ignore_eos=True),
    }
    arrivals = [(0, "a", SHORT), (0, "c", MED), (0, "d", [9, 8, 7]),
                (2, "b", LONG), (3, "e", MED[::-1])]
    kw = dict(
        attention_impl="pallas", max_num_seqs=32, num_kv_blocks=256,
        device_stop=device_stop, prefetch_decode=mode == "staged",
    )

    def run():
        jax.clear_caches()  # the kernels' own jits: count every trace
        pa.reset_launch_counts()
        e = _engine(True, **kw)
        out = _run_staggered(e, arrivals, sps)
        r = e.runner
        keys = {name: sorted(map(repr, getattr(r, name))) for name in (
            "_decode_multi_fns", "_ragged_fns", "_prefill_batch_fns",
            "_prefill_fns", "_decode_fns")}
        return e, out, keys, pa.launch_counts(), dict(r.compile_events)

    e, out, keys, launches, builds = run()
    assert e.runner.ragged_kernel
    lane_steps, idle = e.runner.decode_lane_steps
    assert lane_steps > 0 and lane_steps % 32 == 0
    # never more than 5 of the 32 lanes held a sequence
    assert idle * 32 >= lane_steps * 27
    assert e.stats().decode_lane_steps == (lane_steps, idle)
    if mode == "staged":
        assert e._ragged_rounds_total > 0
    _ship_idle_lanes_as_before(monkeypatch)
    e_p, out_p, keys_p, launches_p, builds_p = run()
    assert out.keys() == out_p.keys() == sps.keys()
    for rid in sps:
        assert out[rid][0] == out_p[rid][0], rid
    lp, lp_p = out["b"][1], out_p["b"][1]
    assert len(lp) == len(lp_p) == 9
    assert lp == lp_p  # same bits: the live rows' arithmetic is the same
    assert out["c"][0][-1] == stream[5] and len(out["c"][0]) < 12
    assert keys == keys_p and any(keys.values())
    assert launches == launches_p
    assert builds == builds_p
