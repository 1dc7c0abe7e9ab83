"""Cross-sequence prefill packing: chunks from several sequences run in
one packed dispatch (round-2 verdict item 2 — burst TTFT). The packed
path must be bit-identical to the round-2 one-sequence-per-step path on
both attention impls, including prefix sharing inside one group.

Reference capability bar: batched chunked prefill inside vLLM
(reference: helm/templates/deployment-vllm-multi.yaml:140-146)."""

import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.model_runner import ModelRunner
from production_stack_tpu.engine.sampling_params import SamplingParams


def tiny_cfg(**overrides) -> EngineConfig:
    kwargs = dict(
        model="pst-tiny-debug",
        tokenizer="byte",
        dtype="float32",
        cache_dtype="float32",
        block_size=4,
        num_kv_blocks=128,
        max_num_seqs=4,
        max_prefill_chunk=16,
        seed=0,
    )
    kwargs.update(overrides)
    return EngineConfig(**kwargs)


def greedy(n):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)


def _prompts():
    rng = np.random.RandomState(7)
    # mixed lengths: same-bucket chunks, smaller last chunks, one-chunk
    # prompts — exercises ragged groups and mid/last chunk mixes
    return [rng.randint(0, 384, size=n).tolist() for n in (5, 23, 45, 12)]


def test_packed_matches_unpacked_engine():
    packed = LLMEngine(tiny_cfg(max_prefill_seqs=8))
    unpacked = LLMEngine(tiny_cfg(max_prefill_seqs=1))
    out_p = [o.token_ids for o in packed.generate(_prompts(), greedy(6))]
    out_u = [o.token_ids for o in unpacked.generate(_prompts(), greedy(6))]
    assert out_p == out_u


def test_packed_pallas_interpret_matches_xla():
    kw = dict(block_size=8, num_kv_blocks=64, max_prefill_chunk=32,
              max_prefill_seqs=8)
    eng_x = LLMEngine(tiny_cfg(attention_impl="xla", **kw))
    out_x = [o.token_ids for o in eng_x.generate(_prompts(), greedy(6))]
    eng_p = LLMEngine(tiny_cfg(attention_impl="pallas", **kw))
    assert eng_p.runner.attention_impl == "pallas"
    out_p = [o.token_ids for o in eng_p.generate(_prompts(), greedy(6))]
    assert out_p == out_x


def test_packed_group_shares_cached_prefix():
    """Two sequences admitted together whose prompts share a cached
    prefix (from an earlier request) must both reuse it and still match
    the unpacked engine."""
    shared = list(range(1, 17))  # 4 whole blocks
    tails = [[100, 101, 102], [200, 201, 202, 203]]
    prompts = [shared + t for t in tails]
    packed = LLMEngine(tiny_cfg(max_prefill_seqs=8))
    unpacked = LLMEngine(tiny_cfg(max_prefill_seqs=1))
    # prime the prefix cache in both engines
    packed.generate([shared], greedy(2))
    unpacked.generate([shared], greedy(2))
    out_p = [o.token_ids for o in packed.generate(prompts, greedy(5))]
    out_u = [o.token_ids for o in unpacked.generate(prompts, greedy(5))]
    assert out_p == out_u
    assert packed.block_manager.prefix_hits > 0


def test_runner_prefill_batch_matches_sequential():
    """Runner-level: one packed dispatch == n sequential prefill calls
    (same logits, same cache contents)."""
    cfg = tiny_cfg()
    r_seq = ModelRunner(cfg)
    r_bat = ModelRunner(cfg)

    rng = np.random.RandomState(3)
    chunks = [rng.randint(0, 384, size=n).tolist() for n in (7, 16, 3)]
    tables = [[2, 3], [4, 5, 6, 7], [8]]
    starts = [0, 0, 0]
    totals = [len(c) for c in chunks]

    seq_results = [
        r_seq.prefill(c, s, bt, tl)
        for c, s, bt, tl in zip(chunks, starts, tables, totals)
    ]
    seq_logits = [np.asarray(lg) for _, lg in seq_results]
    bat_tokens, bat_logits_dev = r_bat.prefill_batch(
        chunks, starts, tables, totals
    )
    bat_logits = np.asarray(bat_logits_dev)
    # on-device greedy sampling agrees with the logits argmax
    for i in range(len(chunks)):
        assert int(np.asarray(bat_tokens)[i]) == int(
            bat_logits[i].argmax()
        )
    for i, sl in enumerate(seq_logits):
        np.testing.assert_allclose(bat_logits[i], sl, rtol=1e-5,
                                   atol=1e-5)
    # identical KV writes (compare only the slots the chunks own; the
    # trash block 0 legitimately differs)
    slots = sorted({
        bt_i * cfg.block_size + o
        for bt in tables for bt_i in bt
        for o in range(cfg.block_size)
    })
    # one-dispatch vs three-dispatch XLA programs fuse differently;
    # allow f32 accumulation noise
    np.testing.assert_allclose(
        np.asarray(r_bat.k_cache[:, :, slots]),
        np.asarray(r_seq.k_cache[:, :, slots]),
        rtol=1e-4, atol=1e-4,
    )


def test_preempted_penalty_seq_uses_host_logits():
    """A post-preemption prefill-final with active penalties has folded
    generated history, so the on-device first-token sample (penalty-free)
    is wrong for it — the engine must fall back to the host logits path.
    Identity check: sync vs packed engines under forced preemption with
    repetition_penalty agree (both ultimately vs the recompute design)."""
    kw = dict(num_kv_blocks=18, enable_prefix_caching=False,
              max_num_seqs=2)
    sp = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True,
                        repetition_penalty=1.5)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 384, size=24).tolist() for _ in range(2)]
    out_p = [o.token_ids
             for o in LLMEngine(tiny_cfg(max_prefill_seqs=8, **kw))
             .generate(prompts, sp)]
    out_u = [o.token_ids
             for o in LLMEngine(tiny_cfg(max_prefill_seqs=1, **kw))
             .generate(prompts, sp)]
    assert out_p == out_u
    assert all(len(t) == 10 for t in out_p)


def test_scheduler_packs_up_to_cap():
    from production_stack_tpu.engine.block_manager import BlockManager
    from production_stack_tpu.engine.scheduler import (
        Scheduler,
        SchedulerConfig,
    )
    from production_stack_tpu.engine.sequence import Sequence

    bm = BlockManager(num_blocks=64, block_size=4,
                      enable_prefix_caching=False)
    sched = Scheduler(
        SchedulerConfig(max_num_seqs=8, max_prefill_chunk=8,
                        max_prefill_seqs=3),
        bm,
    )
    for i in range(5):
        sched.add_seq(Sequence(
            request_id=f"r{i}", prompt_token_ids=list(range(1, 11)),
            sampling_params=SamplingParams(max_tokens=2),
            eos_token_id=None,
        ))
    out = sched.schedule()
    # group capped at max_prefill_seqs, not everything runnable
    assert len(out.prefills) == 3
    assert [w.seq.request_id for w in out.prefills] == ["r0", "r1", "r2"]
    assert all(w.chunk_len == 8 for w in out.prefills)
    # single-chunk-era accessor still works
    assert out.prefill is out.prefills[0]


def test_scheduler_no_packing_without_chunking():
    from production_stack_tpu.engine.block_manager import BlockManager
    from production_stack_tpu.engine.scheduler import (
        Scheduler,
        SchedulerConfig,
    )
    from production_stack_tpu.engine.sequence import Sequence

    bm = BlockManager(num_blocks=64, block_size=4,
                      enable_prefix_caching=False)
    sched = Scheduler(
        SchedulerConfig(max_num_seqs=8, max_prefill_chunk=8,
                        enable_chunked_prefill=False,
                        max_prefill_seqs=4),
        bm,
    )
    for i in range(3):
        sched.add_seq(Sequence(
            request_id=f"r{i}", prompt_token_ids=list(range(1, 11)),
            sampling_params=SamplingParams(max_tokens=2),
            eos_token_id=None,
        ))
    out = sched.schedule()
    # unbounded whole-prompt chunks must not pack (bucket blowup guard)
    assert len(out.prefills) == 1
    assert out.prefills[0].chunk_len == 10


def test_packing_respects_decode_interleave_bound():
    """decode_interleave counts prefill DISPATCHES: a packed group of N
    chunks is one device dispatch whose wall cost is RTT-dominated, so
    under decode load the scheduler still packs a FULL group per
    interleave slot (the earlier chunk-counting reading throttled
    admission to one unpacked chunk per decode round — measured on
    hardware as round-1 p50 TTFT 15.6s vs low seconds in the 10-round
    workload), and a decode round must follow after at most
    `decode_interleave` dispatches."""
    from production_stack_tpu.engine.block_manager import BlockManager
    from production_stack_tpu.engine.scheduler import (
        Scheduler,
        SchedulerConfig,
    )
    from production_stack_tpu.engine.sequence import Sequence

    def build(decode_interleave):
        bm = BlockManager(num_blocks=256, block_size=4,
                          enable_prefix_caching=False)
        sched = Scheduler(
            SchedulerConfig(max_num_seqs=16, max_prefill_chunk=8,
                            max_prefill_seqs=8,
                            decode_interleave=decode_interleave),
            bm,
        )
        # one decode-ready sequence
        d = Sequence(request_id="d", prompt_token_ids=list(range(1, 9)),
                     sampling_params=SamplingParams(max_tokens=64),
                     eos_token_id=None)
        sched.add_seq(d)
        out = sched.schedule()
        for w in out.prefills:
            w.seq.num_computed_tokens += w.chunk_len
        d.append_token(1)
        out = sched.schedule()  # decode round resets the prefill streak
        assert out.decode is not None
        d.num_computed_tokens = d.num_tokens
        d.append_token(1)
        # six fresh prompts wanting prefill
        for i in range(6):
            sched.add_seq(Sequence(
                request_id=f"p{i}", prompt_token_ids=list(range(1, 9)),
                sampling_params=SamplingParams(max_tokens=2),
                eos_token_id=None,
            ))
        return sched

    # K=1: one FULL packed dispatch (all 6 waiting chunks), then a
    # decode round must follow before any further prefill dispatch
    sched = build(decode_interleave=1)
    out = sched.schedule()
    assert len(out.prefills) == 6  # one dispatch packs the whole group
    for w in out.prefills:
        w.seq.num_computed_tokens += w.chunk_len
    out = sched.schedule()
    assert out.decode is not None  # the dispatch bound held

    # K=2: two consecutive packed dispatches are allowed, then decode.
    # 10 fresh prompts with max_prefill_seqs=8 need two dispatches
    sched = build(decode_interleave=2)
    for i in range(6, 10):
        sched.add_seq(Sequence(
            request_id=f"p{i}", prompt_token_ids=list(range(1, 9)),
            sampling_params=SamplingParams(max_tokens=2),
            eos_token_id=None,
        ))
    out = sched.schedule()
    assert len(out.prefills) == 8  # full group, dispatch 1
    for w in out.prefills:
        w.seq.num_computed_tokens += w.chunk_len
    out = sched.schedule()
    assert len(out.prefills) == 2  # remaining chunks, dispatch 2
    for w in out.prefills:
        w.seq.num_computed_tokens += w.chunk_len
    out = sched.schedule()
    assert out.decode is not None  # streak exhausted -> decode

    # no decode-ready sequences: packing is unconstrained
    bm = BlockManager(num_blocks=256, block_size=4,
                      enable_prefix_caching=False)
    sched = Scheduler(
        SchedulerConfig(max_num_seqs=16, max_prefill_chunk=8,
                        max_prefill_seqs=8, decode_interleave=1),
        bm,
    )
    for i in range(6):
        sched.add_seq(Sequence(
            request_id=f"p{i}", prompt_token_ids=list(range(1, 9)),
            sampling_params=SamplingParams(max_tokens=2),
            eos_token_id=None,
        ))
    out = sched.schedule()
    assert len(out.prefills) == 6


def test_precompile_prefill_covers_serving_buckets():
    """precompile_prefill compiles the single/packed/tail programs a
    QPS-paced workload reaches, so no XLA compile lands inside a live
    request's TTFT."""
    eng = LLMEngine(tiny_cfg(max_prefill_seqs=8))
    r = eng.runner
    n = r.precompile_prefill(
        singles=[(16, 16), (16, 32), (4, 32)],
        groups=[(2, 16, 32), (4, 16, 32)],
    )
    assert n == 5
    for chunk, total in [(16, 16), (16, 32), (4, 32)]:
        assert (r._prefill_bucket(chunk), total) in r._prefill_fns
    assert (2, 16, 32) in r._prefill_batch_fns
    assert (4, 16, 32) in r._prefill_batch_fns

    # generating through the engine afterwards must not add prefill keys
    # for a workload whose buckets were precompiled
    before = set(r._prefill_fns)
    eng.generate([list(range(1, 17))], greedy(2))
    assert set(r._prefill_fns) == before


def test_precompile_prefill_pool_guard_skips_oversized():
    """Entries whose trash-block claim could alias live cache blocks are
    skipped individually; small entries still compile."""
    eng = LLMEngine(tiny_cfg(num_kv_blocks=40, max_prefill_seqs=8))
    r = eng.runner
    # single at 32 tokens = 8 blocks: 2*8+64 > 40 -> skipped
    # packed 2x16 tokens = 2*4 blocks: 2*8+64 > 40 -> skipped
    n = r.precompile_prefill(singles=[(16, 32)], groups=[(2, 16, 16)])
    assert n == 0
    assert (16, 32) not in r._prefill_fns
    assert (2, 16, 16) not in r._prefill_batch_fns


def test_precompile_prefill_leaves_cache_semantics_intact():
    """A precompile sweep must not corrupt subsequent generation: outputs
    with and without a preceding sweep are identical."""
    plain = LLMEngine(tiny_cfg(max_prefill_seqs=8))
    swept = LLMEngine(tiny_cfg(max_prefill_seqs=8))
    swept.runner.precompile_prefill(
        singles=[(16, 32)], groups=[(2, 16, 32)]
    )
    out_a = [o.token_ids for o in plain.generate(_prompts(), greedy(6))]
    out_b = [o.token_ids for o in swept.generate(_prompts(), greedy(6))]
    assert out_a == out_b


def test_precompile_serving_covers_all_buckets():
    """--precompile-serving (engine/server startup): the FULL
    config-derivable grid — every pow2 chunk bucket x ctx bucket for
    singles, every pow2 group size for packed groups, the fused-K
    decode program per ctx bucket INCLUDING the smallest (the +K-1
    lookahead shift must not leave it cold), and with spec decode on,
    the packed verify programs for every pow2 lane count."""
    eng = LLMEngine(tiny_cfg(
        max_prefill_seqs=4, num_kv_blocks=256, max_model_len=64,
        num_scheduler_steps=2,
        num_speculative_tokens=2,
    ))
    r = eng.runner
    n = eng.precompile_serving()
    assert n > 0
    cap = 64
    ctxs = []
    c = r._ctx_bucket(1)
    while True:
        ctxs.append(c)
        if c >= cap:
            break
        c = r._ctx_bucket(c + 1)
    tbs = []
    t = r._prefill_bucket(1)
    while True:
        tbs.append(t)
        if t >= r._prefill_bucket(eng.config.max_prefill_chunk):
            break
        t = r._prefill_bucket(t + 1)
    for c in ctxs:
        for t in tbs:
            if t > c:
                continue
            # single-sequence program for every reachable tail bucket
            assert (t, c) in r._prefill_fns, (t, c)
            # every pow2 group size is its own packed program
            for s in (2, 4):
                assert (s, t, c) in r._prefill_batch_fns, (s, t, c)
    # fused-K decode compiled for EVERY bucket, including the smallest
    for c in ctxs:
        assert any(k[1] == c for k in r._decode_multi_fns), c
    # spec verify programs per pow2 lane count at the largest ctx bucket
    tb = r._prefill_bucket(3)  # draft_len = num_speculative_tokens + 1
    for s in (1, 2, 4):
        assert (s, tb, ctxs[-1]) in r._verify_batch_fns, s
