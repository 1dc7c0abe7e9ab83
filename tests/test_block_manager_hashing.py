"""The KV block hash is folded by the block and once (block_manager.
hash_block / iter_chain_hashes): the digests are those of the per-token
form, which the KV controller's matcher, the router's hints and the cache
server share, and a prompt block is hashed at most once an admission,
whoever asks (the prefix match, a restore, the registration of computed
blocks)."""

from types import SimpleNamespace

import numpy as np
import pytest
import xxhash

from production_stack_tpu.engine import block_manager as bm_mod
from production_stack_tpu.engine.block_manager import (
    BlockManager,
    WindowedBlockManager,
    hash_block,
    iter_chain_hashes,
)
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.engine.scheduler import Scheduler, SchedulerConfig
from production_stack_tpu.engine.sequence import PromptIds, Sequence

LORA_SEED = xxhash.xxh64(b"lora:adapter-a").intdigest()


def per_token_hash_block(prev_hash, token_ids, extra=()):
    """The form every component hashed with before: one `update` a
    token. Kept here as the reference the digests are held to."""
    h = xxhash.xxh64()
    h.update(prev_hash.to_bytes(8, "little", signed=False))
    for t in token_ids:
        h.update(int(t).to_bytes(4, "little", signed=False))
    for e in extra:
        h.update(str(e).encode())
    return h.intdigest()


def per_token_chain(token_ids, block_size, seed=0):
    prev, out = seed, []
    for i in range(len(token_ids) // block_size):
        prev = per_token_hash_block(
            prev, tuple(token_ids[i * block_size:(i + 1) * block_size]))
        out.append(prev)
    return out


def uint32_ids(n, seed):
    rng = np.random.default_rng(seed)
    ids = [int(x) for x in rng.integers(0, 2 ** 32, n, dtype=np.uint64)]
    ids[0], ids[1] = 0, 2 ** 32 - 1  # both ends of the range
    return ids


# -- (a) the digests ---------------------------------------------------------
@pytest.mark.parametrize("seed", [0, LORA_SEED], ids=["base", "lora"])
@pytest.mark.parametrize("block_size", [4, 16, 32])
def test_digests_equal_the_per_token_form(block_size, seed):
    # 7 full blocks and a partial one, which is ignored
    ids = uint32_ids(7 * block_size + block_size // 2 + 1, seed=block_size)
    want = per_token_chain(ids, block_size, seed)
    assert len(want) == 7
    assert list(iter_chain_hashes(ids, block_size, seed)) == want
    # resumed past block 3 with that block's hash as the seed
    assert list(iter_chain_hashes(ids, block_size, want[2], start=3)) \
        == want[3:]
    assert list(iter_chain_hashes(ids, block_size, want[-1], start=7)) == []
    # a block alone, as a tuple, a list or numpy ints
    blk = ids[:block_size]
    for form in (tuple(blk), blk, np.asarray(blk, np.uint32)):
        assert hash_block(seed, form) == want[0]
    assert BlockManager(8, block_size).block_hashes_for(ids, seed) == want


def test_extra_is_folded_after_the_tokens_as_before():
    ids = uint32_ids(16, seed=1)
    assert hash_block(5, ids, extra=("a", 7)) \
        == per_token_hash_block(5, ids, extra=("a", 7))
    assert hash_block(5, ids, extra=("a",)) != hash_block(5, ids)


@pytest.mark.parametrize("bad", [-1, 2 ** 32], ids=["negative", "2**32"])
def test_ids_the_per_token_form_refused_are_refused(bad):
    ids = [1, 2, bad, 4]
    with pytest.raises(OverflowError):
        per_token_hash_block(0, ids)
    with pytest.raises(OverflowError):
        hash_block(0, ids)
    with pytest.raises(OverflowError):
        list(iter_chain_hashes(ids, 4))
    # and at admission, before they can reach the step thread
    with pytest.raises(ValueError, match="integers"):
        PromptIds.of(ids)


@pytest.mark.parametrize("bad", [1.5, "7", None])
def test_a_non_integer_id_is_refused_at_admission(bad):
    with pytest.raises(ValueError, match="integers"):
        PromptIds.of([1, bad, 3])
    ok = PromptIds.of([1, np.int64(2), np.int32(3)])
    assert ok == [1, 2, 3] and PromptIds.of(ok) is ok


# -- (b) hashed once ---------------------------------------------------------
@pytest.fixture
def count_hashes(monkeypatch):
    """Every hash_block call of the engine's side, by the blocks hashed."""
    calls = []
    real = bm_mod.hash_block

    def counting(prev_hash, token_ids, extra=()):
        calls.append(prev_hash)
        return real(prev_hash, token_ids, extra)

    monkeypatch.setattr(bm_mod, "hash_block", counting)
    return calls


def register_all(bm, table, ids, seed=0):
    prev = seed
    for i in range(len(ids) // bm.block_size):
        prev = bm.register_block(
            prev, tuple(ids[i * bm.block_size:(i + 1) * bm.block_size]),
            table[i])


def test_match_prefix_hashes_no_block_past_the_first_miss(count_hashes):
    bm = BlockManager(64, 4)
    ids = list(range(100, 140))  # 10 blocks
    table, _ = bm.allocate_prompt(ids[:12])
    register_all(bm, table, ids[:12])  # 3 blocks cached
    del count_hashes[:]
    matched, n = bm.match_prefix(ids)
    assert (len(matched), n) == (3, 12)
    assert len(count_hashes) == 4  # three hits and the miss
    del count_hashes[:]
    assert bm.match_prefix(list(range(500, 540))) == ([], 0)
    assert len(count_hashes) == 1
    # what a caller knows of the chain is not hashed again
    known: list[int] = []
    bm.match_prefix(ids, hashes=known)
    assert len(known) == 4
    del count_hashes[:]
    assert bm.match_prefix(ids, hashes=known)[1] == 12
    assert count_hashes == []
    assert bm.block_hashes_for(ids, hashes=known) == per_token_chain(ids, 4)
    assert len(count_hashes) == 6 and len(known) == 10


def _sched(bm, max_model_len=256):
    return Scheduler(SchedulerConfig(
        max_num_seqs=4, max_prefill_chunk=64, max_model_len=max_model_len,
    ), bm)


def _compute_prompt(bm, seq):
    """What the engine does with an admitted prompt, without a model:
    the chunks are 'computed', the full blocks registered through the
    engine's own `_register_full_blocks`."""
    registered = []
    real = bm.register_block

    def spy(prev_hash, token_ids, block_id, **kw):
        registered.append(block_id)
        return real(prev_hash, token_ids, block_id, **kw)

    bm.register_block = spy
    try:
        bm.prepare_chunk(seq.block_table, seq.num_computed_tokens,
                         seq.num_prompt_tokens)
        seq.num_computed_tokens = seq.num_prompt_tokens
        LLMEngine._register_full_blocks(
            SimpleNamespace(block_manager=bm), seq)
    finally:
        del bm.register_block
    return registered


def _admit(bm, rid, ids):
    sched = _sched(bm)
    seq = Sequence(rid, ids, SamplingParams(max_tokens=4), None)
    sched.add_seq(seq)
    sched.schedule()
    assert seq.block_table
    return sched, seq


def test_an_admission_hashes_each_prompt_block_once(count_hashes):
    bm = BlockManager(64, 4)
    first = list(range(100, 141))  # 10 blocks and a token
    _, a = _admit(bm, "a", first)
    # the match's one miss is block 0, whose hash registers it; the
    # other nine are hashed as they are registered
    assert _compute_prompt(bm, a) == a.block_table[1:10]
    assert len(count_hashes) == 10
    del count_hashes[:]
    hashed_before = bm.blocks_hashed

    second = first[:24] + list(range(900, 917))  # 6 blocks hit of 10
    _, b = _admit(bm, "b", second)
    assert b.num_computed_tokens == 24 and b.num_registered_blocks == 6
    assert b.block_table[:6] == a.block_table[:6]
    assert len(b.block_hashes) == 7  # the adopted six and the miss
    registered = _compute_prompt(bm, b)
    # an adopted block is not registered again, and the block the match
    # missed on is registered with the hash the match computed
    assert registered == b.block_table[7:10]
    assert bm.cached_blocks[b.block_hashes[6]] == b.block_table[6]
    assert b.block_hashes == per_token_chain(second, 4)
    assert len(count_hashes) == 10  # <= one a prompt block in total
    assert bm.blocks_hashed - hashed_before == 10
    # a block that generated tokens fill is hashed, and not counted
    for t in range(4):
        b.append_token(7)
    b.num_computed_tokens = 44
    LLMEngine._register_full_blocks(SimpleNamespace(block_manager=bm), b)
    assert len(count_hashes) == 11 and len(b.block_hashes) == 11
    assert bm.blocks_hashed - hashed_before == 10
    # a third request finds all ten
    assert bm.match_prefix(second)[1] == 40


def test_a_refused_admission_is_not_hashed_again_when_retried(count_hashes):
    bm = BlockManager(8, 4)  # 7 usable blocks
    _, a = _admit(bm, "a", list(range(100, 120)))  # holds 5
    _compute_prompt(bm, a)
    sched = _sched(bm)
    b = Sequence("b", list(range(100, 108)) + list(range(300, 312)),
                 SamplingParams(max_tokens=4), None)
    sched.add_seq(b)
    del count_hashes[:]
    for _ in range(3):  # 2 hit + 3 new needed, 2 free: refused
        sched.schedule()
        assert not b.block_table
    assert len(count_hashes) == 3  # two hits and the miss, once


def windowed(num_blocks=64, window=8, num_window_blocks=24):
    return WindowedBlockManager(num_blocks, 4, True, window=window,
                                num_window_blocks=num_window_blocks)


def test_a_hit_cut_back_inside_the_window_group_is_hashed_once(
        count_hashes):
    bm = windowed()
    first = list(range(100, 141))
    _, a = _admit(bm, "a", first)
    _compute_prompt(bm, a)
    # the first sequence decodes on: the window moves and lets go of the
    # twins of its early blocks, so a later hit cannot end among them
    bm.release_behind(a.block_table, 41)
    bm._drop_twin(a.block_table[4])
    bm._drop_twin(a.block_table[5])
    del count_hashes[:]
    second = first[:24] + list(range(900, 917))
    plain_hit = BlockManager.match_prefix(bm, second)[1]
    assert plain_hit == 24
    del count_hashes[:]
    _, b = _admit(bm, "b", second)
    # cut back from 6 blocks to where the window group still holds the
    # tail: fewer adopted than the primary pool matched
    n = b.num_registered_blocks
    assert n < 6 and b.num_computed_tokens == n * 4
    assert len(b.block_hashes) == 7  # the match's hashes are all kept
    registered = _compute_prompt(bm, b)
    # blocks n..5 are this sequence's own fresh blocks: registering them
    # leaves the cached ones in place (same hash), with no new hashing
    assert registered == b.block_table[7:10]
    assert len(count_hashes) == 10
    assert b.block_hashes == per_token_chain(second, 4)


def tiny_engine(**overrides) -> LLMEngine:
    kwargs = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=4, num_kv_blocks=128,
        max_num_seqs=4, max_prefill_chunk=16, seed=0,
    )
    kwargs.update(overrides)
    return LLMEngine(EngineConfig(**kwargs))


def greedy(n):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)


def test_a_preempted_and_readmitted_sequence_still_registers_and_hits():
    """A pool too small for two growing sequences preempts one: its
    outputs are folded into the prompt and the chain starts over. Both
    answers must be what each request gets alone, and what the engine
    registered must be found by the next request."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 384, size=24).tolist() for _ in range(2)]
    alone = [tiny_engine().generate([p], greedy(10))[0].token_ids
             for p in prompts]
    e = tiny_engine(num_kv_blocks=18, max_num_seqs=2)
    outs = e.generate(prompts, greedy(10))
    assert e.stats().num_preemptions_total >= 1
    assert [o.token_ids for o in outs] == alone
    assert [o.prompt_token_ids for o in outs] == prompts
    bm = e.block_manager
    for p, o in zip(prompts, outs):
        served = p + o.token_ids
        chain = per_token_chain(served, 4)
        # every full block whose tokens were computed (all but the last
        # token's) is content-addressed under the per-token digest
        held = [h in bm.cached_blocks for h in chain[:(len(served) - 1) // 4]]
        assert any(held)
    hits = bm.prefix_hits
    e.generate([prompts[1] + [5, 6, 7]], greedy(2))
    assert bm.prefix_hits > hits


def test_blocks_hashed_per_queried_block_is_at_most_one():
    from prometheus_client import CollectorRegistry, generate_latest

    from production_stack_tpu.engine.metrics import EngineMetrics

    e = tiny_engine(num_kv_blocks=256)
    rng = np.random.RandomState(5)
    shared = rng.randint(0, 384, size=61).tolist()
    prompts = [shared + rng.randint(0, 384, size=n).tolist()
               for n in (7, 12, 3, 9, 16, 5)]
    e.generate(prompts[:2], greedy(6))
    e.generate(prompts[2:], greedy(6))
    s = e.stats()
    assert s.prefix_cache_hits > 0
    # the prompts' blocks, at most once each (the blocks the answers
    # filled are hashed when registered and are not counted: no one
    # queried them)
    assert 0 < s.prefix_blocks_hashed_total <= s.prefix_cache_queries / 4
    assert s.prefix_blocks_hashed_total == sum(len(p) // 4 for p in prompts)
    reg = CollectorRegistry()
    m = EngineMetrics("m", registry=reg)
    m.update_from_snapshot(s)
    text = generate_latest(reg).decode()
    line = [ln for ln in text.splitlines()
            if ln.startswith("tpu:prefix_blocks_hashed_total{")]
    assert line and float(line[0].rpartition(" ")[2]) \
        == s.prefix_blocks_hashed_total
