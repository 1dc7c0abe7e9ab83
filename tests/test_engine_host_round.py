"""What the step thread does between two rounds follows the tokens the
round produced, not the tokens its lanes hold: no copy of a lane's context
on the round's path, and an output that carries no copy of the prompt.
The outputs themselves are held to a golden taken from the tree before
that change (same engine arguments, same seed)."""

import asyncio
import json

import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.engine.sequence import Sequence


def cfg(**overrides) -> EngineConfig:
    kwargs = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=4, num_kv_blocks=128,
        max_num_seqs=4, max_prefill_chunk=16, num_scheduler_steps=4,
        seed=0,
    )
    kwargs.update(overrides)
    return EngineConfig(**kwargs)


def greedy(n, **kw):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True,
                          **kw)


# -- (a) no copy of a lane's context on the round's path --------------------
@pytest.fixture
def list_builds(monkeypatch):
    """Calls of the two properties that build a list of everything a
    sequence holds, by name."""
    calls = {"all_token_ids": 0, "generated_token_ids": 0}
    for name in calls:
        prop = getattr(Sequence, name)

        def counted(self, _name=name, _get=prop.fget):
            calls[_name] += 1
            return _get(self)

        monkeypatch.setattr(Sequence, name, property(counted))
    return calls


def long_prompts(n, length, seed=7):
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, 384, size=length).tolist()
    return [shared + rng.randint(0, 384, size=5 + 3 * i).tolist()
            for i in range(n)]


def run_to_end(e, prompts, sps):
    for i, (p, sp) in enumerate(zip(prompts, sps)):
        e.add_request(f"r{i}", prompt_token_ids=p, sampling_params=sp)
    finals = {}
    steps = 0
    while e.has_unfinished():
        for o in e.step():
            if o.finished:
                finals[o.request_id] = o
        steps += 1
    return [finals[f"r{i}"] for i in range(len(prompts))], steps


def test_a_greedy_batch_builds_no_list_of_a_lanes_context(list_builds):
    """Admission with a prefix hit, prefill chunks beside decode lanes,
    fused decode rounds, finishes: none of it asks a sequence for the
    list of all it holds."""
    e = LLMEngine(cfg(model="pst-tiny-ctx64k-debug", num_kv_blocks=2048,
                      max_prefill_chunk=256))
    prompts = long_prompts(4, 2300)
    e.generate([prompts[0]], greedy(3))  # the shared prefix is cached
    assert not any(list_builds.values()), list_builds
    outs, steps = run_to_end(e, prompts, [greedy(9 + i) for i in range(4)])
    assert steps >= 4
    assert [len(o.token_ids) for o in outs] == [9, 10, 11, 12]
    assert all(o.num_cached_tokens >= 2296 for o in outs)
    assert not any(list_builds.values()), list_builds


@pytest.mark.parametrize("case", ["penalties", "ngram", "guided_choice"])
def test_the_lanes_that_want_a_list_still_get_one(list_builds, case):
    """Penalties and guided choices read the list of what was generated
    and keep the property; n-gram drafts read a bounded tail. All are
    served as before."""
    over = {"ngram": dict(num_speculative_tokens=2,
                          ngram_prompt_lookup_max=3)}.get(case, {})
    e = LLMEngine(cfg(**over))
    sp = {
        "penalties": greedy(8, repetition_penalty=1.3),
        "ngram": greedy(8),
        "guided_choice": SamplingParams(max_tokens=8, temperature=0.0,
                                        guided_choice=["yes", "no"]),
    }[case]
    [out] = e.generate([[5, 6, 7, 8, 5, 6, 7, 8, 5, 6]], sp)
    assert out.finished and out.token_ids
    if case == "guided_choice":
        assert out.text in ("yes", "no")
    else:
        assert len(out.token_ids) == 8
    assert (sum(list_builds.values()) > 0) == (case != "ngram")
    assert list_builds["all_token_ids"] == 0


# -- (b) the contract of outputs --------------------------------------------
# LLMEngine.generate of the tree before this change: cfg() above, one
# request to fill the cache, then these three together
GOLDEN = [
    dict(token_ids=[318, 149, 149, 149, 128, 128, 128, 128, 39],
         cached=32, reason="length",
         text="�������'"),
    dict(token_ids=[242, 85, 85, 85, 85, 85], cached=28, reason="length",
         text="�UUUUU"),
    dict(token_ids=[124, 149, 149, 149, 149, 149, 149, 260], cached=28,
         reason="stop", text="|������"),
]


def golden_requests():
    rng = np.random.RandomState(11)
    shared = rng.randint(0, 384, size=29).tolist()
    prompts = [shared + rng.randint(0, 384, size=n).tolist()
               for n in (5, 9, 2)]
    sps = [greedy(9), greedy(6),
           SamplingParams(max_tokens=12, temperature=0.0,
                          stop_token_ids=[260])]
    return prompts, sps


def check_golden(outs, prompts):
    for o, p, g in zip(outs, prompts, GOLDEN):
        assert o.finished
        assert o.prompt_token_ids == p
        assert list(o.token_ids) == g["token_ids"]
        assert o.num_cached_tokens == g["cached"]
        assert o.finish_reason == g["reason"]
        assert o.text == g["text"]


def test_finished_outputs_equal_the_golden_through_generate():
    e = LLMEngine(cfg())
    prompts, sps = golden_requests()
    e.generate([prompts[0]], sps[0])
    check_golden(e.generate(prompts, sps), prompts)


def test_streamed_outputs_add_up_to_the_finished_one():
    e = LLMEngine(cfg())
    prompts, sps = golden_requests()
    e.generate([prompts[0]], sps[0])
    for i, (p, sp) in enumerate(zip(prompts, sps)):
        e.add_request(f"r{i}", prompt_token_ids=p, sampling_params=sp)
    streams: dict[str, list] = {f"r{i}": [] for i in range(3)}
    while e.has_unfinished():
        for o in e.step():
            # a consumer that reads the cumulative ids of an unfinished
            # output sees the tokens so far
            assert list(o.token_ids)[-len(o.new_token_ids):] \
                == list(o.new_token_ids)
            streams[o.request_id].append(o)
    finals = [streams[f"r{i}"][-1] for i in range(3)]
    check_golden(finals, prompts)
    for outs, final in zip(streams.values(), finals):
        assert len(outs) > 1 and not any(o.finished for o in outs[:-1])
        assert "".join(o.delta_text for o in outs) == final.text
        assert [t for o in outs for t in o.new_token_ids] \
            == list(final.token_ids)
        # the finished output has its ids to itself
        assert final.token_ids is not outs[0].token_ids


def test_a_preempted_request_keeps_its_prompt_and_its_tokens():
    """Preemption folds what was generated into the sequence's prompt:
    the outputs still say which part was the user's."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 384, size=24).tolist() for _ in range(2)]
    alone = [LLMEngine(cfg()).generate([p], greedy(10))[0] for p in prompts]
    e = LLMEngine(cfg(num_kv_blocks=18, max_num_seqs=2))
    for i, p in enumerate(prompts):
        e.add_request(f"r{i}", prompt_token_ids=p,
                      sampling_params=greedy(10))
    streams: dict[str, list] = {"r0": [], "r1": []}
    while e.has_unfinished():
        for o in e.step():
            assert o.prompt_token_ids == prompts[int(o.request_id[1])]
            streams[o.request_id].append(o)
    assert e.stats().num_preemptions_total >= 1
    for outs, want in zip(streams.values(), alone):
        assert list(outs[-1].token_ids) == want.token_ids
        assert [t for o in outs for t in o.new_token_ids] == want.token_ids
        assert "".join(o.delta_text for o in outs) == want.text


def test_the_servers_usage_block_equals_the_golden():
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    prompts, _ = golden_requests()

    async def run():
        srv = EngineServer(cfg())
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            body = dict(max_tokens=9, temperature=0, ignore_eos=True)
            r = await client.post(
                "/v1/completions", json=dict(body, prompt=prompts[0]))
            assert r.status == 200
            blocking = (await r.json())["usage"]
            r = await client.post("/v1/completions", json=dict(
                body, prompt=prompts[0], stream=True,
                stream_options={"include_usage": True}))
            assert r.status == 200
            chunks = [json.loads(ln[6:]) for ln in
                      (await r.text()).splitlines()
                      if ln.startswith("data: {")]
            streamed = [c["usage"] for c in chunks if c.get("usage")][-1]
            text = "".join(c["choices"][0]["text"] for c in chunks
                           if c.get("choices"))
            # an id that is no integer is refused before the engine's
            # lock is asked for, and the step thread never sees it
            bad = await client.post("/v1/completions", json=dict(
                body, prompt=[3, -1, 5]))
            return blocking, streamed, text, bad.status, await bad.json()
        finally:
            await client.close()

    blocking, streamed, text, bad_status, bad_body = asyncio.run(run())
    want = dict(prompt_tokens=34, completion_tokens=9, total_tokens=43)
    for usage in (blocking, streamed):
        assert {k: usage[k] for k in want} == want
    assert text == GOLDEN[0]["text"]
    assert bad_status == 400
    assert "integers" in json.dumps(bad_body)


# -- (c) a lane's K tokens applied in one call -------------------------------
def _apply_rounds(token_by_token: bool):
    """Two fused rounds of K = 8 applied to four lanes whose requests let
    a round's tokens go in together, through `_apply_multi_tokens` with
    tokens and device-stop counts made by hand; each lane's outputs."""
    k = 8
    e = LLMEngine(cfg(num_scheduler_steps=k, block_size=8))
    if token_by_token:
        e._applies_in_one = lambda seq: False
    eos = e.tokenizer.eos_token_id
    sps = [
        greedy(64),                                      # multi-byte text
        SamplingParams(max_tokens=64, temperature=0.0),  # eos at K - 3
        greedy(11),                                      # length, round 2
        greedy(64),                                      # invalid bytes
    ]
    seqs = []
    for i, sp in enumerate(sps):
        e.add_request(f"r{i}", prompt_token_ids=[1, 2, 3],
                      sampling_params=sp)
        seqs.append(e._seqs[f"r{i}"])
    euro = list("€".encode())  # e2 82 ac
    rounds = [
        (np.array([
            # 'h', U+00E9 inside the round, then two of the euro's three
            # bytes: the round ends inside a character
            [ord("h"), 0xC3, 0xA9, ord("i"), 300, ord("!")] + euro[:2],
            [ord("a"), ord("b"), 0xC3, 0xA9, eos, 7, 7, 7],
            [ord("x")] * 8,
            [0xFF, ord("a"), 0xFF, 0xFF, ord("b"), 0xE2, 0x82, ord("c")],
        ], np.int32).T, np.array([8, 5, 8, 8], np.int32)),
        (np.array([
            euro[2:] + [ord("o"), ord("k"), 0xF0, 0x9F, 0x98, 0x80, ord("!")],
            [9] * 8,
            [ord("y")] * 8,
            [0xE2, 0x82, 0xAC, ord("z"), 0xC3, 0xC3, 0xA9, 0xC3],
        ], np.int32).T, np.array([8, 0, 3, 8], np.int32)),
    ]
    streams = [[] for _ in seqs]
    done = set()
    for toks, valid in rounds:
        assert toks.shape == (k, 4)
        e._apply_multi_tokens(seqs, toks, k, valid=valid)
        for s, stream in zip(seqs, streams):
            if s.request_id in done:
                continue
            o = e._make_output(s)
            stream.append((o.delta_text, list(o.new_token_ids), o.text,
                           list(o.token_ids), o.finished, o.finish_reason,
                           s.num_computed_tokens))
            if o.finished:
                done.add(s.request_id)
    return streams, e


def test_a_rounds_tokens_in_one_call_equal_token_by_token():
    one_call, e = _apply_rounds(token_by_token=False)
    by_token, _ = _apply_rounds(token_by_token=True)
    assert one_call == by_token
    multi, stopped, length, invalid = one_call
    # the round that ends inside the euro sign withholds its U+FFFD and
    # the next round sends the whole character
    assert multi[0][0] == "héi!" and multi[1][0] == "€ok\U0001F600!"
    assert "".join(d for d, *_ in multi) == multi[-1][2]
    # the lane the device froze at K - 3 took five tokens and stopped
    assert len(stopped) == 1 and stopped[0][4:6] == (True, "stop")
    assert stopped[0][1][-1] == e.tokenizer.eos_token_id
    assert len(stopped[0][1]) == 5 and stopped[0][0] == "abé"
    # max_tokens 11: eight, then three of the second round's eight
    assert [len(ids) for _, ids, *_ in length] == [8, 3]
    assert length[-1][4:6] == (True, "length")
    # bytes that are no UTF-8 stay U+FFFD and are sent once something
    # follows them; the unfinished lane's last one is still withheld
    assert invalid[-1][2].endswith("é\ufffd") and not invalid[-1][4]
    assert "".join(d for d, *_ in invalid) == invalid[-1][2][:-1]
    assert e._decode_overshoot_tokens_total == 0


# -- (d) a lane's page-table row is kept, not rebuilt -------------------------
def test_kept_page_table_rows_equal_rows_built_from_the_lists():
    r = LLMEngine(cfg()).runner
    rng = np.random.RandomState(0)

    def want(tables, b, n_pages):
        return np.stack([
            r._padded_block_table(tables[i] if i < len(tables) else [],
                                  n_pages)
            for i in range(b)])

    tables = [rng.randint(1, 999, size=n).tolist() for n in (3, 70, 0)]
    for step in range(40):
        n_pages = (8, 64, 128)[step % 3]  # another context bucket
        got = r._page_table_rows(tables, 4, n_pages)
        assert got.dtype == np.int32 and got.shape == (4, n_pages)
        assert np.array_equal(got, want(tables, 4, n_pages)), step
        # what a block manager does to a held table: append
        for t in tables:
            t.extend(rng.randint(1, 999, size=rng.randint(0, 5)).tolist())
        if step % 7 == 3:
            # a lane finishes and the lanes behind it move up; a new
            # admission (or a preemption) brings a NEW list
            tables.pop(0)
            tables.append(rng.randint(1, 999, size=9).tolist())
        if step % 11 == 5:
            tables.reverse()
    # one list in every lane, as the warm-up's trash tables are
    same = list(range(5, 25))
    assert np.array_equal(r._page_table_rows([same] * 4, 4, 16),
                          want([same] * 4, 4, 16))
    assert len(r._kept_rows) == 1
