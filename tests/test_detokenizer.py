"""Incremental detokenizer parity: at EVERY step the accumulated text
must equal a full decode of all ids so far — across byte streams that
split multi-byte UTF-8 characters and across real BPE tokenizers whose
token text depends on neighbours."""

import numpy as np
import pytest

from production_stack_tpu.engine.detokenizer import IncrementalDetokenizer
from production_stack_tpu.engine.tokenizer import ByteTokenizer


def assert_stepwise_parity(tok, ids):
    detok = IncrementalDetokenizer(tok)
    for i, t in enumerate(ids):
        got = detok.append(int(t))
        want = tok.decode([int(x) for x in ids[: i + 1]])
        assert got == want, (i, got, want)
    assert detok.current() == tok.decode([int(x) for x in ids])


def test_byte_tokenizer_ascii():
    tok = ByteTokenizer()
    assert_stepwise_parity(tok, tok.encode("hello world, streaming!",
                                           add_bos=False))


def test_byte_tokenizer_multibyte_utf8_split():
    """é/中/emoji bytes arrive one per token: partial characters decode
    as U+FFFD in the full decode and the incremental path must match
    exactly (including the replacement chars)."""
    tok = ByteTokenizer()
    text = "héllo 中文 🚀 done"
    assert_stepwise_parity(tok, tok.encode(text, add_bos=False))


def test_byte_tokenizer_specials_and_random():
    tok = ByteTokenizer()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 384, size=300).tolist()  # incl. BOS/EOS range
    assert_stepwise_parity(tok, ids)


def test_long_stream_matches_and_is_incremental():
    """The commit point must advance (bounded window), and parity must
    hold over a long stream."""
    tok = ByteTokenizer()
    rng = np.random.RandomState(1)
    ids = rng.randint(0, 256, size=500).tolist()
    detok = IncrementalDetokenizer(tok)
    for i, t in enumerate(ids):
        got = detok.append(t)
        assert got == tok.decode(ids[: i + 1])
    # the uncommitted window stayed bounded — the whole point
    assert len(detok._ids) - detok._c <= 32


def test_hf_bpe_tokenizer_parity(tmp_path):
    """Real byte-level BPE fast tokenizer (merges + byte joins): step
    parity over encoded text and over random ids."""
    from production_stack_tpu.engine.tokenizer import HFTokenizer
    from production_stack_tpu.models.debug_checkpoint import (
        write_debug_tokenizer,
    )

    d = tmp_path / "tok"
    d.mkdir()
    write_debug_tokenizer(str(d))
    tok = HFTokenizer(str(d))

    ids = tok.encode("the quick brown fox jumps over the lazy dog! "
                     "serving engines stream tokens.", add_bos=False)
    assert_stepwise_parity(tok, ids)

    rng = np.random.RandomState(2)
    rand = rng.randint(0, tok.vocab_size, size=200).tolist()
    assert_stepwise_parity(tok, rand)


@pytest.mark.parametrize("seed", [3, 4])
def test_engine_outputs_identical_with_incremental_detok(seed):
    """Engine-level: streamed deltas concatenate to the final text and
    the final text equals a full decode (the pre-incremental contract)."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.llm_engine import LLMEngine
    from production_stack_tpu.engine.sampling_params import SamplingParams

    eng = LLMEngine(EngineConfig(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=4, num_kv_blocks=64,
        max_num_seqs=2, max_prefill_chunk=16, seed=seed,
    ))
    rng = np.random.RandomState(seed)
    prompt = rng.randint(0, 256, size=9).tolist()
    eng.add_request("r", prompt_token_ids=prompt,
                    sampling_params=SamplingParams(
                        max_tokens=24, temperature=0.8, seed=seed,
                        ignore_eos=True))
    deltas, final = [], None
    while eng.has_unfinished():
        for out in eng.step():
            deltas.append(out.delta_text)
            if out.finished:
                final = out
    assert final is not None
    assert "".join(deltas) == final.text
    assert final.text == eng.tokenizer.decode(final.token_ids)


def test_invalid_byte_run_keeps_window_bounded():
    """A long run of permanently-invalid bytes (0xFF) must still advance
    the commit point — their U+FFFD rendering can never change — or the
    hot path regresses to O(n^2) (review finding r4)."""
    tok = ByteTokenizer()
    detok = IncrementalDetokenizer(tok)
    ids = [0xFF] * 200
    for i, t in enumerate(ids):
        got = detok.append(t)
        assert got == tok.decode(ids[: i + 1])
    assert len(detok._ids) - detok._c <= 32


def test_abort_flushes_withheld_tail():
    """An aborted stream whose text ends in a withheld U+FFFD must still
    flush it into the final delta (review finding r4): concatenated
    deltas == final text on EVERY finish path."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.llm_engine import LLMEngine
    from production_stack_tpu.engine.sampling_params import SamplingParams
    from production_stack_tpu.engine.sequence import SequenceStatus

    eng = LLMEngine(EngineConfig(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=4, num_kv_blocks=64,
        max_num_seqs=2, max_prefill_chunk=16, seed=0,
    ))
    eng.add_request("r", prompt_token_ids=[65, 66, 67],
                    sampling_params=SamplingParams(max_tokens=8,
                                                   ignore_eos=True))
    seq = eng._seqs["r"]
    eng.step()  # prefill; first token appended
    # force the stream to end mid-character: append a UTF-8 lead byte
    eng._append_token(seq, 0xC3)  # expects a continuation byte
    assert seq.output_text.endswith("�")
    deltas = [getattr(seq, "_pending_delta", "")]
    assert not deltas[0].endswith("�")  # withheld from the live stream
    seq.status = SequenceStatus.FINISHED_ABORTED
    out = eng._make_output(seq)
    assert out.delta_text.endswith("�")  # flushed on the abort path
    assert out.text.endswith("�")


@pytest.mark.parametrize("k", [1, 3, 8, 32])
def test_extend_equals_appends_at_every_round_boundary(k):
    """A fused round's K ids go in with one `extend`: after each round
    the text is the full decode, as K appends would leave it, and the
    uncommitted window stays bounded however the rounds fall against
    multi-byte characters and invalid bytes."""
    tok = ByteTokenizer()
    rng = np.random.RandomState(k)
    ids = list(tok.encode("héllo 中文 🚀 ", add_bos=False)) * 6 \
        + rng.randint(0, 384, size=240).tolist()
    detok = IncrementalDetokenizer(tok)
    for i in range(0, len(ids), k):
        assert detok.extend(ids[i:i + k]) == tok.decode(ids[:i + k])
        assert len(detok._ids) - detok._c <= 32 + k
    assert detok.current() == tok.decode(ids)
