"""The tile kernel of ops/cache_write.py (interpret mode on the CPU)
against the per-head scatters it replaces where the Pallas walk runs.

Bit for bit: the kernel computes nothing, it moves rows. Every case
holds both caches equal to the scatters' in every slot but slot 0 (the
null block's trash slot, which the kernel does not write: it stays as
it was), and, through the same comparison, untouched wherever no row
was written: the starting caches are random, not zeros. What interpret
mode cannot see (a Mosaic lowering, a tile's packing on the chip) is
tests/test_tpu_aot_compile.py's and scripts/bench_cache_write.py's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.model_runner import ModelRunner
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.ops import cache_write

BS = 32


def pages(rng, n_pages, positions):
    """Slots of one sequence's `positions` over scattered pages."""
    table = rng.permutation(np.arange(1, n_pages))
    positions = np.asarray(positions)
    return table[positions // BS] * BS + positions % BS


def decode_rows(rng, n_pages, n, trash=()):
    """n rows of n sequences, each in a page of its own; `trash` rows
    hold no sequence and write slot 0."""
    out = (rng.permutation(np.arange(1, n_pages))[:n] * BS
           + rng.integers(0, BS, n))
    out[list(trash)] = 0
    return out


def prefill_chunk(rng, n_pages, start, n, pad=0):
    """A chunk's consecutive positions (sharing 16-slot tiles, starting
    and ending inside one), then `pad` padding rows at slot 0."""
    return np.concatenate([
        pages(rng, n_pages, np.arange(start, start + n)),
        np.zeros(pad, np.int64)])


CASES = {
    # the cells' decode rows: lanes x kv heads (K width, stored width)
    "ouro: 16 lanes, 16 heads": dict(
        nkv=16, slots=lambda r: decode_rows(r, 64, 16)),
    "mistral: 32 lanes, 8 heads": dict(
        nkv=8, slots=lambda r: decode_rows(r, 64, 32)),
    "qwen2: 32 lanes, 4 heads": dict(
        nkv=4, slots=lambda r: decode_rows(r, 64, 32)),
    "mimo window layers: 8 heads, K 192 stored at 256": dict(
        nkv=8, dk=192, store=256, slots=lambda r: decode_rows(r, 80, 64)),
    "mimo full layers: 4 heads, K 192 stored at 256": dict(
        nkv=4, dk=192, store=256, slots=lambda r: decode_rows(r, 80, 64)),
    # lanes that hold no sequence, beside live ones
    "5 live of 16 lanes": dict(
        nkv=16, slots=lambda r: decode_rows(
            r, 64, 16, trash=[0, 1, 3, 4, 6, 7, 8, 10, 12, 14, 15])),
    "no live lane": dict(
        nkv=8, slots=lambda r: np.zeros(16, np.int64)),
    # rows that share tiles: a chunk that starts and ends inside one
    "a prefill chunk of 40 from position 23, 8 padded rows": dict(
        nkv=8, slots=lambda r: prefill_chunk(r, 64, 23, 40, pad=8)),
    "a chunk inside ONE tile": dict(
        nkv=4, slots=lambda r: prefill_chunk(r, 64, 3, 8)),
    # more rows than a row block, the last block not whole, a tile that
    # straddles two row blocks
    "a ragged round: 100 chunk rows, 12 lanes": dict(
        nkv=8, slots=lambda r: np.concatenate([
            prefill_chunk(r, 40, 7, 100),
            decode_rows(r, 64, 12, trash=[2, 5]) + 40 * BS])),
    # rows of one tile that are not neighbours in the round
    "two rows of a tile, apart": dict(
        nkv=4, slots=lambda r: np.asarray(
            [70, 200, 0, 75, 331, 64, 0, 79])),
    # a looped stack writes cache layer t * L + i
    "pass 2 of 4 over 3 layers: l = 7": dict(
        nkv=16, layers=12, l=7, slots=lambda r: decode_rows(r, 64, 16)),
    # float32 rows (8-slot tiles) in a cache whose slot count is no
    # multiple of a tile: the tiny engines of the CPU tests
    "float32, 36 slots": dict(
        nkv=2, dk=16, dtype=jnp.float32, n_slots=36,
        slots=lambda r: np.asarray([0, 3, 4, 35, 17, 0])),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_writes_what_the_scatters_write(case):
    c = {"layers": 3, "l": 1, "dk": 128, "dtype": jnp.bfloat16,
         "n_slots": 128 * BS, **CASES[case]}
    rng = np.random.default_rng(len(case))
    slots = jnp.asarray(c["slots"](rng), jnp.int32)
    n, nkv, dk = slots.shape[0], c["nkv"], c["dk"]
    keys = jax.random.split(jax.random.key(n), 4)

    def rand(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(c["dtype"])

    kc = rand(keys[0], c["layers"], nkv, c["n_slots"], c.get("store", dk))
    vc = rand(keys[1], c["layers"], nkv, c["n_slots"], 128 if dk > 16 else dk)
    k = rand(keys[2], n, nkv, dk)
    v = rand(keys[3], n, nkv, vc.shape[-1])
    args = (kc, vc, jnp.int32(c["l"]), slots, k, v)
    want = jax.jit(cache_write.write_kv)(*args)
    got = jax.jit(lambda *a: cache_write.write_kv(
        *a, kernel=True, interpret=True))(*args)
    live = np.asarray(slots)[np.asarray(slots) > 0]
    for name, w, g, before in zip("kv", want, got, (kc, vc)):
        w, g, before = (np.asarray(x.astype(jnp.float32))
                        for x in (w, g, before))
        assert np.array_equal(g[:, :, 1:], w[:, :, 1:]), name
        # the trash slot is as it was
        assert np.array_equal(g[:, :, 0], before[:, :, 0]), name
        # and the scatters did write the rows (the comparison is of
        # something): every live slot of layer l differs from before
        if live.size:
            assert (w[c["l"]][:, live] != before[c["l"]][:, live]).any(), name
    if dk < kc.shape[-1]:
        # the stored width's pad lanes are written as zeros
        assert not np.asarray(
            got[0][c["l"]][:, live, dk:].astype(jnp.float32)).any()


def test_a_forward_plans_its_rows_once():
    """`plan_rows` outside the layer loop and the slots themselves give
    the same caches, on both paths."""
    rng = np.random.default_rng(5)
    slots = jnp.asarray(prefill_chunk(rng, 16, 9, 24), jnp.int32)
    kc = jnp.zeros((2, 4, 16 * BS, 128), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(0), (24, 4, 128), jnp.bfloat16)
    plan = cache_write.plan_rows(slots, kc)
    assert plan.shape == (5, 24)
    for kw in (dict(), dict(kernel=True, interpret=True)):
        a = cache_write.write_kv(kc, kc, jnp.int32(1), slots, k, k, **kw)
        b = cache_write.write_kv(kc, kc, jnp.int32(1), plan, k, k, **kw)
        assert all(bool(jnp.array_equal(x, y)) for x, y in zip(a, b))


# -- the engine: the same tokens as with the scatters --------------------
CHAT = dict(
    tokenizer="byte", dtype="float32", cache_dtype="float32",
    block_size=4, num_kv_blocks=128, max_num_seqs=4, max_prefill_chunk=16,
    seed=3, attention_impl="pallas", num_scheduler_steps=4,
)


def chat(model):
    """Two requests, chunked prefill then fused decode rounds (the
    second shorter: a lane goes idle and writes the trash slot)."""
    e = LLMEngine(EngineConfig(model=model, **CHAT))
    assert e.runner.ragged_kernel
    rng = np.random.default_rng(7)
    vocab = e.runner.model_config.vocab_size
    prompts = [[int(x) for x in rng.integers(1, vocab - 4, n)]
               for n in (37, 9)]
    outs = [o.token_ids for o in e.generate(prompts, [
        SamplingParams(max_tokens=m, temperature=0.0, ignore_eos=True)
        for m in (11, 5)])]
    jax.block_until_ready((e.runner.k_cache, e.runner.v_cache))
    return outs, e.runner


@pytest.mark.parametrize("model", [
    "pst-tiny-debug", "pst-tiny-groups-debug", "pst-tiny-loop-debug"])
def test_the_engine_serves_the_same_tokens_as_with_the_scatters(
    model, monkeypatch
):
    """Greedy tokens of a dense, a layer-group and a looped tiny model
    over prefill and fused decode, with the Pallas walk in interpret
    mode: the tile kernel against the per-head scatters (the parent's
    path, `kernel=False` behind the same function), and the caches
    equal in every slot but slot 0."""
    traced = []
    real = cache_write._write_tiles

    def counting(*a):
        traced.append(a[4].shape)
        return real(*a)

    monkeypatch.setattr(cache_write, "_write_tiles", counting)
    got, runner = chat(model)
    assert traced, "the kernel path never traced the tile kernel"

    def leaves(r):
        return [x for x in jax.tree.leaves((r.k_cache, r.v_cache))
                if x.ndim == 4]

    got_caches = [np.asarray(x) for x in leaves(runner)]
    del runner
    monkeypatch.setattr(
        ModelRunner, "_write_kv",
        lambda self, *a: cache_write.write_kv(*a))
    n = len(traced)
    want, runner = chat(model)
    assert len(traced) == n, "the parent's path traced the tile kernel"
    assert got == want
    for g, w in zip(got_caches, leaves(runner), strict=True):
        assert np.array_equal(g[:, :, 1:], np.asarray(w)[:, :, 1:])
