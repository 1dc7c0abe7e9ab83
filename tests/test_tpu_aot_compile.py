"""Compile-only TPU v5e: real XLA:TPU + Mosaic without a chip.

libtpu can describe a v5e topology on a machine that has none, and
`jax.jit(f).lower(*ShapeDtypeStructs placed on its devices).compile()`
then runs the same compilers a chip run would. Interpret-mode parity
tests cannot see a Mosaic lowering error; these can — the pre-flight for
any kernel or step-program change before chip time is spent on it.

Shapes are llama-3.2-3b's (24 q / 8 kv heads, head_dim 128, block 32),
the model `chip_smoke.py` serves on one chip.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from production_stack_tpu.ops import pallas_attention as pa

BS = 32
NQ, NKV, D = 24, 8, 128


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one device of a compile-only v5e:2x2 topology."""
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"compile-only TPU topology unavailable: {e!r}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cache(sh, d=D, nkv=NKV):
    # (L, nkv, slots, d): stays in HBM, so its size does not matter
    return _spec(sh, (2, nkv, 64 * BS, d), jnp.bfloat16)


SMEM_BYTES = 2**20  # scalar prefetch: test_block_tables_are_bounded_by_smem


def _compile_decode(sh, *, nq=NQ, d=D, lanes=16, pages=32):
    fn = functools.partial(
        pa.paged_decode_attention, block_size=BS, scale=d**-0.5
    )
    return jax.jit(fn).lower(
        _spec(sh, (lanes, nq, d), jnp.bfloat16), _cache(sh, d),
        _cache(sh, d), _spec(sh, (), jnp.int32),
        _spec(sh, (lanes, pages), jnp.int32),
        _spec(sh, (lanes,), jnp.int32),
    ).compile()


def _compile_ragged(sh, *, nq=NQ, nkv=NKV, d=D, rows=128, lanes=16,
                    pages=32, window=None, d_v=None, sink=False,
                    latent_v=None):
    fn = functools.partial(
        pa.ragged_paged_attention, block_size=BS, scale=d**-0.5,
        window=window, latent_v=latent_v,
    )
    blocks = rows // pa.RAGGED_TQ
    scalars = [
        _spec(sh, (), jnp.int32),
        _spec(sh, (lanes, pages), jnp.int32),
        _spec(sh, (blocks + 1,), jnp.int32),
        _spec(sh, (blocks + lanes, 4), jnp.int32),
    ]
    args = [
        _spec(sh, (rows, nq, d), jnp.bfloat16), _cache(sh, d, nkv),
        None if latent_v else _cache(sh, d_v or d, nkv), *scalars,
    ]
    if sink:
        args.append(_spec(sh, (nq,), jnp.float32))
    jax.jit(fn).lower(*args).compile()
    # what rides scalar-prefetch SMEM: the layer, the tables, the CSR
    # offsets and the segment list
    return sum(4 * math.prod(a.shape) for a in scalars)


def test_decode_kernel_compiles(v5e):
    _compile_decode(v5e)


def test_ragged_kernel_compiles(v5e):
    _compile_ragged(v5e)


@pytest.mark.parametrize("nq,nkv,window", [
    (32, 8, None),   # mistral-7b: group 4, a KV block of 128 keys
    (28, 4, None),   # qwen2-7b: group 7, 256 keys
    (8, 2, None),    # mistral-7b under tp=4, per chip: 512 keys
    (32, 8, 4096),   # a sliding window starts its walk inside a block
])
def test_ragged_kernel_compiles_at_benchmark_widths(v5e, nq, nkv, window):
    """The one-row and the fused tile height of the walk, at the head
    counts the benchmark's configurations give it and at the KV block
    `_kv_block_pages` picks for each."""
    _compile_ragged(v5e, nq=nq, nkv=nkv, window=window)


@pytest.mark.parametrize("cell, shape", [
    # a step program's decode rows (lanes rows) and a lane-typed round's
    # [prefill rows | decode rows] at the widest prefill bucket, lanes x
    # the cell's one context bucket of 32-token pages
    ("mistral-7b-l16.chat-sys2k + .batch-fewshot2k",
     dict(nq=32, nkv=8, lanes=32, pages=128)),
    ("qwen2-7b-l14.chat-sys2k", dict(nq=28, nkv=4, lanes=32, pages=128)),
    ("mimo-v2.5-ep16-l7.batch-doc8k, window layers",
     dict(nq=64, nkv=8, lanes=64, pages=512, d=256, d_v=128, sink=True,
          window=128)),
    ("mimo-v2.5-ep16-l7.batch-doc8k, full layers",
     dict(nq=64, nkv=4, lanes=64, pages=512, d=256, d_v=128, sink=True)),
    # the latent kind: one row of 640 stored lanes a token, read as the
    # key and (its first 512 lanes) as the value by 32 heads, no V cache
    ("xing4-29b-l8.chat-doc16k",
     dict(nq=32, nkv=1, lanes=32, pages=1024, d=640, latent_v=512)),
    # plain multi-head attention: 16 kv heads with ONE query row each
    # (group 1, padded to the 8-row tile), where `_kv_block_pages` is at
    # its floor of 128 keys and the ring takes 3 MiB of VMEM
    ("ouro-2.6b-l12.reason-sys2k",
     dict(nq=16, nkv=16, lanes=16, pages=128)),
    # ONE program, two query widths over 8 kv heads: groups of 6 (not a
    # power of two, padded to the 8-row tile in a one-row segment) and
    # of 8; the window kind walks four 128-key KV blocks
    ("laguna-xs.2-l5.chat-doc16k, full layers",
     dict(nq=48, nkv=8, lanes=32, pages=1024)),
    ("laguna-xs.2-l5.chat-doc16k, window layers",
     dict(nq=64, nkv=8, lanes=32, pages=1024, window=512)),
])
@pytest.mark.parametrize("prefill_rows", [0, 512])
def test_walk_compiles_at_the_cells_shapes(v5e, cell, shape, prefill_rows):
    """The kernel as PR 29 left it (a zero-row segment walks nothing
    and stores a zero row) compiles for a v5e at every cell's decode
    shape — 32 lanes x 4,096 at 8 and 4 kv heads; 64 lanes x 16,384
    with K stored at 256 lanes beside V at 128, a sink, the window of
    128; 32 lanes x 32,768 of the latent kind; 16 lanes x 4,096 at 16
    kv heads of group 1 — and what it prefetches
    to SMEM stays under the 1 MiB bound."""
    lanes = shape["lanes"]
    shape = {**shape, "rows": prefill_rows + lanes,
             "lanes": lanes + (8 if prefill_rows else 0)}
    smem = _compile_ragged(v5e, **shape)
    assert smem < SMEM_BYTES, (cell, smem)


@pytest.mark.parametrize("cell, shape", [
    # kv heads, lanes, K as stored, V: the decode rows of a step program
    ("mistral-7b-l16.chat-sys2k + .batch-fewshot2k",
     dict(nkv=8, lanes=32)),
    ("qwen2-7b-l14.chat-sys2k", dict(nkv=4, lanes=32)),
    ("mimo-v2.5-ep16-l7.batch-doc8k, window layers",
     dict(nkv=8, lanes=64, d=256, d_v=128)),
    ("mimo-v2.5-ep16-l7.batch-doc8k, full layers",
     dict(nkv=4, lanes=64, d=256, d_v=128)),
    # a row block's 16 tiles of 16 heads are 1 MiB an array in VMEM
    ("ouro-2.6b-l12.reason-sys2k", dict(nkv=16, lanes=16)),
    # both cache groups: 8 kv heads x 128, whatever the query heads
    ("laguna-xs.2-l5.chat-doc16k", dict(nkv=8, lanes=32)),
    # (xing4-29b-l8.chat-doc16k: a latent kind's one row a layer stays
    # one XLA scatter, layer_groups._latent_qkv)
])
@pytest.mark.parametrize("prefill_rows", [0, 512])
def test_cache_write_compiles_at_the_cells_shapes(
    v5e, cell, shape, prefill_rows
):
    """The tile kernel of ops/cache_write.py compiles for a v5e at
    every cell's decode rows and at a lane-typed round's [prefill rows |
    decode rows] (544 and 576: more than a row block, 528 a last block
    that is not whole), with the caches donated and nothing of their
    size among the temps (the kernel aliases them to its outputs)."""
    from production_stack_tpu.ops import cache_write

    nkv, d = shape["nkv"], shape.get("d", D)
    rows = prefill_rows + shape["lanes"]
    kc, vc = _cache(v5e, d, nkv), _cache(v5e, shape.get("d_v", d), nkv)

    def write(kc, vc, l, slots, k, v):
        return cache_write.write_kv(kc, vc, l, slots, k, v, kernel=True)

    compiled = jax.jit(write, donate_argnums=(0, 1)).lower(
        kc, vc, _spec(v5e, (), jnp.int32), _spec(v5e, (rows,), jnp.int32),
        # mimo's K comes 192 wide and is padded to the stored 256
        _spec(v5e, (rows, nkv, 192 if d == 256 else d), jnp.bfloat16),
        _spec(v5e, (rows, nkv, vc.shape[-1]), jnp.bfloat16),
    ).compile()
    assert "kv_cache_write" in compiled.as_text()
    one_cache = math.prod(vc.shape) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_cache / 4


@pytest.mark.parametrize("rows", [32, 40, 544])
def test_sinkhorn_kernel_compiles(v5e, rows):
    """The hyper-connections' Sinkhorn iterations as one Mosaic kernel
    (`ops/sinkhorn.py`) over a (4, 4, rows) block: the decode lanes'
    rows, a lane-typed round's, two prefill chunks beside the lanes."""
    from production_stack_tpu.ops import sinkhorn as sk

    fn = functools.partial(sk.sinkhorn, iters=20, eps=1e-6, interpret=False)
    jax.jit(fn).lower(_spec(v5e, (4, 4, rows), jnp.float32)).compile()


@pytest.mark.parametrize("cell, stack, e_loc, d, f, rows", [
    # the scanned run's stack, experts held, widths, the pairs of a pass
    ("xing4-29b-l8.chat-doc16k, decode", 6, 64, 3584, 1024, 128),
    ("xing4-29b-l8.chat-doc16k, a prefill round's pass", 6, 64, 3584,
     1024, 512),
    ("mimo-v2.5-ep16-l7.batch-doc8k, the window run", 5, 16, 4096, 2048,
     256),
    ("mimo-v2.5-ep16-l7.batch-doc8k, the full layer", 1, 16, 4096, 2048,
     256),
    ("a few lanes", 6, 64, 3584, 1024, 32),
    # every one of 256 experts held: 768 groups in the window run's
    # stack, a 256 x 256 packing mask, one f tile an expert
    ("laguna-xs.2-l5.chat-doc16k, decode, the window run", 3, 256, 2048,
     512, 256),
    ("laguna-xs.2-l5.chat-doc16k, decode, the full layer", 1, 256, 2048,
     512, 256),
    ("laguna-xs.2-l5.chat-doc16k, a prefill chunk's pass", 3, 256, 2048,
     512, 512),
])
def test_expert_ffn_compiles_at_the_cells_shapes(
    v5e, cell, stack, e_loc, d, f, rows
):
    """The routed experts' one kernel (`ops/expert_ffn.py`) compiles for
    a v5e over the WHOLE stacks of a scanned run, nothing of an expert's
    size among the temps (no slice of a stack is copied), and the
    operation's text names a whole stack in its first 400 characters:
    what `benchmarks/chip/layer_metrics/moe_expert_*.json` find it by."""
    from production_stack_tpu.ops import expert_ffn as ef

    fn = functools.partial(ef.expert_ffn, interpret=False)
    n = stack * e_loc
    compiled = jax.jit(fn).lower(
        _spec(v5e, (rows, d), jnp.bfloat16),
        _spec(v5e, (n, d, f), jnp.bfloat16),
        _spec(v5e, (n, d, f), jnp.bfloat16),
        _spec(v5e, (n, f, d), jnp.bfloat16),
        _spec(v5e, (e_loc,), jnp.int32), _spec(v5e, (), jnp.int32),
        _spec(v5e, (), jnp.int32),
    ).compile()
    call = [line for line in compiled.as_text().splitlines()
            if "custom-call(" in line and "expert_ffn" in line]
    assert len(call) == 1
    assert re.search(rf"bf16\[{n},({d},{f}|{f},{d})\]", call[0][:400])
    assert compiled.memory_analysis().temp_size_in_bytes < d * f * 2


def test_the_ungated_expert_kernel_and_the_state_update_compile(v5e):
    """What `nemotron3-super-ep4-l11.chat-sys2k` adds to the chip's
    kernels, at its shapes: `expert_ffn` without a gate matrix over the
    whole stacks of the "EM" run (640 groups of 1,024 x 2,688, an f tile
    of 896), and the decode lanes' state update in place
    (`ops/ssm.state_update`: 32 lanes of 128 x 64 x 128 float32, packed
    two heads a row, in a pool of 129 slots a layer, nothing of a
    state's size among the temps)."""
    from production_stack_tpu.ops import expert_ffn as ef
    from production_stack_tpu.ops import ssm

    n, d, f, rows = 5 * 128, 1024, 2688, 512
    compiled = jax.jit(functools.partial(
        ef.expert_ffn, interpret=False, act="relu2")).lower(
        _spec(v5e, (rows, d), jnp.bfloat16), None,
        _spec(v5e, (n, d, f), jnp.bfloat16),
        _spec(v5e, (n, f, d), jnp.bfloat16),
        _spec(v5e, (128,), jnp.int32), _spec(v5e, (), jnp.int32),
        _spec(v5e, (), jnp.int32),
    ).compile()
    call = [line for line in compiled.as_text().splitlines()
            if "custom-call(" in line and "expert_ffn" in line]
    assert len(call) == 1
    assert re.search(rf"bf16\[{n},({d},{f}|{f},{d})\]", call[0][:400])
    assert compiled.memory_analysis().temp_size_in_bytes < d * f * 2

    layers, slots, r, h, p, ns, g = 5, 129, 32, 128, 64, 128, 8
    compiled = jax.jit(ssm.state_update, donate_argnums=(0,)).lower(
        _spec(v5e, (layers, slots, h // 2, ns, 2 * p), jnp.float32),
        _spec(v5e, (), jnp.int32), _spec(v5e, (r,), jnp.int32),
        _spec(v5e, (r,), jnp.int32), _spec(v5e, (r,), jnp.bool_),
        _spec(v5e, (r, h, p), jnp.bfloat16), _spec(v5e, (r, h), jnp.float32),
        _spec(v5e, (h,), jnp.float32), _spec(v5e, (r, g, ns), jnp.bfloat16),
        _spec(v5e, (r, g, ns), jnp.bfloat16),
    ).compile()
    assert "ssm_state_update" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < h * p * ns * 4


def test_the_delta_rule_update_and_its_chunked_form_compile(v5e):
    """What `kimi-linear-ep2-l5.chat-doc16k` adds to the chip's kernels,
    at its shapes: the decode lanes' delta-rule update in place
    (`ops/kda.state_update`: 32 lanes of 32 x 128 x 128 float32, keys on
    the sublanes, in a pool of 129 slots a layer) and the chunked form
    over two prefill lanes of 256 rows at the configuration's chunk."""
    from production_stack_tpu.ops import kda

    layers, slots, r, h, kd, vd, rows = 4, 129, 32, 32, 128, 128, 256
    f32 = jnp.float32
    compiled = jax.jit(kda.state_update, donate_argnums=(0,)).lower(
        _spec(v5e, (layers, slots, h, kd, vd), f32),
        _spec(v5e, (), jnp.int32), _spec(v5e, (r,), jnp.int32),
        _spec(v5e, (r,), jnp.int32), _spec(v5e, (r,), jnp.bool_),
        _spec(v5e, (r, h, kd), f32), _spec(v5e, (r, h, kd), f32),
        _spec(v5e, (r, h, vd), jnp.bfloat16), _spec(v5e, (r, h, kd), f32),
        _spec(v5e, (r, h), f32),
    ).compile()
    call = [line for line in compiled.as_text().splitlines()
            if "custom-call(" in line and "kda_state_update" in line]
    assert len(call) == 1
    assert f"f32[{layers},{slots},{h},{kd},{vd}]" in call[0][:400]
    chunked = jax.vmap(functools.partial(kda.scan_chunked, chunk=16))
    jax.jit(chunked).lower(
        _spec(v5e, (2, rows, h, kd), f32), _spec(v5e, (2, rows, h, kd), f32),
        _spec(v5e, (2, rows, h, vd), jnp.bfloat16),
        _spec(v5e, (2, rows, h, kd), f32), _spec(v5e, (2, rows, h), f32),
        _spec(v5e, (2, h, kd, vd), f32),
    ).compile()


@pytest.mark.slow
def test_prefill_kernel_compiles(v5e):
    fn = functools.partial(
        pa.paged_prefill_attention, block_size=BS, scale=D**-0.5
    )
    jax.jit(fn).lower(
        _spec(v5e, (512, NQ, D), jnp.bfloat16), _cache(v5e), _cache(v5e),
        _spec(v5e, (), jnp.int32), _spec(v5e, (32,), jnp.int32),
        _spec(v5e, (), jnp.int32),
    ).compile()


@pytest.mark.parametrize("compile_kernel", [_compile_decode, _compile_ragged])
def test_head_dim_64_is_refused_by_mosaic(v5e, compile_kernel):
    """The hardware rule behind ModelRunner's head_dim % 128 check: a
    page slice of a 64-wide cache is a partial (8, 128) tile."""
    with pytest.raises(Exception, match=r"aligned to tiling \(128\)"):
        compile_kernel(v5e, nq=32, d=64)


def test_block_tables_are_bounded_by_smem(v5e):
    """Scalar-prefetch SMEM is 1 MiB: lanes x ctx-bucket pages x 4 B
    must stay under it, which bounds --max-num-seqs x context."""
    _compile_decode(v5e, lanes=16, pages=4096)  # 256 KiB: fits
    with pytest.raises(Exception, match="memory space smem"):
        _compile_decode(v5e, lanes=64, pages=4096)  # 1 MiB: refused


def _computations(text):
    """{name: lines} of an optimised HLO module's computations."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return comps


def _topk_branch(text):
    """The body of the one computation of an optimised HLO module that
    holds the `TopK` custom call, which has to be a branch computation of
    a `conditional`: XLA:TPU kept the sampler's `lax.cond` as control
    flow and did not make it a `select` of both sides."""
    comps = _computations(text)
    branches = {
        n.strip().lstrip("%")
        for listed in re.findall(
            r" conditional\(.*branch_computations=\{([^}]*)\}", text)
        for n in listed.split(",")}
    with_topk = [name for name, body in comps.items()
                 if any('custom_call_target="TopK"' in line for line in body)]
    assert len(with_topk) == 1 and with_topk[0] in branches, (
        with_topk, branches)
    return "\n".join(comps[with_topk[0]])


def test_sampler_window_stays_a_branch_on_the_chip(v5e):
    """`sample_tokens` at qwen2's vocabulary and the chat cells' lanes:
    the sort of the vocabulary — the `TopK` custom call,
    `custom-call.*_f32_32_64_` in a trace — is in a branch computation,
    so a round of greedy rows does not run it."""
    from production_stack_tpu.engine.sampler import TOP_CAP, sample_tokens

    b, vocab = 32, 152064
    text = sample_tokens.lower(
        _spec(v5e, (b, vocab), jnp.float32), _spec(v5e, (b,), jnp.float32),
        _spec(v5e, (b,), jnp.float32), _spec(v5e, (b,), jnp.int32),
        _spec(v5e, (b, 2), jnp.uint32), min_p=_spec(v5e, (b,), jnp.float32),
    ).compile().as_text()
    assert f"f32[{b},{TOP_CAP}]" in _topk_branch(text)


def _bench_kda():
    """`scripts/bench_kda.py` as a module: a cell's runner for a
    described chip and its fused decode round's program."""
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_kda_for_aot", os.path.join(root, "scripts", "bench_kda.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_the_decode_round_of_the_kimi_cell_compiles_clean(
        v5e, monkeypatch):
    """The fused decode round's program of `kimi-linear-ep2-l5.chat-
    doc16k` as the engine builds it for the chip (32 lanes, 8 steps,
    the 32k context bucket, the ten blocks under the one scan), through
    `scripts/bench_kda.py --step --describe`'s own functions: the text
    holds no prefetch of the latent block's `wo` in the scan's turn and
    no copy of the KDA in-projections' stack (PR 52 took both off: the
    kinds that stand once first in a turn, a stack handed over as the
    chip holds it), no copy of the state pool or of the latent cache
    (each of which one of PR 51's forms cost a chip run to find), each
    kind's body once, and the update kernel writes the pool in place."""
    bench = _bench_kda()
    runner, _, _ = bench.cell_runner(
        one_chip=v5e, as_chip=lambda: monkeypatch.setattr(
            jax, "default_backend", lambda: "tpu"))
    compiled, _ = bench.decode_program(runner, 32768, v5e)
    counts = bench.text_counts(compiled.as_text(), runner)
    headers = {**counts.pop("whiles"), **counts.pop("conditionals")}
    # the scan over the pattern; in its turn a loop of one turn or none
    # for each of the two kinds that stand four times, a branch for
    # each of the two that stand once
    assert headers["layers/while"] == 1
    assert headers["layers/while/body/closed_call/while"] == 2
    assert headers["layers/while/body/closed_call/cond"] == 2
    assert counts == {
        "wo_copy_start": 0, "copy_of_in_proj_stack": 0,
        "copy_of_state_pool": 0, "copy_of_latent_cache": 0,
        "kda_state_update_calls_aliased": [1, 1], "expert_ffn_calls": 1}
    # the in-projections' 232 MB are not among the temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 128 * 2**20


def _under_a_loop(text):
    """The computations of an optimised HLO module that run inside some
    `while`: the loops' bodies and whatever those call."""
    comps = _computations(text)
    called = {
        name: set(re.findall(
            r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
            "\n".join(body))) | {
            n.strip().lstrip("%")
            for listed in re.findall(
                r"branch_computations=\{([^}]*)\}", "\n".join(body))
            for n in listed.split(",")}
        for name, body in comps.items()}
    inside, todo = set(), re.findall(r" while\(.*body=%([\w.\-]+)", text)
    assert todo, "the fused round is a loop"
    while todo:
        name = todo.pop()
        if name not in inside:
            inside.add(name)
            todo += called.get(name, ())
    return {name: comps[name] for name in inside}


@pytest.mark.parametrize("cell,lanes,pages", [
    ("laguna-xs.2-l5.chat-doc16k", 32, 1024),
    ("mimo-v2.5-ep16-l7.batch-doc8k", 64, 512),
])
def test_a_windowed_kinds_tables_are_mapped_once_a_decode_round(
        v5e, monkeypatch, cell, lanes, pages):
    """The fused decode round's program of a cell with a windowed cache
    group as the engine builds it for the chip (its lanes, its context
    bucket's pages, 8 steps): the lanes' whole page tables go through
    the block map in ONE gather, where the round unpacks its constants,
    and no loop's body holds one. Inside the attention call it stood
    in the round's loop, once a step: laguna's `fusion.600 s32[32768]`,
    4.3% of the device's busy time (ledger, PR 52)."""
    bench = _bench_kda()
    runner, _, _ = bench.cell_runner(
        cell=cell, one_chip=v5e, as_chip=lambda: monkeypatch.setattr(
            jax, "default_backend", lambda: "tpu"))
    assert runner.config.max_num_seqs == lanes
    compiled, _ = bench.decode_program(runner, pages * BS, v5e)
    text = compiled.as_text()
    mapped = re.compile(rf"= s32\[{lanes},{pages}\]\S* gather\(")
    assert len(mapped.findall(text)) == 1
    assert not [name for name, body in _under_a_loop(text).items()
                if any(mapped.search(line) for line in body)]


@pytest.mark.slow
@pytest.mark.parametrize("looped", [False, True])
def test_decode_multi_step_compiles_at_full_width_and_depth(
    v5e, monkeypatch, looped
):
    """One whole fused-K decode program of llama-3.2-3b, as the engine
    builds it for the chip: Mosaic kernels (not interpret), pinned cache
    layout, donated caches. No full-cache copy may appear in its temps.
    `looped`: the same for a looped stack of 16 kv heads (ouro-2.6b's
    widths, 12 layers run 4 times a token over 48 cache layers), whose
    layers write the cache through the tile kernel of
    ops/cache_write.py as every model's do."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.model_runner import ModelRunner
    from production_stack_tpu.models import config as mcfg
    from production_stack_tpu.models import llama

    mc = dataclasses.replace(
        mcfg.get_model_config("llama-3.2-3b"), name="llama-3.2-3b-aot"
    )
    if looped:
        mc = dataclasses.replace(
            mc, name="looped-16-heads-aot", hidden_size=2048,
            intermediate_size=5632, num_heads=16, num_kv_heads=16,
            num_layers=12, vocab_size=49152, tie_word_embeddings=False,
            ut_steps=4, sandwich_norm=True, exit_gate=True,
        )
    monkeypatch.setitem(mcfg._PRESETS, mc.name, mc)
    abstract = jax.eval_shape(
        lambda: llama.init_params(mc, jax.random.key(0), jnp.bfloat16)
    )
    runner = ModelRunner(
        EngineConfig(
            model=mc.name, tokenizer="byte", max_model_len=8192,
            max_num_seqs=16, num_scheduler_steps=8, num_kv_blocks=64,
            attention_impl="pallas",
        ),
        params=abstract,
    )
    # the step builders read the backend at trace time (interpret mode,
    # layout pin): trace them as the chip would
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, c_pad, k = 16, 1024, 8
    step = runner._make_decode_multi_step(b, c_pad, k)
    _, packed_len = runner._decode_pack_layout(b, c_pad, False)

    def on_chip(x):
        return _spec(v5e, x.shape, x.dtype)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        jax.tree.map(on_chip, abstract), on_chip(runner.k_cache),
        on_chip(runner.v_cache), _spec(v5e, (packed_len,), jnp.int32),
    ).compile()
    cache_bytes = runner.k_cache.size * runner.k_cache.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"temp_size_in_bytes {temp} of a cache array's {cache_bytes}")
    assert temp < cache_bytes
    text = compiled.as_text()
    # one write kernel a layer body, and no scatter into a cache array
    assert "kv_cache_write" in text
    shape = "bf16[" + ",".join(map(str, runner.k_cache.shape)) + "]"
    assert not re.search(re.escape(shape) + r"\S* fusion\(", text)
    # the sampler's window is a branch inside the scan's body too
    _topk_branch(text)
