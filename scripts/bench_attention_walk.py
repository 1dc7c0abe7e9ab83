"""Microbenchmark of the decode rows' attention walk on the chip: the
ragged kernel (`ops/pallas_attention.py`) alone, at the benchmark cells'
decode shapes, each lane walking its context alone against the lanes of
a row block walking the pages they share once (`shared`), against the
time of the bytes each must read. `chiprun -- python3
scripts/bench_attention_walk.py`; `--tiny` is the CPU rehearsal of its
control flow (no time from it means anything). `--parent FILE` also
times another copy of the kernel's module (the parent commit's, from a
`git archive`), which knows no shared run; given again, further copies
(variants of the kernel under study), each with `shared` None.

Each variant is one jitted program that calls the kernel for LAYERS
layers in a scan, REPEAT times; the time of a layer's call is the best
of five runs over LAYERS x REPEAT. Prints one JSON line a shape:
`alone_us` (every lane's walk of its own, `shared` None), `shared_us`
(the runs the runner would find in these tables), `parent_us`, the time
of the bytes at the chip's HBM rate for each (`*_bytes_us`), and whether
the two outputs are the same bits (`equal`). Where the lanes hold more
than one prefix, lane i holds prefix i % groups: the order of arrival,
in which no row block shares anything; `placed_us` is the same
sequences seated as the engine seats them (`model_runner.place_lanes`:
a prefix's lanes in row blocks of their own, from `seat_least` lanes
on), with the runs found there, and `placed_equal` says whether each
SEQUENCE got the bits its walk alone gives.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from production_stack_tpu.engine.model_runner import (  # noqa: E402
    place_lanes, seat_least, shared_runs,
)
from production_stack_tpu.ops import pallas_attention as pa  # noqa: E402

BS = 32
HBM_BYTES_PER_S = 819e9     # v5e


def lanes_case(rng, lanes, live, ctx_range, shared_keys, groups, pages):
    """Block tables of `lanes` decode lanes of which the lanes `live`
    hold a sequence: lane i's first `shared_keys // BS` pages are those
    of prefix i % groups, the rest its own. -> (tables, ctx, blocks)."""
    n_shared = shared_keys // BS
    tables = np.zeros((lanes, pages), np.int32)
    ctx = np.zeros((lanes,), np.int32)
    nxt = 1
    prefixes = []
    for _ in range(groups):
        prefixes.append(np.arange(nxt, nxt + n_shared))
        nxt += n_shared
    for i in live:
        ctx[i] = rng.integers(*ctx_range)
        own = -(-int(ctx[i]) // BS) - n_shared
        tables[i, :n_shared] = prefixes[i % groups]
        tables[i, n_shared:n_shared + own] = np.arange(nxt, nxt + own)
        nxt += own
    # scatter the pages over the cache as a block manager leaves them
    perm = np.concatenate([[0], 1 + rng.permutation(nxt - 1)])
    return perm[tables].astype(np.int32), ctx, nxt


def program(kernel, layers, repeat, static, with_shared):
    def run(q, kc, vc, tables, blk_seg, seg, sink, shared):
        def layer(acc, l):
            # the rows depend on what came before: nothing is hoisted
            qi = q + (acc[:, :, :1] * 0).astype(q.dtype)
            kw = {"shared": shared} if with_shared else {}
            out = kernel(qi, kc, vc, l, tables, blk_seg, seg, sink,
                         **kw, **static)
            return out.astype(jnp.float32), None

        def once(_, acc):
            return jax.lax.scan(layer, acc, jnp.arange(layers))[0]

        d_v = static.get("latent_v") or vc.shape[-1]
        acc0 = jnp.zeros((*q.shape[:2], d_v), jnp.float32)
        return jax.lax.fori_loop(0, repeat, once, acc0)

    return jax.jit(run)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--repeat", type=int, default=16)
    ap.add_argument("--parent", action="append", default=[],
                    help="another pallas_attention.py to time (the first "
                    "is `parent`, any more go by their file's name)")
    args = ap.parse_args()
    shapes = [
        # name, lanes, live lanes, nq, nkv, K stored, d_v (0: latent),
        # sink, contexts, shared keys, prefixes, table pages
        ("mistral batch-fewshot2k", 32, range(32), 32, 8, 128, 128, False,
         (2400, 3300), 2080, 1, 128),
        ("mimo batch-doc8k full", 64, range(64), 64, 4, 256, 128, True,
         (8400, 9900), 8288, 1, 512),
        ("mistral chat-sys2k", 32, (1, 6, 12, 19, 27), 32, 8, 128, 128,
         False, (2200, 4000), 2080, 4, 128),
        ("qwen2 chat-sys2k", 32, (1, 6, 12, 19, 27), 28, 4, 128, 128,
         False, (2200, 4000), 2080, 4, 128),
        ("ouro, 16 lanes one preamble", 16, range(16), 16, 16, 128, 128,
         False, (2200, 3400), 2080, 1, 128),
        ("xing4 latent, 8 lanes one document", 32, range(8), 32, 1, 640,
         0, False, (16600, 18500), 16512, 1, 1024),
        ("ouro reason-sys2k, 4 live, 2 preambles", 16, range(4), 16, 16,
         128, 128, False, (2300, 2900), 2080, 2, 128),
        ("ouro reason-sys2k, 3 live, 2 preambles", 16, range(3), 16, 16,
         128, 128, False, (2300, 2900), 2080, 2, 128),
        ("laguna full kind, 5 live, 4 documents", 32, range(5), 48, 8,
         128, 128, False, (16600, 18500), 16512, 4, 1024),
        ("xing4 latent, 5 live, 4 documents", 32, range(5), 32, 1, 640,
         0, False, (16600, 18500), 16512, 4, 1024),
        ("xing4 latent, 6 live, 2 documents", 32, range(6), 32, 1, 640,
         0, False, (16600, 18500), 16512, 2, 1024),
        ("xing4 latent, 4 live, 2 documents", 32, range(4), 32, 1, 640,
         0, False, (16600, 18500), 16512, 2, 1024),
    ]
    if args.tiny:
        args.layers, args.repeat = 2, 1
        shapes = [(n, 16 if g > 1 else 8, [j for j in live if j < 8],
                   nq // 4 or 1,
                   max(1, nkv // 4), dk, dv, sink, (600, 800), 512, g, 32)
                  for n, _, live, nq, nkv, dk, dv, sink, _, _, g, _
                  in shapes[:3] + shapes[6:7]]
    kernels = {"alone": (pa.ragged_paged_attention, False),
               "shared": (pa.ragged_paged_attention, True)}
    for i, path in enumerate(args.parent):
        name = os.path.splitext(os.path.basename(path))[0] if i else "parent"
        spec = importlib.util.spec_from_file_location(name + "_pa", path)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        kernels[name] = (other.ragged_paged_attention, False)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "layers": args.layers, "repeat": args.repeat}),
          flush=True)
    rng = np.random.default_rng(44)
    tq = pa.RAGGED_TQ
    for (name, lanes, live, nq, nkv, dk, dv, sink, ctx_range, shared_keys,
         groups, pages) in shapes:
        tables, ctx, blocks = lanes_case(
            rng, lanes, list(live), ctx_range, shared_keys, groups, pages)
        latent = 512 if not dv else None
        static = dict(block_size=BS, scale=dk ** -0.5,
                      interpret=dev.platform != "tpu")
        if latent:
            static["latent_v"] = latent if not args.tiny else 128
        keys = jax.random.split(jax.random.key(lanes + nkv), 4)
        q = jax.random.normal(keys[0], (lanes, nq, dk), jnp.bfloat16)
        kc = jax.random.normal(
            keys[1], (args.layers, nkv, blocks * BS, dk), jnp.bfloat16)
        vc = None if latent else jax.random.normal(
            keys[2], (args.layers, nkv, blocks * BS, dv), jnp.bfloat16)
        sk = jax.random.normal(keys[3], (nq,)) if sink else None
        n_blk = lanes // tq
        c = BS * pa._kv_block_pages(nkv, dk, 2, BS, 0 if latent else dv)
        token_bytes = nkv * (dk + dv) * 2
        lane_tokens = int(ctx.sum())

        def walk(q, tables, ctx):
            """The kernel's arguments for these lanes, and the tokens
            the walk streams with the runs the round's pack finds."""
            lane_ids = np.arange(lanes, dtype=np.int32)
            seg = np.stack([lane_ids, lane_ids % tq,
                            (ctx > 0).astype(np.int32), ctx - 1], axis=1)
            blk_seg = np.arange(n_blk + 1, dtype=np.int32) * tq
            runs = shared_runs(tables, ctx, BS)
            cut = runs[:, 0] // c * c
            n_live = (ctx.reshape(n_blk, tq) > 0).sum(axis=1)
            streamed = lane_tokens - int((cut * (n_live - 1)).sum())
            return (q, kc, vc, jnp.asarray(tables), jnp.asarray(blk_seg),
                    jnp.asarray(seg), sk, jnp.asarray(runs)), streamed

        def bytes_us(tokens):
            return round(tokens * token_bytes / HBM_BYTES_PER_S * 1e6, 1)

        at = np.flatnonzero(ctx > 0)     # the sequences, as they arrived
        ins, streamed = walk(q, tables, ctx)
        row = {"shape": name, "lanes": lanes, "live": len(at),
               "nkv": nkv, "g": nq // nkv, "kv_block": c,
               "lane_tokens": lane_tokens, "streamed_tokens": streamed,
               "alone_bytes_us": bytes_us(lane_tokens),
               "shared_bytes_us": bytes_us(streamed)}
        # variant: (kernel, takes a run, its arguments, its sequences' lanes)
        variants = {v: (*k, ins, at) for v, k in kernels.items()}
        if groups > 1:
            seat = place_lanes(tables[at, 0].tolist(), lanes,
                               seat_least(bool(latent)))
            tables_p, ctx_p = np.zeros_like(tables), np.zeros_like(ctx)
            tables_p[seat], ctx_p[seat] = tables[at], ctx[at]
            q_p = jnp.zeros_like(q).at[seat].set(q[at])
            ins_p, streamed_p = walk(q_p, tables_p, ctx_p)
            row["placed_streamed_tokens"] = streamed_p
            row["placed_bytes_us"] = bytes_us(streamed_p)
            variants["placed"] = (pa.ragged_paged_attention, True, ins_p,
                                  seat)
        got = {}
        for vname, (kernel, with_shared, v_ins, v_at) in variants.items():
            try:
                fn = program(kernel, args.layers, args.repeat, static,
                             with_shared).lower(*v_ins).compile()
                out = jax.block_until_ready(fn(*v_ins))
                best = float("inf")
                for _ in range(5):
                    t0 = time.perf_counter()
                    out = jax.block_until_ready(fn(*v_ins))
                    best = min(best, time.perf_counter() - t0)
                row[f"{vname}_us"] = round(
                    best / (args.layers * args.repeat) * 1e6, 1)
                got[vname] = np.asarray(out)[v_at]
            except Exception as e:  # noqa: BLE001 — report, go on
                row[f"{vname}_error"] = repr(e)[:300]
        for vname in got:
            if vname != "alone" and "alone" in got:
                row[f"{vname}_equal"] = bool(
                    np.array_equal(got[vname], got["alone"]))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
