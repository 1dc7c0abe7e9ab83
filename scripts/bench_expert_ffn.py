"""Microbenchmark of the routed experts' SwiGLU on the chip: the three
`jax.lax.ragged_dot`s over the whole stack (the form before PR 42, and
`ops/expert_ffn._plain` off the TPU), megablox's `gmm` three times, and
the `expert_ffn` kernel, at the three routed cells' shapes (`--only
laguna` for one model's), against the
floor of the active experts' bytes over the chip's HBM bandwidth.
`chiprun -- python3 scripts/bench_expert_ffn.py`; `--tiny` is the CPU
rehearsal of its control flow (no time from it means anything).

Each variant is one jitted program that runs the experts of every layer
of the stack in a scan (the layer's offset into the stack a traced
scalar, as in a step program), REPEAT times; the time of a layer is the
best of five calls over LAYERS x REPEAT. Rows: `live` rows of the batch
each choose `top_k` distinct experts at random, and the pairs are sorted
by expert, as `moe.routed_experts` hands them over; more pairs than
`expert_ffn.MAX_ROWS` go through the kernel in passes, as there. Prints
one JSON line a shape and appends it to chiprun_out/bench_expert_ffn.jsonl.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from production_stack_tpu.ops import expert_ffn as ef  # noqa: E402

HBM_BYTES_PER_S = 819e9  # benchmarks/chip/peaks.json, TPU v5 lite


def ragged_dots(xs, wg, wu, wd, sizes, base):
    return ef._plain(xs, wg, wu, wd, sizes, 0, base)


def megablox(xs, wg, wu, wd, sizes, base):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, d = xs.shape
    f = wg.shape[2]
    group = jax.lax.dynamic_update_slice(
        jnp.zeros((wg.shape[0],), jnp.int32), sizes, (base,))
    tm = min(m, 128)
    interpret = jax.default_backend() != "tpu"
    g = gmm(xs, wg, group, jnp.float32, (tm, d, min(f, 256)),
            interpret=interpret)
    u = gmm(xs, wu, group, jnp.float32, (tm, d, min(f, 256)),
            interpret=interpret)
    a = (jax.nn.silu(g) * u).astype(xs.dtype)
    y = gmm(a, wd, group, jnp.float32, (tm, min(f, 512), min(d, 512)),
            interpret=interpret)
    # gmm leaves the rows past the last group as it found them
    return jnp.where(jnp.arange(m)[:, None] < jnp.sum(sizes), y, 0.0)


def kernel(xs, wg, wu, wd, sizes, base):
    """As `moe.routed_experts` walks its sorted pairs: MAX_ROWS at a
    time, the last pass pulled back to fit."""
    pairs = xs.shape[0]
    m = min(pairs, ef.MAX_ROWS)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    interpret = True if jax.default_backend() != "tpu" else None
    out = []
    for p0 in range(0, pairs, m):
        lo = min(p0, pairs - m)
        skip = p0 - lo
        group = jnp.clip(ends - p0, 0, m - skip) - jnp.clip(
            starts - p0, 0, m - skip)
        y = ef.expert_ffn(xs[lo:lo + m], wg, wu, wd, group, skip, base,
                          interpret=interpret)
        out.append(y[skip:])
    return jnp.concatenate(out) if len(out) > 1 else out[0]


VARIANTS = {"ragged_dot": ragged_dots, "gmm": megablox, "kernel": kernel}


def program(fn, layers, e_loc, repeat):
    def run(xs, wg, wu, wd, sizes):
        def layer(acc, c):
            # the rows hang on what came before: nothing to hoist out
            # of the loops
            rows = xs + (acc[:1, :1] * 1e-30).astype(xs.dtype)
            return acc + fn(rows, wg, wu, wd, sizes, c * e_loc), None

        def once(_, acc):
            return jax.lax.scan(layer, acc, jnp.arange(layers))[0]

        return jax.lax.fori_loop(
            0, repeat, once, jnp.zeros(xs.shape, jnp.float32))

    return jax.jit(run)


def sizes_for(rng, live, top_k, e_all, e_loc):
    """Pairs that fall on the first `e_loc` of `e_all` experts when
    `live` rows each choose `top_k` distinct ones."""
    counts = np.zeros(e_all, np.int32)
    for _ in range(live):
        counts[rng.choice(e_all, top_k, replace=False)] += 1
    return counts[:e_loc]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--repeat", type=int, default=8)
    ap.add_argument("--variants", default="ragged_dot,gmm,kernel")
    ap.add_argument("--f-tiles", default="",
                    help="comma list: time the kernel at each f tile")
    ap.add_argument("--row-tiles", default="",
                    help="comma list: time the kernel at each row tile")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--only", default="",
                    help="time the shapes whose name holds this")
    args = ap.parse_args()
    shapes = [
        # name, layers, E_loc, E_all, top_k, d, f, pairs walked, live rows
        ("xing4 decode, 3 live", 6, 64, 64, 4, 3584, 1024, 128, 3),
        ("xing4 decode, 10 live", 6, 64, 64, 4, 3584, 1024, 128, 10),
        ("xing4 decode, 32 live", 6, 64, 64, 4, 3584, 1024, 128, 32),
        ("xing4 ragged 256+32", 6, 64, 64, 4, 3584, 1024, 1152, 288),
        ("xing4 ragged 512+32", 6, 64, 64, 4, 3584, 1024, 2176, 544),
        # the cell's routing sends a rank ~7 rows an expert (E_all 64
        # here gives 8); 256 of the 512 pairs are walked (moe.py's m)
        ("mimo decode, stack of 5", 5, 16, 64, 8, 4096, 2048, 256, 64),
        ("mimo decode, stack of 1", 1, 16, 64, 8, 4096, 2048, 256, 64),
        ("mimo decode, 8 live", 5, 16, 64, 8, 4096, 2048, 256, 8),
        # every one of 256 experts held, width 512 at hidden 2,048: a
        # decode step walks its 32 lanes' 256 pairs, a 256-row prefill
        # chunk's 2,048 pairs in four passes (PR 43)
        ("laguna decode, 6 live", 3, 256, 256, 8, 2048, 512, 256, 6),
        ("laguna decode, 6 live, stack of 1", 1, 256, 256, 8, 2048, 512,
         256, 6),
        ("laguna decode, 32 live", 3, 256, 256, 8, 2048, 512, 256, 32),
        ("laguna prefill 256", 3, 256, 256, 8, 2048, 512, 2048, 256),
        ("laguna ragged 256+6", 3, 256, 256, 8, 2048, 512, 2304, 262),
    ]
    shapes = [s for s in shapes if args.only in s[0]]
    if args.tiny:
        args.repeat = 1
        shapes = [("tiny decode", 2, 4, 4, 2, 256, 256, 32, 3),
                  ("tiny passes", 2, 4, 8, 2, 256, 256, 1056, 900)]
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "repeat": args.repeat, "seed": args.seed}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    rng = np.random.default_rng(args.seed)
    f_tiles = [int(t) for t in args.f_tiles.split(",") if t]
    row_tiles = [int(t) for t in args.row_tiles.split(",") if t]
    defaults = ef._F_TILES, ef._ROW_TILE
    for name, layers, e_loc, e_all, k, d, f, pairs, live in shapes:
        sizes_np = sizes_for(rng, live, k, e_all, e_loc)
        sizes = jnp.asarray(sizes_np)
        local = int(sizes_np.sum())
        if local > pairs:
            raise SystemExit(f"{name}: {local} local pairs over {pairs}")
        keys = jax.random.split(jax.random.key(pairs + d), 4)
        xs = jax.random.normal(keys[0], (pairs, d), jnp.bfloat16)
        n = layers * e_loc
        # one layer's experts, repeated: what they hold times nothing
        wg, wu, wd = (
            jnp.tile((jax.random.normal(kk, (e_loc, *s)) * 0.02
                      ).astype(jnp.bfloat16), (layers, 1, 1))
            for kk, s in zip(keys[1:], [(d, f), (d, f), (f, d)]))
        active = int((sizes_np > 0).sum())
        floor_us = active * 3 * d * f * 2 / HBM_BYTES_PER_S * 1e6
        row = {"shape": name, "pairs": pairs, "local_pairs": local,
               "active": active, "groups": n, "floor_us": round(floor_us, 1)}
        want = None
        runs = [(v, None, None) for v in args.variants.split(",") if v]
        runs += [("kernel", t, None) for t in f_tiles if f % t == 0]
        runs += [("kernel", None, t) for t in row_tiles if pairs % t == 0]
        for vname, tile, rt in runs:
            ef._F_TILES, ef._ROW_TILE = defaults
            label = vname
            if tile is not None:
                ef._F_TILES = (tile,)
                label = f"kernel_f{tile}"
            elif rt is not None:
                ef._ROW_TILE = rt
                label = f"kernel_r{rt}"
            try:
                fn = program(VARIANTS[vname], layers, e_loc, args.repeat)
                got = fn(xs, wg, wu, wd, sizes)
                got.block_until_ready()
                best = float("inf")
                for _ in range(5):
                    t0 = time.perf_counter()
                    fn(xs, wg, wu, wd, sizes).block_until_ready()
                    best = min(best, time.perf_counter() - t0)
                us = best / (layers * args.repeat) * 1e6
                row[f"{label}_us"] = round(us, 1)
                row[f"{label}_floor_share"] = round(floor_us / us, 3)
                if want is None:
                    want = got
                else:
                    row[f"{label}_max_diff"] = float(
                        jnp.max(jnp.abs(got - want))
                        / (jnp.max(jnp.abs(want)) + 1e-30))
            except Exception as e:  # noqa: BLE001 — a variant the chip
                # refuses is a finding, not the end of the table
                row[f"{label}_error"] = repr(e)[:300]
        print(json.dumps(row), flush=True)
        with open("chiprun_out/bench_expert_ffn.jsonl", "a") as fh:
            fh.write(json.dumps(row) + "\n")
        del wg, wu, wd  # 8.4 GB in the xing4 shapes: gone before the next


if __name__ == "__main__":
    main()
