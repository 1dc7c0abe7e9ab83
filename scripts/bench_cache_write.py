"""Microbenchmark of a layer's cache write on the chip: today's per-head
scatters against ONE XLA scatter an array over the flattened plane and
against the tile kernel (`ops/cache_write.py`), at the benchmark cells'
shapes. `chiprun -- python3 scripts/bench_cache_write.py`; `--tiny` is
the CPU rehearsal of its control flow (no time from it means anything).

Each variant is one jitted program that writes LAYERS layers in a scan,
REPEAT times, into donated caches pinned row-major as the engine's step
programs pin them; the time of a layer's write (K and V) is the best of
five calls over LAYERS x REPEAT. Prints one JSON line a shape, with the
program's temp bytes (a full-cache copy shows there) and whether the
kernel's cache equals the scatters' in every slot but slot 0.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from production_stack_tpu.ops import cache_write  # noqa: E402

BS = 32


def pin(c):
    if jax.default_backend() != "tpu":
        return c
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(c, Layout((0, 1, 2, 3)))


def flat_scatter(kc, vc, l, slots, k, v):
    """(a): one scatter an array over the (L * nkv * slots, d) plane."""
    out = []
    for c, x in ((kc, k), (vc, v)):
        L, nkv, s, d = c.shape
        if d > x.shape[-1]:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, d - x.shape[-1])))
        idx = ((l * nkv + jnp.arange(nkv))[:, None] * s
               + slots[None, :]).reshape(-1)
        rows = x.astype(c.dtype).swapaxes(0, 1).reshape(-1, d)
        out.append(c.reshape(-1, d).at[idx].set(rows).reshape(c.shape))
    return tuple(out)


VARIANTS = {
    "loop": cache_write.write_kv,
    "flat": flat_scatter,
    "tiles": lambda *a: cache_write.write_kv(
        *a, kernel=True, interpret=jax.default_backend() != "tpu"),
}


def program(write, layers, repeat):
    def run(kc, vc, slots, k, v):
        kc, vc = pin(kc), pin(vc)
        if write is not flat_scatter:
            # as a forward does, once, outside its layer scan
            slots = cache_write.plan_rows(slots, kc)

        def layer(carry, l):
            return write(*carry, l, slots, k, v), None

        def once(_, carry):
            return jax.lax.scan(layer, carry, jnp.arange(layers))[0]

        return jax.lax.fori_loop(0, repeat, once, (kc, vc))

    return jax.jit(run, donate_argnums=(0, 1))


def slots_for(rng, blocks, decode, prefill, live):
    """`prefill` rows of one sequence (consecutive slots over scattered
    pages, starting inside a tile) and `decode` rows at scattered slots,
    of which `live` hold a token and the rest write slot 0."""
    pages = rng.permutation(np.arange(1, blocks))
    out = []
    if prefill:
        start = 7
        pos = start + np.arange(prefill)
        out.extend(pages[pos // BS] * BS + pos % BS)
    lanes = pages[-decode:] * BS + rng.integers(0, BS, decode)
    lanes[live:] = 0
    out.extend(lanes)
    return np.asarray(out, np.int32)


@jax.jit
def same(a, b):
    """Equal in every slot but slot 0, the trash slot."""
    return jnp.all((a == b)[:, :, 1:])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--repeat", type=int, default=16)
    ap.add_argument("--blocks", type=int, default=1012)
    args = ap.parse_args()
    shapes = [
        # name, nkv, d_k, K stored, d_v, decode rows, prefill rows, live
        ("ouro decode, all live", 16, 128, 128, 128, 16, 0, 16),
        ("ouro decode, 5 live", 16, 128, 128, 128, 16, 0, 5),
        ("ouro decode, none live", 16, 128, 128, 128, 16, 0, 0),
        ("mistral decode, all live", 8, 128, 128, 128, 32, 0, 32),
        ("mistral decode, 4 live", 8, 128, 128, 128, 32, 0, 4),
        ("qwen2 decode, all live", 4, 128, 128, 128, 32, 0, 32),
        ("qwen2 decode, 4 live", 4, 128, 128, 128, 32, 0, 4),
        ("mimo window decode", 8, 192, 256, 128, 64, 0, 64),
        ("mimo full decode", 4, 192, 256, 128, 64, 0, 64),
        ("ouro ragged 256+16", 16, 128, 128, 128, 16, 256, 5),
        ("mistral ragged 256+32", 8, 128, 128, 128, 32, 256, 4),
        ("mistral ragged 512+32", 8, 128, 128, 128, 32, 512, 32),
        ("qwen2 ragged 512+32", 4, 128, 128, 128, 32, 512, 4),
    ]
    if args.tiny:
        args.layers, args.repeat, args.blocks = 2, 2, 96
        shapes = [s for s in shapes if "256+16" in s[0] or "mimo w" in s[0]]
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "layers": args.layers, "repeat": args.repeat,
                      "slots": args.blocks * BS}), flush=True)
    rng = np.random.default_rng(39)
    for name, nkv, dk, dks, dv, dec, pre, live in shapes:
        slots = jnp.asarray(slots_for(rng, args.blocks, dec, pre, live))
        n = slots.shape[0]
        keys = jax.random.split(jax.random.key(n + nkv), 2)
        k = jax.random.normal(keys[0], (n, nkv, dk), jnp.bfloat16)
        v = jax.random.normal(keys[1], (n, nkv, dv), jnp.bfloat16)
        row = {"shape": name, "nkv": nkv, "rows": n, "live": pre + live}
        got = {}
        for vname, write in VARIANTS.items():
            fn = program(write, args.layers, args.repeat)

            def caches():
                return (jnp.zeros((args.layers, nkv, args.blocks * BS, d),
                                  jnp.bfloat16) for d in (dks, dv))

            try:
                compiled = fn.lower(*caches(), slots, k, v).compile()
                row[f"{vname}_temp_bytes"] = (
                    compiled.memory_analysis().temp_size_in_bytes)
                out = compiled(*caches(), slots, k, v)
                jax.block_until_ready(out)
                best = float("inf")
                for _ in range(5):
                    t0 = time.perf_counter()
                    out = compiled(*out, slots, k, v)
                    jax.block_until_ready(out)
                    best = min(best, time.perf_counter() - t0)
                row[f"{vname}_us"] = round(
                    best / (args.layers * args.repeat) * 1e6, 3)
                got[vname] = out
                del out
            except Exception as e:  # noqa: BLE001 — report, go on
                row[f"{vname}_error"] = repr(e)[:300]
        for vname in ("flat", "tiles"):
            if vname in got and "loop" in got:
                row[f"{vname}_equal"] = all(
                    bool(same(a, b))
                    for a, b in zip(got[vname], got["loop"]))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
