"""Microbenchmark of the KDA mixer's two forms on the chip, at the shapes
of `kimi-linear-ep2-l5` (32 heads of 128 key and 128 value dims, 4
state layers, a pool of 129 slots). `chiprun -- python3
scripts/bench_kda.py`; `--tiny` is the CPU rehearsal of its control
flow (no time from it means anything).

- the decode update (`ops/kda.state_update`, the Mosaic kernel): one
  jitted program that updates LAYERS layers in a scan, REPEAT times, on
  the donated pool, 32 lanes of which 8, 16 or 32 hold a sequence; us a
  call (a layer) against what the bytes allow, 2 x 2 MiB x live lanes
  at 819 GB/s, and against XLA's own gather / `scan_step` / scatter;
- the prefill form (`ops/kda.scan_chunked`) over 256 rows of one and of
  two lanes at chunks of 16, 32 and 64, against the sequential
  recurrence over the same rows, and whether they agree.

Prints one JSON line a shape.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from production_stack_tpu.ops import kda  # noqa: E402

HBM = 819e9


def best(fn, *args, n=5):
    out = None
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return min(times), out


def inputs(key, r, h, kd, vd):
    k = jax.random.split(key, 5)
    return (kda.l2_norm(jax.random.normal(k[0], (r, h, kd))) * kd ** -0.5,
            kda.l2_norm(jax.random.normal(k[1], (r, h, kd))),
            jax.random.normal(k[2], (r, h, vd)).astype(jnp.bfloat16),
            -4.0 * jax.nn.softplus(jax.random.normal(k[3], (r, h, kd))),
            jax.nn.sigmoid(jax.random.normal(k[4], (r, h))))


def update_program(kernel: bool, layers: int, repeat: int):
    def run(s_all, slots, q, k, v, g, beta):
        zero = jnp.zeros_like(slots, dtype=bool)

        def layer(s_all, l):
            if kernel:
                y, s_all = kda.state_update(
                    s_all, l, slots, slots, zero, q, k, v, g, beta,
                    interpret=jax.default_backend() != "tpu")
            else:
                y, s = kda.scan_step(q, k, v, g, beta, s_all[l, slots])
                s_all = s_all.at[l, slots].set(s)
            return s_all, jnp.sum(y)

        def once(_, carry):
            s_all, acc = carry
            s_all, ys = jax.lax.scan(layer, s_all, jnp.arange(layers))
            return s_all, acc + jnp.sum(ys)

        return jax.lax.fori_loop(0, repeat, once, (s_all, 0.0))

    return jax.jit(run, donate_argnums=(0,))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--repeat", type=int, default=16)
    a = ap.parse_args()
    h, kd, vd, layers, slots, lanes, rows = 32, 128, 128, 4, 129, 32, 256
    if a.tiny:
        h, kd, vd, layers, slots, lanes, rows, a.repeat = 2, 8, 16, 2, 9, 4, 24, 1
    key = jax.random.key(7)
    q, k, v, g, beta = inputs(key, lanes, h, kd, vd)
    for live in (lanes // 4, lanes // 2, lanes):
        slot_ids = jnp.where(jnp.arange(lanes) < live,
                             1 + jnp.arange(lanes), 0).astype(jnp.int32)
        line = {"decode_lanes": lanes, "live": live,
                "bytes_us": 2 * h * kd * vd * 4 * live / HBM * 1e6}
        for name, kernel in (("kernel_us", True), ("xla_us", False)):
            pool = jax.random.normal(
                jax.random.key(1), (layers, slots, h, kd, vd), jnp.float32)
            fn = update_program(kernel, layers, a.repeat)
            t = None
            for _ in range(4):
                t0 = time.perf_counter()
                pool, acc = fn(pool, slot_ids, q, k, v, g, beta)
                jax.block_until_ready(acc)
                dt = time.perf_counter() - t0
                t = dt if t is None else min(t, dt)
            line[name] = t / (layers * a.repeat) * 1e6
        line["share_of_bytes"] = line["bytes_us"] / line["kernel_us"]
        print(json.dumps(line), flush=True)

    def sequential(q, k, v, g, beta, s0):
        def token(s, x):
            o, s = kda.scan_step(*(y[:, None] for y in x), s[:, None])
            return s[:, 0], o[:, 0]

        s, o = jax.lax.scan(
            token, s0, tuple(jnp.swapaxes(y, 0, 1) for y in
                             (q, k, v, g, beta)))
        return jnp.swapaxes(o, 0, 1), s

    for n in (1, 2):
        ins = [jnp.stack(x) for x in zip(*(
            inputs(jax.random.fold_in(key, i), rows, h, kd, vd)
            for i in range(n)))]
        s0 = jax.random.normal(jax.random.key(2), (n, h, kd, vd))
        t_seq, (o_seq, s_seq) = best(jax.jit(sequential), *ins, s0)
        line = {"prefill_lanes": n, "rows": rows, "sequential_ms": t_seq * 1e3}
        for chunk in ((8,) if a.tiny else (16, 32, 64)):
            fn = jax.jit(jax.vmap(
                functools.partial(kda.scan_chunked, chunk=chunk)))
            t, (o, s) = best(fn, *ins, s0)
            line[f"chunk{chunk}_ms"] = t * 1e3
            line[f"chunk{chunk}_max_err"] = float(jnp.max(jnp.abs(o - o_seq)))
            line[f"chunk{chunk}_state_err"] = float(
                jnp.max(jnp.abs(s - s_seq)))
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
