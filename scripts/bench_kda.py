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

`--step` times, instead, ONE DECODE STEP of the cell's model: the
runner's own fused decode program (`ModelRunner._make_decode_multi_step`,
32 lanes, 8 steps, device stops, the 32k context bucket) on the cell's
seeded weights; the seconds to trace and lower, to compile, the
executable's serialised size, and us a step (a round's best of five
over its 8 steps) at 4, 8 and 32 live lanes of ~17k tokens of context
each (distinct prompts: no shared run; caches and states as allocated,
nothing prefilled). It times on a TPU and nowhere else. `--step
--describe` compiles the program for a DESCRIBED v5e instead (no chip:
run it in the sandbox with `JAX_PLATFORMS=cpu`), writes the compiled
text under `chiprun_out/bench_kda/` and counts in it what the walk of a
switched block pattern (`layer_groups.forward_blocks`) should or should
not hold: the loop and branch headers by what wrote them (the scan
over the pattern is `layers/while`), prefetches of the latent block's
`wo`, copies of a weight stack, of the state pool or of the latent
cache, whether the update kernel still aliases its pool, and every copy
or fetch of a megabyte or more with the computation it stands in.

Prints one JSON line a shape.
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from production_stack_tpu.ops import kda  # noqa: E402

HBM = 819e9
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


CELL = "kimi-linear-ep2-l5.chat-doc16k"
STEPS = 8


def text_counts(text: str, runner) -> dict:
    """What the compiled text of a decode program holds of the things
    PR 51's traces found a walk of the blocks to cost."""
    stacks = {u: seg[0] for (u, _, _, _), seg in zip(
        runner.model_config.tree_units(), runner.params["segments"])}

    def shape(a, dims=None):
        name = {"bfloat16": "bf16", "float32": "f32"}[str(a.dtype)]
        return f"{name}[" + ",".join(map(str, dims or a.shape)) + "]"

    w_in = shape(stacks["K"]["w_in"])
    wo1 = shape(stacks["*"]["wo"], stacks["*"]["wo"].shape[1:])
    pool = shape(runner.k_cache["ssm"]["s"])
    cache = shape(runner.k_cache["g"][0])

    def lines(pattern):
        return sum(1 for ln in text.splitlines() if re.search(pattern, ln))

    def copies(sh):
        return lines(r"= " + re.escape(sh) + r"\S* copy(-start)?\(")

    def calls(kernel):
        return [ln for ln in text.splitlines()
                if "custom-call(" in ln and kernel in ln]

    def headers(op):
        """The loop or branch headers of the text by what wrote them
        (the jaxpr path under the round's own loop)."""
        names = collections.Counter(
            re.sub(r"^jit\(decode_multi\)/while/body/", "", m.group(1))
            for ln in text.splitlines() if f" {op}(" in ln
            for m in [re.search(r'op_name="([^"]*)"', ln)] if m)
        return dict(sorted(names.items()))

    return {
        "whiles": headers("while"), "conditionals": headers("conditional"),
        "wo_copy_start": lines(re.escape(wo1) + r"\S*, .* copy-start\("),
        "copy_of_in_proj_stack": copies(w_in),
        "copy_of_state_pool": copies(pool),
        "copy_of_latent_cache": copies(cache),
        "kda_state_update_calls_aliased": [
            len(calls("kda_state_update")),
            sum("output_to_operand_aliasing" in ln
                for ln in calls("kda_state_update"))],
        "expert_ffn_calls": len(calls("expert_ffn")),
    }


def big_copies(text: str, floor: int = 1 << 20) -> list[str]:
    """Every `copy` and `copy-start` of `floor` bytes or more in a
    compiled text, with the computation it stands in (a `region` is a
    loop's or a branch's body, `main` the program's own): what a walk
    of the blocks copies or fetches ahead that no block asked for shows
    up here by its shape."""
    width = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    found, where = [], ""
    for ln in text.splitlines():
        m = re.match(r"(?:ENTRY )?%(\S+) \(", ln)
        if m:
            where = m.group(1)
            continue
        m = re.search(r"= \(?(\w+)\[([\d,]*)\].* (copy-start|copy)\(", ln)
        if not m:
            continue
        n = width.get(m.group(1), 4)
        for d in m.group(2).split(","):
            n *= int(d or 1)
        if n >= floor:
            found.append(f"{m.group(3)} {m.group(1)}[{m.group(2)}] "
                         f"{n / 1e6:.1f} MB in {where[:48]}")
    return found


def cell_runner(*, one_chip=None, as_chip=None, cell: str = CELL):
    """The cell's `ModelRunner` on its seeded weights -> (runner, block
    manager, the cell's traffic). With `one_chip`, a sharding on a chip
    that is described and not attached: the parameters abstract, the
    caches as the chip would hold them, no block manager; `as_chip()`
    is called once the runner stands and makes `jax.default_backend()`
    say "tpu", because the step builders ask the backend at trace time
    (Mosaic or interpret, the caches' layout pin)."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
    try:
        import engine_child
        import manifest
    finally:
        del sys.path[0]
    from production_stack_tpu.engine import model_runner
    from production_stack_tpu.engine.__main__ import (
        build_parser, config_from_args,
    )
    from production_stack_tpu.engine.llm_engine import LLMEngine

    cell = manifest.load_cell(cell)
    family = manifest.load_family(cell.family_file)
    engine_args = list(cell.config["engine_args"]) + [
        "--num-kv-blocks", "1024" if one_chip else "20000"]
    if one_chip:
        engine_args += ["--attention-impl", "pallas"]
    args = build_parser().parse_args(
        ["--model", cell.config_name, *engine_args])
    mc = engine_child.model_config(cell.config, family, cell.config_name,
                                   False)
    ecfg = config_from_args(args)
    dtype = jnp.dtype(ecfg.dtype)
    if not one_chip:
        engine = LLMEngine(ecfg, params=engine_child.make_params(
            family, mc, 2147483659, dtype, None))
        return engine.runner, engine.block_manager, cell.traffic
    runner = model_runner.ModelRunner(ecfg, params=jax.eval_shape(
        lambda: family.init_params(mc, jax.random.key(0), dtype)))
    as_chip()
    # the latent rows as the chip stores them (`_k_store_dim`: 576
    # lanes padded to 640)
    runner._k_cache["g"] = tuple(
        jax.ShapeDtypeStruct((*g.shape[:-1], runner._k_store_dim(i)),
                             g.dtype)
        for i, g in enumerate(runner._k_cache["g"]))
    return runner, None, cell.traffic


def decode_program(runner, c_pad: int, one_chip=None):
    """The fused decode round's program of `runner`, compiled (for
    `one_chip` where given) -> (the executable, its seconds and
    size)."""
    from jax.experimental.serialize_executable import serialize

    from production_stack_tpu.engine import model_runner

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    b = runner.config.max_num_seqs
    _, packed_len = runner._decode_pack_layout(b, c_pad, False, stop_cap=0)
    prog = model_runner.jit_program(
        "decode_multi", runner._make_decode_multi_step(
            b, c_pad, STEPS, stop_cap=0),
        donate_argnums=(1, 2))
    t0 = time.perf_counter()
    lowered = prog.lower(*(
        jax.tree.map(place, x) if one_chip else x
        for x in (runner.params, runner.k_cache, runner.v_cache,
                  jax.ShapeDtypeStruct((packed_len,), jnp.int32))))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    return compiled, {
        "trace_lower_s": round(t1 - t0, 3), "compile_s": round(t2 - t1, 3),
        "serialised_mb": round(len(serialize(compiled)[0]) / 1e6, 2)}


def step_bench(a) -> None:
    """`--step`: the fused decode round's program of the cell."""
    import numpy as np

    one_chip = None
    if a.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one_chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.default_backend() != "tpu":
        sys.exit("--step times a decode step on a TPU; without one, "
                 "--step --describe compiles it for a described v5e")
    runner, bm, traffic = cell_runner(
        one_chip=one_chip,
        as_chip=lambda: setattr(jax, "default_backend", lambda: "tpu"))
    mc, b = runner.model_config, runner.config.max_num_seqs
    ctx = int(traffic["shared_prefix_tokens"]) + 500
    c_pad = runner._ctx_bucket(ctx + STEPS)
    print(json.dumps({
        "cell": CELL, "device": "described v5e" if a.describe
        else jax.devices()[0].device_kind, "lanes": b, "steps": STEPS,
        "ctx_bucket": c_pad, "context": ctx, "pattern": mc.block_pattern}),
        flush=True)
    compiled, line = decode_program(runner, c_pad, one_chip)

    if a.describe:
        out = os.path.join(ROOT, "chiprun_out", "bench_kda")
        os.makedirs(out, exist_ok=True)
        text = compiled.as_text()
        with open(os.path.join(out, "decode_multi.txt"), "w") as f:
            f.write(text)
        mem = compiled.memory_analysis()
        print(json.dumps({
            **line, "temp_mb": round(mem.temp_size_in_bytes / 1e6, 1),
            **text_counts(text, runner),
            "copies_over_1mb": big_copies(text)}), flush=True)
        return

    rng = np.random.default_rng(7)
    tables = []
    for _ in range(b):
        table, _ = bm.allocate_prompt(
            rng.integers(1, mc.vocab_size, ctx).tolist(), reuse_cache=False)
        assert bm.ensure_capacity(ctx + STEPS, table)
        tables.append(table)
    kc, vc = runner.k_cache, runner.v_cache      # the maps go up here

    def packed(live: int):
        n = np.arange(live)
        return jnp.asarray(runner._fill_decode_pack(
            c_pad, False, rng.integers(1, mc.vocab_size, live).tolist(),
            [ctx - 1] * live, tables[:live], [ctx] * live,
            np.zeros(live, np.float32), np.ones(live, np.float32),
            np.zeros(live, np.int32),
            np.stack([n, n], 1).astype(np.uint32),
            min_ps=np.zeros(live, np.float32),
            stop=(np.full(live, -1, np.int32), np.zeros(live, np.int32),
                  np.full(live, STEPS, np.int32), None)))

    for live in (4, 8, b):
        buf, times = packed(live), []
        for _ in range(5):
            t0 = time.perf_counter()
            ys, kc, vc = compiled(runner.params, kc, vc, buf)
            jax.block_until_ready(ys)
            times.append(time.perf_counter() - t0)
            kc = {k: v for k, v in kc.items() if k != "stats"}
        assert int(np.asarray(ys[-1])[:live].min()) == STEPS
        line[f"us_a_step_{live}_live"] = round(
            min(times) / STEPS * 1e6, 1)
    print(json.dumps(line), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--repeat", type=int, default=16)
    ap.add_argument("--step", action="store_true",
                    help="one decode step of the cell's model on a TPU, "
                    "not the mixer alone")
    ap.add_argument("--describe", action="store_true",
                    help="with --step: compile it for a described v5e and "
                    "count what the compiled text holds; no chip")
    a = ap.parse_args()
    if a.step:
        return step_bench(a)
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
