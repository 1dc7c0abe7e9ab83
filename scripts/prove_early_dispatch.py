#!/usr/bin/env python3
"""A decode round that starts when the fetch before it returns gives the
tokens it gives when it starts a step later: the proof on the chip, at a
cell's configuration. And the count of how often a round can.

    chiprun -- python3 scripts/prove_early_dispatch.py \
        --workload laguna-xs.2-l5.chat-doc16k --seed <n> [--count 20]

Builds the cell's engine in this process (its configuration, its engine
arguments, its seeded weights), puts two documents of the traffic's
length into the prefix cache, and serves `--requests` requests over
them, taking turns (A B A B ..), greedy and seeded-sampled at 0.8:

- as the engine decides (`LLMEngine._starts_at_fetch`);
- with that decision patched to "never" (every staged round is taken
  by the next step, as before the decision existed).

Every stream of the first part has to be the stream of the second,
token for token and finish reason for finish reason, and some round
has to have started early: exit 1 says otherwise. The share of
decode-bearing rounds that started early is printed beside each part.

`--count SECONDS` then offers the cell's own load to the same engine
for that long, in process (Poisson arrivals at the cell's rate, taken
in between two steps as the server's lock lets them in; a prompt is
one of the cell's documents and a fresh question, an answer is of the
traffic's lengths; every program warmed first as the benchmark warms
them), and prints what no exporter reads: decode-bearing rounds,
lane-typed rounds among them, staged hits and misses (the decode
stage's, and the lane-typed round's own), early starts,
and WHY a stage was refused, by a wrapper of `_reserve_next_round`
that lives here. An engine without the decision (the parent commit:
copy this file beside it) is counted the same way, its early starts 0.
Prints one JSON line a part and a verdict. `--tiny` is the CPU
rehearsal of its control flow.
"""

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)


def counters(engine) -> dict:
    return {
        "decode_rounds": engine._decode_rounds_total,
        "ragged_rounds": engine._ragged_rounds_total,
        "staged_hits": engine._staged_hits_total,
        "staged_misses": engine._staged_misses_total,
        "ragged_staged_hits": engine._ragged_staged_hits_total,
        "ragged_staged_misses": engine._ragged_staged_misses_total,
        "early": getattr(engine, "_early_dispatch_total", 0),
        "phases": engine.phases.pairs(),
    }


def delta(engine, before: dict) -> dict:
    now = counters(engine)
    d = {k: now[k] - before[k] for k in now if k != "phases"}
    # the step thread's phases over the part: ms a span, spans
    d["phase_ms"] = {
        name: [round(1e3 * (s - before["phases"][name][0])
                     / max(n - before["phases"][name][1], 1), 3),
               n - before["phases"][name][1]]
        for name, (s, n) in now["phases"].items()
        if n > before["phases"][name][1]}
    d["early_share"] = round(
        100.0 * d["early"] / max(d["decode_rounds"], 1), 2)
    return d


def watch_refusals(engine) -> dict:
    """Count, by reason, the rounds that staged no successor."""
    why: dict[str, int] = {}

    def note(reason):
        why[reason] = why.get(reason, 0) + 1

    reserve, can_stage = engine._reserve_next_round, engine._can_stage
    mml = engine.scheduler.config.max_model_len

    def reserving(seqs, k):
        ok = reserve(seqs, k)
        left = [s.sampling_params.max_tokens - s.num_generated - k
                for s in seqs]
        if ok:
            note("reserved")
            if min(left) < k:
                note("reserved_with_a_lane_that_ends_in_the_staged_round")
        elif min(left) < 1:
            note("a_lane_ends_in_this_round")
        elif min(left) < k:
            note("a_lane_ends_in_the_staged_round")
        elif any(s.num_tokens + 2 * k >= mml for s in seqs):
            note("max_model_len")
        else:
            note("blocks")
        return ok

    def staging(seqs, k):
        sched = engine.scheduler
        if sched.waiting:
            note("a_request_waits")
        elif any(not s.prefill_done and not s.long_prefill_active
                 for s in sched.running):
            note("a_prefill_lane")
        return can_stage(seqs, k)

    engine._reserve_next_round, engine._can_stage = reserving, staging
    return why


def lognormal(rng, dist: dict) -> int:
    x = math.exp(math.log(dist["median"]) + dist["sigma"] * rng.normal())
    return int(min(max(x, dist["min"]), dist["max"]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=5)
    ap.add_argument("--max-tokens", type=int, default=96)
    ap.add_argument("--count", type=float, default=0.0,
                    help="seconds of the cell's load to count over")
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import engine_child
    import manifest
    from production_stack_tpu.engine.__main__ import (
        build_parser, config_from_args,
    )
    from production_stack_tpu.engine.llm_engine import LLMEngine
    from production_stack_tpu.engine.sampling_params import SamplingParams
    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cell = manifest.load_cell(a.workload)
    family = manifest.load_family(cell.family_file)
    engine_args = list(cell.config["engine_args"])
    if a.tiny:
        engine_args += ["--dtype", "float32", "--kv-cache-dtype", "float32",
                        "--num-kv-blocks", "1024"]
    args = build_parser().parse_args(
        ["--model", cell.config_name, *engine_args])
    configure_compile_cache()
    mc = engine_child.model_config(cell.config, family, cell.config_name,
                                   a.tiny)
    ecfg = config_from_args(args)
    params = engine_child.make_params(
        family, mc, a.seed, jnp.dtype(ecfg.dtype), None)
    engine = LLMEngine(ecfg, params=params)
    decides = hasattr(engine, "_starts_at_fetch")
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "workload": a.workload, "seed": a.seed,
                      "lanes": ecfg.max_num_seqs,
                      "engine_decides": decides}), flush=True)

    rng = np.random.default_rng(a.seed)
    n_prefix = 300 if a.tiny else int(cell.traffic["shared_prefix_tokens"])
    max_tokens = 36 if a.tiny else a.max_tokens

    def ids(n):
        return rng.integers(1, mc.vocab_size, n).tolist()

    documents = [ids(n_prefix) for _ in range(2)]
    prompts = [documents[i % 2] + ids(int(rng.integers(32, 160)))
               for i in range(a.requests)]
    sps = [SamplingParams(max_tokens=max_tokens - 8 * (i % 3),
                          temperature=0.0 if i % 2 == 0 else 0.8,
                          seed=1000 + i, ignore_eos=True)
           for i in range(a.requests)]
    one = SamplingParams(max_tokens=1, temperature=0.0)
    for p in documents:
        engine.generate([p + [7]], one)

    def serve():
        before = counters(engine)
        t0 = time.monotonic()
        outs = engine.generate(prompts, sps)
        return ([(o.token_ids, o.finish_reason) for o in outs],
                {"seconds": round(time.monotonic() - t0, 3),
                 **delta(engine, before)})

    serve()  # every program of the two parts below, built once
    early, note = serve()
    print(json.dumps({"part": "as_the_engine_decides", **note}),
          flush=True)
    started = note["early"]
    if decides:
        engine._starts_at_fetch = lambda *args: False
    try:
        never, note = serve()
    finally:
        if decides:
            del engine._starts_at_fetch
    print(json.dumps({"part": "never", "equal": never == early, **note}),
          flush=True)
    for i, (got, bar) in enumerate(zip(early, never)):
        if got != bar:
            at = next((j for j, (x, y) in enumerate(zip(got[0], bar[0]))
                       if x != y), min(len(got[0]), len(bar[0])))
            print(json.dumps({"differs": "early/never", "request": i,
                              "first_at": at, "finish": [got[1], bar[1]]}),
                  flush=True)
    ok = early == never and (started > 0 or not decides) and not note["early"]

    if a.count > 0:
        t = cell.traffic
        floor = 0 if a.tiny else int(t["shared_prefix_tokens"])
        t0 = time.monotonic()
        n_warm = engine_child.warm_programs(engine, floor, a.tiny)
        print(json.dumps({"part": "warmed", "programs": n_warm,
                          "seconds": round(time.monotonic() - t0, 1)}),
              flush=True)
        variants = [ids(n_prefix) for _ in range(
            2 if a.tiny else int(t["prefix_variants"]))]
        for p in variants:
            engine.generate([p + [7]], one)
        rate = float(t["rate_rps"])
        at, arrivals = 0.0, []
        while at < a.count:
            at += rng.exponential(1.0 / rate)
            n_out = lognormal(rng, t["output_tokens"])
            arrivals.append((at, variants[len(arrivals) % len(variants)]
                             + ids(lognormal(rng, t["prompt_tokens"])),
                             SamplingParams(
                                 max_tokens=8 if a.tiny else n_out,
                                 temperature=0.7, seed=len(arrivals),
                                 ignore_eos=True)))
        why = watch_refusals(engine)
        before, live, steps = counters(engine), 0, 0
        t0, nxt = time.monotonic(), 0
        while nxt < len(arrivals) or engine.has_unfinished():
            now = time.monotonic() - t0
            while nxt < len(arrivals) and arrivals[nxt][0] <= now:
                _, p, sp = arrivals[nxt]
                engine.add_request(f"r{nxt}", prompt_token_ids=p,
                                   sampling_params=sp)
                nxt += 1
            if engine.has_unfinished():
                engine.step()
                live += engine.scheduler.num_running
                steps += 1
            else:
                time.sleep(max(0.0, min(
                    0.005, arrivals[nxt][0] - (time.monotonic() - t0))))
        print(json.dumps({
            "part": "counted", "rate_rps": rate, "requests": len(arrivals),
            "seconds": round(time.monotonic() - t0, 2),
            "running_mean": round(live / max(steps, 1), 2),
            **delta(engine, before), "stage": why}), flush=True)

    print(json.dumps({"workload": a.workload, "seed": a.seed,
                      "device": dev.device_kind, "ok": ok,
                      "early_starts": started}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
