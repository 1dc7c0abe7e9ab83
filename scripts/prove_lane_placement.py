#!/usr/bin/env python3
"""Decode sequences seated by the prefix they hold give the tokens they
give alone: the proof on the chip, at a cell's configuration.

    chiprun -- python3 scripts/prove_lane_placement.py \
        --workload ouro-2.6b-l12.reason-sys2k --seed <n>

Builds the cell's engine in this process (its configuration, its engine
arguments, its seeded weights), puts two prefixes of the traffic's
length into the prefix cache, and serves `--requests` requests over
them, taking turns (A B A B ..: the order of arrival, in which a row
block shares nothing), greedy and seeded-sampled:

- each request ALONE;
- all together with the lanes as the engine seats them
  (`model_runner.place_lanes`);
- all together with sequence i in lane i (the parent's seats, forced by
  patching that one function), and the counters' shared share beside
  the placed one's.

Every stream of the second part has to be the stream of the third,
token for token (a looped stack turns one bit into another token): the
two differ in the seats alone, and exit 1 says they differ. Whether
both are also the streams ALONE is printed beside (`equal_alone`):
alone, a prompt's tail is prefilled by another program than beside
decoding lanes, which is no matter of seats. Prints one JSON line a
part and a verdict. `--tiny` is the CPU rehearsal of its control flow.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=5)
    ap.add_argument("--max-tokens", type=int, default=96)
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import engine_child
    import manifest
    from production_stack_tpu.engine import model_runner
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
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "workload": a.workload, "seed": a.seed,
                      "lanes": ecfg.max_num_seqs}), flush=True)

    rng = np.random.default_rng(a.seed)
    n_prefix = 300 if a.tiny else int(cell.traffic["shared_prefix_tokens"])
    max_tokens = 28 if a.tiny else a.max_tokens

    def ids(n):
        return rng.integers(1, mc.vocab_size, n).tolist()

    prefixes = [ids(n_prefix) for _ in range(2)]
    prompts = [prefixes[i % 2] + ids(int(rng.integers(32, 160)))
               for i in range(a.requests)]
    sps = [SamplingParams(max_tokens=max_tokens - 8 * (i % 3),
                          temperature=0.0 if i % 2 == 0 else 0.8,
                          seed=1000 + i, ignore_eos=True)
           for i in range(a.requests)]
    one = SamplingParams(max_tokens=1, temperature=0.0)
    for p in prefixes:
        engine.generate([p + [7]], one)

    seen = []
    decode_lanes = engine.runner.decode_lanes

    def watched(tables):
        lanes = decode_lanes(tables)
        seen.append(lanes.tolist())
        return lanes

    engine.runner.decode_lanes = watched

    def serve(which):
        del seen[:]
        before = list(engine.runner.attn_lane_tokens)
        t0 = time.monotonic()
        outs = engine.generate([prompts[i] for i in which],
                               [sps[i] for i in which])
        lane, shared = (x - y for x, y in zip(
            engine.runner.attn_lane_tokens, before))
        maps = sorted({tuple(m) for m in seen if len(m) > 1})
        return [o.token_ids for o in outs], {
            "seconds": round(time.monotonic() - t0, 3),
            "shared_share": round(100.0 * shared / max(lane, 1), 2),
            "maps": maps[:6]}

    alone = []
    for i in range(a.requests):
        toks, _ = serve([i])
        alone.append(toks[0])
    print(json.dumps({"part": "alone", "tokens": [len(t) for t in alone]}),
          flush=True)
    everyone = list(range(a.requests))
    placed, note = serve(everyone)
    ok_placed = placed == alone
    moved = any(list(m) != list(range(len(m))) for m in note["maps"])
    print(json.dumps({"part": "placed", "equal_alone": ok_placed,
                      "lanes_moved": moved, **note}), flush=True)
    real = model_runner.place_lanes
    model_runner.place_lanes = (
        lambda pages, b, least: np.arange(len(pages), dtype=np.int32))
    try:
        arrival, note = serve(everyone)
    finally:
        model_runner.place_lanes = real
    print(json.dumps({"part": "lane_i_for_sequence_i",
                      "equal_alone": arrival == alone,
                      "equal_placed": arrival == placed, **note}),
          flush=True)
    for i in everyone:
        for name, got, bar in (("placed/alone", placed, alone),
                               ("arrival/alone", arrival, alone),
                               ("placed/arrival", placed, arrival)):
            if got[i] != bar[i]:
                at = next(j for j, (x, y) in enumerate(
                    zip(got[i], bar[i])) if x != y)
                print(json.dumps({"differs": name, "request": i,
                                  "first_at": at}), flush=True)
    ok = placed == arrival and moved
    print(json.dumps({"workload": a.workload, "seed": a.seed,
                      "device": dev.device_kind, "ok": ok,
                      "equal_alone": ok_placed}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
