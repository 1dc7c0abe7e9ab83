"""The sha256 of the lowered text of the fused decode round
(`decode_multi`: four lanes, four steps, device stops, the Pallas walk
in interpret mode) of the tiny presets, one a kind of model the chip
cells serve; with `--parent DIR` the same from the checkout at DIR, and
which presets lower to the same text in both.

The offline check of a change to the step builders or to a forward that
says it leaves some models' programs as they were (PR 52: everything
but a switched block pattern; PR 53: everything but a windowed cache
group). It runs in the sandbox in a minute and needs no chip:

    git archive <parent commit> | tar -x -C chip_checkout/parent
    JAX_PLATFORMS=cpu python3 scripts/lowered_decode_sha.py \\
        --parent chip_checkout/parent \\
        --same-but pst-tiny-groups-debug pst-tiny-laguna-debug

Prints one JSON line: {"presets": {name: sha256 here}, "same": [...],
"differ": [...]}; with `--same-but`, exits 1 unless every preset but
exactly the ones named lowers to the parent's text. Beside
`scripts/bench_kda.py --step --describe`, which reads the COMPILED text
of one cell's program.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import subprocess
import sys

# name -> (the preset it is made from, what is replaced): the dense
# presets with what mistral (one window for every layer) and qwen2
# (biases on q, k, v) add, a looped stack (ouro), a mixture of experts,
# layer groups with a windowed cache group (mimo, laguna) and without
# (xing4: latent), single-sublayer blocks (nemotron), a switched
# pattern (kimi)
PRESETS = {
    "pst-tiny-debug": ("pst-tiny-debug", {}),
    "pst-tiny-debug+window": ("pst-tiny-debug", {"sliding_window": 8}),
    "pst-tiny-debug+qkv-bias": ("pst-tiny-debug", {"qkv_bias": True}),
    "pst-tiny-loop-debug": ("pst-tiny-loop-debug", {}),
    "pst-tiny-moe-debug": ("pst-tiny-moe-debug", {}),
    "pst-tiny-latent-debug": ("pst-tiny-latent-debug", {}),
    "pst-tiny-nemotron-debug": ("pst-tiny-nemotron-debug", {}),
    "pst-tiny-kimi-debug": ("pst-tiny-kimi-debug", {}),
    "pst-tiny-groups-debug": ("pst-tiny-groups-debug", {}),
    "pst-tiny-laguna-debug": ("pst-tiny-laguna-debug", {}),
}
LANES, STEPS, BLOCK, CONTEXT = 4, 4, 4, 64


def lowered_sha(name: str) -> str:
    import jax
    import jax.numpy as jnp

    from production_stack_tpu.engine import model_runner
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.models import config as mcfg

    base, over = PRESETS[name]
    mc = dataclasses.replace(mcfg.get_model_config(base), name=name, **over)
    mcfg._PRESETS[name] = mc
    runner = model_runner.ModelRunner(EngineConfig(
        model=name, tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=BLOCK, num_kv_blocks=64,
        max_num_seqs=LANES, max_prefill_chunk=16,
        num_scheduler_steps=STEPS, attention_impl="pallas", seed=3))
    _, packed_len = runner._decode_pack_layout(
        LANES, CONTEXT, False, stop_cap=0)
    prog = model_runner.jit_program(
        "decode_multi", runner._make_decode_multi_step(
            LANES, CONTEXT, STEPS, stop_cap=0),
        donate_argnums=(1, 2))
    text = prog.lower(
        runner.params, runner.k_cache, runner.v_cache,
        jax.ShapeDtypeStruct((packed_len,), jnp.int32)).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="a checkout of the parent commit")
    ap.add_argument("--same-but", nargs="*",
                    help="all lower alike but exactly these")
    ap.add_argument("--root", help=argparse.SUPPRESS)
    a = ap.parse_args()
    root = os.path.abspath(a.root or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".."))
    sys.path.insert(0, root)
    os.chdir(root)
    here = {name: lowered_sha(name) for name in PRESETS}
    if a.root:
        print(json.dumps(here))
        return 0
    line = {"presets": here}
    rc = 0
    if a.parent:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--root", a.parent],
            check=True, capture_output=True, text=True).stdout
        there = json.loads(out.strip().splitlines()[-1])
        line["same"] = [n for n in PRESETS if here[n] == there[n]]
        line["differ"] = [n for n in PRESETS if here[n] != there[n]]
        if a.same_but is not None and sorted(a.same_but) != sorted(
                line["differ"]):
            line["expected_to_differ"] = a.same_but
            rc = 1
    print(json.dumps(line))
    return rc


if __name__ == "__main__":
    sys.exit(main())
