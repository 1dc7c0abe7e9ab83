#!/usr/bin/env python3
"""The lower-precision control of a cell's reference check, on the chip.

    chiprun -- python3 scripts/reference_precision_control.py \
        --workload <cell> --seed <n>

`benchmarks/chip/reference.py::compare` decides `correct` between the
served path (bfloat16) and the family's float32 reference on the
log-probabilities of 8 generated tokens after a 256-token prompt. This
computes the family's reference twice on the cell's seeded weights, as
they are and with every matrix rounded to float8 e4m3 (the nearest
precision below the configuration's bfloat16), on `--prompts` prompts of
random ids and the tokens the unrounded reference generates greedily
after them, and holds the pair to the same limits: the rounded one has
to come out as NOT correct, by more than the served path's own distance
from the reference, or the limits could not tell an 8-bit computation
from the stated one. Prints one JSON line a prompt and a verdict.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompts", type=int, default=4)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import engine_child
    import manifest
    import reference

    cell = manifest.load_cell(a.workload)
    family = manifest.load_family(cell.family_file)
    mc = engine_child.model_config(cell.config, family, cell.config_name,
                                   False)
    e4m3 = jnp.float8_e4m3fn

    def rounded(x):
        # matrices only: norms, biases and the float32 vectors stay
        return x.astype(e4m3).astype(x.dtype) if (
            x.ndim >= 2 and x.dtype == jnp.bfloat16) else x

    fwd = jax.jit(family.forward_logprobs, static_argnums=0)
    rng = np.random.default_rng(a.seed)
    n_p, n_g = 256, 8
    prompts = [rng.integers(1, mc.vocab_size, n_p).tolist()
               for _ in range(a.prompts)]
    logps, verdicts = {}, []
    for name in ("stated", "e4m3"):
        params = engine_child.make_params(family, mc, a.seed, jnp.bfloat16,
                                          None)
        if name == "e4m3":
            # XLA drops a narrowing conversion that is widened again at
            # once unless told not to (`xla_allow_excess_precision`)
            params = jax.jit(
                lambda p: jax.tree.map(rounded, p), donate_argnums=0,
            ).lower(params).compile(compiler_options={
                "xla_allow_excess_precision": False})(params)
        for i, prompt in enumerate(prompts):
            if name == "stated":
                ids = list(prompt)
                for _ in range(n_g):
                    with jax.default_matmul_precision("highest"):
                        lp = fwd(mc, params, jnp.asarray(ids, jnp.int32),
                                 jnp.asarray([len(ids) - 1]))
                    ids.append(int(jnp.argmax(lp[0])))
                logps[i, "ids"] = ids
            ids = logps[i, "ids"]
            logps[i, name] = reference.teacher_forced_logprobs(
                family, mc, params, ids[:n_p], ids[n_p:])
        del params
    for i in range(a.prompts):
        out = reference.compare(logps[i, "e4m3"], logps[i, "stated"])
        verdicts.append(out["ok"])
        print(json.dumps({"precision_control": i, **out}), flush=True)
    print(json.dumps({"workload": a.workload, "seed": a.seed,
                      "e4m3_passes": sum(verdicts), "of": len(verdicts),
                      "device": jax.devices()[0].device_kind}))
    return 0 if not any(verdicts) else 1


if __name__ == "__main__":
    sys.exit(main())
