"""Finds a cell's files by the names in BENCHMARK.json.

A cell is an entry of `workloads`. Its configuration is
`configs/<config>.json`, its traffic mix `traffic/<traffic>.json`, and
the load offered in this cell alone (`rate_rps` of an open loop or
`clients` of a closed one, found once by a sweep, with a note on that
sweep) `cells/<cell>.json`. The load cannot sit in the `workloads` entry,
which may hold the contract's five keys and no other, nor in the traffic
file, which a later PR that brings another configuration under the same
mix may not edit. A per-layer metric is `layer_metrics/<metric>.json`,
which names its reader under `layer_metrics/readers/`. A configuration's
file names its architecture under the key `family`, and that is
`families/<family>.py`. Adding a cell, a configuration, a traffic mix, a
metric or an architecture therefore takes new files and new manifest
entries, and no edit to a file that is there. Nothing here looks at what
a name says.

A family file is the only place in the benchmark that knows an
architecture's parameter tree and equations. It gives (`FAMILY_API`):

1. `hf_config(config) -> dict`: the `config.json` that the program's own
   `from_hf_config` reads, from the configuration's file: its published
   keys, and what the family derives from `reduced` and `deployment`
   (the chip's share of a deployment). The harness takes `COMMON_KEYS`
   out of what comes back; a family strips only keys of its own.
2. `init_params(mc, key, dtype) -> pytree`: pure and jittable, the tree
   the program serves, every term non-zero that the reference check
   should see dropped. `engine_child.py` makes the key from `--seed` and
   calls it under ONE `jax.jit`, sharded as the program shards.
3. `forward_logprobs(mc, params, token_ids, rows) -> (r, vocab) float32`:
   the plain reference, importing nothing of the program. `reference.py`
   gives it ids and rows under `highest` precision and compares.
4. the counts the readers divide by (`FAMILY_COUNTS`:
   `layer_stack_bytes`, `kv_bytes_per_token`), each of the
   configuration's dict: readers get the cell's family as
   `ctx["family"]`. Nothing else is asked; a count that only a family's
   own tests read is that family's own. `kv_bytes_per_token` is ONE
   constant a configuration, times the program's one counter of context
   tokens: a family whose layers read different numbers of tokens
   (window layers beside full ones) cannot say so through it. It brings
   a metric and a reader of its own (every metric lists its cells), over
   a counter per layer kind that the program does not have yet.
5. `rehearsal_config(mc, tp) -> ModelConfig`: the tiny widths of a CPU
   rehearsal, keeping what selects code paths in that family.
6. `check(config, mc)`: `SystemExit` where the file and the program's
   `ModelConfig` disagree on what the counts and the equations rest on.

It imports jax inside its functions only (`run.py` imports no jax).
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


CELL_KEYS = {"rate_rps", "clients", "sweep"}
# keys of a configuration file that are the benchmark's in every family,
# not the published config.json's (a family may have its own beside them)
COMMON_KEYS = ("family", "source", "reduced", "assumed", "deployment",
               "chips", "replicas", "engine_args", "router_args")
FAMILY_COUNTS = ("layer_stack_bytes", "kv_bytes_per_token")
FAMILY_API = ("hf_config", "init_params", "forward_logprobs",
              *FAMILY_COUNTS, "rehearsal_config", "check")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config_file: str
    config: dict
    family_file: str           # families/<config["family"]>.py
    traffic: dict
    end_to_end: list[dict]     # the manifest entries this cell reports
    per_layer: list[dict]
    peaks: dict


def _reported(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: str = ROOT,
              bench_dir: str | None = None) -> Cell:
    manifest = _json(os.path.join(root, "BENCHMARK.json"))
    bench_dir = bench_dir or os.path.join(root, manifest["paths"][0])
    entry = next(
        (w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(
            f"no workload {workload!r} in BENCHMARK.json; it has "
            + ", ".join(w["name"] for w in manifest["workloads"]))
    cfg_entry = next(
        c for c in manifest["configs"] if c["name"] == entry["config"])
    config_file = os.path.join(root, cfg_entry["file"])
    config = _json(config_file)
    if "family" not in config:
        raise SystemExit(
            f"{config_file}: no \"family\" key; a configuration names the "
            "file under families/ that knows its architecture")
    traffic = _json(
        os.path.join(bench_dir, "traffic", entry["traffic"] + ".json"))
    cell_file = os.path.join(bench_dir, "cells", workload + ".json")
    if os.path.exists(cell_file):
        load = _json(cell_file)
        if set(load) - CELL_KEYS:
            raise SystemExit(f"{cell_file}: a cell's file gives its offered "
                             f"load ({sorted(CELL_KEYS)}), not "
                             f"{sorted(set(load) - CELL_KEYS)}")
        traffic = {**traffic, **load}
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"], config_file=config_file,
        config=config, traffic=traffic,
        family_file=os.path.join(
            bench_dir, "families", config["family"] + ".py"),
        end_to_end=[m for m in manifest["end_to_end"]
                    if _reported(m, workload)],
        per_layer=[m for m in manifest["per_layer"]
                   if _reported(m, workload)],
        peaks=_json(os.path.join(bench_dir, "peaks.json")),
    )


def _module(name: str, path: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, bench_dir: str = BENCH_DIR):
    """(spec, read) of a per-layer metric: its JSON file and the `read`
    function of the reader that file names."""
    spec = _json(os.path.join(bench_dir, "layer_metrics", metric + ".json"))
    path = os.path.join(
        bench_dir, "layer_metrics", "readers", spec["reader"] + ".py")
    return spec, _module("chipbench_reader_" + spec["reader"], path).read


def load_family(path: str):
    """The module of a family file (`Cell.family_file`), refused by name
    where it is not there or lacks one of `FAMILY_API`."""
    if not os.path.isfile(path):
        raise SystemExit(f"no family file {path}")
    name = os.path.splitext(os.path.basename(path))[0]
    mod = _module("chipbench_family_" + name, path)
    missing = [n for n in FAMILY_API if not callable(getattr(mod, n, None))]
    if missing:
        raise SystemExit(f"{path}: a family file gives {list(FAMILY_API)}; "
                         f"this one lacks {missing}")
    return mod


def parse_prometheus(text: str) -> dict[str, float]:
    """Sample name -> value summed over label sets (`_sum`, `_count`,
    `_total` and `_bucket` samples keep their suffix)."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name = head.partition("{")[0]
        if name.endswith("_bucket") or name.endswith("_created"):
            continue
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def read_layer_metrics(cell: Cell, ctx: dict,
                       bench_dir: str = BENCH_DIR) -> dict:
    """Every per-layer metric of the cell whose reader found something
    to read; a reader that finds nothing returns None and the metric is
    left out (the contract's rule; `run.py` names the ones left out on
    an earlier line, so a renamed counter or HLO shape is not silent)."""
    units = {m["name"]: m["unit"] for m in cell.per_layer}
    cache: dict[str, float | None] = {}

    def read(name: str):
        if name not in cache:
            spec, fn = load_reader(name, bench_dir)
            cache[name] = fn(spec, ctx)
        return cache[name]

    ctx["read"] = read
    out = {}
    for m in cell.per_layer:
        value = read(m["name"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": units[m["name"]]}
    return out
