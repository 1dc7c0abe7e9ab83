"""Finds a cell's files by the names in BENCHMARK.json.

A cell is an entry of `workloads`. Its configuration is
`configs/<config>.json`, its traffic mix `traffic/<traffic>.json`, and
the load offered in this cell alone (`rate_rps` of an open loop or
`clients` of a closed one, found once by a sweep, with a note on that
sweep) `cells/<cell>.json`. The load cannot sit in the `workloads` entry,
which may hold the contract's five keys and no other, nor in the traffic
file, which a later PR that brings another configuration under the same
mix may not edit. A per-layer metric is `layer_metrics/<metric>.json`,
which names its reader under `layer_metrics/readers/`. Adding a cell, a configuration, a traffic mix
or a metric therefore takes new files and new manifest entries, and no
edit to a file that is there. Nothing here looks at what a name says.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


CELL_KEYS = {"rate_rps", "clients", "sweep"}


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
        config=_json(config_file), traffic=traffic,
        end_to_end=[m for m in manifest["end_to_end"]
                    if _reported(m, workload)],
        per_layer=[m for m in manifest["per_layer"]
                   if _reported(m, workload)],
        peaks=_json(os.path.join(bench_dir, "peaks.json")),
    )


def load_reader(metric: str, bench_dir: str = BENCH_DIR):
    """(spec, read) of a per-layer metric: its JSON file and the `read`
    function of the reader that file names."""
    spec = _json(os.path.join(bench_dir, "layer_metrics", metric + ".json"))
    path = os.path.join(
        bench_dir, "layer_metrics", "readers", spec["reader"] + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_reader_" + spec["reader"], path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return spec, mod.read


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
