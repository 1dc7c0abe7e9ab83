"""The ten per-layer metrics of PR 40: a round's hand-over from the
step thread to the event loop, read off the program's own pairs
(`tpu:engine_phase_{deliver,lock_wait}_seconds`, the six
`tpu:engine_phase_<p>_offcpu_seconds`, `tpu:deliver_pickup_seconds`,
`tpu:token_delivery_seconds`, `tpu:server_send_seconds`) by the
`counter_ratio` reader that was there. Each is worked by hand on a pair
of scrapes, reads nothing on a program without its counters (the
parent), and lists exactly its cells."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench_handover_" + name,
        os.path.join(ROOT, "benchmarks", "chip", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


manifest = _load("manifest")

CHAT = ["mistral-7b-l16.chat-sys2k", "qwen2-7b-l14.chat-sys2k",
        "xing4-29b-l8.chat-doc16k", "ouro-2.6b-l12.reason-sys2k"]
BATCH = ["mistral-7b-l16.batch-fewshot2k"]
MIMO = "mimo-v2.5-ep16-l7.batch-doc8k"
HOST = ("schedule", "pack", "h2d", "dispatch", "apply", "deliver")
DISPATCHES = "tpu:engine_phase_dispatch_seconds_count"

# metric -> (unit, the samples whose deltas it adds up, the sample whose
# delta it divides by, seconds -> its unit)
METRICS = {
    "round_handover_ms": (
        "ms", ["tpu:engine_phase_deliver_seconds_sum",
               "tpu:engine_phase_lock_wait_seconds_sum"],
        DISPATCHES, 1e3),
    "round_host_offcpu_ms": (
        "ms", [f"tpu:engine_phase_{p}_offcpu_seconds_sum" for p in HOST],
        DISPATCHES, 1e3),
    "loop_pickup_mean_ms": (
        "ms", ["tpu:deliver_pickup_seconds_sum"],
        "tpu:deliver_pickup_seconds_count", 1e3),
    "token_delivery_mean_ms": (
        "ms", ["tpu:token_delivery_seconds_sum"],
        "tpu:token_delivery_seconds_count", 1e3),
    "server_send_us": (
        "us", ["tpu:server_send_seconds_sum"],
        "tpu:server_send_seconds_count", 1e6),
}
NAMES = [f"{m}.{mix}" for m in METRICS for mix in ("serve", "batch")]


def _entry(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    return entry


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_loads_and_lists_exactly_its_cells(name):
    metric, mix = name.rsplit(".", 1)
    unit, numerator, denominator, scale = METRICS[metric]
    spec, read = manifest.load_reader(name)
    assert spec == {"reader": "counter_ratio", "scrape": "engine",
                    "numerator": numerator, "denominator": [denominator],
                    "scale": scale}
    assert callable(read)
    assert _entry(name) == {
        "name": name, "unit": unit, "better": "lower",
        "source": "program_span", "layer": "engine server and admission",
        "moves": "tpot_mean_ms" if mix == "serve" else "output_tok_per_s",
        "workloads": CHAT if mix == "serve" else BATCH,
    }
    # and the cells report it: the manifest's lists are what a cell reads
    for cell in CHAT + BATCH + [MIMO]:
        listed = name in {
            m["name"] for m in manifest.load_cell(cell).per_layer}
        assert listed == (cell in _entry(name)["workloads"]), cell


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_reads_a_hand_worked_pair_of_scrapes(name):
    metric, _ = name.rsplit(".", 1)
    _, numerator, denominator, scale = METRICS[metric]
    spec, read = manifest.load_reader(name)
    # every numerator sample grew by 0.003 s x its position, over 40
    # observations: sum(0.003 i) / 40 s an observation
    before = {n: 1.5 + i for i, n in enumerate(numerator)}
    after = {n: before[n] + 0.003 * (i + 1)
             for i, n in enumerate(numerator)}
    before[denominator], after[denominator] = 1000.0, 1040.0
    k = len(numerator)
    want = 0.003 * k * (k + 1) / 2 / 40 * scale
    ctx = {"engine_before": before, "engine_after": after}
    assert read(spec, ctx) == pytest.approx(want)
    # a sample the first scrape lacks counts from zero
    first = numerator[0]
    assert read(spec, {"engine_before": {}, "engine_after": after}) \
        == pytest.approx(sum(after[n] for n in numerator) / 1040.0 * scale)
    # the parent: no such counter in the scrape, nothing to read; nor
    # without a scrape, nor over a window in which nothing was observed
    for missing in (first, numerator[-1], denominator):
        gone = {n: v for n, v in after.items() if n != missing}
        assert read(spec, {"engine_before": before,
                           "engine_after": gone}) is None
    assert read(spec, {"engine_before": None, "engine_after": None}) is None
    still = {**after, denominator: before[denominator]}
    assert read(spec, {"engine_before": before,
                       "engine_after": still}) is None


def test_the_hand_over_is_what_the_round_counters_leave_out():
    """`round_host_ms.*` and `round_handover_ms.*` divide by the same
    dispatches and share no numerator sample; `round_host_offcpu_ms.*`
    is the off-CPU part of `round_host_ms.*`'s five phases plus
    `deliver`'s."""
    for mix in ("serve", "batch"):
        host, _ = manifest.load_reader(f"round_host_ms.{mix}")
        fetch, _ = manifest.load_reader(f"round_fetch_wait_ms.{mix}")
        over, _ = manifest.load_reader(f"round_handover_ms.{mix}")
        off, _ = manifest.load_reader(f"round_host_offcpu_ms.{mix}")
        assert host["denominator"] == over["denominator"] \
            == off["denominator"] == fetch["denominator"]
        taken = set(host["numerator"]) | set(fetch["numerator"])
        assert not taken & set(over["numerator"])
        assert {n.replace("_offcpu", "") for n in off["numerator"]} \
            == set(host["numerator"]) | {
                "tpu:engine_phase_deliver_seconds_sum"}


def test_the_mimo_cells_pinned_set_is_as_it_was():
    """`test_chipbench_mimo_v2.py` pins the cell's set; this PR's
    metrics list their cells and the mimo cell is in none of the lists
    (its readings of the same counters are the builder's, in PERF.md)."""
    names = {m["name"] for m in manifest.load_cell(MIMO).per_layer}
    assert not names & set(NAMES)
    assert len(names) == 16
    assert {"round_host_ms.batch", "loop_blocked_share.batch"} <= names
