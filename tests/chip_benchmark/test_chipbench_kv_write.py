"""`kv_write_op_share.*`: the share of the device's busy time in the
operations that put a layer's new rows into a cache array outside the
walk. Before PR 39 those were XLA scatters, one a kv head and array
(a fusion whose result and first operand have the cache's shape and
whose second is the rows' slots); since, where the walk runs, one
`kv_cache_write` kernel a layer. The texts below are as a v5e's
profiler trace gave them (PR 38's traced run of
`ouro-2.6b-l12.reason-sys2k`) or as XLA:TPU prints them for a described
v5e (the kernel's line, the latent row's)."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench_kv_write_" + name,
        os.path.join(ROOT, "benchmarks", "chip", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


manifest = _load("manifest")

CACHE = "bf16[48,16,32384,128]{3,2,1,0:T(8,128)(2,1)}"
OPS = {
    # the parent's scatters: a decode step's 16 rows, a ragged round's 272
    "scatter, 16 rows": (
        f"%fusion.342 = {CACHE} fusion({CACHE} %get-tuple-element.2466, "
        "s32[16]{0:T(128)S(1)} %get-tuple-element.2347, "
        "bf16[16,128]{1,0:T(8,128)(2,1)S(1)} %get-tuple-element.2380), "
        "kind=kCustom, calls=%fused_computation.21.clone.clone.clone"),
    "scatter, 272 rows": (
        f"%fusion.588 = {CACHE} fusion({CACHE} %get-tuple-element.3593, "
        "s32[272]{0:T(512)S(1)} %get-tuple-element.3114, "
        "bf16[272,128]{1,0:T(8,128)(2,1)S(1)} %get-tuple-element.3116), "
        "kind=kCustom, calls=%fused_computation.13.clone.clone"),
    # a latent kind's one row a layer (640 stored lanes, one head): XLA
    # drops the head axis of 1 and scatters into three dimensions (the
    # compiled text of such a scatter for a described v5e; operand
    # shapes as a trace prints them). NOT counted: a pattern wide enough
    # for it would take any in-place update of a rank-3 array, and the
    # one row a layer is what the latent cell's step already paid
    "scatter, latent row": (
        "%fusion.6 = bf16[8,40960,640]{2,1,0:T(8,128)(2,1)} fusion("
        "bf16[8,40960,640]{2,1,0:T(8,128)(2,1)} %bitcast.16, "
        "s32[32]{0:T(128)S(1)} %fusion.5, bf16[32,640]{1,0:T(8,128)(2,1)} "
        "%broadcast_multiply_fusion.2), kind=kCustom"),
    "write kernel": (
        f"%kv_cache_write.11 = ({CACHE}, {CACHE}) custom-call("
        "s32[1]{0:T(128)} %dynamic_slice.10, s32[5,16]{1,0:T(8,128)S(1)} "
        "%get-tuple-element.9, bf16[16,16,128]{2,1,0:T(8,128)(2,1)S(1)} "
        "%get-tuple-element.979), custom_call_target=\"tpu_custom_call\""),
    # not writes: the walk (it reads the cache), a projection whose
    # result has four dimensions, a dense fusion
    "walk": (
        "%ragged_paged_attention.7 = bf16[16,16,128]{2,1,0:T(8,128)(2,1)S(1)}"
        " custom-call(s32[1]{0:T(128)} %dynamic_slice.147, "
        f"bf16[16,16,128]{{2,1,0}} %copy.72, {CACHE} %fusion.340)"),
    "prefill walk": (
        "%paged_prefill_attention.3 = bf16[256,16,128]{2,1,0} custom-call("
        f"s32[2]{{0}} %stack, {CACHE} %fusion.340, {CACHE} %fusion.341)"),
    "projection": (
        "%fusion.341 = bf16[4,8,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} fusion("
        "bf16[12,2048,2048]{2,1,0:T(8,128)(2,1)} %get-tuple-element.2498, "
        "s32[]{:T(128)} %get-tuple-element.2463, "
        "bf16[16,2048]{1,0:T(8,128)(2,1)S(1)} %get-tuple-element.2464), "
        "kind=kOutput, calls=%fused_computation.103.clone.clone.clone"),
    "rope on four dimensions": (
        "%fusion.12 = bf16[4,8,8,128]{3,2,1,0} fusion(bf16[4,8,8,128]"
        "{3,2,1,0} %fusion.341, f32[8,64]{1,0} %cos), kind=kLoop"),
    "matmul": (
        "%fusion.360 = f32[16,5632]{1,0:T(8,128)} fusion(bf16[16,2048]"
        "{1,0} %x, bf16[12,2048,5632]{2,1,0} %w), kind=kOutput"),
}
WRITES = {"scatter, 16 rows", "scatter, 272 rows", "write kernel"}
METRICS = {
    "kv_write_op_share.serve": ("tpot_mean_ms", [
        "mistral-7b-l16.chat-sys2k", "qwen2-7b-l14.chat-sys2k",
        "xing4-29b-l8.chat-doc16k", "ouro-2.6b-l12.reason-sys2k"]),
    "kv_write_op_share.batch": ("output_tok_per_s", [
        "mistral-7b-l16.batch-fewshot2k"]),
}


@pytest.mark.parametrize("metric", list(METRICS))
@pytest.mark.parametrize("op", list(OPS))
def test_the_pattern_finds_the_writes_and_nothing_else(metric, op):
    spec, read = manifest.load_reader(metric)
    ctx = {"trace": {"busy_s": 8.0, "window_s": 10.0, "ops": {
        op: {"s": 2.0, "n": 5.0, "wrapper": False, "text": OPS[op]}}}}
    assert read(spec, ctx) == (25.0 if op in WRITES else 0.0)


@pytest.mark.parametrize("metric", list(METRICS))
def test_the_share_is_of_busy_time_and_silent_without_a_trace(metric):
    spec, read = manifest.load_reader(metric)
    ops = {k: {"s": 1.0, "n": 1.0, "wrapper": False, "text": t}
           for k, t in OPS.items()}
    # a `while` that wraps the writes is no leaf
    ops["loop"] = {"s": 9.0, "n": 1.0, "wrapper": True,
                   "text": OPS["scatter, 16 rows"]}
    ctx = {"trace": {"busy_s": 16.0, "window_s": 20.0, "ops": ops}}
    assert read(spec, ctx) == 100.0 * len(WRITES) / 16.0
    assert read(spec, {}) is None


@pytest.mark.parametrize("metric", list(METRICS))
def test_the_metrics_list_exactly_their_cells(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (m,) = [m for m in bench["per_layer"] if m["name"] == metric]
    moves, cells = METRICS[metric]
    assert m == {"name": metric, "unit": "%", "better": "lower",
                 "source": "device_trace", "layer": "kernels",
                 "moves": moves, "workloads": cells}
    for cell in cells:
        assert metric in [
            x["name"] for x in manifest.load_cell(cell).per_layer]
