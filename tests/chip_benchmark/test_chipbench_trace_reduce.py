"""The trace reduction on a hand-built ProfileData-shaped fixture."""

import importlib.util
import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

BENCH = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "benchmarks", "chip"))


def _load(name, sub=""):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name, os.path.join(BENCH, sub, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


tr = _load("trace_reduce")
MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS))


def line(name, *events):
    return NS(name=name, events=list(events))


# operations are named by their HLO text, as the TPU's trace names them
FUSION = ("%fusion.1 = bf16[32,4096]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[32,"
          "4096]{1,0} %get-tuple-element.3), kind=kOutput")
ATTN = ("%ragged_paged_attention.9 = bf16[544,32,128]{2,1,0} custom-call("
        "s32[1]{0:T(128)} %dynamic_slice.1), custom_call_target=\"tpu\"")
WHILE = ("%while.51 = (s32[]{:T(128)}, bf16[32,4096]{1,0:T(8,128)(2,1)}, "
         "bf16[16,8,104800,128]{3,2,1,0}) while((s32[]{:T(128)}, bf16[32,"
         "4096]{1,0}) %tuple.4), condition=%cond, body=%body")


def fixture():
    """Two chips over a 100 ms span. Chip 0: a while 10-40 that WRAPS
    a fusion 10-30 and an attention kernel 20-40 (busy 10-40 = 30), a
    fusion 60-70 (busy 10): busy 40 ms. Chip 1: busy 20 ms."""
    chip0 = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", ev("jit_run(123)", 10, 30),
             ev("jit_run(456)", 60, 10), ev("jit_other(9)", 0, 0)),
        line("XLA Ops", ev(WHILE, 10, 30), ev(FUSION, 10, 20),
             ev(ATTN, 20, 20), ev(FUSION, 60, 10)),
        line("Steps", ev("0", 0, 100)),
    ])
    chip1 = NS(name="/device:TPU:1", lines=[
        line("XLA Modules", ev("jit_run(123)", 10, 20)),
        line("XLA Ops", ev(WHILE, 10, 20), ev(FUSION, 10, 20)),
    ])
    host = NS(name="/host:CPU", lines=[
        line("python", ev("outer", 0, 100), ev("schedule", 40, 20),
             ev("fetch", 70, 25)),
        line("other", ev("tick", 99, 1)),
    ])
    meta = NS(name="Task Environment", lines=[])
    return NS(planes=[host, chip1, meta, chip0])


def test_union_merges_nested_and_touching_intervals():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 12), (20, 21)]) == [
        (0, 4), (5, 12), (20, 21)]
    assert tr.union([]) == []


def test_busy_is_the_union_and_idle_share_follows():
    r = tr.reduce(fixture())
    assert r["chips"] == 2
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s_per_chip"] == pytest.approx([0.040, 0.020])
    assert r["busy_s"] == pytest.approx(0.030)
    idle = _load("trace_idle_share", "layer_metrics/readers")
    assert idle.read({}, {"trace": r}) == pytest.approx(70.0)
    assert idle.read({}, {"trace": None}) is None


def test_per_operation_sums_are_a_mean_over_chips_and_skip_wrappers():
    r = tr.reduce(fixture())
    assert tr.op_key(FUSION) == "fusion.1 bf16[32,4096]"
    assert tr.op_key(WHILE) == "while.51 s32[]"
    assert tr.op_key("np.asarray") == "np.asarray"
    assert tr.is_wrapper(WHILE) and not tr.is_wrapper(FUSION)
    ops = r["ops"]
    assert ops["fusion.1 bf16[32,4096]"]["s"] == pytest.approx(
        (0.030 + 0.020) / 2)
    assert ops["fusion.1 bf16[32,4096]"]["n"] == pytest.approx(1.5)
    assert ops["ragged_paged_attention.9 bf16[544,32,128]"]["s"] == \
        pytest.approx(0.010)
    assert ops["while.51 s32[]"]["wrapper"] and ops["while.51 s32[]"]["s"] == \
        pytest.approx(0.025)
    # the breakdown lists leaves only, longest first
    assert [k for k, _ in r["device_ops"]] == [
        "fusion.1 bf16[32,4096]",
        "ragged_paged_attention.9 bf16[544,32,128]"]
    share = _load("trace_op_share", "layer_metrics/readers")
    with open(os.path.join(BENCH, "layer_metrics",
                           "attn_kernel_share.serve.json")) as f:
        spec = json.load(f)
    assert share.read(spec, {"trace": r}) == pytest.approx(
        0.010 / 0.030 * 100)
    assert share.read({"ops": "no such op"}, {"trace": r}) == 0.0


def test_module_names_lose_their_ids_and_the_decode_scan_gives_the_step():
    r = tr.reduce(fixture())
    assert tr.module_name("jit_run(123)") == "jit_run"
    assert r["modules"]["jit_run"]["count"] == pytest.approx(1.5)
    assert r["modules"]["jit_run"]["total_s"] == pytest.approx(0.030)
    step = _load("trace_decode_scan_step_ms", "layer_metrics/readers")
    with open(os.path.join(BENCH, "layer_metrics",
                           "decode_step_ms.serve.json")) as f:
        spec = json.load(f)
    ctx = {"trace": r, "config": {
        "hidden_size": 4096, "engine_args": ["--max-num-seqs", "32"]}}
    # the while that carries bf16[32,4096]: 30 ms and 20 ms, one each
    assert step.read(spec, ctx) == pytest.approx(25.0)
    ctx["config"]["engine_args"] = ["--max-num-seqs", "16"]
    assert step.read(spec, ctx) is None


def test_idle_gaps_are_named_by_the_innermost_host_event():
    r = tr.reduce(fixture())
    gaps = dict(r["idle_gaps"])
    # chip 0 idle: 0-10 (outer), 40-60 (schedule), 70-100 (fetch)
    assert gaps["outer"] == pytest.approx(0.010)
    assert gaps["schedule"] == pytest.approx(0.020)
    assert gaps["fetch"] == pytest.approx(0.030)
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s_per_chip"][0])


def test_a_trace_with_no_device_plane_reduces_to_nothing():
    host_only = NS(planes=[NS(name="/host:CPU", lines=[
        line("t", ev("x", 0, 5))])])
    r = tr.reduce(host_only)
    assert r["chips"] == 0 and r["busy_s"] == 0.0
