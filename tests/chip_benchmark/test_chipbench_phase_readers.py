"""The readers that read the program's phase spans and counters, on
hand-built `ctx` dictionaries, and the trace reduction naming an idle gap
by the phase span that covers it."""

import importlib.util
import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

BENCH = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "benchmarks", "chip"))
READERS = os.path.join("layer_metrics", "readers")


def _load(name, sub=""):
    spec = importlib.util.spec_from_file_location(
        "chipbench_pr24_" + name, os.path.join(BENCH, sub, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def spec_of(metric):
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        return json.load(f)


tr = _load("trace_reduce")
opcount = _load("dense", "families")     # the dense family's counts
gap_share = _load("trace_gap_share", READERS).read
module_ms = _load("trace_module_ms", READERS).read
kv_share = _load("attn_kv_stream_share", READERS).read
counter_rate = _load("counter_rate", READERS).read
counter_value = _load("counter_value", READERS).read
counter_ratio = _load("counter_ratio", READERS).read
MS = 1_000_000


# -- trace_gap_share -----------------------------------------------------------
def test_gap_share_with_and_without_unattributed_rows():
    spec = spec_of("idle_unattributed_share.serve")
    assert spec == spec_of("idle_unattributed_share.batch")
    gaps = [["engine.fetch", 0.06], ["unattributed", 0.03],
            ["engine.apply", 0.01]]
    assert gap_share(spec, {"trace": {"chips": 1, "idle_gaps": gaps}}) \
        == pytest.approx(30.0)
    named = [["engine.fetch", 0.06], ["engine.schedule", 0.04]]
    assert gap_share(spec, {"trace": {"chips": 1, "idle_gaps": named}}) == 0.0
    # a device that never idled has no unattributed idle time either
    assert gap_share(spec, {"trace": {"chips": 1, "idle_gaps": []}}) == 0.0
    # a name that merely contains the word is not the row
    odd = [["unattributed_thing", 1.0]]
    assert gap_share(spec, {"trace": {"chips": 1, "idle_gaps": odd}}) == 0.0
    assert gap_share(spec, {"trace": None}) is None
    assert gap_share(spec, {"trace": {"chips": 0, "idle_gaps": []}}) is None


# -- trace_module_ms -----------------------------------------------------------
def test_module_ms_picks_the_programs_of_a_kind_by_name():
    modules = {
        "jit_decode_multi": {"count": 40.0, "total_s": 5.0},
        "jit_ragged_rows": {"count": 8.0, "total_s": 1.4},
        "jit_ragged": {"count": 2.0, "total_s": 0.6},
        "jit_prefill_rows": {"count": 1.0, "total_s": 0.05},
    }
    ctx = {"trace": {"modules": modules}}
    assert module_ms(spec_of("decode_round_ms.serve"), ctx) \
        == pytest.approx(125.0)
    # both ragged builders are one kind of round: (1.4 + 0.6) / 10
    assert module_ms(spec_of("ragged_round_ms.batch"), ctx) \
        == pytest.approx(200.0)
    # the parent's trace: every program is `jit_step`
    old = {"trace": {"modules": {"jit_step": {"count": 9.0,
                                             "total_s": 1.0}}}}
    assert module_ms(spec_of("decode_round_ms.batch"), old) is None
    assert module_ms(spec_of("ragged_round_ms.serve"), {"trace": None}) \
        is None


# -- attn_kv_stream_share ------------------------------------------------------
ATTN = ("%ragged_paged_attention.9 = bf16[544,32,128]{2,1,0} custom-call("
        "s32[1]{0:T(128)} %dynamic_slice.1), custom_call_target=\"tpu\"")
DECODE_ATTN = "%paged_decode_attention.2 = bf16[32,32,128]{2,1,0} custom-call("
FUSION = "%fusion.1 = bf16[32,4096]{1,0} fusion(bf16[32,4096]{1,0} %p)"
WHILE = "%while.5 = (s32[], bf16[32,4096]{1,0}) while((s32[]) %t), body=%b"


def test_kv_stream_share_against_numbers_worked_by_hand():
    spec = spec_of("attn_kv_stream_share.batch")
    assert spec["ops"] == spec_of("attn_kernel_share.batch")["ops"]
    config = {"hidden_size": 4096, "intermediate_size": 14336,
              "num_hidden_layers": 16, "num_attention_heads": 32,
              "num_key_value_heads": 8, "vocab_size": 32000}
    # 2 (k, v) x 16 layers x 8 heads x 128 x 2 bytes = 64 KiB a token
    assert opcount.kv_bytes_per_token(config) == 65536
    ops = {
        "a": {"s": 2.0, "n": 100.0, "wrapper": False, "text": ATTN},
        "b": {"s": 0.5, "n": 100.0, "wrapper": False, "text": DECODE_ATTN},
        "c": {"s": 1.0, "n": 100.0, "wrapper": False, "text": FUSION},
        # a wrapper that contains the kernels is no kernel time
        "d": {"s": 4.0, "n": 10.0, "wrapper": True,
              "text": WHILE + ATTN},
    }
    ctx = {
        "trace": {"ops": ops, "window_s": 5.0, "busy_s": 4.8},
        "engine_before": {"tpu:attn_context_tokens_sum": 1.0e6},
        "engine_after": {"tpu:attn_context_tokens_sum": 1.0e6 + 3.125e8},
        "window_s": 50.0, "chips": 1, "config": config,
        "family": opcount, "peak": {"hbm_bytes_per_s": 819.2e9},
    }
    # 3.125e8 tokens x 65,536 B = 2.048e13 B; / 819.2e9 B/s = 25 s of
    # streaming in a 50 s window = 0.5; the kernels ran 2.5 s of a 5 s
    # trace = 0.5; share = 100%
    assert kv_share(spec, ctx) == pytest.approx(100.0)
    # four chips each hold a quarter of every token's KV
    assert kv_share(spec, {**ctx, "chips": 4}) == pytest.approx(25.0)
    # the counter missing (the parent), no trace, no kernel in the trace
    assert kv_share(spec, {**ctx, "engine_after": {}}) is None
    assert kv_share(spec, {**ctx, "trace": None}) is None
    no_kernel = {"ops": {"c": ops["c"]}, "window_s": 5.0, "busy_s": 1.0}
    assert kv_share(spec, {**ctx, "trace": no_kernel}) is None


# -- counter_rate, counter_value, and counter_ratio on the new samples ---------
def test_counter_rate_is_a_share_of_the_window():
    spec = spec_of("loop_blocked_share.serve")
    ctx = {"engine_before": {"tpu:event_loop_lock_wait_seconds_sum": 2.0},
           "engine_after": {"tpu:event_loop_lock_wait_seconds_sum": 7.1},
           "window_s": 51.0}
    assert counter_rate(spec, ctx) == pytest.approx(10.0)
    assert counter_rate(spec, {**ctx, "engine_after": {}}) is None
    assert counter_rate(spec, {**ctx, "engine_after": None}) is None


def test_counter_value_reads_the_totals_at_the_windows_end():
    spec = spec_of("setup_trace_lower_s")
    after = {"tpu:program_trace_seconds_sum": 30.5,
             "tpu:program_lower_seconds_sum": 12.25,
             "tpu:program_compile_seconds_sum": 9.0}
    ctx = {"engine_before": {"tpu:program_trace_seconds_sum": 30.0},
           "engine_after": after}
    assert counter_value(spec, ctx) == pytest.approx(42.75)
    assert counter_value(spec_of("setup_backend_compile_s"), ctx) == 9.0
    del after["tpu:program_lower_seconds_sum"]
    assert counter_value(spec, ctx) is None
    assert counter_value(spec, {"engine_after": None}) is None


def test_round_host_ms_is_host_work_per_dispatched_program():
    spec = spec_of("round_host_ms.serve")
    phases = ("schedule", "pack", "h2d", "dispatch", "apply")
    assert spec["numerator"] == [
        f"tpu:engine_phase_{p}_seconds_sum" for p in phases]
    before = {n: 1.0 for n in spec["numerator"]}
    after = {n: 1.0 + 0.1 * (i + 1)
             for i, n in enumerate(spec["numerator"])}
    before["tpu:engine_phase_dispatch_seconds_count"] = 100.0
    after["tpu:engine_phase_dispatch_seconds_count"] = 400.0
    ctx = {"engine_before": before, "engine_after": after}
    # 0.1 + 0.2 + 0.3 + 0.4 + 0.5 s over 300 dispatches = 5 ms
    assert counter_ratio(spec, ctx) == pytest.approx(5.0)
    fetch = spec_of("round_fetch_wait_ms.batch")
    after["tpu:engine_phase_fetch_seconds_sum"] = 36.0
    assert counter_ratio(fetch, ctx) == pytest.approx(120.0)
    # the parent exports none of these
    assert counter_ratio(spec, {"engine_before": {}, "engine_after": {}}) \
        is None


# -- the reduction names a gap by the phase span over it -----------------------
def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS))


def line(name, *events):
    return NS(name=name, events=list(events))


def test_an_idle_gap_under_engine_pack_comes_out_under_that_name():
    """Chip busy 0-40 and 50-90 of a 100 ms span. The gap 40-50 lies in
    `engine.pack` (42-49), inside `engine.step` (38-95): the shortest
    cover names it. The gap 90-100 is covered by `engine.step` alone up
    to 95, its midpoint: it takes the step's name, and the gap before
    any host event none."""
    chip = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", ev("jit_decode_multi(7)", 0, 40),
             ev("jit_ragged_rows(8)", 50, 40)),
        line("XLA Ops", ev(FUSION, 0, 40), ev(ATTN, 50, 40)),
    ])
    host = NS(name="/host:CPU", lines=[
        line("engine-step-loop", ev("engine.step", 38, 58),
             ev("engine.pack", 42, 7), ev("engine.dispatch", 49.2, 0.5)),
        line("other", ev("tick", 99.9, 0.1)),
    ])
    r = tr.reduce(NS(planes=[host, chip]))
    gaps = dict(r["idle_gaps"])
    assert gaps["engine.pack"] == pytest.approx(0.010)
    assert gaps["engine.step"] == pytest.approx(0.010)
    assert "unattributed" not in gaps
    assert sorted(r["modules"]) == ["jit_decode_multi", "jit_ragged_rows"]
    ctx = {"trace": r}
    assert gap_share(spec_of("idle_unattributed_share.serve"), ctx) == 0.0
    assert module_ms(spec_of("decode_round_ms.serve"), ctx) \
        == pytest.approx(40.0)
    assert module_ms(spec_of("ragged_round_ms.serve"), ctx) \
        == pytest.approx(40.0)
