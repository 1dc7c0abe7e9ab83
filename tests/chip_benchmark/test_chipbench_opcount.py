"""The dense family's counts against the figures the configurations were
chosen by, and its plain reference, through `reference.py`, against the
program's own dense reference."""

import importlib.util
import json
import os
import sys

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


opcount = _load("dense", "families")     # the dense family's counts


def hf(name, **over):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return {**json.load(f), **over}


@pytest.mark.parametrize("name,depth,params_b,step_gb,kv_kib", [
    ("mistral-7b-l16", 16, 3.75, 7.24, 64),
    ("qwen2-7b-l14", 14, 4.35, 7.62, 28),
    # the published depth, as the four-chip deployment serves it
    ("mistral-7b-l16", 32, 7.24, 14.22, 128),
])
def test_sizes_of_the_configurations(name, depth, params_b, step_gb, kv_kib):
    c = hf(name, num_hidden_layers=depth)
    assert opcount.total_params(c) / 1e9 == pytest.approx(params_b, abs=0.01)
    assert opcount.decode_weight_bytes(c) / 1e9 == pytest.approx(
        step_gb, abs=0.01)
    assert opcount.kv_bytes_per_token(c) == kv_kib * 1024


def test_decode_bytes_leave_out_the_embedding_and_count_the_biases():
    c = hf("qwen2-7b-l14")
    h, v = c["hidden_size"], c["vocab_size"]
    assert (opcount.total_params(c) * 2 - opcount.decode_weight_bytes(c)
            == v * h * 2)
    assert (opcount.decode_weight_bytes(c) - opcount.layer_stack_bytes(c)
            == (v * h + h) * 2)
    no_bias = dict(c, qkv_bias=False)
    assert (opcount.layer_params(c) - opcount.layer_params(no_bias)
            == (28 + 2 * 4) * 128)


@pytest.mark.parametrize("name,depth,preset", [
    ("mistral-7b-l16", 32, "mistral-7b"), ("qwen2-7b-l14", 28, "qwen2-7b")])
def test_program_counts_the_same_parameters(name, depth, preset):
    """At the published depth the yardstick and the program's preset
    count the same matrices. The program's `num_params` leaves the q/k/v
    biases out (PERF.md Open questions); the yardstick counts them."""
    from production_stack_tpu.models import config as mcfg

    mc = mcfg.get_model_config(preset)
    biases = mc.num_layers * (mc.q_size + 2 * mc.kv_size) * mc.qkv_bias
    assert opcount.total_params(hf(name, num_hidden_layers=depth)) == \
        mc.num_params() + biases


def test_weight_stream_share_is_bytes_over_bandwidth_over_step_time():
    reader = _load("weight_stream_share", "layer_metrics/readers")
    c = hf("mistral-7b-l16")
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    least_ms = opcount.layer_stack_bytes(c) / 819e9 * 1e3
    ctx = {"config": c, "chips": 1, "peak": peak, "family": opcount,
           "read": lambda name: 2 * least_ms}
    assert reader.read({"step_metric": "x"}, ctx) == pytest.approx(50.0)
    ctx["read"] = lambda name: None
    assert reader.read({"step_metric": "x"}, ctx) is None


def test_record_mean_reader_is_a_mean_over_the_windows_records():
    reader = _load("record_mean", "layer_metrics/readers")
    loadgen = _load("loadgen")
    recs = [loadgen.Record(idx=i, phase="window", due=0.0, tokens=2,
                           events=[(0.5, 1), (t, 1)])
            for i, t in enumerate((1.0, 2.0, 6.0))]
    recs.append(loadgen.Record(idx=9, phase="window"))   # never answered
    ctx = {"loadgen": loadgen, "records": recs}
    assert reader.read({"field": "request_ms"}, ctx) == pytest.approx(3000.0)
    assert reader.read({"field": "norm_latency_ms"}, ctx) == \
        pytest.approx(1500.0)
    assert reader.read({"field": "request_ms"},
                       {"loadgen": loadgen, "records": []}) is None


def test_compare_decides_correct():
    reference = _load("reference")
    ref = [-5.0, -6.0, -7.0, -5.5]
    assert reference.compare([x + 0.02 for x in ref], ref)["ok"]
    # one position far off: a wrong computation
    assert not reference.compare([-5.0, -6.0, -9.0, -5.5], ref)["ok"]
    # every position a little off: a drop in precision
    assert not reference.compare([x - 0.05 for x in ref], ref)["ok"]
    assert not reference.compare([], [])["ok"]
    assert not reference.compare(ref[:3], ref)["ok"]


def test_reference_agrees_with_the_programs_dense_reference_with_biases():
    """GQA, rope, SwiGLU and NON-ZERO q/k/v biases, float32 on the CPU:
    the benchmark's reference and tests/reference_model.py give the same
    log-probabilities, and a dropped bias does not."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from reference_model import dense_forward

    from production_stack_tpu.models import config as mcfg
    from production_stack_tpu.models import llama

    reference = _load("reference")
    dense = _load("manifest").load_family(
        os.path.join(BENCH, "families", "dense.py"))
    mc = dataclasses.replace(mcfg.TINY_DEBUG, name="t", qkv_bias=True,
                             tie_word_embeddings=False, rms_norm_eps=1e-6)
    params = llama.init_params(mc, jax.random.key(0), jnp.float32)
    k = jax.random.split(jax.random.key(1), 3)
    for i, b in enumerate(("bq", "bk", "bv")):
        params["layers"][b] = 0.5 * jax.random.normal(
            k[i], params["layers"][b].shape, jnp.float32)
    prompt, gen = list(range(5, 45)), [7, 300, 12, 99]
    got = reference.teacher_forced_logprobs(dense, mc, params, prompt, gen)
    logits = dense_forward(mc, params, prompt + gen)
    want = np.asarray(jax.nn.log_softmax(logits, -1))
    for i, g in enumerate(gen):
        assert got[i] == pytest.approx(
            float(want[len(prompt) - 1 + i, g]), abs=2e-4)
    zeroed = dict(params, layers={
        **params["layers"],
        "bq": jnp.zeros_like(params["layers"]["bq"]),
        "bk": jnp.zeros_like(params["layers"]["bk"])})
    off = reference.teacher_forced_logprobs(dense, mc, zeroed, prompt, gen)
    assert max(abs(a - b) for a, b in zip(off, got)) > 1e-3
