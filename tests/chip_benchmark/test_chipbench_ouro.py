"""The ouro family, its configuration and its cell, as the files PR 38
added beside the benchmark's own: the published keys and the cut against
the sizes it was reckoned by, the cell's metrics and traffic, the new
readers on hand-built traces, the family served under a temporary
directory through the harness's own path, and the reference against the
program's forward pass (prefill in two chunks, then decode, through a
cache of `cache_layers` layers) with each norm dropped and with one
cache shared among the passes."""

import dataclasses
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
CELL = "ouro-2.6b-l12.reason-sys2k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench_ouro_" + name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


manifest = _load("manifest")
engine_child = _load("engine_child")
reference = _load("reference")
loadgen = _load("loadgen")
family = manifest.load_family(os.path.join(BENCH, "families", "ouro.py"))


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


# -- the configuration's file ----------------------------------------------
def test_every_width_is_as_published_and_only_the_depth_is_cut(cell):
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"], c["total_ut_steps"], c["early_exit_threshold"],
            c["rope_theta"], c["rms_norm_eps"], c["use_sliding_window"],
            c["tie_word_embeddings"], c["max_position_embeddings"]) == (
        2048, 16, 16, 128, 5632, 49152, 4, 1, 1000000, 1e-6, False, False,
        65536)
    assert set(c["reduced"]) == {"num_hidden_layers"}
    assert (c["reduced"]["num_hidden_layers"]["published"],
            c["num_hidden_layers"]) == (48, 12)
    for key in ("norm_placement", "norm_between_passes", "cache_per_pass",
                "exit_gate", "biases", "head_dim", "weights", "tokenizer",
                "engine_args"):
        assert c["assumed"][key], key
    assert "four-stage pipeline" in c["deployment"]
    assert "v5e-4" in c["deployment"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == cell.config_name)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == c["source"]


def test_every_published_key_is_in_the_file_under_its_key(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(ln) for ln in f if '"Ouro-2.6B"' in ln)
    assert cell.config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cell.config["reduced"]:
            continue
        assert cell.config[key] == value, key


def test_the_counts_are_the_sizes_the_cut_was_reckoned_by(cell):
    c = cell.config
    assert family.layer_params(c) / 1e6 == pytest.approx(51.39, abs=0.01)
    embed_and_head = 2 * c["vocab_size"] * c["hidden_size"]
    assert embed_and_head / 1e6 == pytest.approx(201.3, abs=0.1)
    assert family.total_params(c) / 1e9 == pytest.approx(0.818, abs=0.001)
    assert family.total_params(c) * 2 / 1e9 == pytest.approx(1.636,
                                                             abs=0.001)
    # ONE pass reads 1.23 GB of layer weights; a decode step four
    # times that and the head: 5.13 GB, 6.3 ms at 819 GB/s
    assert family.layer_stack_bytes(c) == 12 * family.layer_params(c) * 2
    assert family.layer_stack_bytes(c) / 1e9 == pytest.approx(1.233,
                                                              abs=0.001)
    assert family.step_weight_bytes(c) / 1e9 == pytest.approx(5.135,
                                                              abs=0.001)
    # K and V of every layer and pass: 384 KiB a token here, 1.5 MiB at
    # the published depth
    assert family.kv_bytes_per_token(c) == 2 * 16 * 128 * 2 * 12 * 4
    assert family.kv_bytes_per_token(c) == 384 * 1024
    whole = dict(c, num_hidden_layers=48)
    assert family.kv_bytes_per_token(whole) == 1536 * 1024
    assert family.total_params(whole) / 1e9 == pytest.approx(2.67, abs=0.01)


def test_the_cell_reports_its_metrics_and_its_traffic_is_the_issues(cell):
    assert [m["name"] for m in cell.end_to_end] == ["tpot_mean_ms",
                                                    "setup_s"]
    own = {"loop_pass_ms.serve", "loop_weight_stream_share.serve",
           "loop_exit_pass_mean.serve"}
    names = {m["name"] for m in cell.per_layer}
    assert own <= names
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_mean_ms"
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    assert {m["moves"] for m in cell.per_layer} == {"tpot_mean_ms",
                                                    "setup_s"}
    assert not [n for n in names if n.endswith(".batch")]
    # a looped model's decode round nests two `while`s that carry
    # [lanes, hidden]; `decode_step_ms.serve` cannot tell them apart
    assert "decode_step_ms.serve" not in names
    t = cell.traffic
    assert (t["loop"], t["arrival"], t["stratify_seconds"],
            t["session_pool"], t["prefix_variants"],
            t["shared_prefix_tokens"], t["drain_seconds"]) == (
        "open", "poisson", 5, 0, 2, 2100, 30)
    assert t["history"] == {"enabled": False}
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 160,
                                  "sigma": 0.8, "min": 32, "max": 640}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 384,
                                  "sigma": 0.5, "min": 96, "max": 768}
    assert t["setup"] == {"warm_seconds": 15}
    assert 0 < t["rate_rps"] <= 4 and t["sweep"]
    args = cell.config["engine_args"]
    assert args[args.index("--max-num-seqs") + 1] == "16"
    assert args[args.index("--max-model-len") + 1] == "4096"
    # a 257-token prompt ends in a one-token chunk, computed or cached:
    # the cached path is the cold path bit for bit (Findings PR 38)
    assert args[args.index("--max-prefill-chunk") + 1] == "256"


def test_every_context_of_the_traffic_lies_in_one_bucket(cell):
    """2,048 < every prefill's start, every context < 4,096; and the
    pool at its worst fits ~32k tokens of cache."""
    plan = loadgen.build_plan(cell.traffic, 4123456789, 51)
    assert not plan.sessions and len(plan.prefixes) == 2
    window = [t for t in plan.turns if t.phase == "window"]
    assert len(window) == round(51 * cell.traffic["rate_rps"])
    for turn in plan.turns:
        msgs = [{"role": "system", "content": plan.prefixes[0]},
                {"role": "user", "content": turn.user_text}]
        n = loadgen.prompt_tokens(msgs)
        assert 2048 < n - len(turn.user_text) and n + turn.max_tokens < 4096
        assert 96 <= turn.max_tokens <= 768
    assert 2 * 2100 + 16 * (3545 - 2100) < 1000 * 32


def test_the_arrivals_fill_the_generators_strata_evenly(cell):
    """`stratified_order` deals a phase's sorted sizes and gaps to its
    strata in rows; a last row that is not whole hands the LONGEST
    answers and gaps to strata the seed picks, and `tpot_mean_ms` then
    follows the seed (at 33 arrivals over 10 strata the driver's check
    read a spread of 4.8 and 6.8%: Findings PR 38). The rate keeps both
    phases' counts whole multiples of their strata."""
    t = cell.traffic
    for span in (51.0, t["setup"]["warm_seconds"]):
        strata = round(span / t["stratify_seconds"])
        assert round(t["rate_rps"] * span) % strata == 0, span
    plan = loadgen.build_plan(t, 2147484391, 51)
    window = sorted((x for x in plan.turns if x.phase == "window"),
                    key=lambda x: x.due_s)
    per = len(window) // 10
    ranked = sorted(x.max_tokens for x in window)
    for i in range(10):
        # each stratum: one answer of each tenth-wide row of the sizes
        block = sorted(x.max_tokens for x in window[i * per:(i + 1) * per])
        for row, size in enumerate(block):
            assert ranked[10 * row] <= size <= ranked[10 * row + 9]


def test_the_new_readers_on_hand_built_traces(cell):
    """`loop_pass_ms.serve` picks the loop over LAYERS among the two
    `while`s that carry [16, 2048]; `loop_weight_stream_share.serve` is
    passes x a pass's bytes at the peak bandwidth over the programs'
    share of the trace; both read nothing from a program without the
    loop (the parent), and `loop_exit_pass_mean.serve` is sum / count."""
    head = "%while.{} = (s32[], bf16[16,2048]{{1,0}}, bf16[48,16,32768,128]"
    ops = {
        "layers": {"s": 1.6, "n": 800.0, "wrapper": True,
                   "text": head.format(3)},
        "passes": {"s": 1.7, "n": 200.0, "wrapper": True,
                   "text": head.format(4)},
        "steps": {"s": 1.8, "n": 25.0, "wrapper": True,
                  "text": "%while.5 = (s32[], s32[16]{0}, bf16[48,16,32768"},
        "prefill": {"s": 0.5, "n": 10.0, "wrapper": True,
                    "text": "%while.9 = (s32[], bf16[272,2048]{1,0}"},
        "leaf": {"s": 1.0, "n": 9600.0, "wrapper": False,
                 "text": "%fusion.1 = bf16[16,2048]{1,0} fusion()"},
    }
    trace = {"window_s": 5.0, "busy_s": 4.0, "ops": ops, "modules": {
        "jit_decode_multi": {"count": 20, "total_s": 1.5},
        "jit_ragged_rows": {"count": 5, "total_s": 0.5},
        "jit_kv_import": {"count": 1, "total_s": 1.0}}}
    ctx = {"trace": trace, "config": cell.config, "chips": 1,
           "family": family, "window_s": 50.0,
           "peak": cell.peaks["TPU v5 lite"],
           "engine_before": {"tpu:loop_passes_total": 1000.0,
                             "tpu:loop_exit_pass_sum": 10.0,
                             "tpu:loop_exit_pass_count": 4.0},
           "engine_after": {"tpu:loop_passes_total": 9000.0,
                            "tpu:loop_exit_pass_sum": 260.0,
                            "tpu:loop_exit_pass_count": 104.0}}
    got = {}
    for name in ("loop_pass_ms.serve", "loop_weight_stream_share.serve",
                 "loop_exit_pass_mean.serve"):
        spec, read = manifest.load_reader(name)
        got[name] = read(spec, ctx)
    assert got["loop_pass_ms.serve"] == pytest.approx(2.0)
    least = 8000 * family.layer_stack_bytes(cell.config) / 819e9 / 50.0
    assert got["loop_weight_stream_share.serve"] == pytest.approx(
        least / (2.0 / 5.0) * 100.0)
    assert 50.0 < got["loop_weight_stream_share.serve"] < 70.0
    assert got["loop_exit_pass_mean.serve"] == pytest.approx(2.5)
    # a program without the counters or the loop: nothing to read
    bare = {**ctx, "engine_before": {}, "engine_after": {},
            "trace": {**trace, "ops": {k: ops[k] for k in ("leaf",)}}}
    for name in got:
        spec, read = manifest.load_reader(name)
        assert read(spec, bare) is None
    once = {**ctx, "config": {**cell.config, "total_ut_steps": 1}}
    spec, read = manifest.load_reader("loop_pass_ms.serve")
    assert read(spec, once) is None


# -- served: under a temporary directory, through the harness's path -------
TINY = {
    "architectures": ["OuroForCausalLM"], "model_type": "ouro",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "vocab_size": 384, "max_position_embeddings": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "layer_types": ["full_attention"] * 2, "max_window_layers": 2,
    "total_ut_steps": 3, "early_exit_threshold": 1,
    "family": "ouro", "source": "a fixture of the tests", "reduced": {},
    "assumed": {}, "deployment": "none", "chips": 1, "replicas": 1,
    "engine_args": ["--tokenizer", "byte"], "router_args": [],
}
PROMPT, GEN = list(range(5, 45)), [7, 300, 12, 99]
CHUNK = 24


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    import jax.numpy as jnp

    root = tmp_path_factory.mktemp("checkout")
    for sub in ("configs", "traffic", "families"):
        (root / "bench" / sub).mkdir(parents=True)
    os.symlink(family.__file__, root / "bench" / "families" / "ouro.py")
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (root / "bench" / "traffic" / "few.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2}))
    (root / "bench" / "peaks.json").write_text(json.dumps({"none": {}}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "paths": ["bench"],
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.few", "config": "tiny",
                       "traffic": "few", "chips": 1}],
        "end_to_end": [{"name": "setup_s"}], "per_layer": []}))
    c = manifest.load_cell("tiny.few", root=str(root),
                           bench_dir=str(root / "bench"))
    fam = manifest.load_family(c.family_file)
    mc = engine_child.model_config(c.config, fam, "fixture-tiny-ouro",
                                   False)
    params = engine_child.make_params(fam, mc, 4123456789, jnp.float32,
                                      None)
    yield {"cell": c, "mc": mc, "params": params,
           "control": engine_child.Control(fam, mc, params, "")}
    from production_stack_tpu.models import config as mcfg
    mcfg._PRESETS.pop(mc.name)


def program_logprobs(mc, params):
    """The program's own forward pass (`models/llama.py`) over prompt +
    generated ids as serving runs it: the prompt in two chunks, then a
    token a call, through ONE contiguous cache of `cache_layers` layers
    (row = position) and the XLA attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.models import llama
    from production_stack_tpu.ops import attention as xla_attn

    ids = PROMPT + GEN
    t = len(ids)
    kc = jnp.zeros((mc.cache_layers, mc.num_kv_heads, t, mc.head_dim))
    vc = kc
    out = {}
    cuts = [0, CHUNK, len(PROMPT)] + list(range(len(PROMPT) + 1, t + 1))
    for a, b in zip(cuts, cuts[1:]):
        pos = jnp.arange(a, b, dtype=jnp.int32)

        def attn(q, l, k, v, pos=pos, b=b):
            return xla_attn.context_attention_prefill(
                q, k[l].swapaxes(0, 1), v[l].swapaxes(0, 1), pos,
                jnp.int32(b), mc.attn_scale)

        logits, kc, vc = llama.forward(
            mc, params, jnp.asarray(ids[a:b], jnp.int32), pos, kc, vc, pos,
            attn, logits_rows=jnp.asarray([b - a - 1]))
        out[b - 1] = np.asarray(jax.nn.log_softmax(logits[0]))
    return [float(out[len(PROMPT) - 1 + i][g]) for i, g in enumerate(GEN)]


def test_the_file_reaches_the_program_through_its_own_config_path(served):
    mc = served["mc"]
    hf = engine_child.hf_config_of(served["cell"].config, family)
    assert not set(hf) & set(manifest.COMMON_KEYS)
    assert hf["total_ut_steps"] == 3 and hf["model_type"] == "ouro"
    assert (mc.ut_steps, mc.sandwich_norm, mc.exit_gate, mc.num_layers,
            mc.cache_layers, mc.num_kv_heads, mc.qkv_bias) == (
        3, True, True, 2, 6, 4, False)
    assert not mc.layer_groups and mc.sliding_window is None


def test_the_familys_tree_is_the_tree_the_program_serves(served):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.models import llama

    mc, params = served["mc"], served["params"]
    want = jax.eval_shape(
        lambda k: llama.init_params(mc, k, jnp.float32), jax.random.key(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert got == jax.tree.map(lambda a: (a.shape, a.dtype), want)
    config = served["cell"].config
    held = sum(a.size for a in jax.tree.leaves(params["layers"]))
    assert family.layer_stack_bytes(config) == 2 * held
    assert family.total_params(config) == sum(
        a.size for a in jax.tree.leaves(params)) == mc.num_params()
    # every gain away from 1, the output gains about OUT_GAIN, the gate
    # non-zero: a dropped or swapped norm shows
    for name, mean in (("attn_norm", 1.0), ("mlp_norm", 1.0),
                       ("attn_out_norm", family.OUT_GAIN),
                       ("mlp_out_norm", family.OUT_GAIN)):
        a = np.asarray(params["layers"][name])
        assert float(np.abs(a - 1.0).min()) > 0.0, name
        assert float(a.mean()) == pytest.approx(mean, rel=0.15), name
        assert float(a.std()) == pytest.approx(
            family.GAIN_SPREAD * mean, rel=0.3), name
    assert float(np.abs(np.asarray(params["final_norm"]) - 1).min()) > 0
    assert float(np.abs(np.asarray(params["exit_gate_w"])).min()) > 0
    assert float(params["exit_gate_b"]) == family.GATE_BIAS
    assert float(np.std(np.asarray(params["embed"]))) == pytest.approx(
        1.0, rel=0.05)
    assert float(np.std(np.asarray(params["lm_head"]))) == pytest.approx(
        family.HEAD_GAIN * mc.hidden_size ** -0.5, rel=0.05)


def test_the_reference_agrees_with_prefill_then_decode_through_the_cache(
        served):
    """Float32 on both sides: 2e-4, a few roundings of logits of
    magnitude ~1 in another order of sums."""
    got = served["control"].reference(
        {"prompt_ids": PROMPT, "generated_ids": GEN})["logprobs"]
    want = program_logprobs(served["mc"], served["params"])
    assert len(got) == len(GEN)
    for a, b in zip(got, want):
        assert a == pytest.approx(b, abs=2e-4)
    assert reference.compare(want, got)["ok"]


def _reference(served, **kw):
    import jax
    import jax.numpy as jnp

    mc, params = served["mc"], served["params"]
    ids = jnp.asarray(PROMPT + GEN, jnp.int32)
    rows = jnp.arange(len(PROMPT) - 1, len(PROMPT) - 1 + len(GEN))
    with jax.default_matmul_precision("highest"):
        lp = family.forward_logprobs(mc, params, ids, rows, **kw)
    return [float(lp[i, g]) for i, g in enumerate(GEN)]


@pytest.mark.parametrize("norm", family.NORMS)
def test_a_dropped_norm_fails_the_comparison(served, norm):
    """The sublayers' output norms (g2 after attention, g4 after the
    MLP) and the norm between passes, each left out of the reference
    alone: the served log-probabilities, which agree with the true
    reference, FAIL `compare` against it."""
    want = program_logprobs(served["mc"], served["params"])
    assert reference.compare(want, _reference(served))["ok"]
    cmp_ = reference.compare(want, _reference(served, drop=(norm,)))
    assert not cmp_["ok"] and cmp_["max_abs_diff"] > 0.3


def test_a_program_without_the_output_norms_fails_the_comparison(served):
    """The other way round: the program serving the same tree as a
    plain pre-norm stack (no `sandwich_norm`) against the true
    reference."""
    off = dataclasses.replace(served["mc"], sandwich_norm=False)
    got = program_logprobs(off, served["params"])
    cmp_ = reference.compare(got, _reference(served))
    assert not cmp_["ok"] and cmp_["max_abs_diff"] > 0.3
    once = dataclasses.replace(served["mc"], ut_steps=1)
    cmp_ = reference.compare(program_logprobs(once, served["params"]),
                             _reference(served))
    assert not cmp_["ok"] and cmp_["max_abs_diff"] > 0.3


def test_one_cache_shared_among_the_passes_fails_the_comparison(served):
    """A reference whose passes share ONE cache slot a layer (the
    prompt's second chunk reading what the first chunk's last pass
    wrote) is not what the program serves: the slot `t * L + l` is
    tested, not assumed."""
    want = program_logprobs(served["mc"], served["params"])
    cmp_ = reference.compare(
        want, _reference(served, shared_cache_from=CHUNK))
    assert not cmp_["ok"] and cmp_["max_abs_diff"] > 0.3


# -- the guard ---------------------------------------------------------------
def test_check_refuses_a_program_that_would_run_the_stack_once(served):
    """`check` exits where the program's ModelConfig has no `ut_steps`
    (the parent commit's), another count than the file's, or lacks the
    output norms or the gate; and passes what `from_hf_config` builds."""
    config, mc = served["cell"].config, served["mc"]
    family.check(config, mc)
    fields = {f.name: getattr(mc, f.name) for f in dataclasses.fields(mc)
              if f.name not in ("ut_steps", "sandwich_norm", "exit_gate")}
    parent = dataclasses.make_dataclass(
        "ParentModelConfig", [(k, type(v)) for k, v in fields.items()],
        frozen=True)(**fields)
    with pytest.raises(SystemExit, match="total_ut_steps=3.*ut_steps=None"):
        family.check(config, parent)
    with pytest.raises(SystemExit, match="total_ut_steps=3.*ut_steps=1"):
        family.check(config, dataclasses.replace(mc, ut_steps=1))
    with pytest.raises(SystemExit, match="sandwich_norm=False"):
        family.check(config, dataclasses.replace(mc, sandwich_norm=False))
    with pytest.raises(SystemExit, match="exit_gate=False"):
        family.check(config, dataclasses.replace(mc, exit_gate=False))
    with pytest.raises(SystemExit, match="shapes"):
        family.check({**config, "intermediate_size": 256}, mc)
    with pytest.raises(SystemExit, match="qkv_bias=True"):
        family.check(config, dataclasses.replace(mc, qkv_bias=True))


def test_the_rehearsal_keeps_the_loop(cell):
    mc = engine_child.model_config(cell.config, family, "t-ouro-rehearsal",
                                   True)
    try:
        assert (mc.ut_steps, mc.sandwich_norm, mc.exit_gate) == (
            4, True, True)
        assert (mc.hidden_size, mc.num_heads, mc.num_kv_heads,
                mc.tie_word_embeddings) == (64, 4, 4, False)
        assert mc.max_model_len == 65536 and mc.cache_layers == 8
    finally:
        from production_stack_tpu.models import config as mcfg
        mcfg._PRESETS.pop(mc.name)
