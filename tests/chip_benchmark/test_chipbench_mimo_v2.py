"""The mimo_v2 family, its configuration and its cell, as the files
PR 28 added beside the benchmark's own: the counts against the sizes the
configuration was cut by, the cell's metrics, the family served under a
temporary directory and from the real tree at rehearsal widths, and the
reference against the program's forward pass with each term dropped."""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
CELL = "mimo-v2.5-ep16-l7.batch-doc8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench_mimo_" + name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


manifest = _load("manifest")
engine_child = _load("engine_child")
reference = _load("reference")
family = manifest.load_family(os.path.join(BENCH, "families", "mimo_v2.py"))


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


# -- the configuration's file --------------------------------------------------
def test_every_width_is_as_published(cell):
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["swa_num_key_value_heads"],
            c["head_dim"], c["v_head_dim"], c["sliding_window"],
            c["moe_intermediate_size"], c["intermediate_size"],
            c["router_experts"], c["num_experts_per_tok"]) == (
        4096, 64, 4, 8, 192, 128, 128, 2048, 16384, 256, 8)
    assert set(c["reduced"]) == {
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"}
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (7, 16, 19072)
    assert c["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert c["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert (c["ep_size"], c["ep_rank"]) == (16, 0)
    for word in ("16 chips", "data-parallel attention", "rank 0",
                 "8 slices"):
        assert word in c["deployment"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == cell.config_name)
    assert set(entry["reduced"]) == set(c["reduced"])
    assert entry["source"] == c["source"]


def test_every_published_number_is_in_the_file_under_its_key(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(ln) for ln in f if '"MiMo-V2.5"' in ln)
    assert cell.config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cell.config["reduced"]:
            continue
        assert cell.config[key] == value, key
    pattern = row["config"]["hybrid_layer_pattern"]
    assert cell.config["hybrid_layer_pattern"] == (
        [pattern[0]] + pattern[6:12])


def test_the_counts_are_the_sizes_the_cut_was_reckoned_by(cell):
    c = cell.config
    m = 1e6
    assert family.attention_params(c, "full") / m == pytest.approx(
        89.13, abs=0.01)
    assert family.attention_params(c, "window") / m == pytest.approx(
        94.37, abs=0.01)
    assert family.expert_params(c) / m == pytest.approx(25.17, abs=0.01)
    assert 16 * family.expert_params(c) / m == pytest.approx(402.65,
                                                             abs=0.01)
    assert family.layer_params(c, 0) / m == pytest.approx(290.46, abs=0.02)
    window_layers = sum(family.layer_params(c, i) for i in range(1, 6))
    assert window_layers / m == pytest.approx(2490.4, abs=0.2)
    assert family.layer_params(c, 6) / m == pytest.approx(492.8, abs=0.1)
    embed_and_head = 2 * c["vocab_size"] * c["hidden_size"]
    assert embed_and_head / m == pytest.approx(156.2, abs=0.1)
    # served weights 6.86 GB +- 1%
    assert family.total_params(c) * 2 / 1e9 == pytest.approx(6.86,
                                                             rel=0.01)
    assert family.layer_stack_bytes(c) == 2 * sum(
        family.layer_params(c, i) for i in range(7))
    assert family.expert_bytes(c) == 50_331_648
    assert family.expert_flops_per_row(c) == 2 * 3 * 4096 * 2048
    # KV bytes a token, at the width the chip's cache stores K (256
    # lanes; at the logical 192 they would be 5,120 and 25,600)
    by_kind = family.kv_bytes_per_token_by_kind(c)
    assert by_kind == {"full": 2 * 4 * (256 + 128) * 2,
                       "window": 5 * 8 * (256 + 128) * 2}
    assert family.kv_bytes_per_token(c) == by_kind["full"]


def test_the_cell_reports_exactly_its_metrics(cell):
    assert [m["name"] for m in cell.end_to_end] == ["output_tok_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "tokens_per_round.batch", "compiles_in_window.batch",
        "attn_kernel_share.batch", "device_idle_share.batch",
        "round_host_ms.batch", "round_fetch_wait_ms.batch",
        "idle_unattributed_share.batch", "ragged_round_ms.batch", "loop_blocked_share.batch",
        "setup_trace_lower_s", "setup_backend_compile_s",
        "moe_expert_op_share.batch", "moe_expert_roofline_share.batch",
        "moe_rows_per_active_expert.batch",
        "attn_kv_stream_share_kinds.batch",
        "kv_window_blocks_per_seq.batch"}
    t = cell.traffic
    assert (t["loop"], t["clients"], t["shared_prefix_tokens"]) == (
        "closed", 96, 8300)
    assert (t["prompt_tokens"]["median"], t["prompt_tokens"]["min"],
            t["prompt_tokens"]["max"]) == (256, 64, 640)
    assert (t["output_tokens"]["median"], t["output_tokens"]["min"],
            t["output_tokens"]["max"]) == (512, 128, 900)
    args = cell.config["engine_args"]
    assert args[args.index("--max-num-seqs") + 1] == "64"
    assert args[args.index("--max-model-len") + 1] == "16384"
    for name in ("moe_expert_roofline_share.batch",
                 "attn_kv_stream_share_kinds.batch"):
        spec, read = manifest.load_reader(name)
        # on a program without the counters (the parent): nothing to read
        assert read(spec, {"trace": {"window_s": 1.0, "busy_s": 1.0,
                                     "ops": {}},
                           "engine_before": {}, "engine_after": {},
                           "family": family, "window_s": 1.0}) is None


# -- served: under a temporary directory, and from the real tree -----------
TINY = {
    "model_type": "mimo_v2", "hidden_size": 64, "head_dim": 24,
    "v_head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 1,
    "swa_num_key_value_heads": 2, "swa_head_dim": 24, "swa_v_head_dim": 16,
    "swa_num_attention_heads": 4, "num_hidden_layers": 4,
    "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1],
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "router_experts": 16, "ep_size": 4,
    "ep_rank": 1, "num_experts_per_tok": 4, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "n_shared_experts": None,
    "routed_scaling_factor": None, "rope_theta": 1e7,
    "swa_rope_theta": 1e4, "sliding_window": 8,
    "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "vocab_size": 384,
    "max_position_embeddings": 256, "layernorm_epsilon": 1e-5,
    "tie_word_embeddings": False,
    "family": "mimo_v2", "source": "a fixture of the tests", "reduced": {},
    "assumed": {}, "deployment": "none", "chips": 1, "replicas": 1,
    "engine_args": ["--tokenizer", "byte"], "router_args": [],
}
PROMPT, GEN = list(range(5, 45)), [7, 300, 12, 99]
BS = 4


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A configuration of the family under a temporary directory (its
    file, a traffic mix, peaks, a manifest with one cell; the family
    file is the real one, found by name), through the harness's own
    path, as expert rank 1 of 4."""
    import jax.numpy as jnp

    root = tmp_path_factory.mktemp("checkout")
    for sub in ("configs", "traffic", "families"):
        (root / "bench" / sub).mkdir(parents=True)
    os.symlink(family.__file__, root / "bench" / "families" / "mimo_v2.py")
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
    mc = engine_child.model_config(c.config, fam, "fixture-tiny-mimo",
                                   False)
    params = engine_child.make_params(fam, mc, 4123456789, jnp.float32,
                                      None)
    yield {"cell": c, "mc": mc, "params": params,
           "control": engine_child.Control(fam, mc, params, "")}
    from production_stack_tpu.models import config as mcfg
    mcfg._PRESETS.pop(mc.name)


def program_logprobs(mc, params):
    """The program's own forward pass (`models/layer_groups.py`) over
    prompt + generated ids: a contiguous cache per kind (row = position,
    one block in front for the null block) and the XLA attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.models import layer_groups
    from production_stack_tpu.ops import attention as xla_attn

    ids = jnp.asarray(PROMPT + GEN, jnp.int32)
    t = ids.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    layers = [mc.layer_kinds.count(i) for i in range(len(mc.attn_kinds))]
    kc = {"g": tuple(
        jnp.zeros((layers[i], ak.num_kv_heads, t + BS, mc.head_dim))
        for i, ak in enumerate(mc.attn_kinds)),
        "map": jnp.arange(t // BS + 2, dtype=jnp.int32),
        "stats": jnp.zeros((layer_groups.N_STATS,), jnp.int32)}
    vc = {"g": tuple(
        jnp.zeros((layers[i], ak.num_kv_heads, t + BS, mc.v_dim))
        for i, ak in enumerate(mc.attn_kinds))}

    def attn(q, l, k, v, spec):
        return xla_attn.context_attention_prefill(
            q, k[l].swapaxes(0, 1)[BS:], v[l].swapaxes(0, 1)[BS:], pos,
            jnp.int32(t), mc.head_dim ** -0.5, window=spec.window,
            sink=spec.sink)

    rows = jnp.arange(len(PROMPT) - 1, len(PROMPT) - 1 + len(GEN))
    logits, _, _ = layer_groups.forward(
        mc, params, ids, pos, kc, vc, pos + BS, attn, logits_rows=rows,
        block_size=BS)
    lp = np.asarray(jax.nn.log_softmax(logits, -1))
    return [float(lp[i, g]) for i, g in enumerate(GEN)]


def test_the_file_reaches_the_program_through_its_own_config_path(served):
    mc = served["mc"]
    hf = engine_child.hf_config_of(served["cell"].config, family)
    assert hf["n_routed_experts"] == 16 and "router_experts" not in hf
    assert not set(hf) & set(manifest.COMMON_KEYS)
    assert mc.layer_groups and (mc.router_experts, mc.local_experts,
                                mc.ep_rank) == (16, 4, 1)
    assert [k.num_kv_heads for k in mc.attn_kinds] == [1, 2]
    assert (mc.head_dim, mc.v_dim, mc.rope_dim, mc.v_scale) == (
        24, 16, 8, 0.707)


def test_the_familys_tree_is_the_tree_the_program_serves(served):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.models import layer_groups

    mc, params = served["mc"], served["params"]
    want = jax.eval_shape(
        lambda k: layer_groups.init_params(mc, k, jnp.float32),
        jax.random.key(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert got == jax.tree.map(lambda a: (a.shape, a.dtype), want)
    held = sum(a.size for seg in params["segments"]
               for a in jax.tree.leaves(seg))
    assert family.layer_stack_bytes(served["cell"].config) == 2 * held
    assert family.total_params(served["cell"].config) == sum(
        a.size for a in jax.tree.leaves(params)) == mc.num_params()
    for seg in params["segments"]:
        for name in ("sink", "router_bias"):
            if name in seg:
                assert float(np.abs(np.asarray(seg[name])).min()) > 0.0


def test_the_seeded_weights_have_the_scales_the_family_states(served):
    """One standard deviation, hidden ** -0.5, for every matrix and unit
    variance for the embedding's entries (`init_params` says why: a
    token's row must not drown in the first attention layer's output,
    and one expert must stay small beside the stream)."""
    import numpy as np

    mc, params = served["mc"], served["params"]
    std = mc.hidden_size ** -0.5
    assert float(np.std(np.asarray(params["embed"]))) == pytest.approx(
        1.0, rel=0.05)
    assert float(np.std(np.asarray(params["lm_head"]))) == pytest.approx(
        std, rel=0.05)
    for seg in params["segments"]:
        for name, a in seg.items():
            if name in ("wq", "wk", "wv", "wo", "router", "w_gate", "w_up",
                        "w_down"):
                assert float(np.std(np.asarray(a))) == pytest.approx(
                    std, rel=0.1), name


def test_the_reference_agrees_with_the_programs_forward_pass(served):
    got = served["control"].reference(
        {"prompt_ids": PROMPT, "generated_ids": GEN})["logprobs"]
    want = program_logprobs(served["mc"], served["params"])
    assert len(got) == len(GEN)
    for a, b in zip(got, want):
        assert a == pytest.approx(b, abs=2e-4)


@pytest.mark.parametrize("term", ["sink", "router_bias", "router"])
def test_a_zeroed_term_fails_the_comparison(served, term):
    import jax.numpy as jnp

    params = served["params"]
    if term == "router_bias":
        # among 16 experts the seeded bias (0.1 N(0,1), sized for a
        # router of 256) seldom changes the chosen four: the true model
        # of this case carries it ten times as large
        params = {**params, "segments": [
            {k: (10.0 * v if k == term else v) for k, v in seg.items()}
            for seg in params["segments"]]}
    zeroed = {**params, "segments": [
        {k: (jnp.zeros_like(v) if k == term else v) for k, v in seg.items()}
        for seg in params["segments"]]}
    body = {"prompt_ids": PROMPT, "generated_ids": GEN}
    true = engine_child.Control(
        family, served["mc"], params, "").reference(body)["logprobs"]
    off = engine_child.Control(
        family, served["mc"], zeroed, "").reference(body)["logprobs"]
    assert max(abs(a - b) for a, b in zip(off, true)) > 1e-3
    # the program serving the zeroed tree agrees with the reference of
    # the zeroed tree and fails against the true one
    program = program_logprobs(served["mc"], zeroed)
    assert reference.compare(program, off)["ok"]
    assert not reference.compare(program, true)["ok"]


def test_the_real_configuration_serves_at_rehearsal_widths(cell):
    """From the real tree: the real file through `from_hf_config` and
    the family's `check` at published widths, then the rehearsal's tiny
    widths served and compared."""
    import jax.numpy as jnp

    mc = engine_child.model_config(cell.config, family, "fixture-real-mimo",
                                   False)
    from production_stack_tpu.models import config as mcfg
    try:
        assert (mc.hidden_size, mc.num_layers, mc.router_experts,
                mc.local_experts, mc.vocab_size) == (4096, 7, 256, 16,
                                                     19072)
        assert mc.segments() == ((0, False, 1, 0), (1, True, 5, 0),
                                 (0, True, 1, 1))
        assert mc.num_params() == family.total_params(cell.config)
        small = family.rehearsal_config(mc, 1)
        assert small.layer_groups and small.name == mc.name
        assert small.hidden_size == mcfg.TINY_GROUPS_DEBUG.hidden_size
        params = engine_child.make_params(family, small, 3987654321,
                                          jnp.float32, None)
        got = engine_child.Control(family, small, params, "").reference(
            {"prompt_ids": PROMPT, "generated_ids": GEN})["logprobs"]
        want = program_logprobs(small, params)
        assert max(abs(a - b) for a, b in zip(got, want)) < 2e-4
    finally:
        mcfg._PRESETS.pop(mc.name)


@pytest.mark.parametrize("key,value,says", [
    ("v_head_dim", 32, "v head dim"),
    ("swa_num_key_value_heads", 4, "kv heads"),
    ("n_routed_experts", 8, "experts held"),
    ("attention_value_scale", 1.0, "v scale"),
    ("sliding_window", 16, "windows"),
])
def test_check_refuses_a_file_that_disagrees_with_the_program(
        served, key, value, says):
    config = dict(served["cell"].config, **{key: value})
    with pytest.raises(SystemExit, match=says):
        family.check(config, served["mc"])
