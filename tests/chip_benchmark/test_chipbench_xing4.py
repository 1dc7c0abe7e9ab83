"""The xing4 family, its configuration and its cell, as the files PR 33
added beside the benchmark's own: the widths and the cut against the
sizes it was reckoned by, the cell's metrics and traffic, the family
served under a temporary directory and from the real tree at rehearsal
widths, and the reference against the program's forward pass with each
term dropped."""

import dataclasses
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
CELL = "xing4-29b-l8.chat-doc16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench_xing4_" + name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


manifest = _load("manifest")
engine_child = _load("engine_child")
reference = _load("reference")
family = manifest.load_family(os.path.join(BENCH, "families", "xing4.py"))


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


# -- the configuration's file --------------------------------------------------
def test_every_width_is_as_published(cell):
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["n_routed_experts"],
            c["n_shared_experts"], c["num_experts_per_tok"],
            c["routed_scaling_factor"], c["hc_mult"], c["vocab_size"],
            c["ep_size"]) == (
        3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 64, 1, 4, 2, 4,
        131072, 1)
    assert set(c["reduced"]) == {"num_hidden_layers"}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"]) == (8, 2)
    assert "num_nextn_predict_layers" in c["assumed"]["left_out"]
    for key in ("streams", "sinkhorn_order", "rotary", "weights"):
        assert c["assumed"][key]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == cell.config_name)
    assert set(entry["reduced"]) == set(c["reduced"])
    assert entry["source"] == c["source"]


def test_every_published_number_is_in_the_file_under_its_key(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(ln) for ln in f
                   if '"Xing4.0-29B-A4B"' in ln)
    assert cell.config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cell.config["reduced"]:
            continue
        assert cell.config[key] == value, key


def test_the_counts_are_the_sizes_the_cut_was_reckoned_by(cell):
    c = cell.config
    m = 1e6
    # W_dq 2.75 + W_uq 4.72 + W_dkv 2.06 + W_ukv 4.19 + W_o 14.68
    assert family.attention_params(c) / m == pytest.approx(28.41, abs=0.01)
    assert family.mixing_params(c) / m == pytest.approx(0.69, abs=0.01)
    assert family.expert_params(c) / m == pytest.approx(11.01, abs=0.01)
    assert 64 * family.expert_params(c) / m == pytest.approx(704.64,
                                                             abs=0.01)
    # an expert layer 744.98 M = 1.490 GB, a dense one 128.19 M
    assert family.layer_params(c, 2) / m == pytest.approx(744.98, abs=0.05)
    assert family.layer_params(c, 2) * 2 / 1e9 == pytest.approx(1.490,
                                                                abs=0.001)
    assert family.layer_params(c, 0) / m == pytest.approx(128.19, abs=0.05)
    assert family.layer_params(c, 1) == family.layer_params(c, 0)
    assert family.layer_params(c, 7) == family.layer_params(c, 2)
    embed_and_head = 2 * c["vocab_size"] * c["hidden_size"]
    assert embed_and_head * 2 / 1e9 == pytest.approx(1.879, abs=0.001)
    # served weights 11.33 GB
    assert family.total_params(c) * 2 / 1e9 == pytest.approx(11.33,
                                                             abs=0.01)
    assert family.layer_stack_bytes(c) == 2 * sum(
        family.layer_params(c, i) for i in range(8))
    assert family.expert_bytes(c) == 2 * 3 * 3584 * 1024
    assert family.expert_flops_per_row(c) == 2 * 3 * 3584 * 1024
    # one cached row a token and layer: 640 stored lanes of bf16 (576 are
    # the architecture's), no V array
    assert family.kv_bytes_per_token_by_kind(c) == {"latent": 8 * 1280}
    assert family.kv_bytes_per_token(c) == 10240
    # the whole published model: 29B
    whole = dict(c, num_hidden_layers=40)
    assert family.total_params(whole) / 1e9 == pytest.approx(29.0, abs=1.0)


def test_the_cell_reports_exactly_its_metrics(cell):
    assert [m["name"] for m in cell.end_to_end] == ["tpot_mean_ms",
                                                    "setup_s"]
    own = {"attn_kv_stream_share_kinds.serve", "latent_attn_op_share.serve",
           "moe_expert_op_share.serve", "moe_expert_roofline_share.serve",
           "moe_rows_per_active_expert.serve", "hc_mix_op_share.serve"}
    names = {m["name"] for m in cell.per_layer}
    assert own <= names
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    for m in per_layer:
        if m["name"] in own:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_mean_ms"
    # every metric of the cell moves one of its end-to-end metrics, and
    # none of another mix's is there (`decode_step_ms.serve` finds no
    # scan that carries [lanes, hidden] in a model of four streams)
    assert {m["moves"] for m in cell.per_layer} == {"tpot_mean_ms",
                                                    "setup_s"}
    assert not [n for n in names if n.endswith(".batch")]
    assert "decode_step_ms.serve" not in names
    t = cell.traffic
    assert (t["loop"], t["arrival"], t["stratify_seconds"],
            t["session_pool"], t["prefix_variants"],
            t["shared_prefix_tokens"], t["drain_seconds"]) == (
        "open", "poisson", 5, 48, 4, 16500, 30)
    assert t["history"] == {
        "enabled": True, "retire_context_tokens": 18500,
        "initial_tokens": {"dist": "uniform", "min": 100, "max": 700}}
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 200,
                                  "sigma": 1.0, "min": 16, "max": 700}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 150,
                                  "sigma": 0.7, "min": 16, "max": 384}
    assert t["setup"] == {"turn0_output_tokens": 16, "warm_seconds": 5}
    assert 0 < t["rate_rps"] <= 6 and t["sweep"]
    args = cell.config["engine_args"]
    assert args[args.index("--max-num-seqs") + 1] == "32"
    assert args[args.index("--max-model-len") + 1] == "32768"
    for name in ("moe_expert_roofline_share.serve",
                 "attn_kv_stream_share_kinds.serve"):
        spec, read = manifest.load_reader(name)
        # on a program without the counters (the parent): nothing to read
        assert read(spec, {"trace": {"window_s": 1.0, "busy_s": 1.0,
                                     "ops": {}},
                           "engine_before": {}, "engine_after": {},
                           "family": family, "window_s": 1.0}) is None


def test_the_new_readers_find_this_models_operations(cell):
    """The op-share metrics match the shapes this model's programs
    carry: the four-stream axis, the experts' 3584 x 1024 matrices, the
    attention kernels by name."""
    ops = {
        "a": {"s": 2.0, "wrapper": False, "text":
              "%fusion.1 = bf16[4,32,3584]{2,1,0} fusion(f32[4,4,32] %p)"},
        "b": {"s": 1.0, "wrapper": False, "text":
              "%ragged-dot.3 = f32[128,1024] ragged-dot(bf16[128,3584] %x,"
              " bf16[384,3584,1024] %w)"},
        "c": {"s": 3.0, "wrapper": False, "text":
              "%ragged_paged_attention.2 = bf16[32,32,512] custom-call()"},
        "d": {"s": 4.0, "wrapper": False, "text":
              "%fusion.9 = bf16[32,9216] fusion(bf16[32,3584] %y)"},
    }
    ctx = {"trace": {"busy_s": 10.0, "window_s": 10.0, "ops": ops}}
    got = {}
    for name in ("hc_mix_op_share.serve", "moe_expert_op_share.serve",
                 "latent_attn_op_share.serve"):
        spec, read = manifest.load_reader(name)
        got[name] = read(spec, ctx)
    assert got == {"hc_mix_op_share.serve": 20.0,
                   "moe_expert_op_share.serve": 10.0,
                   "latent_attn_op_share.serve": 30.0}


# -- served: under a temporary directory, and from the real tree -----------
TINY = {
    "model_type": "xing4_0", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_hidden_layers": 4, "first_k_dense_replace": 1,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "routed_scaling_factor": 2,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1, "ep_size": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rope_theta": 10000, "rope_scaling": {
        "type": "yarn", "factor": 4, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    "num_nextn_predict_layers": 1, "attention_bias": False,
    "hidden_act": "silu", "vocab_size": 384,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "family": "xing4", "source": "a fixture of the tests", "reduced": {},
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
    path."""
    import jax.numpy as jnp

    root = tmp_path_factory.mktemp("checkout")
    for sub in ("configs", "traffic", "families"):
        (root / "bench" / sub).mkdir(parents=True)
    os.symlink(family.__file__, root / "bench" / "families" / "xing4.py")
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
    mc = engine_child.model_config(c.config, fam, "fixture-tiny-xing4",
                                   False)
    params = engine_child.make_params(fam, mc, 4123456789, jnp.float32,
                                      None)
    yield {"cell": c, "mc": mc, "params": params,
           "control": engine_child.Control(fam, mc, params, "")}
    from production_stack_tpu.models import config as mcfg
    mcfg._PRESETS.pop(mc.name)


def program_logprobs(mc, params):
    """The program's own forward pass (`models/layer_groups.py`, latent
    attention absorbed) over prompt + generated ids: a contiguous latent
    cache (row = position, one block in front for the null block), no V
    cache, and the XLA attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.models import layer_groups
    from production_stack_tpu.ops import attention as xla_attn

    ids = jnp.asarray(PROMPT + GEN, jnp.int32)
    t = ids.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    lat = mc.attn_kinds[0].latent_dim
    kc = {"g": (jnp.zeros((mc.num_layers, 1, t + BS, lat + mc.rope_dim)),),
          "map": jnp.arange(t // BS + 2, dtype=jnp.int32),
          "stats": jnp.zeros((layer_groups.N_STATS,), jnp.int32)}
    vc = {"g": (None,)}

    def attn(q, l, k, v, spec):
        assert v is None and spec.latent_v == lat
        rows = k[l].swapaxes(0, 1)[BS:]
        return xla_attn.context_attention_prefill(
            q, rows, rows[..., :lat], pos, jnp.int32(t), mc.attn_scale)

    rows = jnp.arange(len(PROMPT) - 1, len(PROMPT) - 1 + len(GEN))
    logits, _, _ = layer_groups.forward(
        mc, params, ids, pos, kc, vc, pos + BS, attn, logits_rows=rows,
        block_size=BS)
    lp = np.asarray(jax.nn.log_softmax(logits, -1))
    return [float(lp[i, g]) for i, g in enumerate(GEN)]


def test_the_file_reaches_the_program_through_its_own_config_path(served):
    mc = served["mc"]
    hf = engine_child.hf_config_of(served["cell"].config, family)
    assert not set(hf) & set(manifest.COMMON_KEYS)
    assert hf["ep_size"] == 1 and hf["n_routed_experts"] == 16
    assert mc.layer_groups and (mc.router_experts, mc.local_experts,
                                mc.ep_size) == (16, 16, 1)
    assert [(k.num_kv_heads, k.latent_dim) for k in mc.attn_kinds] == [
        (1, 32)]
    assert (mc.head_dim, mc.v_dim, mc.rope_dim, mc.q_lora_rank,
            mc.hc_mult, mc.shared_experts, mc.routed_scaling) == (
        24, 16, 8, 24, 4, 1, 2.0)
    assert mc.rope_yarn.factor == 4.0 and mc.segments() == (
        (0, False, 1, 0), (0, True, 3, 1))


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
        for name, a in seg.items():
            if name.startswith("hc_") or name in (
                    "router_bias", "ws_gate", "ws_up", "ws_down"):
                assert float(np.abs(np.asarray(a)).min()) > 0.0, name


def test_the_seeded_weights_have_the_scales_the_family_states(served):
    import numpy as np

    mc, params = served["mc"], served["params"]
    std = mc.hidden_size ** -0.5
    assert float(np.std(np.asarray(params["embed"]))) == pytest.approx(
        1.0, rel=0.05)
    assert float(np.std(np.asarray(params["lm_head"]))) == pytest.approx(
        std, rel=0.05)
    n = mc.hc_mult
    for seg in params["segments"]:
        for name, a in seg.items():
            a = np.asarray(a)
            if name in ("w_dq", "w_uq", "w_dkv", "w_ukv", "wo", "router",
                        "w_gate", "w_up", "ws_gate", "ws_up", "ws_down"):
                assert float(np.std(a)) == pytest.approx(std, rel=0.1), name
            elif name == "w_down":
                gain = family.EXPERT_DOWN_GAIN if "router" in seg else 1.0
                assert float(np.std(a)) == pytest.approx(
                    std * gain, rel=0.1), name
            elif name.endswith("_phi"):
                assert float(np.std(a)) == pytest.approx(
                    (n * mc.hidden_size) ** -0.5, rel=0.1), name
            elif name.endswith("_b"):
                # H_res's diagonal is favoured: a stream stays mostly
                # its own
                res = a[:, 2 * n:].reshape(-1, n, n)
                diag = np.einsum("lii->l", res) / n
                off = (res.sum((1, 2)) - n * diag) / (n * n - n)
                assert (diag - off).mean() == pytest.approx(
                    family.HC_RES_DIAGONAL, abs=0.8), name


def test_the_reference_agrees_with_the_programs_forward_pass(served):
    got = served["control"].reference(
        {"prompt_ids": PROMPT, "generated_ids": GEN})["logprobs"]
    want = program_logprobs(served["mc"], served["params"])
    assert len(got) == len(GEN)
    for a, b in zip(got, want):
        assert a == pytest.approx(b, abs=2e-4)


def _without(params, term):
    """The tree with one term zeroed or dropped."""
    import jax.numpy as jnp

    def seg_map(fn):
        return {**params, "segments": [
            {k: fn(k, v) for k, v in seg.items()}
            for seg in params["segments"]]}

    if term == "shared expert":
        return seg_map(lambda k, v: jnp.zeros_like(v)
                       if k == "ws_down" else v)
    if term in ("alpha", "b", "phi"):
        return seg_map(lambda k, v: jnp.zeros_like(v)
                       if k.startswith("hc_") and k.endswith("_" + term)
                       else v)
    assert term == "router_bias"
    return seg_map(lambda k, v: jnp.zeros_like(v) if k == term else v)


@pytest.mark.parametrize("term", [
    "shared expert", "scaling factor", "alpha", "b", "phi", "mscale",
    "yarn blend", "router_bias"])
def test_a_dropped_term_fails_the_comparison(served, term):
    """Each of the shared expert, the scaling factor 2, the mixing's
    alpha, b and phi, the softmax scale's mscale squared, YaRN's blend
    of frequencies and the selection bias, zeroed in the tree or dropped
    from the configuration: the program serving the changed model agrees
    with the reference of the changed model and FAILS `compare` against
    the true one."""
    mc, params = served["mc"], served["params"]
    if term == "router_bias":
        # among 16 experts the seeded bias (0.1 N(0,1), sized for a
        # router of 64) seldom changes the chosen four: the true model
        # of this case carries it ten times as large
        params = {**params, "segments": [
            {k: (10.0 * v if k == term else v) for k, v in seg.items()}
            for seg in params["segments"]]}
    if term == "scaling factor":
        # the routed experts' down projections carry the family's gain
        # of 0.1, sized so that ONE flipped expert stays small at
        # published widths; the true model of this case carries them
        # at the common deviation, where the routed sum is a term the
        # comparison can see
        params = {**params, "segments": [
            {k: (v / family.EXPERT_DOWN_GAIN if k == "w_down"
                 and "router" in seg else v) for k, v in seg.items()}
            for seg in params["segments"]]}
    off_mc, off_params = mc, params
    if term == "scaling factor":
        off_mc = dataclasses.replace(mc, routed_scaling=1.0)
    elif term == "mscale":
        off_mc = dataclasses.replace(mc, rope_yarn=dataclasses.replace(
            mc.rope_yarn, mscale_all_dim=0.0, mscale=0.0))
    elif term == "yarn blend":
        # plain rotary frequencies, the softmax scale kept
        off_mc = dataclasses.replace(mc, rope_yarn=dataclasses.replace(
            mc.rope_yarn, beta_fast=1e-9, beta_slow=1e-9 / 2))
    else:
        off_params = _without(params, term)
    body = {"prompt_ids": PROMPT, "generated_ids": GEN}
    true = engine_child.Control(
        family, mc, params, "").reference(body)["logprobs"]
    off = engine_child.Control(
        family, off_mc, off_params, "").reference(body)["logprobs"]
    assert max(abs(a - b) for a, b in zip(off, true)) > 1e-3
    program = program_logprobs(off_mc, off_params)
    assert reference.compare(program, off)["ok"]
    assert not reference.compare(program, true)["ok"]


def test_the_real_configuration_serves_at_rehearsal_widths(cell):
    """From the real tree: the real file through `from_hf_config` and
    the family's `check` at published widths, then the rehearsal's tiny
    widths served and compared."""
    import jax.numpy as jnp

    mc = engine_child.model_config(cell.config, family,
                                   "fixture-real-xing4", False)
    from production_stack_tpu.models import config as mcfg
    try:
        assert (mc.hidden_size, mc.num_layers, mc.router_experts,
                mc.local_experts, mc.vocab_size, mc.hc_mult) == (
            3584, 8, 64, 64, 131072, 4)
        assert mc.segments() == ((0, False, 2, 0), (0, True, 6, 2))
        assert mc.num_params() == family.total_params(cell.config)
        assert mc.attn_scale == pytest.approx(
            192 ** -0.5 * 1.4159 ** 2, rel=1e-4)
        small = family.rehearsal_config(mc, 1)
        assert small.layer_groups and small.name == mc.name
        assert small.hidden_size == mcfg.TINY_LATENT_DEBUG.hidden_size
        assert small.max_model_len == 262144
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
    ("kv_lora_rank", 64, "latent dims"),
    ("n_shared_experts", 2, "shared experts"),
    ("routed_scaling_factor", 1.0, "scaling factor"),
    ("hc_mult", 2, "streams"),
    ("first_k_dense_replace", 2, "dense layers"),
])
def test_check_refuses_a_file_that_disagrees_with_the_program(
        served, key, value, says):
    config = dict(served["cell"].config, **{key: value})
    with pytest.raises(SystemExit, match=says):
        family.check(config, served["mc"])
