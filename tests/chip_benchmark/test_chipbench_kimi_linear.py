"""The kimi_linear family, its configuration and its cell, as the files PR
51 added beside the benchmark's own: the widths and the cut against the
catalog, against the sizes it was reckoned by and against the tree the
program builds, the cell's metrics and traffic, the cell's sessions
against the snapshot pool, the readers on hand-made operations, and the
reference's independence of the program."""

import ast
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
CELL = "kimi-linear-ep2-l5.chat-doc16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size"}


def _load(name, path=None):
    spec = importlib.util.spec_from_file_location(
        "chipbench_kimi_" + name, path or os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


manifest = _load("manifest")
engine_child = _load("engine_child")
FAMILY_FILE = os.path.join(BENCH, "families", "kimi_linear.py")
family = manifest.load_family(FAMILY_FILE)


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


@pytest.fixture(scope="module")
def mc(cell):
    return engine_child.model_config(
        cell.config, family, "kimi-linear-ep2-l5-test", False)


# -- the configuration's file --------------------------------------------------
def test_every_width_is_as_published_and_the_cuts_are_three(cell):
    c = cell.config
    lin = c["linear_attn_config"]
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_attention_heads"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["q_lora_rank"], c["mla_use_nope"],
            lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
            c["num_experts_per_token"], c["num_shared_experts"],
            c["routed_scaling_factor"], c["first_k_dense_replace"]) == (
        2304, 9216, 1024, 32, 512, 128, 64, 128, None, True, 32, 128, 4, 8,
        1, 2.446, 1)
    assert set(c["reduced"]) == REDUCED
    assert (c["num_hidden_layers"], lin["kda_layers"],
            lin["full_attn_layers"], c["num_experts"], c["router_experts"],
            c["ep_size"], c["ep_rank"], c["vocab_size"]) == (
        5, [1, 2, 3, 5], [4], 128, 256, 2, 0, 81920)
    published = c["reduced"]["linear_attn_config"]["published"]
    assert (len(published["kda_layers"]),
            len(published["full_attn_layers"])) == (20, 7)
    assert published["kda_layers"][:4] == lin["kda_layers"]
    assert [c["reduced"][k]["published"] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")] == [
        27, 256, 163840]
    for key in ("layer_lists", "kda", "latent_attention", "routing",
                "state", "weights", "tokenizer", "kv_cache", "engine_args"):
        assert c["assumed"][key]
    assert "rank 0 of 2" in c["deployment"]
    # no new user of the flag PR 50 left parsed and ignored
    assert not [a for a in c["engine_args"] if "adaptive" in a]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == cell.config_name)
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == c["source"]


def test_every_published_number_is_in_the_file_under_its_key(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(ln) for ln in f
                   if '"Kimi-Linear-48B-A3B-Instruct"' in ln)
    assert cell.config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "linear_attn_config":
            # a nested group: copied whole, its two layer lists cut
            mine = cell.config[key]
            assert {k: v for k, v in mine.items() if "layers" not in k} == {
                k: v for k, v in value.items() if "layers" not in k}
            assert cell.config["reduced"][key]["published"] == {
                k: v for k, v in value.items() if "layers" in k}
        elif key in REDUCED:
            assert cell.config["reduced"][key]["published"] == value, key
        else:
            assert cell.config[key] == value, key
    whole = dict(row["config"], router_experts=256)
    assert family.total_params(whole) / 1e9 == pytest.approx(49.1, abs=0.1)


def test_the_counts_are_the_sizes_the_cut_was_reckoned_by(cell):
    c = cell.config
    m = 1e6
    assert family.kda_params(c) / m == pytest.approx(39.5, abs=0.05)
    assert family.latent_params(c) / m == pytest.approx(29.1, abs=0.05)
    assert family.expert_params(c) / m == pytest.approx(7.078, abs=0.001)
    assert family.expert_bytes(c) == 2 * 3 * 2304 * 1024
    assert family.expert_flops_per_row(c) == 2 * 3 * 2304 * 1024
    assert [family.layer_params(c, i) / m for i in (1, 2, 4)] == (
        pytest.approx([103.2, 953.2, 942.8], abs=0.05))
    assert 2 * c["vocab_size"] * c["hidden_size"] / m == pytest.approx(
        377.5, abs=0.05)
    # held: 4.283 B parameters, 8.57 GB; the fullest device >= 8.5 GB
    assert family.total_params(c) / 1e9 == pytest.approx(4.283, abs=0.001)
    assert family.total_params(c) * 2 / 1e9 == pytest.approx(8.57, abs=0.01)
    assert family.layer_stack_bytes(c) == 2 * sum(
        family.layer_params(c, i) for i in range(1, 6))
    # one latent layer's row of 512 + 64 dims, stored at 640 lanes
    assert family.kv_bytes_per_token(c) == 1280
    # 4 x (2 MiB + 72 KiB) = 8.28 MiB a sequence
    assert family.state_bytes_per_seq(c) == 4 * (2 * 2**20 + 72 * 2**10)
    assert family.state_bytes_per_seq(c) / 2**20 == pytest.approx(
        8.28, abs=0.01)
    assert family.state_update_bytes_per_lane(c) == 4 * 2**20


def test_the_byte_arithmetic_is_the_tree_the_program_builds(mc, cell):
    """`ModelConfig.num_params()` counts the KDA, latent and dense
    blocks exactly: the tree `init_params` builds (the program's and the
    family's, by their shapes alone) has as many, and the state group a
    sequence's slot as many bytes as the family says."""
    import jax
    import jax.numpy as jnp

    from production_stack_tpu.models import layer_groups
    from production_stack_tpu.ops import ssm

    for init in (family.init_params, layer_groups.init_params):
        tree = jax.eval_shape(
            lambda k: init(mc, k, jnp.bfloat16), jax.random.key(0))
        n = sum(a.size for a in jax.tree.leaves(tree))
        assert n == mc.num_params() == family.total_params(cell.config)
    assert mc.num_params() == 4_282_936_192
    assert mc.block_pattern == "K-KEKE*EKE"
    assert sum(len(u) for u, _, _, _ in mc.units()) == 8 and mc.switched
    assert [(u, c) for u, c, _, _ in mc.tree_units()] == [
        ("K", 4), ("-", 1), ("E", 4), ("*", 1)]
    assert mc.state_bytes_per_seq() == family.state_bytes_per_seq(
        cell.config) == 8_683_520
    assert ssm.packed_shape(mc) == (32, 128, 128)
    (mixer,), (dense,), (experts,), (attn,) = tree["segments"]
    assert mixer["w_in"].shape == (4, 2304, 12288 + 2 * 128 + 32)
    assert mixer["conv_w"].shape == (4, 4, 12288)
    assert mixer["w_gb"].shape == (4, 128, 4096)
    assert experts["w_gate"].shape == (4, 128, 2304, 1024)
    assert experts["w_down"].shape == (4, 128, 1024, 2304)
    assert experts["router"].shape == (4, 2304, 256)
    assert experts["ws_up"].shape == (4, 2304, 1024)
    assert attn["wq"].shape == (1, 2304, 32 * 192)
    assert attn["w_dkv"].shape == (1, 2304, 576)
    assert attn["w_ukv"].shape == (1, 512, 32 * 256)
    assert dense["w_up"].shape == (1, 2304, 9216)
    assert tree["embed"].shape == (81920, 2304)


def test_the_reference_imports_nothing_of_the_program():
    with open(FAMILY_FILE) as f:
        tree = ast.parse(f.read())
    fn = next(n for n in tree.body if isinstance(n, ast.FunctionDef)
              and n.name == "forward_logprobs")
    imported = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"jax"}
    top = {n.module for n in tree.body if isinstance(n, ast.ImportFrom)} | {
        a.name for n in tree.body if isinstance(n, ast.Import)
        for a in n.names}
    assert top == {"__future__", "dataclasses"}


# -- the cell ---------------------------------------------------------------------
OWN = {"kda_op_share.serve", "kda_state_stream_share.serve",
       "moe_expert_op_share.serve_k", "moe_rows_per_active_expert.serve_k",
       "latent_attn_op_share.serve_k", "prefix_state_cutback_tokens.serve_k",
       "state_snapshots_resident.serve_k"}
# what the cell reports of the metrics the benchmark had, each by
# appending the cell's name to its `workloads` (the nemotron cell's list)
SHARED = {
    "ttft_p50_ms", "ttft_p95_ms", "itl_p95_ms", "norm_latency_mean_ms",
    "request_mean_ms", "loadgen_late_p95_ms", "router_overhead_mean_ms",
    "queue_wait_mean_ms", "prefix_hit_share", "compiles_in_window.serve",
    "attn_kernel_share.serve", "device_idle_share.serve",
    "round_host_ms.serve", "round_fetch_wait_ms.serve",
    "idle_unattributed_share.serve", "decode_round_ms.serve",
    "ragged_round_ms.serve", "server_ttft_mean_ms",
    "admit_lock_wait_mean_ms", "loop_blocked_share.serve",
    "setup_trace_lower_s", "setup_backend_compile_s",
    "idle_lane_step_share.serve",
    "sampler_topk_op_share.serve", "sampler_window_step_share.serve"}


def test_the_cell_reports_exactly_its_metrics(cell):
    assert [m["name"] for m in cell.end_to_end] == ["tpot_mean_ms",
                                                    "setup_s"]
    assert cell.chips == 1
    names = {m["name"] for m in cell.per_layer}
    assert names == OWN | SHARED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_mean_ms"
        if CELL in m.get("workloads", ()):
            # the one share of a roofline here is the new kernel's, per
            # call; no share of the expert kernel's (PERF.md Open
            # questions 6) and none of a peak
            assert "mfu" not in m["name"] and "peak" not in m["name"]
            assert "roofline" not in m["name"]
    assert {m["moves"] for m in cell.per_layer} == {"tpot_mean_ms",
                                                    "setup_s"}
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"]) == (
        "kimi-linear-ep2-l5", "chat-doc16k")
    # the traffic file is xing4's and laguna's own, unedited
    t = cell.traffic
    assert (t["loop"], t["arrival"], t["stratify_seconds"],
            t["session_pool"], t["prefix_variants"],
            t["shared_prefix_tokens"]) == ("open", "poisson", 5, 48, 4,
                                           16500)
    assert 0 < t["rate_rps"] <= 6 and t["sweep"]
    with open(os.path.join(BENCH, "configs", "xing4-29b-l8.json")) as f:
        xing4 = json.load(f)["engine_args"]
    assert cell.config["engine_args"] == [
        a for a in xing4 if "adaptive" not in a]


def test_the_rate_fills_the_generators_strata(cell):
    t = cell.traffic
    strata = round(51.0 / t["stratify_seconds"])
    assert round(t["rate_rps"] * 51.0) % strata == 0


# what the decode update's operation looks like in a trace of this cell
# (my chip run, PR 51): the text `kda_state_stream_share.serve` matches
KDA_UPDATE_TEXT = (
    "%kda_state_update.3 = (f32[4,129,32,128,128]{4,3,2,1,0:T(8,128)}, "
    "f32[32,32,128]{2,1,0:T(8,128)}) custom-call(s32[97]{0} %concatenate.9, "
    "f32[4,129,32,128,128]{4,3,2,1,0:T(8,128)} %get-tuple-element.77)")


def test_the_readers_find_this_models_operations_and_nothing_on_a_parent(
        cell):
    ctx = {"trace": {"window_s": 5.0, "busy_s": 4.0, "ops": {}},
           "engine_before": {}, "engine_after": {}, "family": family,
           "config": cell.config, "window_s": 51.0, "chips": 1,
           "peak": {"hbm_bytes_per_s": 819e9}}
    for name in ("kda_state_stream_share.serve",
                 "prefix_state_cutback_tokens.serve_k",
                 "state_snapshots_resident.serve_k",
                 "moe_rows_per_active_expert.serve_k"):
        spec, read = manifest.load_reader(name)
        # a program without the operations or the counters (the parent):
        # None, not a number
        assert read(spec, ctx) is None
    spec, read = manifest.load_reader("moe_expert_op_share.serve_k")
    ctx["trace"]["ops"] = {
        "a": {"s": 2.0, "n": 10.0, "wrapper": False, "text":
              "%expert_ffn.8 = f32[512,2304]{1,0} custom-call(s32[1536] %m,"
              " bf16[512,2304,1024]{2,1,0} %wg, bf16[512,2304,1024] %wu,"
              " bf16[512,1024,2304] %wd, bf16[512,2304] %x)"},
        "b": {"s": 1.0, "n": 10.0, "wrapper": False, "text":
              "%fusion.3 = bf16[32,2304]{1,0} fusion()"}}
    assert read(spec, ctx) == pytest.approx(50.0)
    spec, read = manifest.load_reader("kda_state_stream_share.serve")
    # the counters move but the trace holds no call of the kernel: None
    steps, calls = ("tpu:ssm_lane_layer_steps_total",
                    "tpu:state_update_calls_total")
    ctx["engine_before"] = {steps: 500.0, calls: 100.0}
    ctx["engine_after"] = {steps: 48500.0, calls: 4100.0}
    assert read(spec, ctx) is None
    # 12 live lanes a call (48,000 lane-layer steps over 4,000 calls),
    # each 2 x 2 MiB at 819 GB/s = 5.12 us: 61.5 us a call at the least;
    # the kernel's 4,000 events of the trace took 0.4 s, 100 us a call
    ctx["trace"]["ops"] = {"u": {"s": 0.4, "n": 4000.0, "wrapper": False,
                                 "text": KDA_UPDATE_TEXT}}
    want = (12 * 4 * 2**20 / 819e9) / (0.4 / 4000) * 100
    assert read(spec, ctx) == pytest.approx(want, rel=1e-6)
    assert want == pytest.approx(61.5, abs=0.1)
    # an idle stretch of the trace cannot enter: the same calls in a
    # trace twice as long read the same
    ctx["trace"]["window_s"] = 10.0
    assert read(spec, ctx) == pytest.approx(want, rel=1e-6)
    ctx["engine_before"] = {"tpu:prefix_state_cutback_tokens_total": 100.0}
    ctx["engine_after"] = {"tpu:prefix_state_cutback_tokens_total": 420.0,
                           "tpu:ssm_snapshots_resident": 77.0}
    spec, read = manifest.load_reader("prefix_state_cutback_tokens.serve_k")
    assert read(spec, ctx) == 320.0
    spec, read = manifest.load_reader("state_snapshots_resident.serve_k")
    assert read(spec, ctx) == 77.0
    spec, read = manifest.load_reader("kda_op_share.serve")
    ctx["trace"]["ops"]["u"]["s"] = 1.0
    assert read(spec, ctx) == pytest.approx(25.0)


# -- the cell's sessions against the snapshot pool --------------------------------
def test_the_cells_sessions_come_back_to_their_snapshots(cell):
    """48 sessions and their replacements over four 16.5k documents
    against the pool the runner gives the cell (3 snapshots a lane, 96):
    a returning session's hit ends at the deepest boundary under its
    hashed blocks, so a turn gives up under one interval of 256 tokens
    unless its snapshot went; here a lost snapshot costs up to the whole
    document. The replay is the nemotron cell's test's, the block
    manager alone under this cell's plan."""
    replay = _load("nemotron_test", os.path.join(
        HERE, "test_chipbench_nemotron_h.py"))._replay
    hits, cut, computed, evicted = replay(cell, 96)
    assert cut / (hits + cut) < 0.02 and computed < 600
    # a pool of one snapshot a lane loses documents' worth of tokens
    small = replay(cell, 32)
    assert small[2] > 2 * computed
