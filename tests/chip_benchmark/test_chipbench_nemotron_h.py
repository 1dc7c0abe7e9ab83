"""The nemotron_h family, its configuration and its cell, as the files PR
45 added beside the benchmark's own: the widths and the cut against the
sizes it was reckoned by and against the tree the program builds, the
cell's metrics and traffic, the cell's sessions against the snapshot
pool, the readers on hand-made operations, and the reference's
independence of the program."""

import ast
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
CELL = "nemotron3-super-ep4-l11.chat-sys2k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"}


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench_nemotron_" + name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


manifest = _load("manifest")
engine_child = _load("engine_child")
loadgen = _load("loadgen")
FAMILY_FILE = os.path.join(BENCH, "families", "nemotron_h.py")
family = manifest.load_family(FAMILY_FILE)


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


@pytest.fixture(scope="module")
def mc(cell):
    return engine_child.model_config(
        cell.config, family, "nemotron3-super-ep4-l11-test", False)


# -- the configuration's file --------------------------------------------------
def test_every_width_is_as_published(cell):
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["mamba_num_heads"],
            c["mamba_head_dim"], c["n_groups"], c["ssm_state_size"],
            c["conv_kernel"], c["chunk_size"], c["moe_latent_size"],
            c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"],
            c["num_experts_per_tok"], c["routed_scaling_factor"],
            c["mlp_hidden_act"]) == (
        4096, 32, 2, 128, 128, 64, 8, 128, 4, 128, 1024, 2688, 5376, 22, 5,
        "relu2")
    assert set(c["reduced"]) == REDUCED
    assert (c["num_hidden_layers"], c["hybrid_override_pattern"],
            c["n_routed_experts"], c["router_experts"], c["ep_size"],
            c["ep_rank"], c["vocab_size"]) == (
        11, "EMEMEMEMEM*", 128, 512, 4, 0, 32768)
    published = c["reduced"]["hybrid_override_pattern"]["published"]
    assert published[26:37] == c["hybrid_override_pattern"]
    assert (published.count("M"), published.count("E"),
            published.count("*"), len(published)) == (40, 40, 8, 88)
    for key in ("attention_without_positions", "router_input",
                "latent_experts", "mamba", "state", "mtp", "weights",
                "tokenizer", "engine_args"):
        assert c["assumed"][key]
    assert "rank 0 of 4" in c["deployment"]
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
                   if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in ln)
    assert cell.config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cell.config["reduced"][key]["published"] == value, key
        else:
            assert cell.config[key] == value, key
    whole = dict(row["config"], router_experts=512)
    assert family.total_params(whole) / 1e9 == pytest.approx(120.7, abs=0.1)


def test_the_counts_are_the_sizes_the_cut_was_reckoned_by(cell):
    c = cell.config
    m = 1e6
    assert (family.layer_params(c, 1) / m, family.layer_params(c, 10) / m,
            family.layer_params(c, 0) / m) == pytest.approx(
        (109.6, 35.7, 759.2), abs=0.05)
    assert family.expert_params(c) / m == pytest.approx(5.505, abs=0.001)
    assert family.expert_bytes(c) == 2 * 2 * 1024 * 2688
    assert family.expert_flops_per_row(c) == 2 * 2 * 1024 * 2688
    assert 2 * c["vocab_size"] * c["hidden_size"] / m == pytest.approx(
        268.4, abs=0.05)
    # held: 4.65 B parameters, 9.30 GB; the fullest device >= 9 GB
    assert family.total_params(c) / 1e9 == pytest.approx(4.648, abs=0.001)
    assert family.total_params(c) * 2 / 1e9 == pytest.approx(9.30, abs=0.01)
    assert family.layer_stack_bytes(c) == 2 * sum(
        family.layer_params(c, i) for i in range(11))
    assert family.kv_bytes_per_token(c) == 1024
    # 5 x (4 MiB + 60 KiB) = 20.3 MiB a sequence
    assert family.state_bytes_per_seq(c) == 5 * (4 * 2**20 + 60 * 2**10)
    assert family.state_bytes_per_seq(c) / 2**20 == pytest.approx(
        20.3, abs=0.01)


def test_the_byte_arithmetic_is_the_tree_the_program_builds(mc, cell):
    """`ModelConfig.num_params()` counts the state-space and the
    latent-expert weights exactly: the tree `init_params` builds (the
    program's and the family's, by their shapes alone) has as many, and
    the state group a sequence's slot as many bytes as the family says."""
    import jax
    import jax.numpy as jnp

    from production_stack_tpu.models import layer_groups

    for init in (family.init_params, layer_groups.init_params):
        tree = jax.eval_shape(
            lambda k: init(mc, k, jnp.bfloat16), jax.random.key(0))
        n = sum(a.size for a in jax.tree.leaves(tree))
        assert n == mc.num_params() == family.total_params(cell.config)
    assert mc.num_params() == 4_648_163_712
    assert mc.units() == (("EM", 5, 0, 0), ("*", 1, 0, 5))
    assert mc.state_bytes_per_seq() == family.state_bytes_per_seq(
        cell.config) == 21_278_720
    (experts, mixer), (attn,) = tree["segments"]
    assert experts["w_up"].shape == (5, 128, 1024, 2688)
    assert experts["w_down"].shape == (5, 128, 2688, 1024)
    assert experts["router"].shape == (5, 4096, 512)
    assert experts["ws_up"].shape == (5, 4096, 5376)
    assert mixer["w_in"].shape == (5, 4096, 8192 + 10240 + 128)
    assert mixer["conv_w"].shape == (5, 4, 10240)
    assert attn["wq"].shape == (1, 4096, 4096)
    assert attn["wk"].shape == (1, 4096, 256)
    assert tree["embed"].shape == (32768, 4096)


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
OWN = {"ssm_op_share.serve", "ssm_state_stream_share.serve",
       "moe_expert_op_share.serve_lat", "moe_rows_per_active_expert.serve_lat",
       "prefix_state_cutback_tokens.serve", "ssm_snapshots_resident.serve"}
# what the cell reports of the metrics the benchmark had, each by
# appending the cell's name to its `workloads` (the laguna cell's list)
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
        per_layer = json.load(f)["per_layer"]
    for m in per_layer:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_mean_ms"
        if CELL in m.get("workloads", ()):
            # the one share of a roofline here divides a trace's calls by
            # the same trace's seconds (PERF.md Open questions 6)
            assert "mfu" not in m["name"] and "peak" not in m["name"]
            assert ("roofline" not in m["name"]
                    and "moe_expert_roofline" not in m["name"])
    assert {m["moves"] for m in cell.per_layer} == {"tpot_mean_ms",
                                                    "setup_s"}
    t = cell.traffic
    assert (t["loop"], t["arrival"], t["stratify_seconds"],
            t["session_pool"], t["prefix_variants"],
            t["shared_prefix_tokens"]) == ("open", "poisson", 5, 64, 4, 2100)
    assert 0 < t["rate_rps"] <= 6 and t["sweep"]
    with open(os.path.join(BENCH, "configs", "mistral-7b-l16.json")) as f:
        dense = json.load(f)["engine_args"]
    mine = cell.config["engine_args"]
    extra = ["--max-prefill-chunk", "256", "--max-prefill-seqs", "2"]
    assert [a for a in mine if a not in extra] == dense
    assert all(a in mine for a in extra)


def test_the_rate_fills_the_generators_strata(cell):
    t = cell.traffic
    for span in (51.0, t["setup"]["warm_seconds"]):
        strata = round(span / t["stratify_seconds"])
        assert round(t["rate_rps"] * span) % strata == 0, span


def test_the_readers_find_this_models_operations_and_nothing_on_a_parent(
        cell):
    ctx = {"trace": {"window_s": 5.0, "busy_s": 4.0, "ops": {}},
           "engine_before": {}, "engine_after": {}, "family": family,
           "config": cell.config, "window_s": 51.0, "chips": 1,
           "peak": {"hbm_bytes_per_s": 819e9}}
    for name in ("ssm_state_stream_share.serve",
                 "prefix_state_cutback_tokens.serve",
                 "ssm_snapshots_resident.serve",
                 "moe_rows_per_active_expert.serve_lat"):
        spec, read = manifest.load_reader(name)
        # a program without the operations or the counters (the parent)
        assert read(spec, ctx) is None
    spec, read = manifest.load_reader("moe_expert_op_share.serve_lat")
    ctx["trace"]["ops"] = {
        "a": {"s": 2.0, "n": 10.0, "wrapper": False, "text":
              "%expert_ffn.8 = f32[512,1024]{1,0} custom-call(s32[1536] %m,"
              " bf16[640,1024,2688]{2,1,0} %wu, bf16[640,2688,1024] %wd,"
              " bf16[512,1024] %x)"},
        "b": {"s": 1.0, "n": 10.0, "wrapper": False, "text":
              "%fusion.3 = bf16[32,4096]{1,0} fusion()"}}
    assert read(spec, ctx) == pytest.approx(50.0)
    spec, read = manifest.load_reader("ssm_state_stream_share.serve")
    # the kernel by its name: 0.25 s of a 5 s trace; 51,000 updates of
    # lanes that hold a sequence in the 51 s window, each 2 x (4 MiB +
    # 60 KiB) at 819 GB/s = 10.39 us: 0.0104 of the window against 0.05
    ctx["trace"]["ops"] = {"u": {"s": 0.25, "n": 4000.0, "wrapper": False,
                                 "text": SSM_UPDATE_TEXT}}
    name = "tpu:ssm_lane_layer_steps_total"
    ctx["engine_before"], ctx["engine_after"] = {name: 500.0}, {
        name: 51500.0}
    want = (51000 * 2 * (4 * 2**20 + 60 * 2**10) / 819e9 / 51.0) / (
        0.25 / 5.0) * 100
    assert read(spec, ctx) == pytest.approx(want, rel=1e-6)
    assert want == pytest.approx(20.8, abs=0.1)
    ctx["engine_before"] = {"tpu:prefix_state_cutback_tokens_total": 100.0}
    ctx["engine_after"] = {"tpu:prefix_state_cutback_tokens_total": 420.0,
                           "tpu:ssm_snapshots_resident": 77.0}
    spec, read = manifest.load_reader("prefix_state_cutback_tokens.serve")
    assert read(spec, ctx) == 320.0
    spec, read = manifest.load_reader("ssm_snapshots_resident.serve")
    assert read(spec, ctx) == 77.0


# what the decode update's operation looks like in a trace of this cell
# (my chip run, PR 45): the text `ssm_state_stream_share.serve` matches
SSM_UPDATE_TEXT = (
    "%ssm_state_update.3 = (f32[5,129,64,128,128]{4,3,2,1,0:T(8,128)}, "
    "f32[32,64,128]{2,1,0:T(8,128)}) custom-call(s32[97]{0} %concatenate.9, "
    "f32[5,129,64,128,128]{4,3,2,1,0:T(8,128)} %get-tuple-element.77)")


# -- the cell's sessions against the snapshot pool --------------------------------
def _replay(cell, snapshots, seed=4123456789, lanes=32):
    """The cell's plan (set-up and a window's turns, one request at a
    time) against the block manager alone, no model: prompts by their
    token COUNTS, their content fresh ids so that hashes chain. ->
    (hit tokens granted, hit tokens cut back) in the window, prompt
    tokens a turn of the window had to compute, evictions."""
    from production_stack_tpu.engine.block_manager import StateBlockManager

    bs, chunk = 32, 256
    plan = loadgen.build_plan(cell.traffic, seed, 51)
    bm = StateBlockManager(16384, bs, True, num_state_slots=lanes,
                           num_snapshots=snapshots,
                           interval_blocks=chunk // bs)
    fresh_from = [1000]

    def fresh(n):
        first = fresh_from[0]
        fresh_from[0] += n
        return list(range(first, first + n))

    def serve(tokens, n_out):
        table, cached = bm.allocate_prompt(tokens)
        n, done = len(tokens), cached // bs
        prev = bm.blocks[table[done - 1]].block_hash if done else 0
        tokens = tokens + fresh(n_out)

        def register(upto):
            nonlocal prev, done
            for i in range(done, upto // bs):
                prev = bm.register_block(
                    prev, tuple(tokens[i * bs:(i + 1) * bs]), table[i])
                bm.note_saved(table, i)
            done = max(done, upto // bs)

        for start in range(cached, n, chunk):
            bm.prepare_chunk(table, start, min(start + chunk, n))
            register(min(start + chunk, n))
        for pos in range(n, n + n_out):
            assert bm.ensure_capacity(pos + 1, table)
            register(pos + 1)
        bm.free(table)
        return tokens, n - cached

    docs = {pre: fresh(cell.traffic["shared_prefix_tokens"])
            for pre in plan.prefixes}
    for doc in docs.values():
        serve(doc + fresh(42), 1)
    context = {}

    def start_of(session):
        own = loadgen.prompt_tokens(session.messages) - len(
            docs[session.messages[0]["content"]])
        return docs[session.messages[0]["content"]] + fresh(max(own, 1))

    for turn in plan.setup_turns:
        s = plan.sessions[turn.pick]
        context[s.sid], _ = serve(
            start_of(s) + fresh(loadgen.message_tokens(
                "user", turn.user_text)), turn.max_tokens)
    hits0, cut0, ev0 = bm.prefix_hits, bm.cutback_tokens, (
        bm.snapshot_evictions)
    sessions, taken, computed = list(plan.sessions), 0, 0
    for turn in plan.turns:
        at = turn.pick % len(sessions)
        s = sessions[at]
        user = loadgen.message_tokens("user", turn.user_text)
        ctx = context.get(s.sid) or start_of(s)
        if len(ctx) + user + turn.max_tokens > cell.traffic["history"][
                "retire_context_tokens"]:
            s = sessions[at] = plan.spares[taken]
            taken += 1
            ctx = start_of(s)
        context[s.sid], n = serve(ctx + fresh(user), turn.max_tokens)
        computed += n
    assert bm.state_slots_in_use == 0 and not bm._pending
    return (bm.prefix_hits - hits0, bm.cutback_tokens - cut0,
            computed / len(plan.turns), bm.snapshot_evictions - ev0)


def test_the_cells_sessions_come_back_to_their_snapshots(cell):
    """64 sessions and their replacements over four 2.1k system prompts
    against the pool the runner gives the cell (3 snapshots a lane): a
    returning session's hit ends at the deepest boundary under its
    hashed blocks, so a turn gives up under one interval of 256 tokens
    and computes its own new tokens and that remainder, unless a session
    that has just left pushed its snapshot out: at 4.7 req/s a pool of
    96 cuts 7.6% of the hit tokens back where 128 and any larger pool
    cut 2.9% (four a lane cost more on the chip than it saved: PERF.md,
    Findings PR 45), and under half the size sessions fall back to the
    system prompt's."""
    hits, cut, computed, evicted = _replay(cell, 96)
    assert evicted > 0                      # the pool is full and turns over
    assert cut / (hits + cut) < 0.09 and computed < 650
    four = _replay(cell, 128)
    assert four[1] / (four[0] + four[1]) < 0.05 and four[2] < 500
    # and no larger pool would do better: nobody's deepest snapshot went
    assert _replay(cell, 1000)[:3] == four[:3]
    small = _replay(cell, 48)
    assert small[1] / (small[0] + small[1]) > 3 * cut / (hits + cut)
    assert small[2] > 2 * computed
