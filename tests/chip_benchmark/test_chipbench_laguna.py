"""The laguna family, its configuration and its cell, as the files PR 43
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
CELL = "laguna-xs.2-l5.chat-doc16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LISTS = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench_laguna_" + name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


manifest = _load("manifest")
engine_child = _load("engine_child")
reference = _load("reference")
loadgen = _load("loadgen")
family = manifest.load_family(os.path.join(BENCH, "families", "laguna.py"))


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


# -- the configuration's file --------------------------------------------------
def test_every_width_is_as_published(cell):
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["num_experts"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["shared_expert_intermediate_size"],
            c["moe_routed_scaling_factor"], c["sliding_window"],
            c["vocab_size"], c["gating"]) == (
        2048, 48, 8, 128, 8192, 256, 512, 8, 512, 2.5, 512, 100352, True)
    assert set(c["reduced"]) == {"num_hidden_layers", *LISTS}
    assert c["num_hidden_layers"] == 5
    assert c["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert c["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    rp = c["rope_parameters"]
    assert (rp["full_attention"]["rope_theta"],
            rp["full_attention"]["partial_rotary_factor"],
            rp["full_attention"]["factor"],
            rp["full_attention"]["beta_fast"],
            rp["sliding_attention"]["rope_theta"],
            rp["sliding_attention"]["partial_rotary_factor"]) == (
        500000, 0.5, 64, 64, 10000, 1)
    for key in ("output_gate", "routing", "activation_and_norms", "rotary",
                "weights", "tokenizer", "engine_args"):
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
        row = next(json.loads(ln) for ln in f if '"Laguna-XS.2"' in ln)
    assert cell.config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in LISTS:
            # cut to a prefix of the published list
            assert cell.config[key] == value[:5], key
        elif key not in cell.config["reduced"]:
            assert cell.config[key] == value, key


def test_the_counts_are_the_sizes_the_cut_was_reckoned_by(cell):
    c = cell.config
    m = 1e6
    # q 12.58 + k, v 4.19 + o 12.58 + gate 0.10; 64 heads: 16.78 x 2 + ...
    assert family.attention_params(c, "full") / m == pytest.approx(
        29.46, abs=0.01)
    assert family.attention_params(c, "window") / m == pytest.approx(
        37.88, abs=0.01)
    assert family.expert_params(c) / m == pytest.approx(3.146, abs=0.001)
    assert 256 * family.expert_params(c) / m == pytest.approx(805.3,
                                                              abs=0.05)
    # layer 0 79.8 M, a routed window layer 846.9 M, a routed full layer
    # 838.4 M
    assert family.layer_params(c, 0) / m == pytest.approx(79.8, abs=0.05)
    assert family.layer_params(c, 1) / m == pytest.approx(846.9, abs=0.05)
    assert family.layer_params(c, 4) / m == pytest.approx(838.4, abs=0.05)
    assert family.layer_params(c, 2) == family.layer_params(c, 3) == (
        family.layer_params(c, 1))
    embed_and_head = 2 * c["vocab_size"] * c["hidden_size"]
    assert embed_and_head / m == pytest.approx(411.0, abs=0.05)
    # held: 3.87 B parameters, 7.74 GB
    assert family.total_params(c) / 1e9 == pytest.approx(3.87, abs=0.005)
    assert family.total_params(c) * 2 / 1e9 == pytest.approx(7.74,
                                                             abs=0.01)
    assert family.layer_stack_bytes(c) == 2 * sum(
        family.layer_params(c, i) for i in range(5))
    assert family.expert_bytes(c) == 2 * 3 * 2048 * 512 == 6291456
    assert family.expert_flops_per_row(c) == 2 * 3 * 2048 * 512
    # two full layers and three window layers of 8 kv heads x 128, K and V
    assert family.kv_bytes_per_token_by_kind(c) == {
        "full": 8192, "window": 12288}
    assert family.kv_bytes_per_token(c) == 8192
    # the whole published model: 33.4 B
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(ln) for ln in f if '"Laguna-XS.2"' in ln)
        assert family.total_params(row["config"]) / 1e9 == pytest.approx(
            33.44, abs=0.01)


OWN = {"moe_expert_op_share.serve_fw", "attn_full_op_share.serve",
       "attn_window_op_share.serve", "attn_kv_stream_share_kinds.serve_fw",
       "kv_window_blocks_per_seq.serve",
       "prefix_window_cutback_blocks.serve",
       # `moe_rows_per_active_expert.serve` under a name of this cell's:
       # test_chipbench_xing4.py pins that metric to the xing4 cell
       "moe_rows_per_active_expert.serve_fw"}
# what the cell reports of the metrics the benchmark had, each by
# appending the cell's name to its `workloads`
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
# what ISSUE 43 asked for and the cell does NOT report: each is pinned to
# the cells it had by a test of the benchmark's own (test_chipbench_
# kv_write.py, test_chipbench_handover.py, test_chipbench_xing4.py),
# which a model_config PR may not edit
PINNED = {
    "kv_write_op_share.serve", "round_handover_ms.serve",
    "round_host_offcpu_ms.serve", "loop_pickup_mean_ms.serve",
    "token_delivery_mean_ms.serve", "server_send_us.serve",
    "moe_rows_per_active_expert.serve"}


def test_the_cell_reports_exactly_its_metrics(cell):
    assert [m["name"] for m in cell.end_to_end] == ["tpot_mean_ms",
                                                    "setup_s"]
    assert cell.chips == 1
    names = {m["name"] for m in cell.per_layer}
    assert names == OWN | SHARED and not names & PINNED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    for m in per_layer:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_mean_ms"
        # no share of a roofline or of a peak in this cell: the one the
        # benchmark has divides a window's counters by a trace's seconds
        # (PERF.md Open questions 6)
        if CELL in m.get("workloads", ()):
            assert "roofline" not in m["name"] and "peak" not in m["name"]
            assert "mfu" not in m["name"]
    assert {m["moves"] for m in cell.per_layer} == {"tpot_mean_ms",
                                                    "setup_s"}
    # one KV constant cannot say two kinds, and no scan of this model's
    # programs carries [lanes, hidden] through all layers
    assert "attn_kv_stream_share.serve" not in names
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
    with open(os.path.join(BENCH, "configs", "xing4-29b-l8.json")) as f:
        assert cell.config["engine_args"] == json.load(f)["engine_args"]
    for name in ("attn_kv_stream_share_kinds.serve_fw",
                 "prefix_window_cutback_blocks.serve",
                 "kv_window_blocks_per_seq.serve"):
        spec, read = manifest.load_reader(name)
        # on a program without the counters (the parent): nothing to read
        assert read(spec, {"trace": {"window_s": 1.0, "busy_s": 1.0,
                                     "ops": {}},
                           "engine_before": {}, "engine_after": {},
                           "family": family, "window_s": 1.0}) is None


def test_the_rate_fills_the_generators_strata(cell):
    """`stratified_order` deals a phase's sorted sizes and gaps to its
    strata in rows; a last row that is not whole hands the longest
    answers and gaps to strata the seed picks (Findings PR 38). The rate
    keeps the window's arrivals a whole multiple of its 10 strata."""
    t = cell.traffic
    for span in (51.0, t["setup"]["warm_seconds"]):
        strata = round(span / t["stratify_seconds"])
        assert round(t["rate_rps"] * span) % strata == 0, span
    plan = loadgen.build_plan(t, 4123456789, 51)
    window = [x for x in plan.turns if x.phase == "window"]
    assert len(window) == round(51 * t["rate_rps"])


def _replay(cell, pool, seed=4123456789):
    """The cell's plan (set-up and a window's turns, one request at a
    time) against the block manager of two pools, no model: prompts by
    their token COUNTS, their content fresh ids so that hashes chain.
    -> blocks cut back in set-up and in the window, prompt tokens a
    turn of the window had to compute."""
    from production_stack_tpu.engine.block_manager import (
        WindowedBlockManager)

    bs, chunk = 32, 256
    plan = loadgen.build_plan(cell.traffic, seed, 51)
    bm = WindowedBlockManager(8192, bs, True,
                              window=cell.config["sliding_window"],
                              num_window_blocks=pool)
    fresh_from = [1000]

    def fresh(n):
        first = fresh_from[0]
        fresh_from[0] += n
        return list(range(first, first + n))

    def serve(tokens, n_out):
        table, cached = bm.allocate_prompt(tokens)
        n, prev, done = len(tokens), 0, 0
        tokens = tokens + fresh(n_out)

        def register(upto):
            nonlocal prev, done
            for i in range(done, upto // bs):
                prev = bm.register_block(
                    prev, tuple(tokens[i * bs:(i + 1) * bs]), table[i])
            done = max(done, upto // bs)

        register(cached)
        for start in range(cached, n, chunk):
            bm.prepare_chunk(table, start, min(start + chunk, n))
            register(min(start + chunk, n))
        for pos in range(n, n + n_out):
            assert bm.ensure_capacity(pos + 1, table)
            bm.release_behind(table, pos)
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
    setup_cut = bm.prefix_cutback[0]
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
    return (setup_cut, bm.prefix_cutback[0] - setup_cut,
            computed / len(plan.turns))


def test_the_cells_sessions_come_back_whole_to_the_window_pool(cell):
    """What the cell was added to find out (PERF.md, Findings PR 43): 48
    sessions and their replacements over four 16.5k documents against
    the windowed pool. At the size the runner gives it no prefix hit is
    cut back and a turn computes its own new tokens; at the size it had
    until PR 43 (one cached end a lane) each document's end is lost
    once to the documents' own prefill, learned, and kept from then on,
    and the window's cuts are a few sessions' ends, back to the
    document's (under two blocks a turn), where the parent's order of
    eviction cut every hit back to nothing."""
    lanes, win, ahead = 32, 512 // 32, 256 // 32
    pool = lanes * (win + ahead + 2) + 4 * lanes * (win + 1) + 1
    assert pool == 3009
    setup_cut, window_cut, computed = _replay(cell, pool)
    assert (setup_cut, window_cut) == (0, 0)
    # its new history (a spare's first turn) and its user message
    assert computed < 500
    setup_cut, window_cut, _ = _replay(cell, 32 * (2 * win + ahead + 2) + 1)
    # 515 blocks a document, once
    assert setup_cut == 4 * 515 and 0 < window_cut < 2 * 264


def test_the_new_readers_find_this_models_operations(cell):
    """The op-share metrics match what this model's programs carry: the
    whole expert stacks as `expert_ffn`'s operands (768 groups in the
    window run, 256 in the full layer's), the attention kernels by name
    and by the kind's query heads; the counter metrics are ratios of the
    window's deltas."""
    ops = {
        "a": {"s": 2.0, "wrapper": False, "text":
              "%expert_ffn.8 = f32[256,2048]{1,0} custom-call(s32[768] %m,"
              " bf16[768,2048,512]{2,1,0} %wg, bf16[768,2048,512] %wu,"
              " bf16[768,512,2048] %wd, bf16[256,2048] %x)"},
        "b": {"s": 0.5, "wrapper": False, "text":
              "%expert_ffn.9 = f32[256,2048]{1,0} custom-call(s32[768] %m,"
              " bf16[256,2048,512]{2,1,0} %wg)"},
        "c": {"s": 3.0, "wrapper": False, "text":
              "%ragged_paged_attention.2 = bf16[32,48,128]{2,1,0} "
              "custom-call()"},
        "d": {"s": 1.0, "wrapper": False, "text":
              "%ragged_paged_attention.3 = bf16[288,64,128]{2,1,0} "
              "custom-call()"},
        "e": {"s": 1.5, "wrapper": False, "text":
              "%fusion.9 = bf16[32,512] fusion(bf16[32,2048] %y, "
              "bf16[2048,512] %shared)"},
    }
    before = {"tpu:attn_context_tokens_full_total": 0.0,
              "tpu:attn_context_tokens_window_total": 0.0,
              "tpu:kv_window_blocks_per_seq_sum": 10.0,
              "tpu:kv_window_blocks_per_seq_count": 1.0,
              "tpu:prefix_window_cutback_blocks_sum": 4.0,
              "tpu:prefix_window_cutback_blocks_count": 2.0,
              "tpu:moe_local_rows_total": 100.0,
              "tpu:moe_active_experts_total": 10.0}
    after = {"tpu:attn_context_tokens_full_total": 819e6,
             "tpu:attn_context_tokens_window_total": 0.0,
             "tpu:kv_window_blocks_per_seq_sum": 210.0,
             "tpu:kv_window_blocks_per_seq_count": 11.0,
             "tpu:prefix_window_cutback_blocks_sum": 10.0,
             "tpu:prefix_window_cutback_blocks_count": 5.0,
             "tpu:moe_local_rows_total": 1300.0,
             "tpu:moe_active_experts_total": 310.0}
    ctx = {"trace": {"busy_s": 10.0, "window_s": 10.0, "ops": ops},
           "engine_before": before, "engine_after": after,
           "family": family, "config": cell.config, "chips": 1,
           "peak": {"hbm_bytes_per_s": 819e9}, "window_s": 20.0}
    got = {}
    for name in sorted(OWN):
        spec, read = manifest.load_reader(name)
        got[name] = read(spec, ctx)
    assert got == {
        "moe_expert_op_share.serve_fw": 25.0,
        "attn_full_op_share.serve": 30.0,
        "attn_window_op_share.serve": 10.0,
        # 819e6 tokens x 8,192 B at 819e9 B/s = 8.192 s of 20 s, over
        # kernels 4 s of 10 s
        "attn_kv_stream_share_kinds.serve_fw": pytest.approx(102.4),
        "kv_window_blocks_per_seq.serve": 20.0,
        "prefix_window_cutback_blocks.serve": 2.0,
        "moe_rows_per_active_expert.serve_fw": 4.0,
    }
    # no cutback at all is 0, not nothing
    after["tpu:prefix_window_cutback_blocks_sum"] = 4.0
    spec, read = manifest.load_reader("prefix_window_cutback_blocks.serve")
    assert read(spec, ctx) == 0.0


# -- served: under a temporary directory, and from the real tree -----------
TINY = {
    "model_type": "laguna", "vocab_size": 384, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 5,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 256, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 12,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 100, "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 64, "beta_slow": 1,
            "beta_fast": 8, "attention_factor": 1.1386294361119891,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 64},
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention"],
    "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "family": "laguna", "source": "a fixture of the tests", "reduced": {},
    "assumed": {}, "deployment": "none", "chips": 1, "replicas": 1,
    "engine_args": ["--tokenizer", "byte"], "router_args": [],
}
# 70 positions: past YaRN's original 64 and five windows long
PROMPT, GEN = [5 + (7 * i) % 370 for i in range(66)], [7, 300, 12, 99]
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
    os.symlink(family.__file__, root / "bench" / "families" / "laguna.py")
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
    mc = engine_child.model_config(c.config, fam, "fixture-tiny-laguna",
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
    counts = [mc.layer_kinds.count(i) for i in range(len(mc.attn_kinds))]
    kc = {"g": tuple(
        jnp.zeros((counts[i], ak.num_kv_heads, t + BS, mc.head_dim))
        for i, ak in enumerate(mc.attn_kinds)),
        "map": jnp.arange(t // BS + 2, dtype=jnp.int32),
        "stats": jnp.zeros((layer_groups.N_STATS,), jnp.int32)}
    vc = {"g": tuple(
        jnp.zeros((counts[i], ak.num_kv_heads, t + BS, mc.v_dim))
        for i, ak in enumerate(mc.attn_kinds))}

    def attn(q, l, k, v, spec):
        return xla_attn.context_attention_prefill(
            q, k[l].swapaxes(0, 1)[BS:], v[l].swapaxes(0, 1)[BS:], pos,
            jnp.int32(t), mc.attn_scale, window=spec.window)

    rows = jnp.arange(len(PROMPT) - 1, len(PROMPT) - 1 + len(GEN))
    logits, _, _ = layer_groups.forward(
        mc, params, ids, pos, kc, vc, pos + BS, attn, logits_rows=rows,
        block_size=BS)
    lp = np.asarray(jax.nn.log_softmax(logits, -1))
    return [float(lp[i, g]) for i, g in enumerate(GEN)]


def test_the_file_reaches_the_program_through_its_own_config_path(served):
    from production_stack_tpu.models import config as mcfg

    mc = served["mc"]
    hf = engine_child.hf_config_of(served["cell"].config, family)
    assert not set(hf) & set(manifest.COMMON_KEYS)
    assert hf["num_experts"] == 16 and hf["gating"] is True
    assert mc.layer_groups and mc.head_gate and (
        mc.router_experts, mc.local_experts, mc.ep_size) == (16, 16, 1)
    assert [(k.num_heads, k.num_kv_heads, k.window, k.rotary_dim,
             k.rope_theta) for k in mc.kinds] == [
        (6, 2, None, 8, 100.0), (8, 2, 12, 16, 10000.0)]
    # the file's spelling gives the preset's kinds
    assert mc.attn_kinds == mcfg.TINY_LAGUNA_DEBUG.attn_kinds
    assert mc.segments() == mcfg.TINY_LAGUNA_DEBUG.segments() == (
        (0, False, 1, 0), (1, True, 3, 0), (0, True, 1, 1))
    assert (mc.shared_experts, mc.routed_scaling, mc.router_scoring,
            mc.router_renorm, mc.attn_scale) == (
        1, 2.5, "softmax", True, 0.25)


def test_the_familys_tree_is_the_tree_the_program_serves(served):
    import jax
    import jax.numpy as jnp

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


def test_the_seeded_weights_have_the_scales_the_family_states(served):
    import numpy as np

    mc, params = served["mc"], served["params"]
    std = mc.hidden_size ** -0.5
    assert float(np.std(np.asarray(params["embed"]))) == pytest.approx(
        1.0, rel=0.05)
    assert float(np.std(np.asarray(params["lm_head"]))) == pytest.approx(
        std, rel=0.05)
    for seg in params["segments"]:
        for name, a in seg.items():
            if name.endswith("_norm"):
                continue
            gain = (family.EXPERT_DOWN_GAIN
                    if name == "w_down" and "router" in seg else 1.0)
            assert float(np.std(np.asarray(a))) == pytest.approx(
                std * gain, rel=0.15), name


def test_the_reference_agrees_with_the_programs_forward_pass(served):
    got = served["control"].reference(
        {"prompt_ids": PROMPT, "generated_ids": GEN})["logprobs"]
    want = program_logprobs(served["mc"], served["params"])
    assert len(got) == len(GEN)
    for a, b in zip(got, want):
        assert a == pytest.approx(b, abs=2e-4)


def _zeroed(params, name):
    import jax.numpy as jnp

    return {**params, "segments": [
        {k: (jnp.zeros_like(v) if k == name else v)
         for k, v in seg.items()} for seg in params["segments"]]}


@pytest.mark.parametrize("term", [
    "gate", "factor on cos and sin", "rotary width", "shared expert",
    "router", "scaling factor", "renormalisation"])
def test_a_dropped_term_fails_the_comparison(served, term):
    """Each of the output gate, YaRN's factor on cos and sin, the full
    kind's half-head rotary width, the shared expert, the router, the
    scaling factor 2.5 and the renormalisation, zeroed in the tree or
    dropped from the configuration: the program serving the changed
    model agrees with the reference of the changed model and FAILS
    `compare` against the true one."""
    mc, params = served["mc"], served["params"]
    if term in ("router", "scaling factor", "renormalisation"):
        # the routed experts' down projections carry the family's gain
        # of 0.1, sized so that ONE flipped expert stays small at
        # published widths; the true model of these cases carries them
        # at the common deviation, where the routed sum is a term the
        # comparison can see
        params = {**params, "segments": [
            {k: (v / family.EXPERT_DOWN_GAIN if k == "w_down"
                 and "router" in seg else v) for k, v in seg.items()}
            for seg in params["segments"]]}

    def kinds(**full):
        return (dataclasses.replace(mc.attn_kinds[0], **full),
                mc.attn_kinds[1])

    off_mc, off_params = mc, params
    if term == "gate":
        off_mc = dataclasses.replace(mc, head_gate=False)
    elif term == "factor on cos and sin":
        off_mc = dataclasses.replace(mc, attn_kinds=kinds(rope_factor=1.0))
    elif term == "rotary width":
        off_mc = dataclasses.replace(mc, attn_kinds=kinds(rotary_dim=16))
    elif term == "shared expert":
        off_params = _zeroed(params, "ws_down")
    elif term == "router":
        # every expert scores alike: the first four are chosen
        off_params = _zeroed(params, "router")
    elif term == "scaling factor":
        off_mc = dataclasses.replace(mc, routed_scaling=1.0)
    else:
        off_mc = dataclasses.replace(mc, router_renorm=False)
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
                                   "fixture-real-laguna", False)
    from production_stack_tpu.models import config as mcfg
    try:
        assert (mc.hidden_size, mc.num_layers, mc.router_experts,
                mc.local_experts, mc.vocab_size, mc.head_gate) == (
            2048, 5, 256, 256, 100352, True)
        assert [(k.num_heads, k.num_kv_heads, k.window, k.rotary_dim)
                for k in mc.kinds] == [(48, 8, None, 64), (64, 8, 512, 128)]
        assert mc.kinds[0].rope_factor == pytest.approx(1.41589, abs=1e-5)
        assert mc.segments() == (
            (0, False, 1, 0), (1, True, 3, 0), (0, True, 1, 1))
        assert mc.num_params() == family.total_params(cell.config)
        assert mc.attn_scale == 128 ** -0.5
        small = family.rehearsal_config(mc, 1)
        assert small.layer_groups and small.name == mc.name
        assert small.hidden_size == mcfg.TINY_LAGUNA_DEBUG.hidden_size
        assert small.segments() == mc.segments()
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
    ("sliding_window", 24, "windows"),
    ("num_attention_heads_per_layer", [6, 4, 4, 4, 6], "q heads"),
    ("shared_expert_intermediate_size", 64, "shared experts"),
    ("moe_routed_scaling_factor", 1.0, "scaling factor"),
    ("gating", False, "output gate"),
    ("num_experts_per_tok", 2, "experts a token"),
    ("mlp_layer_types", ["dense", "dense", "sparse", "sparse", "sparse"],
     "dense layers"),
])
def test_check_refuses_a_file_that_disagrees_with_the_program(
        served, key, value, says):
    config = dict(served["cell"].config, **{key: value})
    with pytest.raises(SystemExit, match=says):
        family.check(config, served["mc"])
    rp = json.loads(json.dumps(served["cell"].config["rope_parameters"]))
    rp["full_attention"]["attention_factor"] = 1.0
    with pytest.raises(SystemExit, match="yarn"):
        family.check(dict(served["cell"].config, rope_parameters=rp),
                     served["mc"])
