"""BENCHMARK.json against the driver's contract, the data-driven loader,
and the pieces of engine_child.py that need no device."""

import importlib.util
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(
    r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|"
    r"expansion|experts_per_tok")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


manifest = _load("manifest")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    M = json.load(_f)
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["paths"]) <= 16 and len(M["command"]) <= 32
    assert 1 <= len(M["configs"]) <= 24 and 1 <= len(M["workloads"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16 and len(M["per_layer"]) <= 128
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word
    assert any(w.startswith(p + "/") for p in M["paths"]
               for w in M["command"])


@pytest.mark.parametrize(
    "entry", M["configs"] + M["workloads"] + METRICS,
    ids=lambda e: e["name"])
def test_entry_has_just_the_contracts_keys_and_characters(entry):
    assert NAME.match(entry["name"])
    if "file" in entry:
        want = {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in entry["reduced"])
        assert len(entry["reduced"]) <= 16
    elif "traffic" in entry:
        want = {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(entry["traffic"]) and entry["chips"] in (1, 4)
    elif "bound" in entry:
        want = {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= entry["bound"] <= 0.1
        assert entry["source"] in ("host_clock", "device_trace")
    else:
        want = {"name", "unit", "better", "source", "layer", "moves"}
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    assert want <= set(entry) <= want | {"workloads"}
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")


def test_names_are_unique_and_cells_are_well_formed():
    for group in (M["configs"], M["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    configs = {c["name"] for c in M["configs"]}
    assert {w["config"] for w in M["workloads"]} == configs
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(M["workloads"]) // 4)
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))


def test_every_file_the_manifest_names_exists_under_paths():
    for p in M["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    for c in M["configs"]:
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in M["workloads"]:
        assert os.path.isfile(
            os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    for top, _, names in os.walk(BENCH):
        for n in names:
            if "__pycache__" not in top:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", n), n


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_moves_a_metric_each_of_its_cells_reports(metric):
    target = next(m for m in M["end_to_end"] if m["name"] == metric["moves"])
    assert set(cells_of(metric)) <= set(cells_of(target))
    spec, read = manifest.load_reader(metric["name"])
    assert callable(read)
    with open(os.path.join(ROOT, "PERF.md")) as f:
        assert f"**{metric['layer']}**" in f.read()


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer_metric(cell):
    e2e = [m["name"] for m in M["end_to_end"] if cell in cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in cells_of(m) for m in M["per_layer"])
    loaded = manifest.load_cell(cell)
    assert [m["name"] for m in loaded.end_to_end] == e2e
    if loaded.traffic["loop"] == "open":
        assert loaded.traffic["rate_rps"] > 0
    assert set(loaded.peaks) == {"TPU v5 lite"}


def _configuration(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    family = manifest.load_family(
        os.path.join(BENCH, "families", cfg["family"] + ".py"))
    return cfg, family


@pytest.mark.parametrize("entry", M["configs"], ids=lambda c: c["name"])
def test_configuration_file_is_what_the_manifest_says(entry):
    """What the contract asks of EVERY configuration, whatever its
    architecture."""
    cfg, _ = _configuration(entry)
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert not any(WIDTH.search(k) for k in entry["reduced"])
    assert set(manifest.COMMON_KEYS) <= set(cfg)


@pytest.mark.parametrize("entry", M["configs"], ids=lambda c: c["name"])
def test_configurations_family_is_a_file_that_gives_the_whole_contract(
        entry):
    cfg, family = _configuration(entry)
    assert NAME.match(cfg["family"])
    for name in manifest.FAMILY_API:
        assert callable(getattr(family, name))
    # the cell finds the same file by the same name
    cell = next(w["name"] for w in M["workloads"]
                if w["config"] == entry["name"])
    assert os.path.samefile(manifest.load_cell(cell).family_file,
                            family.__file__)
    for count in manifest.FAMILY_COUNTS:
        assert getattr(family, count)(cfg) > 0


@pytest.mark.parametrize("entry", M["configs"], ids=lambda c: c["name"])
def test_configuration_reaches_the_program_and_passes_its_familys_check(
        entry):
    engine_child = _load("engine_child")
    cfg, family = _configuration(entry)
    hf = engine_child.hf_config_of(cfg, family)
    assert not set(hf) & set(manifest.COMMON_KEYS)
    # model_config runs the family's check
    mc = engine_child.model_config(cfg, family, "test-" + entry["name"],
                                   False)
    try:
        assert mc.num_layers == cfg["num_hidden_layers"]
        small = family.rehearsal_config(mc, 1)
        assert small.name == mc.name
        assert small.hidden_size < mc.hidden_size
    finally:
        from production_stack_tpu.models import config as mcfg
        mcfg._PRESETS.pop(mc.name)


# what each configuration was chosen for, asked of the configuration it
# belongs to and of no other
DENSE_CONFIGS = {
    "mistral-7b-l16": {"hidden_size": 4096, "qkv_bias": False,
                       "num_kv_heads": 8, "rms_norm_eps": 1e-5},
    "qwen2-7b-l14": {"hidden_size": 3584, "qkv_bias": True,
                     "num_kv_heads": 4, "rms_norm_eps": 1e-6},
}


def test_the_cases_by_name_are_dense_configurations_of_the_benchmark():
    # one way only: a later dense configuration comes as data files and
    # needs no case here (`dense.check` holds it to its ModelConfig)
    dense = {c["name"] for c in M["configs"]
             if _configuration(c)[0]["family"] == "dense"}
    assert set(DENSE_CONFIGS) <= dense


@pytest.mark.parametrize("name", sorted(DENSE_CONFIGS))
def test_dense_configuration_holds_the_published_keys(name):
    engine_child = _load("engine_child")
    want = DENSE_CONFIGS[name]
    entry = next(c for c in M["configs"] if c["name"] == name)
    cfg, family = _configuration(entry)
    assert cfg["family"] == "dense" and family.OWN_KEYS == ("qkv_bias",)
    hf = engine_child.hf_config_of(cfg, family)
    assert "engine_args" not in hf and "qkv_bias" not in hf
    assert hf["hidden_size"] == want["hidden_size"]
    mc = engine_child.model_config(cfg, family, "test-dense-" + name, False)
    try:
        assert mc.head_dim == 128 and mc.sliding_window is None
        assert mc.qkv_bias == cfg["qkv_bias"] == want["qkv_bias"]
        assert mc.num_kv_heads == want["num_kv_heads"]
        assert mc.rms_norm_eps == hf["rms_norm_eps"] == want["rms_norm_eps"]
        assert not mc.is_moe and mc.hidden_act == "silu"
    finally:
        from production_stack_tpu.models import config as mcfg
        mcfg._PRESETS.pop(mc.name)


def test_tokenizer_stand_in_is_one_reversible_character_per_id():
    tok = _load("engine_child").make_tokenizer()
    ids = [0, 65, 255, 256, 257, 31999, 152063]
    text = tok.decode(ids)
    assert len(text) == len(ids)
    assert tok.encode(text, add_bos=False) == ids
    mixed = "<|user|>\nhi\n" + text
    assert tok.encode(mixed, add_bos=False) == list(b"<|user|>\nhi\n") + ids
    assert json.loads(json.dumps(text)) == text
    from production_stack_tpu.engine.detokenizer import (
        IncrementalDetokenizer,
    )
    d = IncrementalDetokenizer(tok)
    out = ""
    for i in ids * 5:
        out = d.append(i)
    assert out == tok.decode(ids * 5)


def test_parse_prometheus_sums_label_sets_and_skips_buckets():
    text = (
        "# HELP x y\n# TYPE x counter\n"
        'vllm:generation_tokens_total{model_name="m"} 10.0\n'
        'tpu:compile_events_total{kind="a",model_name="m"} 2.0\n'
        'tpu:compile_events_total{kind="b",model_name="m"} 3.0\n'
        'tpu:request_queue_seconds_bucket{le="0.1"} 7.0\n'
        "tpu:request_queue_seconds_sum 1.5\n"
        "tpu:request_queue_seconds_count 6.0\n"
        "x_created 1.7e9\n")
    got = manifest.parse_prometheus(text)
    assert got == {"vllm:generation_tokens_total": 10.0,
                   "tpu:compile_events_total": 5.0,
                   "tpu:request_queue_seconds_sum": 1.5,
                   "tpu:request_queue_seconds_count": 6.0}


def test_a_later_pr_adds_a_cell_with_new_files_and_entries_only(tmp_path):
    """One new configuration, traffic mix, cell and counter-delta layer
    metric, as files and manifest entries in a copy; no file that was
    there is edited, and the loader finds all of it by name."""
    root = tmp_path / "checkout"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    m = json.loads(json.dumps(M))
    cfg = json.loads((bench / "configs" / "qwen2-7b-l14.json").read_text())
    cfg["num_hidden_layers"] = 7
    (bench / "configs" / "qwen2-7b-l7.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "short-only.json").write_text(json.dumps({
        "loop": "open", "arrival": "poisson", "session_pool": 0,
        "prompt_tokens": {"dist": "fixed", "n": 64},
        "output_tokens": {"dist": "fixed", "n": 32}}))
    (bench / "cells" / "qwen2-7b-l7.short-only.json").write_text(
        json.dumps({"rate_rps": 12.5}))
    (bench / "layer_metrics" / "preemptions.json").write_text(json.dumps({
        "reader": "counter_ratio", "scrape": "engine",
        "numerator": ["vllm:num_preemptions_total"]}))
    m["configs"].append({
        "name": "qwen2-7b-l7", "source": cfg["source"],
        "file": "benchmarks/chip/configs/qwen2-7b-l7.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    m["workloads"].append({
        "name": "qwen2-7b-l7.short-only", "config": "qwen2-7b-l7",
        "traffic": "short-only", "chips": 1, "why": "test"})
    for e in m["end_to_end"]:
        if e["name"] == "tpot_mean_ms":
            e["workloads"].append("qwen2-7b-l7.short-only")
    m["per_layer"].append({
        "name": "preemptions", "unit": "count", "better": "lower",
        "source": "program_counter",
        "layer": "scheduler and block manager",
        "moves": "tpot_mean_ms",
        "workloads": ["qwen2-7b-l7.short-only"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    cell = manifest.load_cell("qwen2-7b-l7.short-only", root=str(root))
    assert cell.config["num_hidden_layers"] == 7 and cell.chips == 1
    assert cell.family_file == str(bench / "families" / "dense.py")
    assert cell.traffic["rate_rps"] == 12.5
    assert cell.traffic["prompt_tokens"] == {"dist": "fixed", "n": 64}
    assert [x["name"] for x in cell.end_to_end] == [
        "tpot_mean_ms", "setup_s"]
    assert [x["name"] for x in cell.per_layer] == ["preemptions"]
    ctx = {"engine_before": {"vllm:num_preemptions_total": 2.0},
           "engine_after": {"vllm:num_preemptions_total": 5.0},
           "router_before": {}, "router_after": {}}
    got = manifest.read_layer_metrics(cell, ctx, bench_dir=str(bench))
    assert got == {"preemptions": {"value": 3.0, "unit": "count"}}
    # a reader that finds nothing to read returns nothing
    ctx["engine_after"] = {}
    assert manifest.read_layer_metrics(
        cell, ctx, bench_dir=str(bench)) == {}
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_cells_file_gives_its_offered_load_and_nothing_else(tmp_path):
    root = tmp_path / "checkout"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "BENCHMARK.json").write_text(json.dumps(M))
    cell = manifest.load_cell(CELLS[0], root=str(root))
    assert cell.traffic["rate_rps"] == 1.6 and "sweep" in cell.traffic
    (bench / "cells" / (CELLS[0] + ".json")).write_text(
        json.dumps({"rate_rps": 1.6, "shared_prefix_tokens": 0}))
    with pytest.raises(SystemExit, match="shared_prefix_tokens"):
        manifest.load_cell(CELLS[0], root=str(root))


class _FakeRunner:
    """The runner's bucket functions as model_runner.py has them today
    (block 16, chunk 512), and recorders for its precompile entries."""
    ragged_kernel = prefill_pipeline = True

    def __init__(self, max_model_len):
        self.max_model_len, self.calls = max_model_len, []

    def _prefill_bucket(self, n):
        return min(1 << (max(n, 8) - 1).bit_length(), 512)

    def _ctx_bucket(self, n):
        blocks = 1 << (max(1, -(-n // 16)) - 1).bit_length()
        return min(blocks * 16, self.max_model_len)

    def precompile_prefill(self, singles, groups):
        self.calls.append(("prefill", singles, groups))
        return len(singles) + len(groups)

    def precompile_decode(self, ctxs, k, **kw):
        self.calls.append(("decode", ctxs, k))
        return 1

    def precompile_ragged(self, ctxs, ks, lanes, chunk, **kw):
        self.calls.append(("ragged", ctxs, lanes, chunk))
        return 1


@pytest.mark.parametrize("floor,max_len,want_ctxs", [
    (2100, 4096, [4096]), (1024, 4096, [2048, 4096]),
    (2100, 8192, [4096, 8192])])
def test_warm_programs_walks_the_runners_own_buckets_above_the_floor(
        floor, max_len, want_ctxs):
    """No list of buckets is kept with the benchmark: chunk and context
    buckets are asked of the runner, between the traffic's floor (its
    shared prefix) and max_model_len."""
    import types

    engine_child = _load("engine_child")
    rnr = _FakeRunner(max_len)
    engine = types.SimpleNamespace(
        runner=rnr, _async_decode=False, _prefetch_decode=True,
        _device_stop=True, _ragged_dispatch=True,
        config=types.SimpleNamespace(
            max_prefill_chunk=512, max_prefill_seqs=8, max_model_len=max_len,
            num_scheduler_steps=8))
    n = engine_child.warm_programs(engine, floor, rehearse=False)
    chunks = [8, 16, 32, 64, 128, 256, 512]
    pre = [c for c in rnr.calls if c[0] == "prefill"]
    assert [c[1] for c in pre] == [[(t, x) for t in chunks]
                                   for x in want_ctxs]
    assert pre[0][2] == [(2, t, want_ctxs[0]) for t in chunks] + [
        (4, 512, want_ctxs[0]), (8, 512, want_ctxs[0])]
    assert [c[1] for c in rnr.calls if c[0] == "decode"] == [
        [x - 7] for x in want_ctxs]
    ragged = [c for c in rnr.calls if c[0] == "ragged"]
    assert [(c[2], c[3]) for c in ragged[:8]] == [
        (1, t) for t in chunks] + [(8, 512)]
    assert n == len(want_ctxs) * (7 + 9 + 1 + 8)
    short = _FakeRunner(max_len)
    engine.runner = short
    engine_child.warm_programs(engine, floor, rehearse=True)
    assert [c[1] for c in short.calls if c[0] == "prefill"] == [
        [(64, want_ctxs[-1]), (512, want_ctxs[-1])]]


def test_counter_ratio_is_a_delta_over_a_delta():
    cell = manifest.load_cell("mistral-7b-l16.chat-sys2k")
    ctx = {
        "engine_before": {"vllm:gpu_prefix_cache_hits_total": 100.0,
                          "vllm:gpu_prefix_cache_queries_total": 200.0,
                          "tpu:request_queue_seconds_sum": 1.0,
                          "tpu:request_queue_seconds_count": 10.0,
                          "tpu:compile_events_total": 40.0},
        "engine_after": {"vllm:gpu_prefix_cache_hits_total": 400.0,
                         "vllm:gpu_prefix_cache_queries_total": 600.0,
                         "tpu:request_queue_seconds_sum": 3.0,
                         "tpu:request_queue_seconds_count": 50.0,
                         "tpu:compile_events_total": 40.0},
        "router_before": None, "router_after": None,
        "records": [], "trace": None,
    }
    ctx["loadgen"] = _load("loadgen")
    got = manifest.read_layer_metrics(cell, ctx)
    assert got["prefix_hit_share"]["value"] == pytest.approx(75.0)
    assert got["queue_wait_mean_ms"]["value"] == pytest.approx(50.0)
    assert got["compiles_in_window.serve"]["value"] == 0.0
    # no trace, no router scrape, no records: those are left out
    assert set(got) == {"prefix_hit_share", "queue_wait_mean_ms",
                        "compiles_in_window.serve"}
