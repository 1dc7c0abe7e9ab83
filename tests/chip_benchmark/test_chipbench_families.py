"""The seam between the harness and an architecture: a configuration
names its family and the family is a file. The dense family against the
parent's weights and guards, and a family the harness has never seen,
brought as new files alone."""

import ast
import dataclasses
import hashlib
import importlib.util
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
FIXTURE_FAMILY = os.path.join(
    HERE, "fixtures", "families", "moe-softmax-topk.py")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench_fam_" + name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


manifest = _load("manifest")
engine_child = _load("engine_child")
dense = manifest.load_family(os.path.join(BENCH, "families", "dense.py"))


def tiny(**over):
    from production_stack_tpu.models import config as mcfg

    return dataclasses.replace(mcfg.TINY_DEBUG, name="t", **over)


def file_of(mc, **over) -> dict:
    """A configuration file's dict that agrees with `mc`."""
    return {"hidden_size": mc.hidden_size,
            "intermediate_size": mc.intermediate_size,
            "num_hidden_layers": mc.num_layers,
            "num_attention_heads": mc.num_heads,
            "num_key_value_heads": mc.num_kv_heads,
            "head_dim": mc.head_dim, "vocab_size": mc.vocab_size,
            "qkv_bias": mc.qkv_bias, **over}


# -- the dense family is the parent's code, moved --------------------------
def digest(tree) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    for path, leaf in sorted(
            leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), a.dtype, a.shape):
            h.update(str(part).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 over the sorted leaves (path, dtype, shape, bytes) of what the
# PARENT's `engine_child.make_params(mc, seed, dtype, None)` gives at the
# tiny debug widths on the CPU: commit 616ef57, before the tree moved to
# families/dense.py (PR 27)
@pytest.mark.parametrize("bias,tie,dtype,seed,want", [
    (False, True, "float32", 7,
     "d0a454262bceedb74ba544861a44edb3959ce020b9bfd5e7e1d51b4a0035c28f"),
    (True, False, "float32", 3000000019,
     "9fb6f061d2d2e5ae3c06d5ee8d19e7fc3f63a291403e0108f4651bce4da4b576"),
    (True, False, "bfloat16", 3000000019,
     "9795fdb8e27e401bca6f1eeb4f4bfa82786dc04ac6238658c3470466b05da3b2"),
])
def test_dense_weights_are_the_parents_bit_for_bit(bias, tie, dtype, seed,
                                                   want):
    import jax.numpy as jnp

    mc = tiny(qkv_bias=bias, tie_word_embeddings=tie)
    params = engine_child.make_params(dense, mc, seed, jnp.dtype(dtype),
                                      None)
    assert ("bq" in params["layers"]) == bias
    assert ("lm_head" in params) == (not tie)
    assert digest(params) == want


def test_dense_biases_are_seeded_non_zero():
    import jax.numpy as jnp
    import numpy as np

    params = engine_child.make_params(
        dense, tiny(qkv_bias=True), 11, jnp.float32, None)
    for b in ("bq", "bk", "bv"):
        assert float(np.abs(np.asarray(params["layers"][b])).min()) > 0.0


@pytest.mark.parametrize("mc_over,file_over,says", [
    ({"qkv_bias": True}, {"qkv_bias": False}, "qkv_bias"),
    ({}, {"intermediate_size": 256}, "shapes"),
    ({}, {"num_key_value_heads": 4}, "shapes"),
    ({"num_experts": 4}, {}, "num_experts=4"),
    ({"sliding_window": 128}, {}, "sliding_window=128"),
    ({"hidden_act": "gelu_tanh"}, {}, "gelu_tanh"),
])
def test_dense_check_refuses_what_its_counts_and_reference_do_not_cover(
        mc_over, file_over, says):
    mc = tiny(**mc_over)
    config = file_of(mc, **file_over)
    with pytest.raises(SystemExit, match=says):
        dense.check(config, mc)


@pytest.mark.parametrize("bias", [False, True])
def test_dense_check_passes_where_file_and_program_agree(bias):
    mc = tiny(qkv_bias=bias)
    dense.check(file_of(mc), mc)


@pytest.mark.parametrize("tp,kv", [(1, 2), (4, 4)])
def test_dense_rehearsal_keeps_what_selects_code_paths(tp, kv):
    from production_stack_tpu.models import config as mcfg

    mc = mcfg.ModelConfig(
        name="big", vocab_size=152064, hidden_size=3584,
        intermediate_size=18944, num_layers=14, num_heads=28,
        num_kv_heads=4, head_dim=128, max_model_len=32768,
        rope_theta=1e6, rms_norm_eps=1e-6, qkv_bias=True)
    small = dense.rehearsal_config(mc, tp)
    assert (small.name, small.qkv_bias, small.rms_norm_eps,
            small.rope_theta, small.tie_word_embeddings,
            small.max_model_len) == ("big", True, 1e-6, 1e6, False, 32768)
    assert small.hidden_size == mcfg.TINY_DEBUG.hidden_size
    assert small.num_kv_heads == kv


# -- how a family is found and refused -------------------------------------
def test_a_family_file_that_is_not_there_is_refused_by_name(tmp_path):
    with pytest.raises(SystemExit, match="no family file .*latent.py"):
        manifest.load_family(str(tmp_path / "families" / "latent.py"))


def test_a_family_file_that_lacks_part_of_the_contract_is_refused(tmp_path):
    path = tmp_path / "half.py"
    path.write_text("def hf_config(config):\n    return config\n"
                    "check = 3\n")
    with pytest.raises(SystemExit) as e:
        manifest.load_family(str(path))
    lacks = ast.literal_eval(str(e.value).rsplit("lacks ", 1)[1])
    assert lacks == [n for n in manifest.FAMILY_API if n != "hf_config"]
    # of the counts, the two that readers divide by and no other
    assert set(manifest.FAMILY_COUNTS) == {"layer_stack_bytes",
                                           "kv_bytes_per_token"}


def test_a_configuration_without_a_family_is_refused_by_name(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    root = tmp_path / "checkout"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    path = bench / "configs" / "qwen2-7b-l14.json"
    cfg = json.loads(path.read_text())
    del cfg["family"]
    path.write_text(json.dumps(cfg))
    with pytest.raises(SystemExit, match='qwen2-7b-l14.json: no "family"'):
        manifest.load_cell("qwen2-7b-l14.chat-sys2k", root=str(root))
    cell = manifest.load_cell("mistral-7b-l16.chat-sys2k", root=str(root))
    assert cell.family_file == str(bench / "families" / "dense.py")


# -- a family the harness has never seen, as new files alone ---------------
PROMPT, GEN = list(range(5, 45)), [7, 300, 12, 99]


@pytest.fixture(scope="module")
def moe(tmp_path_factory):
    """Under a temporary directory, with nothing that exists copied or
    patched: a family file, a configuration that names it, a traffic
    mix, a table of peaks, a manifest with one cell. Then the harness's
    own path: `load_cell`, `load_family`, `model_config`, the common
    jitted parameter path, `Control`."""
    import jax.numpy as jnp

    root = tmp_path_factory.mktemp("checkout")
    bench = root / "bench"
    for sub in ("families", "configs", "traffic"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(FIXTURE_FAMILY, bench / "families")
    (bench / "configs" / "tiny-moe.json").write_text(json.dumps({
        "architectures": ["MixtralForCausalLM"], "model_type": "mixtral",
        "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 384,
        "max_position_embeddings": 256, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5, "num_local_experts": 4,
        "num_experts_per_tok": 2, "tie_word_embeddings": False,
        "family": "moe-softmax-topk", "source": "a fixture of the tests",
        "reduced": {}, "assumed": {}, "deployment": "none", "chips": 1,
        "replicas": 1, "engine_args": ["--tokenizer", "byte"],
        "router_args": []}))
    (bench / "traffic" / "few.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2}))
    (bench / "peaks.json").write_text(json.dumps({"none": {}}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "paths": ["bench"],
        "configs": [{"name": "tiny-moe",
                     "file": "bench/configs/tiny-moe.json"}],
        "workloads": [{"name": "tiny-moe.few", "config": "tiny-moe",
                       "traffic": "few", "chips": 1}],
        "end_to_end": [{"name": "setup_s"}],
        "per_layer": []}))

    cell = manifest.load_cell("tiny-moe.few", root=str(root),
                              bench_dir=str(bench))
    family = manifest.load_family(cell.family_file)
    mc = engine_child.model_config(cell.config, family, "fixture-tiny-moe",
                                   False)
    params = engine_child.make_params(family, mc, 4123456789, jnp.float32,
                                      None)
    control = engine_child.Control(family, mc, params, str(root / "out"))
    yield {"cell": cell, "family": family, "mc": mc, "params": params,
           "control": control, "bench": bench}
    from production_stack_tpu.models import config as mcfg
    mcfg._PRESETS.pop(mc.name)


def program_logprobs(mc, params):
    """The program's own forward pass (`models/llama.py::forward`) over
    prompt + generated ids, as `tests/test_moe.py` drives it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.models import llama
    from production_stack_tpu.parallel.ring_attention import (
        attention_reference,
    )

    def attn(q, layer, k_cache, v_cache):
        return attention_reference(
            q[None], k_cache[layer].swapaxes(0, 1)[None],
            v_cache[layer].swapaxes(0, 1)[None], causal=True)[0]

    ids = jnp.asarray(PROMPT + GEN, jnp.int32)
    n = ids.shape[0]
    kc = jnp.zeros((mc.num_layers, mc.num_kv_heads, n, mc.head_dim),
                   jnp.float32)
    rows = jnp.arange(len(PROMPT) - 1, len(PROMPT) - 1 + len(GEN))
    logits, _, _ = llama.forward(
        mc, params, ids, jnp.arange(n, dtype=jnp.int32), kc,
        jnp.zeros_like(kc), jnp.arange(n, dtype=jnp.int32), attn,
        logits_rows=rows)
    lp = np.asarray(jax.nn.log_softmax(logits, -1))
    return [float(lp[i, g]) for i, g in enumerate(GEN)]


def test_new_family_is_found_beside_its_configuration(moe):
    cell = moe["cell"]
    assert cell.family_file == str(
        moe["bench"] / "families" / "moe-softmax-topk.py")
    assert not os.path.exists(
        os.path.join(BENCH, "families", "moe-softmax-topk.py"))
    assert cell.traffic == {"loop": "closed", "clients": 2}
    hf = engine_child.hf_config_of(cell.config, moe["family"])
    assert not set(hf) & set(manifest.COMMON_KEYS)
    assert hf["num_local_experts"] == 4


def test_new_family_reaches_the_program_through_its_own_config_path(moe):
    mc = moe["mc"]
    assert mc.is_moe and (mc.num_experts, mc.num_experts_per_tok) == (4, 2)
    assert mc.num_layers == 2 and mc.hidden_size == 64
    # the bytes a reader divides by are those of the layers served, and
    # the family carries no count that the harness does not read
    import jax
    served = sum(a.size for a in jax.tree.leaves(moe["params"]["layers"]))
    assert moe["family"].layer_stack_bytes(moe["cell"].config) == 2 * served
    assert not hasattr(moe["family"], "total_params")
    small = moe["family"].rehearsal_config(mc, 1)
    assert small.is_moe and small.name == mc.name


def test_new_familys_tree_is_the_tree_the_program_serves(moe):
    import jax
    import jax.numpy as jnp

    from production_stack_tpu.models import llama

    mc = moe["mc"]
    want = jax.eval_shape(
        lambda k: llama.init_params(mc, k, jnp.float32), jax.random.key(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), moe["params"])
    assert got == jax.tree.map(lambda a: (a.shape, a.dtype), want)
    assert moe["params"]["layers"]["w_gate"].shape == (2, 4, 64, 128)


def test_new_familys_reference_agrees_with_the_programs_forward_pass(moe):
    got = moe["control"].reference(
        {"prompt_ids": PROMPT, "generated_ids": GEN})["logprobs"]
    want = program_logprobs(moe["mc"], moe["params"])
    assert len(got) == len(GEN)
    for a, b in zip(got, want):
        assert a == pytest.approx(b, abs=2e-4)


def test_a_zeroed_router_changes_the_new_familys_log_probabilities(moe):
    import jax.numpy as jnp

    params = moe["params"]
    zeroed = dict(params, layers={
        **params["layers"],
        "moe_gate": jnp.zeros_like(params["layers"]["moe_gate"])})
    control = engine_child.Control(moe["family"], moe["mc"], zeroed, "")
    body = {"prompt_ids": PROMPT, "generated_ids": GEN}
    off = control.reference(body)["logprobs"]
    got = moe["control"].reference(body)["logprobs"]
    assert max(abs(a - b) for a, b in zip(off, got)) > 1e-3
    # and the program served with the zeroed router fails the comparison
    reference = _load("reference")
    served = program_logprobs(moe["mc"], zeroed)
    assert reference.compare(served, off)["ok"]
    assert not reference.compare(served, got)["ok"]


# -- a third configuration of a family that is here, as data files alone ---
def test_a_third_dense_configuration_is_data_files_alone(tmp_path):
    """A Llama-class configuration neither of the two is like (tied
    lm_head, head_dim 16, no bias) next to the benchmark's own files,
    none of them copied or edited: its file, its cell's load and the
    manifest entries. It is served, checked by `dense.check` and agrees
    with the program's forward pass."""
    import jax.numpy as jnp

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    (tmp_path / "third.json").write_text(json.dumps({
        "architectures": ["LlamaForCausalLM"], "model_type": "llama",
        "hidden_size": 64, "intermediate_size": 160,
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 384,
        "max_position_embeddings": 256, "rope_theta": 500000.0,
        "rms_norm_eps": 1e-5, "hidden_act": "silu",
        "tie_word_embeddings": True, "qkv_bias": False,
        "family": "dense", "source": "a fixture of the tests",
        "reduced": {}, "assumed": {}, "deployment": "none", "chips": 1,
        "replicas": 1, "engine_args": ["--tokenizer", "byte"],
        "router_args": []}))
    m["configs"].append({"name": "third", "file": "third.json"})
    m["workloads"].append({"name": "third.batch-fewshot2k", "chips": 1,
                           "config": "third", "traffic": "batch-fewshot2k"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    cell = manifest.load_cell("third.batch-fewshot2k", root=str(tmp_path),
                              bench_dir=BENCH)
    assert os.path.samefile(cell.family_file, dense.__file__)
    family = manifest.load_family(cell.family_file)
    mc = engine_child.model_config(cell.config, family, "fixture-third",
                                   False)
    try:
        assert mc.tie_word_embeddings and mc.head_dim == 16
        params = engine_child.make_params(family, mc, 3987654321,
                                          jnp.float32, None)
        assert "lm_head" not in params
        served = sum(a.size for a in params["layers"].values())
        assert family.layer_stack_bytes(cell.config) == 2 * served
        got = engine_child.Control(
            family, mc, params, str(tmp_path / "out")).reference(
                {"prompt_ids": PROMPT, "generated_ids": GEN})["logprobs"]
        want = program_logprobs(mc, params)
        assert max(abs(a - b) for a, b in zip(got, want)) < 2e-4
    finally:
        from production_stack_tpu.models import config as mcfg
        mcfg._PRESETS.pop(mc.name)


def test_the_dense_family_refuses_the_new_architecture(moe):
    with pytest.raises(SystemExit, match="num_experts=4"):
        engine_child.model_config(moe["cell"].config, dense, "never", False)
