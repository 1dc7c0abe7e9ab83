"""Latent attention, hyper-connections, a shared expert and a routed
scaling factor in models/layer_groups.py against the plain reference
(tests/reference_model.py::xing4_forward, un-absorbed attention), at the
tiny widths of `pst-tiny-latent-debug`: a 32-dim latent row + 8 rotary
dims read as key and as value by 4 heads, q through a 24-dim bottleneck,
YaRN past 64 original positions with the softmax scale's mscale squared,
four residual streams mixed by Sinkhorn-projected matrices, a leading
dense layer, then 16 sigmoid-routed experts (top-4, scaling 2) beside
one shared expert.

TOLERANCE 2e-4 on float32 logits, as tests/test_layer_groups.py states
it: both sides compute in float32 and differ in the order of sums (the
served path scores the cached latent rows with the query taken through
the key up-projection, chunk by chunk; the reference builds every
head's keys and values and holds one dense mask).
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_model as rm
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.models import config as mcfg
from production_stack_tpu.models import layer_groups
from production_stack_tpu.models.config import TINY_LATENT_DEBUG as MC
from production_stack_tpu.ops import attention as xla_attn
from production_stack_tpu.ops import layers as ops_layers
from production_stack_tpu.ops import moe

TOL = 2e-4
BS = 4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def engine(**kw) -> LLMEngine:
    cfg = dict(
        model=MC.name, tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=BS, num_kv_blocks=128,
        max_num_seqs=4, max_prefill_chunk=16, seed=3,
    )
    cfg.update(kw)
    return LLMEngine(EngineConfig(**cfg))


@pytest.fixture(scope="module")
def eng():
    return engine()


@pytest.fixture(scope="module")
def kernel_eng():
    """The programs the chip runs, the Pallas walk in interpret mode."""
    e = engine(attention_impl="pallas", num_scheduler_steps=4)
    assert e.runner.ragged_kernel
    return e


def ids(n: int, seed: int = 0) -> list[int]:
    return [int(x) for x in
            np.random.default_rng(seed).integers(1, MC.vocab_size - 4, n)]


def reference(params, tokens, cfg=MC) -> np.ndarray:
    with jax.default_matmul_precision("highest"):
        return np.asarray(rm.xing4_forward(cfg, params, tokens))


def serve(e: LLMEngine, tokens, n_prompt: int, chunk: int, reuse=True):
    """tests/test_layer_groups.py::serve: chunked prefill, then decode
    teacher-forced, through the runner and the block manager."""
    r, bm = e.runner, e.block_manager
    table, cached = bm.allocate_prompt(tokens[:n_prompt], reuse_cache=reuse)
    rows, start, prev_hash, done = {}, cached, 0, 0
    while start < n_prompt:
        end = min(start + chunk, n_prompt)
        bm.prepare_chunk(table, start, end)
        _, logits = r.prefill(tokens[start:end], start, table, end)
        rows[end - 1] = np.asarray(logits)
        start = end
        for i in range(done, end // BS):
            prev_hash = bm.register_block(
                prev_hash, tuple(tokens[i * BS:(i + 1) * BS]), table[i])
        done = end // BS
    for pos in range(n_prompt, len(tokens)):
        assert bm.ensure_capacity(pos + 1, table)
        logits = r.decode([tokens[pos]], [pos], [table], [pos + 1])
        rows[pos] = np.asarray(logits[0])
    return rows, cached, table


def assert_rows(rows: dict, ref: np.ndarray, tol: float = TOL) -> None:
    for pos, row in rows.items():
        np.testing.assert_allclose(row, ref[pos], rtol=tol, atol=tol,
                                   err_msg=f"position {pos}")


# -- (a) through the cache --------------------------------------------------
@pytest.mark.parametrize("chunk", [16, 7])
def test_chunked_prefill_then_decode_equals_the_reference(eng, chunk):
    # 80 tokens: past YaRN's original 64 positions
    tokens = ids(80, seed=chunk)
    rows, cached, table = serve(eng, tokens, 66, chunk, reuse=False)
    assert cached == 0
    assert_rows(rows, reference(eng.runner.params, tokens))
    eng.block_manager.free(table)


def test_the_cache_group_is_one_array_a_layer_and_no_v(eng):
    r = eng.runner
    (kc,), (vc,) = r.k_cache["g"], r.v_cache["g"]
    lat = MC.attn_kinds[0].latent_dim
    assert vc is None
    assert kc.shape == (MC.num_layers, 1, 128 * BS, lat + MC.rope_dim)
    assert type(eng.block_manager).__name__ == "BlockManager"
    stats = eng._layer_group_stats()
    assert set(stats["attn_context_by_kind"]) == {"latent"}
    assert set(stats["kv_blocks_in_use"]) == {"latent"}


def test_a_pair_on_one_page_keeps_its_seats_under_the_latent_walk(
        kernel_eng):
    """The latent kind's shared pass costs more than two lanes' walks
    (`model_runner.seat_least`): the runner seats three on one leading
    page together and leaves a pair where it arrived."""
    r = kernel_eng.runner
    assert r._seat_least == 3
    b = r.config.max_num_seqs
    pair = [[7, 20], [9, 21], [7, 22], [11, 23]][:b]
    assert r.decode_lanes(pair).tolist() == list(range(len(pair)))
    r16 = engine(max_num_seqs=16).runner
    three = [[7, 20], [9, 21], [7, 22], [9, 23], [7, 24]]
    assert r16.decode_lanes(three).tolist() == [0, 8, 1, 9, 2]


def test_a_prefix_hit_on_the_kernel_path_serves_the_same_logits(kernel_eng):
    e = kernel_eng
    first = ids(40, seed=7)
    rows, cached, table = serve(e, first, 40, 16)
    assert cached == 0
    assert_rows(rows, reference(e.runner.params, first))
    e.block_manager.free(table)
    second = first[:36] + ids(14, seed=8)
    rows, cached, table = serve(e, second, 44, 16)
    assert cached == 36  # nine latent blocks
    assert_rows(rows, reference(e.runner.params, second))
    e.block_manager.free(table)


@pytest.mark.parametrize("which", ["kernel", "xla"])
def test_a_packed_round_equals_the_reference(kernel_eng, eng, which):
    """Two sequences' second chunks in one program: the ragged kernel's
    rows, and the XLA path's per-sequence gather (a rehearsal's)."""
    e = kernel_eng if which == "kernel" else eng
    r, bm = e.runner, e.block_manager
    a, b = ids(29, seed=21), ids(22, seed=22)
    tables = []
    for t in (a, b):
        table, _ = bm.allocate_prompt(t, reuse_cache=False)
        tables.append(table)
        bm.prepare_chunk(table, 0, 16)
        r.prefill(t[:16], 0, table, 16)
        bm.prepare_chunk(table, 16, len(t))
    _, logits = r.prefill_batch(
        [a[16:], b[16:]], [16, 16], tables, [len(a), len(b)])
    for i, t in enumerate((a, b)):
        np.testing.assert_allclose(
            np.asarray(logits[i]), reference(r.params, t)[-1],
            rtol=TOL, atol=TOL)
    for table in tables:
        bm.free(table)


def test_the_engine_serves_mixed_rounds_with_a_prefix_hit(kernel_eng):
    """Ragged-rows prefill groups, fused decode rounds and lane-typed
    mixed rounds: the second request is admitted while the first
    decodes and shares its first 24 tokens through the prefix cache.
    The routed layers' counters count this model's pairs."""
    e = kernel_eng
    before = e.runner.moe_stats()
    a = ids(41, seed=31)
    b = a[:24] + ids(19, seed=32)
    sp = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    e.add_request("a", prompt_token_ids=a, sampling_params=sp)
    done = {}
    for step in range(200):
        if step == 4:
            e.add_request("b", prompt_token_ids=b, sampling_params=sp)
        for o in e.step():
            if o.finished:
                done[o.request_id] = o
        if len(done) == 2:
            break
    assert done["b"].num_cached_tokens == 24
    for rid, prompt in (("a", a), ("b", b)):
        got = list(done[rid].token_ids)
        ref = reference(e.runner.params, prompt + got)
        want = [int(np.argmax(ref[len(prompt) - 1 + i]))
                for i in range(len(got))]
        assert got == want, rid
    jax.block_until_ready(list(e.runner._stats_pending))
    routed, local, active = (
        x - y for x, y in zip(e.runner.moe_stats(), before))
    # 3 routed layers, 4 experts a token; every expert is held here
    rows = 41 + 9 + (len(b) - 24) + 9
    assert routed == local == 3 * 4 * rows
    assert 0 < active <= 3 * 16 * 40


# -- (b) absorbed against un-absorbed ------------------------------------------
def test_absorbed_attention_equals_the_unabsorbed_form():
    """One latent layer's attention alone: the query through W_uk
    against the cached rows, the output through W_uv (`_latent_qkv`,
    the XLA attention over the rows as K and their first lanes as V),
    against every head's own keys and values."""
    ak = MC.attn_kinds[0]
    lat, rot = ak.latent_dim, MC.rope_dim
    nq, dk, dv = MC.num_heads, MC.head_dim, MC.v_dim
    nope = dk - rot
    t = 50
    keys = iter(jax.random.split(jax.random.key(0), 8))
    x = jax.random.normal(next(keys), (t, MC.hidden_size))
    lp = {
        "w_dq": 0.2 * jax.random.normal(next(keys), (64, MC.q_lora_rank)),
        "q_norm": 1 + 0.1 * jax.random.normal(next(keys), (MC.q_lora_rank,)),
        "w_uq": 0.3 * jax.random.normal(
            next(keys), (MC.q_lora_rank, nq * dk)),
        "w_dkv": 0.2 * jax.random.normal(next(keys), (64, lat + rot)),
        "kv_norm": 1 + 0.1 * jax.random.normal(next(keys), (lat,)),
        "w_ukv": 0.3 * jax.random.normal(
            next(keys), (lat, nq * (nope + dv))),
    }
    pos = jnp.arange(t, dtype=jnp.int32)
    cos, sin = ops_layers.rope_cos_sin(pos, rot, ak.rope_theta,
                                       yarn=MC.rope_yarn)
    kc = jnp.zeros((1, 1, t, lat + rot))
    q, kc, w_uv = layer_groups._latent_qkv(
        MC, ak, x, lp, kc, 0, pos, cos, sin, jnp.float32)
    rows = kc[0, 0][:, None, :]                      # (t, 1, lat + rot)
    out_lat = xla_attn.context_attention_prefill(
        q, rows, rows[..., :lat], pos, jnp.int32(t), MC.attn_scale)
    absorbed = jnp.einsum("nhl,lhd->nhd", out_lat, w_uv)

    c, k_rope = kc[0, 0, :, :lat], kc[0, 0, :, lat:]
    kv = (c @ lp["w_ukv"]).reshape(t, nq, nope + dv)
    cq = ops_layers.rms_norm(x @ lp["w_dq"], lp["q_norm"], MC.rms_norm_eps)
    qh = (cq @ lp["w_uq"]).reshape(t, nq, dk)
    q_rope, _ = ops_layers.apply_rope(qh[..., nope:], qh[:, :1, nope:],
                                      cos, sin)
    s = (jnp.einsum("thd,shd->ths", qh[..., :nope], kv[..., :nope])
         + jnp.einsum("thd,sd->ths", q_rope, k_rope)) * MC.attn_scale
    s = jnp.where((pos[None, :] <= pos[:, None])[:, None, :], s, -jnp.inf)
    plain = jnp.einsum("ths,shd->thd", jax.nn.softmax(s, -1),
                       kv[..., nope:])
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(plain),
                               rtol=1e-4, atol=1e-5)


# -- (c) the mixing matrices --------------------------------------------------
def test_h_res_is_doubly_stochastic_and_the_clamp_is_active():
    n, h = MC.hc_mult, MC.hidden_size
    keys = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(keys[0], (n, 37, h))
    phi = jax.random.normal(keys[1], (n * h, 2 * n + n * n)) * (n * h) ** -0.5
    alpha = jnp.asarray([1.0, 1.0, 0.5])
    b = 0.5 * jax.random.normal(keys[2], (2 * n + n * n,))
    pre, post, res = layer_groups.hc_mix(MC, x, phi, alpha, b)
    assert pre.shape == post.shape == (n, 37) and res.shape == (n, n, 37)
    assert float(pre.min()) > 0 and float(pre.max()) < 1
    assert float(post.min()) > 0 and 1 < float(post.max()) < 2
    # raw entries within a unit or two (the seeded weights' scale): 20
    # iterations reach 1e-5, rows (axis 1 sums over the streams read)
    # and columns. Entries e^4 apart take more: Sinkhorn's rate is the
    # matrix's, and the published 20 are what is served
    res = np.asarray(res)
    assert res.min() > 0
    np.testing.assert_allclose(res.sum(0), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(1), 1.0, atol=1e-5)
    xs = jnp.asarray(x).transpose(1, 0, 2)
    for got, want in zip((pre, post, res),
                         rm.hc_matrices(MC, xs, phi, alpha, b)):
        np.testing.assert_allclose(
            np.moveaxis(np.asarray(got), -1, 0), np.asarray(want),
            rtol=1e-4, atol=1e-6)

    # a phi this large drives the raw matrix far past +-30: the clamp
    # holds it there (the reference's clamp gives the same matrices),
    # and without it exp overflows float32
    big = 1600.0 * phi
    v = np.asarray(xs).reshape(37, n * h)
    xt = v / np.sqrt((v * v).mean(-1, keepdims=True) + MC.rms_norm_eps)
    raw = 0.5 * (xt @ np.asarray(big))[:, 2 * n:] + np.asarray(b)[2 * n:]
    assert (np.abs(raw) > 30).mean() > 0.5 and np.abs(raw).max() > 100
    clamped = np.asarray(layer_groups.hc_mix(MC, x, big, alpha, b)[2])
    assert np.isfinite(clamped).all()
    np.testing.assert_allclose(
        np.moveaxis(clamped, -1, 0),
        np.asarray(rm.hc_matrices(MC, xs, big, alpha, b)[2]),
        rtol=1e-3, atol=1e-5)
    loose = dataclasses.replace(MC, hc_res_clamp=(-1e9, 1e9))
    assert not np.isfinite(
        np.asarray(layer_groups.hc_mix(loose, x, big, alpha, b)[2])).all()


def test_the_sinkhorn_kernel_equals_its_plain_form():
    """On the chip the 20 iterations run inside one Pallas kernel
    (`ops/sinkhorn.py`); here, in interpret mode, against the plain jnp
    form every CPU path takes: the same `_iterate`."""
    from production_stack_tpu.ops import sinkhorn as sk

    x = jnp.exp(jax.random.normal(jax.random.key(5), (4, 4, 40)))
    plain = sk.sinkhorn(x, 20, 1e-6)
    kernel = sk.sinkhorn(x, 20, 1e-6, interpret=True)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(plain),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(plain).sum(0), 1.0, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(plain).transpose(2, 0, 1),
        np.asarray(rm.sinkhorn(x.transpose(2, 0, 1), 20, 1e-6)),
        rtol=1e-5, atol=1e-7)


# -- (d) YaRN ------------------------------------------------------------------
def test_yarn_frequencies_and_the_softmax_scale_at_published_values():
    y = mcfg.YarnScaling(factor=64.0, original_max_position=4096,
                         beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                         mscale_all_dim=1.0)
    got = np.asarray(ops_layers.yarn_inv_freq(64, 10000.0, y))
    want = rm.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 1.0 / (10000.0 ** (np.arange(0, 64, 2) / 64))
    # fast dims keep their frequency, slow ones take it over 64, and
    # some lie between
    np.testing.assert_allclose(got[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(got[-4:], plain[-4:] / 64, rtol=1e-6)
    between = (got < plain * 0.999) & (got > plain / 64 * 1.001)
    assert between.any()
    assert ops_layers.yarn_mscale(64.0, 1.0) == pytest.approx(1.4159,
                                                              abs=1e-4)
    cfg = dataclasses.replace(MC, head_dim=192, rope_yarn=y)
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2,
                                           rel=1e-4)
    cos, _ = ops_layers.rope_cos_sin(jnp.arange(3), 64, 10000.0, yarn=y)
    assert float(cos[0, 0]) == 1.0  # mscale / mscale_all_dim = 1


# -- (e) the routed layer's new terms ---------------------------------------------
def test_the_scaling_factor_multiplies_the_routed_sum_only():
    keys = jax.random.split(jax.random.key(2), 6)
    h, f = 32, 16
    x = jax.random.normal(keys[0], (24, h))
    args = (jax.random.normal(keys[1], (h, 8)),
            0.1 * jax.random.normal(keys[5], (8,)),
            *(0.2 * jax.random.normal(k, s) for k, s in zip(
                keys[2:5], [(8, h, f), (8, h, f), (8, f, h)])))
    kw = dict(top_k=2, first_expert=0, scoring="sigmoid")
    one, _ = moe.routed_experts(x, *args, **kw)
    two, _ = moe.routed_experts(x, *args, scale=2.0, **kw)
    np.testing.assert_allclose(np.asarray(two), 2 * np.asarray(one),
                               rtol=1e-6, atol=1e-6)
    idx, w = moe.route(x, args[0], args[1], 2, "sigmoid", True, 2.0)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.0, rtol=1e-6)


# -- (f) the configuration's path -------------------------------------------------
def test_from_hf_config_reads_the_catalogs_keys(tmp_path):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(ln) for ln in f
                   if '"Xing4.0-29B-A4B"' in ln)
    (tmp_path / "config.json").write_text(json.dumps(row["config"]))
    mc = mcfg.from_hf_config(str(tmp_path), name="xing4-catalog")
    assert mc.layer_groups and mc.num_layers == 40
    assert mc.attn_kinds == (mcfg.AttnKind(
        num_kv_heads=1, rope_theta=10000.0, latent_dim=512),)
    assert (mc.hidden_size, mc.num_heads, mc.head_dim, mc.v_dim,
            mc.rope_dim, mc.q_lora_rank) == (3584, 32, 192, 128, 64, 768)
    assert (mc.intermediate_size, mc.moe_intermediate_size,
            mc.dense_layers, mc.router_experts, mc.local_experts,
            mc.num_experts_per_tok, mc.shared_experts,
            mc.routed_scaling) == (9216, 1024, 2, 64, 64, 4, 1, 2.0)
    assert (mc.router_scoring, mc.router_bias, mc.router_renorm) == (
        "sigmoid", True, True)
    assert (mc.hc_mult, mc.hc_sinkhorn_iters, mc.hc_eps,
            mc.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert mc.rope_yarn == mcfg.YarnScaling(
        factor=64.0, original_max_position=4096, beta_fast=32.0,
        beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
    assert mc.attn_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2,
                                          rel=1e-4)
    assert mc.segments() == ((0, False, 2, 0), (0, True, 38, 2))
    assert (mc.vocab_size, mc.max_model_len, mc.rms_norm_eps,
            mc.tie_word_embeddings) == (131072, 262144, 1e-6, False)
    # 29B, of which the tree layer_groups builds holds every one
    assert mc.num_params() / 1e9 == pytest.approx(29.0, abs=1.0)
    for key, value in (("n_group", 8), ("attention_bias", True),
                       ("moe_layer_freq", 2)):
        (tmp_path / "config.json").write_text(
            json.dumps({**row["config"], key: value}))
        with pytest.raises(ValueError, match=key):
            mcfg.from_hf_config(str(tmp_path))


def test_the_mimo_configuration_comes_out_field_for_field_as_before(
        tmp_path):
    """`_from_mimo_v2` used to refuse `n_shared_experts` and
    `routed_scaling_factor` by name; they are fields now, which MiMo's
    own file leaves at none and 1. Every field of the benchmark's MiMo
    configuration, written out."""
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           "mimo-v2.5-ep16-l7.json")) as f:
        hf = json.load(f)
    hf["n_routed_experts"] = hf.pop("router_experts")
    (tmp_path / "config.json").write_text(json.dumps(hf))
    mc = mcfg.from_hf_config(str(tmp_path), name="mimo")
    assert mc == mcfg.ModelConfig(
        name="mimo", vocab_size=19072, hidden_size=4096,
        intermediate_size=16384, num_layers=7, num_heads=64,
        num_kv_heads=4, head_dim=192, max_model_len=1048576,
        rope_theta=1e7, rms_norm_eps=1e-5, tie_word_embeddings=False,
        attn_kinds=(
            mcfg.AttnKind(num_kv_heads=4, rope_theta=1e7),
            mcfg.AttnKind(num_kv_heads=8, rope_theta=1e4, window=128,
                          sink=True)),
        layer_kinds=(0, 1, 1, 1, 1, 1, 0), v_head_dim=128, rotary_dim=64,
        v_scale=0.707, router_experts=256, num_experts_per_tok=8,
        router_scoring="sigmoid", router_bias=True, router_renorm=True,
        moe_intermediate_size=2048, dense_layers=1, ep_rank=0,
        ep_size=16)
    assert (mc.shared_experts, mc.routed_scaling, mc.hc_mult,
            mc.q_lora_rank, mc.rope_yarn) == (0, 1.0, 1, 0, None)
    assert mc.attn_scale == 192 ** -0.5
    (tmp_path / "config.json").write_text(json.dumps(
        {**hf, "n_shared_experts": 1, "routed_scaling_factor": 2.5}))
    other = mcfg.from_hf_config(str(tmp_path), name="mimo")
    assert (other.shared_experts, other.routed_scaling) == (1, 2.5)
    assert dataclasses.replace(other, shared_experts=0,
                               routed_scaling=1.0) == mc


def test_what_has_no_code_path_is_refused_by_name():
    with pytest.raises(ValueError, match="enable-lora.*num-speculative"):
        engine(enable_lora=True, num_speculative_tokens=2)
    with pytest.raises(ValueError, match="tensor-parallel"):
        engine(tensor_parallel_size=2)
