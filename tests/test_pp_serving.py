"""Pipeline parallelism as a SERVING config: the engine runs its real
step loop (prefill + decode + sampling) with layers and KV sharded over
a pp mesh axis (parallel/pp_serving.py). Reference capability:
ray-cluster.yaml + pipelineParallelSize (tutorial 15); ours is
--pipeline-parallel-size, one SPMD program per step.

Runs on the conftest's 8 virtual CPU devices."""

from __future__ import annotations

import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.sampling_params import SamplingParams


def make_engine(pp=1, tp=1, **overrides) -> LLMEngine:
    kw = dict(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=8, num_kv_blocks=64,
        max_num_seqs=2, max_prefill_chunk=32, seed=0,
        tensor_parallel_size=tp, pipeline_parallel_size=pp,
    )
    kw.update(overrides)
    return LLMEngine(EngineConfig(**kw))


PROMPTS = ["pipeline parallel serving", "second stream here"]


def test_pp2_matches_single_device():
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    ref = [o.token_ids for o in make_engine().generate(PROMPTS, sp)]
    pp = make_engine(pp=2)
    assert pp.runner.mesh is not None
    assert pp.runner.mesh.shape["pp"] == 2
    out = [o.token_ids for o in pp.generate(PROMPTS, sp)]
    assert out == ref


def test_pp2_tp2_matches_single_device():
    """pp x tp composition: layer axis manual over pp, Megatron tp left
    to GSPMD inside the partial-manual shard_map."""
    sp = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    ref = [o.token_ids for o in make_engine().generate(PROMPTS, sp)]
    eng = make_engine(pp=2, tp=2)
    assert eng.runner.mesh.shape == {"pp": 2, "tp": 2}
    out = [o.token_ids for o in eng.generate(PROMPTS, sp)]
    assert out == ref


def test_pp_sampled_and_multistep():
    """Sampled decode and the fused multi-step loop run through the
    staged forward too (same seeded-key parity as single-device)."""
    sp = SamplingParams(max_tokens=8, temperature=0.9, seed=3,
                        ignore_eos=True)
    ref = [o.token_ids for o in make_engine().generate(PROMPTS, sp)]
    out = [o.token_ids
           for o in make_engine(pp=2).generate(PROMPTS, sp)]
    assert out == ref
    sp0 = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    ref0 = [o.token_ids for o in make_engine().generate(PROMPTS, sp0)]
    out0 = [o.token_ids for o in make_engine(
        pp=2, num_scheduler_steps=4,
    ).generate(PROMPTS, sp0)]
    assert out0 == ref0


def test_pp_validation():
    import dataclasses

    # layers not divisible by pp
    with pytest.raises(ValueError, match="divisible"):
        make_engine(pp=3)
    # LoRA not stage-sharded yet
    with pytest.raises(ValueError, match="lora"):
        make_engine(pp=2, enable_lora=True)
    # pallas kernels don't nest in the pp manual region
    with pytest.raises(ValueError, match="pallas"):
        make_engine(pp=2, attention_impl="pallas")
    # config carries the knob (helm/CRD expose it)
    cfg = EngineConfig(model="pst-tiny-debug", pipeline_parallel_size=4)
    assert dataclasses.asdict(cfg)["pipeline_parallel_size"] == 4


def test_pp_embeddings_staged():
    """/v1/embeddings under pp rides the staged forward too (review r5:
    a plain scan over pp-sharded params would all-gather the full layer
    stack per device — the exact failure pp exists to avoid)."""
    import numpy as np

    ref_vec, _ = make_engine().embed_one("embedding text")
    pp_vec, n_toks = make_engine(pp=2).embed_one("embedding text")
    assert n_toks > 0
    np.testing.assert_allclose(pp_vec, ref_vec, rtol=1e-5, atol=1e-5)
