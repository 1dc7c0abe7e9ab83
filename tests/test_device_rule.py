"""Nothing on the engine's start-up path may hide the device.

- `python -m production_stack_tpu.engine` refuses the CPU backend unless
  `JAX_PLATFORMS` names it;
- a kernel the compiler refuses fails `ModelRunner` construction — it
  never selects another attention path;
- head_dim % 128 != 0 leaves the Pallas path only under `auto`;
- a TPU that reports no `bytes_limit` is an error, not a 16 GiB guess;
- the compile cache lives where `JAX_COMPILATION_CACHE_DIR` says, else
  in `<checkout>/.jax_cache`.
"""

from __future__ import annotations

import os

import jax
import pytest

from production_stack_tpu.engine import __main__ as engine_main
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.model_runner import ModelRunner
from production_stack_tpu.models import config as mcfg
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.ops import pallas_attention
from production_stack_tpu.utils import compile_cache

# smallest shape the Pallas kernels accept on a TPU (head_dim 128)
D128_CFG = ModelConfig(
    name="pst-tiny-d128",
    vocab_size=512,
    hidden_size=256,
    intermediate_size=256,
    num_layers=2,
    num_heads=2,
    num_kv_heads=1,
    head_dim=128,
    max_model_len=128,
    rope_theta=10000.0,
    tie_word_embeddings=True,
)


@pytest.fixture
def jax_config():
    """Restore the jax options these tests flip."""
    names = (
        "jax_platforms", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs",
    )
    saved = {n: getattr(jax.config, n) for n in names}
    yield jax.config
    for name, value in saved.items():
        jax.config.update(name, value)


@pytest.fixture
def as_if_on_tpu(monkeypatch):
    """The runner decides from `jax.default_backend()`; report a TPU."""
    monkeypatch.setitem(mcfg._PRESETS, D128_CFG.name, D128_CFG)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _config(model: str, **kw) -> EngineConfig:
    return EngineConfig(
        model=model, tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=8, max_num_seqs=2,
        **{"num_kv_blocks": 16, **kw},
    )


# -- the engine refuses the CPU unless asked for it -------------------------
@pytest.mark.parametrize("platforms", [None, "", "tpu"])
def test_cpu_backend_is_refused_unless_named(jax_config, platforms):
    assert jax.default_backend() == "cpu"
    engine_main.require_accelerator()  # conftest named cpu: accepted
    jax_config.update("jax_platforms", platforms)
    with pytest.raises(SystemExit, match="no accelerator"):
        engine_main.require_accelerator()
    jax_config.update("jax_platforms", "tpu,cpu")
    engine_main.require_accelerator()


def test_main_checks_the_device_before_building_the_engine(
    jax_config, monkeypatch
):
    def no_server(*a, **kw):
        raise AssertionError("engine built on a CPU nobody asked for")

    monkeypatch.setattr(engine_main, "EngineServer", no_server)
    monkeypatch.setattr(
        engine_main, "configure_compile_cache", lambda: "unused"
    )
    jax_config.update("jax_platforms", None)
    with pytest.raises(SystemExit, match="no accelerator"):
        engine_main.main(["--model", "pst-tiny-debug"])


# -- no fallback between attention paths -------------------------------------
def _refused(*a, **kw):
    raise RuntimeError("Mosaic failed to compile TPU kernel: refused")


def test_kernel_compile_error_fails_construction(as_if_on_tpu, monkeypatch):
    monkeypatch.setattr(pallas_attention, "paged_prefill_attention", _refused)
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        ModelRunner(_config(D128_CFG.name))


def test_ragged_kernel_compile_error_fails_construction(
    as_if_on_tpu, monkeypatch
):
    # the per-lane kernels "compile"; only the ragged one is refused
    monkeypatch.setattr(
        ModelRunner, "_pallas_smoke_test", lambda self, mc: None
    )
    monkeypatch.setattr(pallas_attention, "ragged_paged_attention", _refused)
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        ModelRunner(_config(D128_CFG.name))


def test_head_dim_64_leaves_pallas_only_under_auto(as_if_on_tpu):
    runner = ModelRunner(_config("pst-tiny-debug"))  # head_dim 16, auto
    report = runner.device_report()
    assert report["attention_impl"] == "xla"
    assert report["ragged_kernel"] is False
    with pytest.raises(ValueError, match="head_dim % 128"):
        ModelRunner(_config("pst-tiny-debug", attention_impl="pallas"))


def test_tpu_without_bytes_limit_is_an_error(as_if_on_tpu):
    # the CPU devices under the patched backend report no memory stats
    with pytest.raises(RuntimeError, match="bytes_limit"):
        ModelRunner(_config("pst-tiny-debug", num_kv_blocks=None))


def test_version_endpoint_serves_the_device_report():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    async def run():
        srv = EngineServer(_config("pst-tiny-debug"))
        srv.app.on_startup.clear()  # no step thread: nothing is generated
        srv.app.on_cleanup.clear()
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            return await (await client.get("/version")).json()
        finally:
            await client.close()

    body = asyncio.new_event_loop().run_until_complete(run())
    assert body["version"]
    assert (body["platform"], body["attention_impl"]) == ("cpu", "xla")
    assert body["device_count"] == len(jax.devices())
    assert "ragged_kernel" in body and "bytes_in_use" in body


# -- compile cache placed from outside ----------------------------------------
def test_cache_dir_from_the_environment_is_left_alone(
    jax_config, monkeypatch, tmp_path
):
    before = jax_config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax_config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_the_checkout(jax_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    expected = os.path.join(checkout, ".jax_cache")
    assert compile_cache.configure_compile_cache() == expected
    assert jax_config.jax_compilation_cache_dir == expected
    assert jax_config.jax_persistent_cache_min_compile_time_secs == 1.0
    assert jax_config.jax_persistent_cache_min_entry_size_bytes == -1
