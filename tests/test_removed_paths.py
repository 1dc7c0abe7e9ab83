"""What PR 30 took out stays out: the second step protocol
(`async_decode`), the switch for a third attention path
(`ragged_kernel`) and `bench.py`, the tool that flipped them. A change
is attributed by the driver's pairs on the chip and the ledger, not by
a flag beside every mechanism."""

from __future__ import annotations

import dataclasses
import re
import subprocess
from pathlib import Path

import pytest

from production_stack_tpu.engine import __main__ as engine_main
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.model_runner import ModelRunner
from production_stack_tpu.engine.scheduler import (
    decode_precompile_variants,
)

REPO = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("flag", [
    "--async-decode", "--no-async-decode",
    "--ragged-kernel", "--no-ragged-kernel",
])
def test_the_engine_parser_refuses_the_removed_flags(flag, capsys):
    parser = engine_main.build_parser()
    parser.parse_args(["--model", "pst-tiny-debug"])  # the rest parses
    with pytest.raises(SystemExit) as exc:
        parser.parse_args(["--model", "pst-tiny-debug", flag])
    assert exc.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err


def test_engine_config_has_neither_field():
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    assert not fields & {"async_decode", "ragged_kernel"}
    # the switches that remain: each `--no-*` side is a mesh's or a
    # multihost engine's path and the tests' reference (ROADMAP D1)
    assert {"device_stop", "prefetch_decode", "prefill_pipeline",
            "ragged_dispatch", "sync_kv_offload"} <= fields
    for name in ("async_decode", "ragged_kernel"):
        with pytest.raises(TypeError):
            EngineConfig(model="pst-tiny-debug", **{name: False})
    # the one name the benchmark's warm-up still reads (ROADMAP D3)
    assert LLMEngine._async_decode is False


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_ragged_kernel_is_the_attention_impl(impl):
    runner = ModelRunner(EngineConfig(
        model="pst-tiny-debug", tokenizer="byte", dtype="float32",
        cache_dtype="float32", block_size=8, num_kv_blocks=16,
        max_num_seqs=2, attention_impl=impl,
    ))
    assert runner.attention_impl == impl
    assert runner.ragged_kernel is (impl == "pallas")
    assert runner.device_report()["ragged_kernel"] is (impl == "pallas")


@pytest.mark.parametrize("overlap,device_stop,want", [
    (True, True, [(8, True, True)]),
    (False, True, [(8, False, True)]),
    (True, False, [(8, True, False)]),
])
def test_decode_variants_follow_the_two_switches_that_remain(
        overlap, device_stop, want):
    """A staged round dispatches the chained program WITH its stop
    masks: no variant is warmed without them where device stops are on
    (the chained rounds of the protocol that went carried none)."""
    assert decode_precompile_variants(
        8, False, overlap=overlap, device_stop=device_stop) == want
    adaptive = decode_precompile_variants(
        8, True, overlap=overlap, device_stop=device_stop)
    assert [k for k, _, _ in adaptive] == [1, 2, 4, 8]
    assert adaptive[0] == (1, False, False)  # K=1 is the single step


# history lines may name what went; nothing else may send a reader there
_HISTORY = {"CHANGES.md", "PERF.md", "ROADMAP.md", "ISSUE.md",
            "PERF_LEDGER.jsonl", "tests/test_removed_paths.py"}


def _tracked_files() -> list[str]:
    out = subprocess.run(
        ["git", "ls-files"], cwd=REPO, capture_output=True, text=True)
    if out.returncode == 0:
        return out.stdout.splitlines()
    # a copy without .git holds only what git would commit
    return [str(p.relative_to(REPO)) for p in REPO.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts]


def test_no_tracked_file_sends_a_reader_to_bench_py():
    assert not (REPO / "bench.py").exists()
    pat = re.compile(r"\bbench\.py\b|PST_BENCH_")
    named = []
    for rel in _tracked_files():
        path = REPO / rel
        if rel in _HISTORY or not path.is_file():
            continue
        try:
            text = path.read_text()
        except UnicodeDecodeError:
            continue
        if pat.search(text):
            named.append(rel)
    assert named == []
