"""What PR 30 took out stays out: the second step protocol
(`async_decode`), the switch for a third attention path
(`ragged_kernel`) and `bench.py`, the tool that flipped them. A change
is attributed by the driver's pairs on the chip and the ledger, not by
a flag beside every mechanism.

And what PR 50 took out: the prefill stage with the scheduler's
zero-cost bypass (`stage_prefill`, `staged_prefill_ready`,
`max_staged_prefill_run`) and adaptive K with the K axis of the
program space (`adaptive_decode_k`, `pick_decode_k`). A decode round
has one size, `--num-scheduler-steps`; `--no-adaptive-decode-k` still
parses, and changes nothing, while the benchmark's configurations pass
it."""

from __future__ import annotations

import dataclasses
import json
import re
import subprocess
from pathlib import Path

import pytest

from production_stack_tpu.engine import __main__ as engine_main
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.llm_engine import LLMEngine
from production_stack_tpu.engine.model_runner import ModelRunner
from production_stack_tpu.engine.scheduler import (
    Scheduler,
    SchedulerConfig,
    decode_precompile_variant,
)

REPO = Path(__file__).resolve().parent.parent
CHIP_CONFIGS = sorted(
    p.name for p in (REPO / "benchmarks" / "chip" / "configs").glob("*.json"))


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
    (True, True, (8, True, True)),
    (False, True, (8, False, True)),
    (True, False, (8, True, False)),
])
def test_decode_variants_follow_the_two_switches_that_remain(
        overlap, device_stop, want):
    """A staged round dispatches the chained program WITH its stop
    masks: no variant is warmed without them where device stops are on
    (the chained rounds of the protocol that went carried none)."""
    assert decode_precompile_variant(
        8, overlap=overlap, device_stop=device_stop) == want


def test_the_single_step_has_no_variant():
    assert decode_precompile_variant(
        1, overlap=True, device_stop=True) == (1, False, False)


# -- PR 50: the prefill stage and adaptive K -------------------------------
@pytest.mark.parametrize("build,error", [
    (lambda: EngineConfig(
        model="pst-tiny-debug", adaptive_decode_k=False), TypeError),
    (lambda: SchedulerConfig(adaptive_decode_k=False), TypeError),
    (lambda: SchedulerConfig(max_staged_prefill_run=8), TypeError),
    (lambda: ModelRunner.stage_prefill, AttributeError),
    (lambda: ModelRunner.stage_prefill_batch, AttributeError),
    (lambda: ModelRunner.prefill(
        None, [1], 0, [0], 1, staged=((), None)), TypeError),
    (lambda: Scheduler.pick_decode_k, AttributeError),
], ids=["engine.adaptive_decode_k", "scheduler.adaptive_decode_k",
        "max_staged_prefill_run", "stage_prefill", "stage_prefill_batch",
        "prefill(staged=)", "pick_decode_k"])
def test_the_names_that_went_are_gone(build, error):
    with pytest.raises(error):
        build()


def test_the_inert_flag_parses_and_changes_nothing():
    parser = engine_main.build_parser()
    base = ["--model", "pst-tiny-debug", "--num-scheduler-steps", "8"]
    assert engine_main.config_from_args(
        parser.parse_args(base + ["--no-adaptive-decode-k"])
    ) == engine_main.config_from_args(parser.parse_args(base))


def test_the_parser_refuses_the_switch_that_went(capsys):
    with pytest.raises(SystemExit) as exc:
        engine_main.build_parser().parse_args(
            ["--model", "pst-tiny-debug", "--adaptive-decode-k"])
    assert exc.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err


@pytest.mark.parametrize("name", CHIP_CONFIGS)
def test_a_cell_resolves_to_one_round_size(name):
    """Every configuration of the benchmark still parses (the seven
    that stood when the switch went pass the inert flag; a file added
    since need not, and PR 51's does not), and its rounds have the one
    size it names: this PR cannot strand a cell."""
    config = json.loads(
        (REPO / "benchmarks" / "chip" / "configs" / name).read_text())
    args = list(config["engine_args"])
    steps = int(args[args.index("--num-scheduler-steps") + 1])
    ecfg = engine_main.config_from_args(
        engine_main.build_parser().parse_args(
            ["--model", Path(name).stem, *args]))
    assert ecfg.num_scheduler_steps == steps > 1
    assert decode_precompile_variant(
        ecfg.num_scheduler_steps, overlap=ecfg.prefetch_decode,
        device_stop=ecfg.device_stop) == (steps, True, True)


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


def _files_naming(pat: re.Pattern) -> dict[str, int]:
    """Tracked file outside the history -> lines of it that match."""
    named = {}
    for rel in _tracked_files():
        path = REPO / rel
        if rel in _HISTORY or not path.is_file():
            continue
        try:
            text = path.read_text()
        except UnicodeDecodeError:
            continue
        hits = sum(1 for line in text.splitlines() if pat.search(line))
        if hits:
            named[rel] = hits
    return named


def test_no_tracked_file_sends_a_reader_to_bench_py():
    assert not (REPO / "bench.py").exists()
    assert _files_naming(re.compile(r"\bbench\.py\b|PST_BENCH_")) == {}


def test_no_tracked_file_names_the_stage_or_adaptive_k():
    assert _files_naming(re.compile(
        "adaptive_decode_k|pick_decode_k|ADMISSION_K_CLAMP"
        "|decode_k_buckets|_staged_prefill|staged_prefill_ready"
        "|stage_prefill|max_staged_prefill_run|note_staged_prefill_miss"
        "|prefill_staged_|observe_decode_k|drain_decode_k")) == {}


def test_the_flag_is_named_by_the_configurations_and_one_parser_line():
    named = _files_naming(re.compile("adaptive-decode-k"))
    parser = "production_stack_tpu/engine/__main__.py"
    assert named.pop(parser) == 1
    assert sorted(named) == [
        f"benchmarks/chip/configs/{name}" for name in CHIP_CONFIGS]
