"""stackcheck analyzer tests: per-rule fixtures (positive + negative +
suppression), CLI exit-code contract, and the tier-1 gate that the repo
self-scan stays at zero unsuppressed findings.

The fixtures double as executable documentation of each rule's semantics;
keep them small and obvious.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from production_stack_tpu.analysis import (
    all_rules,
    analyze_paths,
    analyze_source,
)

REPO_ROOT = Path(__file__).resolve().parent.parent
PACKAGE = REPO_ROOT / "production_stack_tpu"


def findings_for(src: str, rule: str | None = None):
    found = analyze_source(textwrap.dedent(src), path="fixture.py")
    live = [f for f in found if not f.suppressed]
    if rule is not None:
        live = [f for f in live if f.rule == rule]
    return live


# -- fixtures: one (positive, negative, suppressed) triple per rule ---------
# positive snippets MUST trip exactly their rule; negatives must be clean
# for that rule; suppressed carries a stackcheck directive.
FIXTURES = {
    "falsy-walrus-gate": dict(
        positive="""
            from aiohttp import web

            def check(body):
                if "model" not in body:
                    return web.json_response({"error": "x"}, status=400)
                return None

            def handler(body):
                if err := check(body):
                    return err
                return "ok"
        """,
        negative="""
            from aiohttp import web

            def check(body):
                if "model" not in body:
                    return web.json_response({"error": "x"}, status=400)
                return None

            def handler(body):
                if (err := check(body)) is not None:
                    return err
                return "ok"
        """,
        suppressed="""
            def make():
                return dict(a=1)

            def handler(body):
                # stackcheck: disable=falsy-walrus-gate — always non-empty
                if cfg := make():
                    return cfg
        """,
    ),
    "blocking-async": dict(
        positive="""
            import time

            async def handler():
                time.sleep(0.5)
                return 1
        """,
        negative="""
            import asyncio
            import time

            def backoff():          # sync helper: fine
                time.sleep(0.5)

            async def handler():
                await asyncio.sleep(0.5)
                loop = asyncio.get_running_loop()
                await loop.run_in_executor(None, backoff)
        """,
        suppressed="""
            import time

            async def handler():
                # stackcheck: disable=blocking-async — provably off-loop
                time.sleep(0.5)
        """,
    ),
    "device-sync-hot": dict(
        positive="""
            import jax

            # stackcheck: hot-path
            def dispatch(runner, tokens):
                logits = runner.decode(tokens)
                return float(logits[0])
        """,
        negative="""
            import jax
            import numpy as np

            # stackcheck: hot-path
            def dispatch(runner, tokens):
                arr = np.asarray([1, 2, 3])   # literal: host prep
                x = float("inf")              # constant: host-only
                return runner.decode(tokens)

            def cold(x):
                return float(x)               # unmarked function: fine
        """,
        suppressed="""
            import numpy as np

            # stackcheck: hot-path
            def fetch_round(pending):
                # stackcheck: disable=device-sync-hot — THE intended fetch
                return np.asarray(pending.tokens)
        """,
    ),
    "fire-and-forget-task": dict(
        positive="""
            import asyncio

            async def start(loop_fn):
                asyncio.create_task(loop_fn())
        """,
        negative="""
            import asyncio

            async def start(self, loop_fn):
                self.task = asyncio.create_task(loop_fn())
                done = await asyncio.ensure_future(loop_fn())
                return done
        """,
        suppressed="""
            import asyncio

            async def start(loop_fn):
                # stackcheck: disable=fire-and-forget-task — daemon-like
                asyncio.ensure_future(loop_fn())
        """,
    ),
    "guarded-by-lock": dict(
        positive="""
            import threading

            class Engine:
                def __init__(self):
                    self.streams = {}  # guarded by: self.lock
                    self.lock = threading.Lock()

                def deliver(self, rid, out):
                    self.streams[rid].put(out)
        """,
        negative="""
            import threading

            class Engine:
                def __init__(self):
                    self.streams = {}  # guarded by: self.lock
                    self.lock = threading.Lock()

                def deliver(self, rid, out):
                    with self.lock:
                        self.streams[rid].put(out)

                async def adeliver(self, rid, out):
                    async with self.lock:
                        self.streams[rid].put(out)
        """,
        suppressed="""
            import threading

            class Engine:
                def __init__(self):
                    self.streams = {}  # guarded by: self.lock
                    self.lock = threading.Lock()

                def teardown(self):
                    # stackcheck: disable=guarded-by-lock — post-join
                    self.streams.clear()
        """,
    ),
    "silent-except": dict(
        positive="""
            def probe(url):
                try:
                    return fetch(url)
                except Exception:
                    return None
        """,
        negative="""
            import logging

            logger = logging.getLogger(__name__)

            def probe(url):
                try:
                    return fetch(url)
                except ValueError:      # narrow: fine
                    return None
                except Exception as e:
                    logger.debug("probe failed: %s", e)
                    return None

            def surface(url):
                try:
                    return fetch(url)
                except Exception as e:
                    return {"error": str(e)}
        """,
        suppressed="""
            def probe(url):
                try:
                    return fetch(url)
                # stackcheck: disable=silent-except — best-effort probe
                except Exception:
                    return None
        """,
    ),
    "mutable-shared-state": dict(
        positive="""
            CACHE = {}

            def f(items=[]):
                return items

            async def handler(key, value):
                CACHE[key] = value
        """,
        negative="""
            CACHE = {}

            def f(items=None):
                return items or []

            def initialize(key, value):   # sync initializer: fine
                CACHE[key] = value

            async def handler(key):
                return CACHE.get(key)     # read-only access: fine
        """,
        suppressed="""
            SEEN = set()

            async def handler(key):
                # stackcheck: disable=mutable-shared-state — single loop
                SEEN.add(key)
        """,
    ),
    # -- v2 interprocedural rules (call-graph propagation) ------------------
    "device-sync-transitive": dict(
        positive="""
            import jax

            # stackcheck: hot-path
            def step(x):
                return stage(x)

            def stage(x):
                return x.item()
        """,
        negative="""
            import jax

            # stackcheck: hot-path
            def step(x):
                return stage(x)

            # stackcheck: not-hot — sanctioned fetch seam
            def stage(x):
                return x.item()
        """,
        suppressed="""
            # stackcheck: hot-path
            def step(x):
                return stage(x)

            def stage(x):
                # stackcheck: disable=device-sync-transitive — intended
                # fetch point for this round's sampled tokens
                return x.item()
        """,
    ),
    "blocking-hot": dict(
        positive="""
            import time

            # stackcheck: hot-path
            def step(batch):
                flush(batch)

            def flush(batch):
                time.sleep(0.1)
        """,
        negative="""
            import time

            # stackcheck: hot-path
            def step(batch):
                flush(batch)

            # stackcheck: not-hot — offload worker submission seam
            def flush(batch):
                time.sleep(0.1)
        """,
        suppressed="""
            import time

            # stackcheck: hot-path
            def step(batch):
                flush(batch)

            def flush(batch):
                # stackcheck: disable=blocking-hot — deliberate yield
                time.sleep(0.001)
        """,
    ),
    "blocking-async-transitive": dict(
        positive="""
            import time

            async def handler(req):
                return prepare(req)

            def prepare(req):
                time.sleep(0.1)
                return req
        """,
        negative="""
            import time

            async def handler(req):
                return prepare(req)

            def cli_main(req):
                return prepare(req)

            def prepare(req):
                time.sleep(0.1)
                return req
        """,
        suppressed="""
            import time

            async def handler(req):
                return prepare(req)

            def prepare(req):
                # stackcheck: disable=blocking-async-transitive — 100ms
                # calibrated settle before the fleet probe
                time.sleep(0.1)
                return req
        """,
    ),
    # -- v2 contract rules --------------------------------------------------
    "wall-clock-banned": dict(
        positive="""
            # stackcheck: monotonic-only — interval math module
            import time

            def refill(last):
                return time.time() - last
        """,
        negative="""
            # stackcheck: monotonic-only — interval math module
            import time

            def refill(last):
                return time.monotonic() - last
        """,
        suppressed="""
            # stackcheck: monotonic-only — interval math module
            import time

            def export_stamp():
                # stackcheck: disable=wall-clock-banned — the export
                # edge needs a calendar timestamp, not an interval
                return time.time()
        """,
    ),
    "paired-release": dict(
        positive="""
            def handle(req):
                admission = get_admission_controller()
                ticket, shed = admission.admit(req)
                do_work(req)
                return ticket
        """,
        negative="""
            def handle(req):
                admission = get_admission_controller()
                ticket, shed = admission.admit(req)
                try:
                    do_work(req)
                finally:
                    admission.release(ticket)
        """,
        suppressed="""
            def handle(req):
                admission = get_admission_controller()
                # stackcheck: disable=paired-release — probe path:
                # the ticket is released by the caller's finally
                ticket, shed = admission.admit(req)
                return ticket
        """,
    ),
    "exactly-once-note": dict(
        positive="""
            # stackcheck: slo-finish
            def finish(self, ok):
                if ok:
                    self._note_slo(ok)
                return ok
        """,
        negative="""
            # stackcheck: slo-finish
            def finish(self, ok):
                self._note_slo(ok)
                return ok
        """,
        suppressed="""
            # stackcheck: slo-finish
            def finish(self, ok):
                if not ok:
                    # stackcheck: disable=exactly-once-note — rejected
                    # before the pipeline; nothing to judge
                    return None
                self._note_slo(ok)
                return ok
        """,
    ),
}


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_positive(rule):
    live = findings_for(FIXTURES[rule]["positive"], rule)
    assert live, f"{rule}: positive fixture produced no finding"


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_negative(rule):
    live = findings_for(FIXTURES[rule]["negative"], rule)
    assert not live, f"{rule}: negative fixture flagged: {live}"


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_suppressed(rule):
    src = textwrap.dedent(FIXTURES[rule]["suppressed"])
    all_found = [f for f in analyze_source(src) if f.rule == rule]
    assert all_found, f"{rule}: suppressed fixture produced no finding"
    assert all(f.suppressed for f in all_found), (
        f"{rule}: suppression directive did not apply"
    )


def test_fixture_rules_cover_registry():
    assert set(FIXTURES) == set(all_rules()), (
        "every registered rule needs a fixture triple (and vice versa)"
    )


# -- framework behaviors ----------------------------------------------------
def test_disable_all_and_multi_rule():
    src = textwrap.dedent("""
        import asyncio
        import time

        async def go(loop_fn):
            # stackcheck: disable=all — fixture
            time.sleep(1)
            asyncio.create_task(loop_fn())  # stackcheck: disable=blocking-async,fire-and-forget-task
    """)
    assert all(f.suppressed for f in analyze_source(src))


def test_suppression_records_justification():
    src = textwrap.dedent("""
        import time

        async def go():
            # stackcheck: disable=blocking-async — calibrated warmup stall
            time.sleep(1)
    """)
    (f,) = analyze_source(src)
    assert f.suppressed and "calibrated warmup stall" in f.justification


def test_falsy_gate_sees_awaited_and_boolop_walruses():
    src = """
        from aiohttp import web

        async def check(req):
            return web.json_response({}, status=400)

        async def handler(req, ready):
            if err := await check(req):
                return err
            if (e2 := await check(req)) and ready:
                return e2
    """
    assert len(findings_for(src, "falsy-walrus-gate")) == 2
    clean = """
        from aiohttp import web

        async def check(req):
            return web.json_response({}, status=400)

        async def handler(req):
            if (err := await check(req)) is not None:
                return err
    """
    assert not findings_for(clean, "falsy-walrus-gate")


def test_comma_space_suppression_covers_later_rules():
    """`disable=a, b` with the natural comma-space style must suppress
    rule b too (regression: the rule list used to stop at the space and
    swallow the rest into the justification)."""
    src = textwrap.dedent("""
        import time

        async def go():
            # stackcheck: disable=silent-except, blocking-async — x
            time.sleep(1)
    """)
    (f,) = analyze_source(src)
    assert f.suppressed and f.justification == "x"


def test_nonexistent_scan_path_raises(tmp_path):
    with pytest.raises(ValueError, match="not a python file"):
        analyze_paths([str(tmp_path / "renamed_dir")])


def test_multiline_justification_is_folded():
    src = textwrap.dedent("""
        import time

        async def go():
            # stackcheck: disable=blocking-async — calibrated warmup
            # stall measured against the device link
            time.sleep(1)
    """)
    (f,) = analyze_source(src)
    assert f.suppressed
    assert f.justification == (
        "calibrated warmup stall measured against the device link"
    )


def test_wrong_rule_suppression_does_not_apply():
    src = textwrap.dedent("""
        import time

        async def go():
            # stackcheck: disable=silent-except — wrong rule
            time.sleep(1)
    """)
    (f,) = analyze_source(src)
    assert not f.suppressed


def test_hot_path_mark_survives_multiline_comment():
    """The mark's rationale usually wraps; the whole contiguous comment
    block above the def must count (regression: only the line directly
    above used to)."""
    src = textwrap.dedent("""
        # stackcheck: hot-path — dispatch-only; any hidden sync here
        # serializes the whole pipeline (rationale wraps to this line)
        def dispatch(x):
            return float(x)
    """)
    assert findings_for(src, "device-sync-hot")


def test_spawn_watched_handle_must_be_stored():
    src = textwrap.dedent("""
        from production_stack_tpu.utils.tasks import spawn_watched

        async def start(loop_fn):
            spawn_watched(loop_fn(), "bg")
    """)
    assert findings_for(src, "fire-and-forget-task")


def test_hot_path_decorator_marks_function():
    src = textwrap.dedent("""
        def hot_path(fn):
            return fn

        @hot_path
        def dispatch(x):
            return float(x)
    """)
    assert findings_for(src, "device-sync-hot")


def test_syntax_error_reported_not_raised():
    found = analyze_source("def broken(:\n")
    assert [f.rule for f in found] == ["syntax-error"]


def test_select_unknown_rule_raises():
    with pytest.raises(ValueError):
        analyze_source("x = 1", select=["no-such-rule"])


def test_analyze_paths_counts_files(tmp_path):
    (tmp_path / "a.py").write_text("x = 1\n")
    sub = tmp_path / "pkg"
    sub.mkdir()
    (sub / "b.py").write_text("import time\n\nasync def f():\n"
                              "    time.sleep(1)\n")
    report = analyze_paths([str(tmp_path)])
    assert report.files_scanned == 2
    assert [f.rule for f in report.unsuppressed] == ["blocking-async"]


# -- CLI contract (acceptance criteria) -------------------------------------
def run_cli(*args: str):
    return subprocess.run(
        [sys.executable, "-m", "production_stack_tpu.analysis", *args],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
    )


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_cli_exits_nonzero_on_each_rule_violation(rule, tmp_path):
    f = tmp_path / f"{rule.replace('-', '_')}_violation.py"
    f.write_text(textwrap.dedent(FIXTURES[rule]["positive"]))
    proc = run_cli(str(f))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert rule in proc.stdout


def test_cli_exits_zero_on_clean_file(tmp_path):
    f = tmp_path / "clean.py"
    f.write_text("import asyncio\n\n\nasync def f():\n"
                 "    await asyncio.sleep(0)\n")
    proc = run_cli(str(f))
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_json_output(tmp_path):
    f = tmp_path / "v.py"
    f.write_text(textwrap.dedent(FIXTURES["blocking-async"]["positive"]))
    proc = run_cli(str(f), "--json")
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    assert data["summary"]["unsuppressed"] == 1
    assert data["findings"][0]["rule"] == "blocking-async"
    assert data["findings"][0]["line"] > 0


def test_cli_usage_error_on_missing_path(tmp_path):
    proc = run_cli(str(tmp_path / "does_not_exist_dir"))
    assert proc.returncode == 2


# -- tier-1 gate: the repo itself stays clean -------------------------------
def test_repo_self_scan_is_clean_api():
    report = analyze_paths([str(PACKAGE)])
    assert report.files_scanned > 50
    assert report.unsuppressed == [], "\n".join(
        f.format() for f in report.unsuppressed
    )


def test_repo_self_scan_is_clean_cli():
    """The exact acceptance-criteria invocation: `python -m
    production_stack_tpu.analysis production_stack_tpu/` exits 0."""
    proc = run_cli("production_stack_tpu/")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_kv_tiering_stays_off_hot_paths():
    """Zero-stall KV tiering (PR 4) + disaggregated PD transfer (PR 8)
    + shared-cache RemoteTier (PR 10): the deferred-export staging
    (LLMEngine._flush_kv_exports, ModelRunner.stage_export_blocks), the
    staged-restore staging/landing (_advance_kv_restore,
    stage_import_blocks, import_staged_blocks), the chain pull/serve
    paths (offload.request_chain_reads,
    transfer.KVTransferServer._snapshot_chain), the remote tier's
    scheduler-thread probes (remote.RemoteTier.contains — memo only,
    the socket lives on the worker), and everything else in engine/ +
    kv/ must keep device syncs and event-loop stalls off the marked hot
    paths — the blocking d2h / tier IO / peer+cache sockets belong to
    the offload worker thread (or the executor, producer side)."""
    report = analyze_paths(
        [
            str(PACKAGE / "engine"),
            str(PACKAGE / "kv"),
        ],
        select=["device-sync-hot", "blocking-async"],
    )
    assert report.files_scanned >= 26
    assert report.unsuppressed == [], "\n".join(
        f.format() for f in report.unsuppressed
    )
    # the transfer/cache-server/peer/remote modules must actually be
    # INSIDE the sweep — a rename or move dropping them out would pass
    # the zero-findings assertion silently
    kv_report = analyze_paths(
        [str(PACKAGE / "kv")],
        select=["device-sync-hot", "blocking-async"],
    )
    assert kv_report.files_scanned >= 8  # __init__, wire, controller,
    # offload, cache_server, transfer, peer, remote


def test_kv_tiering_hot_marks_present():
    """The gate above is only meaningful while the staging functions
    actually carry the hot-path mark — a dropped mark would pass
    silently. Parse the sources and assert each is marked (including
    the PD transfer pull/serve paths: the producer's under-lock
    snapshot and the consumer's enqueue-only chain-read request)."""
    from production_stack_tpu.analysis.core import ModuleContext, iter_functions

    want = {
        ("engine", "llm_engine.py"): {"_flush_kv_exports", "step"},
        ("engine", "model_runner.py"): {
            "stage_export_blocks", "stage_import_blocks",
            "import_staged_blocks",
        },
        ("kv", "transfer.py"): {"_snapshot_chain"},
        ("kv", "offload.py"): {"request_chain_reads", "contains_local"},
        # the shared-cache tier's scheduler-thread probe must stay a
        # memo lookup (the socket client runs only on the offload
        # worker: put/flush/get_chain)
        ("kv", "remote.py"): {"contains"},
    }
    for (sub, fname), funcs in want.items():
        path = PACKAGE / sub / fname
        ctx = ModuleContext(str(path), path.read_text())
        hot = {
            f.name for f in iter_functions(ctx.tree) if ctx.is_hot(f)
        }
        missing = funcs - hot
        assert not missing, f"{fname}: unmarked hot paths {missing}"


def test_elastic_decode_stays_off_hot_paths():
    """Fused decode with device-side stop masks: the stop-array build
    (LLMEngine._stop_arrays) and the dispatch/staging path it feeds
    (decode_multi / stage_decode_multi) must keep device syncs and
    event-loop stalls off the marked hot paths — zero unsuppressed
    device-sync-hot + blocking-async over the touched engine files."""
    report = analyze_paths(
        [str(PACKAGE / "engine")],
        select=["device-sync-hot", "blocking-async"],
    )
    assert report.files_scanned >= 20
    assert report.unsuppressed == [], "\n".join(
        f.format() for f in report.unsuppressed
    )


def test_elastic_decode_hot_marks_present():
    """The sweep above only bites while the elastic-decode functions
    carry the hot-path mark — a dropped mark would pass silently."""
    from production_stack_tpu.analysis.core import (
        ModuleContext,
        iter_functions,
    )

    want = {
        "llm_engine.py": {"_stop_arrays", "_step_impl"},
        "model_runner.py": {"decode_multi", "stage_decode_multi"},
    }
    for fname, funcs in want.items():
        path = PACKAGE / "engine" / fname
        ctx = ModuleContext(str(path), path.read_text())
        hot = {
            f.name for f in iter_functions(ctx.tree) if ctx.is_hot(f)
        }
        missing = funcs - hot
        assert not missing, f"{fname}: unmarked hot paths {missing}"


def test_ragged_dispatch_stays_off_hot_paths():
    """Unified ragged dispatch (PR 7): the lane-typed round's host
    build/stage/dispatch (model_runner._fill_ragged_pack /
    stage_ragged / ragged_dispatch) and the scheduler's lane planner
    (plan_ragged_round) run once per engine round — zero unsuppressed
    device-sync-hot + blocking-async findings over engine/ (the one
    sanctioned fetch set lives in the UNMARKED bookkeeping helpers,
    same split as the decode path's step/_apply_multi_tokens)."""
    report = analyze_paths(
        [str(PACKAGE / "engine")],
        select=["device-sync-hot", "blocking-async"],
    )
    assert report.files_scanned >= 20
    assert report.unsuppressed == [], "\n".join(
        f.format() for f in report.unsuppressed
    )


def test_ragged_dispatch_hot_marks_present():
    """The sweep above only bites while the ragged build/stage/plan
    functions carry the hot-path mark — a dropped mark would pass
    silently."""
    from production_stack_tpu.analysis.core import (
        ModuleContext,
        iter_functions,
    )

    want = {
        "model_runner.py": {
            "ragged_dispatch", "stage_ragged", "_fill_ragged_pack",
            # single-kernel mode (PR 11): the ragged-ROWS pack/
            # dispatch helpers and the one attention dispatch seam
            "_ragged_rows_dispatch", "_fill_ragged_rows_pack",
            "_fill_rows_prefill_pack", "_attn",
        },
        "scheduler.py": {"plan_ragged_round"},
    }
    for fname, funcs in want.items():
        path = PACKAGE / "engine" / fname
        ctx = ModuleContext(str(path), path.read_text())
        hot = {
            f.name for f in iter_functions(ctx.tree) if ctx.is_hot(f)
        }
        missing = funcs - hot
        assert not missing, f"{fname}: unmarked hot paths {missing}"


def test_router_proxy_stays_off_blocking_paths():
    """Router data plane (PR 6): the proxy hot path
    (route_general_request / process_request) relays every chunk of
    every request — one blocking call or swallowed exception there
    stalls or silently degrades the WHOLE router, so router/services/
    must stay at zero unsuppressed blocking-async + silent-except
    findings."""
    report = analyze_paths(
        [str(PACKAGE / "router" / "services")],
        select=["blocking-async", "silent-except"],
    )
    assert report.files_scanned >= 6
    assert report.unsuppressed == [], "\n".join(
        f.format() for f in report.unsuppressed
    )


def test_admission_stays_off_hot_paths():
    """Admission control (PR 13) runs INSIDE the marked proxy hot path
    on every request — one blocking call, swallowed exception, or
    device sync there throttles the very traffic it is protecting:
    router/admission/ stays at zero unsuppressed findings across the
    blocking/silent-except/device-sync sweeps."""
    report = analyze_paths(
        [str(PACKAGE / "router" / "admission")],
        select=["blocking-async", "silent-except", "device-sync-hot"],
    )
    assert report.files_scanned >= 4
    assert report.unsuppressed == [], "\n".join(
        f.format() for f in report.unsuppressed
    )


def test_admission_hot_marks_present():
    """The sweep above only bites while the admission decision path
    carries the hot-path mark — a dropped mark would pass silently."""
    from production_stack_tpu.analysis.core import (
        ModuleContext,
        iter_functions,
    )

    expected = {
        "controller.py": {"admit", "release", "resolve_tenant",
                          "load_score"},
        "tenants.py": {"try_acquire", "_refill"},
        "load.py": {"compute_load"},
    }
    for fname, needed in expected.items():
        path = PACKAGE / "router" / "admission" / fname
        ctx = ModuleContext(str(path), path.read_text())
        hot = {f.name for f in iter_functions(ctx.tree) if ctx.is_hot(f)}
        missing = needed - hot
        assert not missing, f"{fname}: unmarked hot paths {missing}"


def test_router_proxy_hot_marks_present():
    """The sweep above only bites while the proxy entry points carry
    the hot-path mark — a dropped mark would pass silently."""
    from production_stack_tpu.analysis.core import (
        ModuleContext,
        iter_functions,
    )

    path = PACKAGE / "router" / "services" / "request_service.py"
    ctx = ModuleContext(str(path), path.read_text())
    hot = {f.name for f in iter_functions(ctx.tree) if ctx.is_hot(f)}
    missing = {"route_general_request", "process_request"} - hot
    assert not missing, f"request_service.py: unmarked hot paths {missing}"


def test_slo_stays_off_hot_paths():
    """SLO tracking (ISSUE 15) runs on the proxy hot path for every
    finished request AND inside the admission decision (shed_burn):
    one blocking call, swallowed exception, or device sync there taxes
    every request the tracker is judging — router/stats/slo.py stays
    at zero unsuppressed findings across the sweeps."""
    report = analyze_paths(
        [str(PACKAGE / "router" / "stats" / "slo.py")],
        select=["blocking-async", "silent-except", "device-sync-hot"],
    )
    assert report.files_scanned == 1
    assert report.unsuppressed == [], "\n".join(
        f.format() for f in report.unsuppressed
    )


def test_slo_hot_marks_present():
    """The sweep above only bites while the SLO feed path carries the
    hot-path mark — a dropped mark would pass silently."""
    from production_stack_tpu.analysis.core import (
        ModuleContext,
        iter_functions,
    )

    expected = {
        ("router", "stats", "slo.py"): {
            "observe_request", "observe_shed", "shed_burn", "_match",
            "bucket",
        },
        ("router", "services", "request_service.py"): {"_note_slo"},
    }
    for parts, needed in expected.items():
        path = PACKAGE.joinpath(*parts)
        ctx = ModuleContext(str(path), path.read_text())
        hot = {f.name for f in iter_functions(ctx.tree) if ctx.is_hot(f)}
        missing = needed - hot
        assert not missing, f"{path.name}: unmarked hot paths {missing}"


def test_timeline_recording_stays_off_hot_paths():
    """Request-timeline recording (tracing/ + its engine call sites)
    must not introduce device syncs or event-loop stalls on the marked
    hot paths: zero unsuppressed device-sync-hot / blocking-async
    findings over the engine pipeline and the tracing package."""
    report = analyze_paths(
        [
            str(PACKAGE / "tracing"),
            str(PACKAGE / "engine"),
        ],
        select=["device-sync-hot", "blocking-async"],
    )
    assert report.files_scanned >= 25
    assert report.unsuppressed == [], "\n".join(
        f.format() for f in report.unsuppressed
    )


def test_long_prefill_stays_off_hot_paths():
    """Long-prefill lane (context-parallel ring prefill): the chunk
    dispatch / token staging / batch landing that run on the engine
    step thread (long_prefill.advance -> _dispatch_next_chunk /
    _land_one_batch, long_context.stage_tokens / prefill_chunk) must
    keep device syncs and blocking IO off the scheduler thread — the
    ring wait, logits fetch, and KV d2h belong to the long-prefill
    worker (_materialize), mirroring the kv/offload.py split. Zero
    unsuppressed device-sync-hot + blocking-async over engine/ (now
    including long_prefill.py) and parallel/."""
    report = analyze_paths(
        [
            str(PACKAGE / "engine"),
            str(PACKAGE / "parallel"),
        ],
        select=["device-sync-hot", "blocking-async"],
    )
    # engine/ gained long_prefill.py; parallel/ must actually be
    # INSIDE the sweep (the ring chunk dispatch lives there)
    assert report.files_scanned >= 29
    assert report.unsuppressed == [], "\n".join(
        f.format() for f in report.unsuppressed
    )


def test_long_prefill_hot_marks_present():
    """The sweep above only bites while the long-prefill dispatch /
    staging / landing functions carry the hot-path mark — a dropped
    mark would pass silently. The worker-side _materialize must NOT be
    marked: it is the sanctioned home of the blocking ring wait + KV
    d2h."""
    from production_stack_tpu.analysis.core import (
        ModuleContext,
        iter_functions,
    )

    want = {
        ("engine", "long_prefill.py"): {
            "advance", "_dispatch_next_chunk", "_land_one_batch",
        },
        ("parallel", "long_context.py"): {
            "stage_tokens", "prefill_chunk",
        },
    }
    for (sub, fname), funcs in want.items():
        path = PACKAGE / sub / fname
        ctx = ModuleContext(str(path), path.read_text())
        hot = {
            f.name for f in iter_functions(ctx.tree) if ctx.is_hot(f)
        }
        missing = funcs - hot
        assert not missing, f"{fname}: unmarked hot paths {missing}"
        if fname == "long_prefill.py":
            assert "_materialize" not in hot, (
                "_materialize is the worker body (blocking by design) "
                "and must stay unmarked"
            )


# -- call-graph unit tests (satellite: alias / method / cycle) --------------


def _write_pkg(tmp_path, files: dict[str, str]) -> Path:
    """Materialize a tiny importable package for call-graph tests."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    for name, src in files.items():
        (pkg / name).write_text(textwrap.dedent(src))
    return pkg


def test_callgraph_resolves_aliased_cross_module_import(tmp_path):
    """``from pkg.helpers import force as materialize`` must link the
    hot caller to the helper in the OTHER module, and the finding must
    land at the forcer with the cross-module chain in its message."""
    pkg = _write_pkg(tmp_path, {
        "helpers.py": """
            def force(x):
                return x.item()
        """,
        "engine.py": """
            from pkg.helpers import force as materialize

            # stackcheck: hot-path
            def step(x):
                return materialize(x)
        """,
    })
    report = analyze_paths([str(pkg)], select=["device-sync-transitive"])
    live = report.unsuppressed
    assert [f.rule for f in live] == ["device-sync-transitive"]
    assert live[0].path.endswith("helpers.py")
    assert "pkg.engine.step" in live[0].message
    assert "pkg.helpers.force" in live[0].message


def test_callgraph_binds_self_method_through_base_class():
    """``self.flush()`` on a derived class resolves through the base
    chain to the inherited method body."""
    src = """
        import time

        class Base:
            def flush(self):
                time.sleep(0.5)

        class Worker(Base):
            # stackcheck: hot-path
            def step(self):
                self.flush()
    """
    live = findings_for(src, "blocking-hot")
    assert len(live) == 1
    assert "Base.flush" in live[0].message


def test_callgraph_tolerates_call_cycles():
    """Mutually recursive functions must not hang the BFS, and the
    blocking call inside the cycle is still reported exactly once."""
    src = """
        import time

        # stackcheck: hot-path
        def a(x):
            return b(x)

        def b(x):
            if x:
                return a(x - 1)
            time.sleep(0.2)
    """
    live = findings_for(src, "blocking-hot")
    assert len(live) == 1


def test_callgraph_transitive_callees_shortest_chain(tmp_path):
    """Direct API check: BFS yields shortest chains, stop() prunes the
    subtree, callers_of inverts the edges."""
    from production_stack_tpu.analysis.callgraph import ProjectContext
    from production_stack_tpu.analysis.core import ModuleContext

    pkg = _write_pkg(tmp_path, {
        "a.py": """
            from pkg.b import mid, leaf

            def entry(x):
                mid(x)
                return leaf(x)
        """,
        "b.py": """
            def mid(x):
                return leaf(x)

            def leaf(x):
                return x
        """,
    })
    ctxs = [
        ModuleContext(str(p), p.read_text())
        for p in (pkg / "a.py", pkg / "b.py")
    ]
    project = ProjectContext(ctxs)
    entry = next(f for f in project.functions if f.name == "entry")
    reach = project.transitive_callees(entry)
    by_name = {fn.name: chain for fn, chain in reach.items()}
    assert set(by_name) == {"mid", "leaf"}
    # leaf is reachable both directly and via mid; BFS keeps the
    # 2-hop chain, not the 3-hop one
    assert len(by_name["leaf"]) == 2
    # stop() prunes: stopping mid leaves only the direct leaf edge
    pruned = project.transitive_callees(
        entry, stop=lambda fn: fn.name == "mid"
    )
    assert {fn.name for fn in pruned} == {"leaf"}
    # callers_of inverts: leaf is called by both entry and mid
    leaf = next(f for f in project.functions if f.name == "leaf")
    callers = project.callers_of()[id(leaf)]
    assert {c.name for c in callers} == {"entry", "mid"}


# -- regression: v1 (intraprocedural) miss, v2 (call-graph) catch -----------

INDIRECTION_FIXTURE = """
    import numpy as np

    # stackcheck: hot-path
    def decode_step(logits_dev):
        return _pick(logits_dev)

    def _pick(logits_dev):
        # one hop of indirection: v1's device-sync-hot only looks
        # inside marked functions, so this materialization is invisible
        # to it -- the v2 call graph walks the edge and reports it here
        return np.asarray(logits_dev)
"""


def test_v1_misses_one_hop_indirection_v2_catches(tmp_path):
    # v1 behaviour, still selectable: the marked function contains no
    # forcer, so the intraprocedural rule stays silent
    assert findings_for(INDIRECTION_FIXTURE, "device-sync-hot") == []
    v1 = analyze_source(
        textwrap.dedent(INDIRECTION_FIXTURE), select=["device-sync-hot"]
    )
    assert v1 == []
    # v2 default run reports the forcer through the call edge
    live = findings_for(INDIRECTION_FIXTURE, "device-sync-transitive")
    assert len(live) == 1
    assert "decode_step" in live[0].message and "_pick" in live[0].message
    # same contract through the CLI
    target = tmp_path / "indirect.py"
    target.write_text(textwrap.dedent(INDIRECTION_FIXTURE))
    old = run_cli(str(target), "--select", "device-sync-hot")
    assert old.returncode == 0, old.stdout
    new = run_cli(str(target))
    assert new.returncode == 1, new.stdout
    assert "device-sync-transitive" in new.stdout


# -- SARIF output -----------------------------------------------------------


def test_cli_sarif_output(tmp_path):
    target = tmp_path / "mixed.py"
    target.write_text(textwrap.dedent("""
        import time

        async def handler(req):
            time.sleep(1)

        async def other(req):
            # stackcheck: disable=blocking-async — calibrated settle
            time.sleep(0.1)
    """))
    proc = run_cli(str(target), "--sarif")
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "stackcheck"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert set(all_rules()) <= rule_ids
    results = run["results"]
    assert len(results) == 2
    by_level = {r["level"]: r for r in results}
    live = by_level["error"]
    assert live["ruleId"] == "blocking-async"
    loc = live["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("mixed.py")
    assert loc["region"]["startLine"] >= 1
    muted = by_level["note"]
    assert muted["suppressions"][0]["kind"] == "inSource"
    assert "settle" in muted["suppressions"][0]["justification"]


def test_cli_sarif_clean_file_exits_zero(tmp_path):
    target = tmp_path / "clean.py"
    target.write_text("def ok():\n    return 1\n")
    proc = run_cli(str(target), "--sarif")
    assert proc.returncode == 0
    doc = json.loads(proc.stdout)
    assert doc["runs"][0]["results"] == []


def test_cli_json_and_sarif_are_exclusive(tmp_path):
    target = tmp_path / "clean.py"
    target.write_text("def ok():\n    return 1\n")
    proc = run_cli(str(target), "--json", "--sarif")
    assert proc.returncode == 2


# -- --changed-only ---------------------------------------------------------


def _git(cwd: Path, *args: str) -> None:
    subprocess.run(
        ["git", *args], cwd=cwd, check=True, capture_output=True,
        env={**os.environ,
             "GIT_CONFIG_GLOBAL": "/dev/null",
             "GIT_CONFIG_SYSTEM": "/dev/null"},
    )


def _run_cli_in(cwd: Path, *args: str):
    """CLI run with an explicit cwd (git discovery) while keeping the
    analyzer importable from the repo."""
    return subprocess.run(
        [sys.executable, "-m", "production_stack_tpu.analysis", *args],
        capture_output=True, text=True, cwd=cwd, timeout=120,
        env={**os.environ, "PYTHONPATH": str(REPO_ROOT)},
    )


def _seed_git_repo(tmp_path: Path) -> Path:
    repo = tmp_path / "proj"
    repo.mkdir()
    _git(repo, "init", "-q")
    _git(repo, "config", "user.email", "t@example.com")
    _git(repo, "config", "user.name", "t")
    (repo / "old.py").write_text(textwrap.dedent("""
        import time

        async def legacy(req):
            time.sleep(1)
    """))
    (repo / "fresh.py").write_text("def ok():\n    return 1\n")
    _git(repo, "add", ".")
    _git(repo, "commit", "-q", "-m", "seed")
    return repo


def test_changed_only_reports_only_changed_files(tmp_path):
    repo = _seed_git_repo(tmp_path)
    # introduce a NEW violation in fresh.py; old.py keeps its committed
    # violation but is unchanged, so it must not be reported
    (repo / "fresh.py").write_text(textwrap.dedent("""
        import time

        async def handler(req):
            time.sleep(2)
    """))
    proc = _run_cli_in(repo, ".", "--changed-only", "HEAD")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "fresh.py" in proc.stdout
    assert "old.py" not in proc.stdout
    # a full run over the same tree still sees both
    full = _run_cli_in(repo, ".")
    assert full.returncode == 1
    assert "old.py" in full.stdout


def test_changed_only_clean_tree_exits_zero(tmp_path):
    repo = _seed_git_repo(tmp_path)
    # the tree HAS a committed violation, but nothing changed vs HEAD
    proc = _run_cli_in(repo, ".", "--changed-only", "HEAD")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 changed python file(s)" in proc.stdout


def test_changed_only_bad_ref_exits_two(tmp_path):
    repo = _seed_git_repo(tmp_path)
    proc = _run_cli_in(repo, ".", "--changed-only", "no-such-ref")
    assert proc.returncode == 2
    assert "error" in proc.stderr
