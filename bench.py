"""Benchmark: serving throughput + TTFT on the real TPU chip.

Workload shape follows the reference's multi-round-qa definition scaled to
one chip (reference: benchmarks/multi-round-qa/run.sh — shared system
prompt + long per-user history + ~100-token answers): concurrent sessions
with a shared prefix exercise chunked prefill, prefix caching, continuous
batching, and paged decode together.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

vs_baseline is the fraction of the HBM-bandwidth decode roofline achieved
(roofline tok/s = batch * HBM_BW / model_bytes — every decode step must
stream the weights once; the reference repo commits no absolute numbers to
compare against, see BASELINE.md).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

os.environ.setdefault("PST_LOG_LEVEL", "WARNING")  # keep stdout JSON-only

import numpy as np  # noqa: E402

MODEL = os.environ.get("PST_BENCH_MODEL", "llama-3.2-3b")
# north-star config is Llama-3-8B tp=8 on a v5e-8; the driver exposes one
# chip, so the default serves the largest family member that fits it with
# the Pallas kernels engaged (3B, head_dim 128 — the 1B's head_dim 64
# cannot tile the kernels, see engine/model_runner.py).
# On a full slice: PST_BENCH_MODEL=llama-3-8b PST_BENCH_TP=8 python bench.py
TP = int(os.environ.get("PST_BENCH_TP", "1"))
NUM_USERS = int(os.environ.get("PST_BENCH_USERS", "16"))
SYSTEM_PROMPT_TOK = int(os.environ.get("PST_BENCH_SYS_TOK", "512"))
HISTORY_TOK = int(os.environ.get("PST_BENCH_HISTORY_TOK", "1024"))
ANSWER_TOK = int(os.environ.get("PST_BENCH_ANSWER_TOK", "100"))
# chat rounds per user (reference: multi-round-qa/run.sh drives 10 rounds
# per session). Rounds 2+ resume from the prefix cache — only the tail
# past the last cached whole block re-prefills — so multi-round is both
# the faithful workload shape AND the one the paged prefix cache exists
# for. All lengths are deterministic (greedy + ignore_eos), so every
# resume-tail bucket is precompiled analytically below.
ROUNDS = int(os.environ.get("PST_BENCH_ROUNDS", "10"))
# tokens appended as the user's next question between rounds
QUESTION_TOK = int(os.environ.get("PST_BENCH_QUESTION_TOK", "64"))
# fused decode iterations per dispatch (one host<->device round trip
# per K tokens; cost on an attached chip: not measured)
SCHED_STEPS = int(os.environ.get("PST_BENCH_SCHED_STEPS", "8"))
# cross-sequence prefill packing group cap (1 = round-2 behavior)
PREFILL_SEQS = int(os.environ.get("PST_BENCH_PREFILL_SEQS", "8"))
# prefill chunk size: bigger chunks = fewer dispatches per cold prompt,
# at the cost of larger programs and coarser decode interleaving
PREFILL_CHUNK = int(os.environ.get("PST_BENCH_PREFILL_CHUNK", "512"))
# double-buffered decode dispatch (0 = synchronous fetch per round).
# Default OFF (the engine default): chained decode keeps the device
# busy and delays prefill admission. Either side's cost on an attached
# chip: not measured
ASYNC_DECODE = os.environ.get("PST_BENCH_ASYNC", "0") == "1"
# speculative h2d prefetch (engine prefetch_decode): stage the next
# fused round's packed inputs during the current round's fetch
PREFETCH = os.environ.get("PST_BENCH_PREFETCH", "1") == "1"
# pipelined prefill (engine prefill_pipeline): fused h2d buffer per
# prefill dispatch + staged chunk uploads + cold-prompt chunk chaining.
# Attribution slots: BENCH_SWEEP_pfpipe.json (on, default) vs
# BENCH_SWEEP_nopfpipe.json (@nopfpipe label modifier)
PREFILL_PIPELINE = os.environ.get("PST_BENCH_PREFILL_PIPELINE", "1") == "1"
# request tracing (engine request_timeline + memory span exporter): the
# overhead A/B pinning the zero-cost-when-disabled claim. Default OFF so
# every existing sweep stays a tracing-free control; @trace enables.
# Slots: BENCH_SWEEP_trace.json (on) vs the matching untraced config
TRACE = os.environ.get("PST_BENCH_TRACE", "0") == "1"
# elastic fused decode (engine device_stop + adaptive_decode_k): stop
# conditions evaluated INSIDE the fused scan (finished lanes freeze,
# per-lane valid counts, whole-round early exit) and per-round K sized
# from pow2 buckets under admission pressure / remaining budget.
# Default ON (the engine default); @noelastic pins the fixed-trip
# fixed-K control for the A/B. Slots:
# BENCH_SWEEP_elastic.json (on) vs the matching @noelastic control
ELASTIC = os.environ.get("PST_BENCH_ELASTIC", "1") == "1"
# unified ragged prefill+decode dispatch (engine ragged_dispatch):
# mixed rounds run prefill-chunk lanes and fused decode lanes in ONE
# lane-typed device program — the interleave throttle and the
# admission-K clamp for in-round prefill work dissolve. Default ON
# (the engine default); @noragged pins the split alternating rounds
# as the attribution control. Slots: BENCH_SWEEP_ragged.json (on) vs
# the matching @noragged control
RAGGED = os.environ.get("PST_BENCH_RAGGED", "1") == "1"
# single-kernel ragged paged attention (engine ragged_kernel): ONE
# batched-grid Pallas kernel serves any lane mix (decode rows +
# prefill q-tiles share the grid), and program variants key on padded
# row-count buckets instead of the (group, chunk) lane-mix grid.
# Default ON (the engine default, effective only under
# attention_impl=pallas i.e. on a real chip); @norpakernel pins the
# composed per-lane kernels as the attribution control. Slots:
# BENCH_SWEEP_rpa.json (on) vs the matching @norpakernel control
RAGGED_KERNEL = os.environ.get("PST_BENCH_RAGGED_KERNEL", "1") == "1"
# KV tiering workload (@kvoff): cap the HBM pool so the multi-round
# working set churns through the cpu/disk offload tiers — the zero-stall
# async export/staged-restore measurement. PST_BENCH_KV_BLOCKS overrides
# the cap (default: ~1.15x the peak ACTIVE working set, so finished
# sessions' prefixes spill between rounds while running lanes always
# fit). Slots: BENCH_SWEEP_kvoff.json (async tiering, default) vs
# BENCH_SWEEP_kvoff_sync.json (@synckv -> --sync-kv-offload control)
KV_OFFLOAD = os.environ.get("PST_BENCH_KV_OFFLOAD", "0") == "1"
KV_BLOCKS = int(os.environ.get("PST_BENCH_KV_BLOCKS", "0"))
# disaggregated prefill/decode (@pd): round-1 prompts prefill on a
# SEPARATE prefill-role engine (own step thread, in-process
# KVTransferServer) and the measured decode engine pulls the chain
# through its PeerTier staged restore before decoding — the PD data
# plane end to end, colocated on ONE chip (both engines share the
# device, so weights sit in HBM twice and device work serializes;
# this measures the transfer machinery's cost/win shape, it
# UNDERSTATES the multi-chip win where prefill compute is genuinely
# offloaded — run it with the small-model configs). Rounds 2+ resume
# directly on the decode engine (prefix-affine, the router pd
# policy's PPD behavior). @nopd pins the single-engine control.
# Slots: BENCH_SWEEP_pd.json vs the matching @nopd control (PERF.md)
PD = os.environ.get("PST_BENCH_PD", "0") == "1"
SYNC_KV = os.environ.get("PST_BENCH_SYNC_KV", "0") == "1"
# shared KV cache server (@remotekv, requires @kvoff): run an
# in-process kv.cache_server and wire the engine's RemoteTier at it —
# the LMCache-like topology (small host RAM buffer + cluster cache, NO
# local disk tier): exports write through as write-behind batched PUT
# frames, and resumes whose prefix aged out of the cpu buffer restore
# over the wire as ONE get_chain pull instead of recomputing.
# @noremotekv pins the local-tiers-only control (the @kvoff default).
# Slots: BENCH_SWEEP_kvremote.json vs the matching @noremotekv control
KV_REMOTE = os.environ.get("PST_BENCH_KV_REMOTE", "0") == "1"
# long-context scenario (@longctx): instead of the multi-round QA
# workload, sweep ONE prompt per length over 8k -> 128k tokens and
# record TTFT vs length + per-phase attribution (ring / d2h / land /
# overflow) + the HBM high-water mark. @nolongctx runs the same sweep
# with the ring lane OFF (chunked-prefill control — the A/B the staged
# BENCH_SWEEP_longctx.json entry in PERF.md measures).
LONGCTX = os.environ.get("PST_BENCH_LONGCTX", "0") == "1"
LONGCTX_RING = os.environ.get("PST_BENCH_LONGCTX_RING", "1") == "1"
LONGCTX_SP = int(os.environ.get("PST_BENCH_LONGCTX_SP", "4"))
LONGCTX_THRESHOLD = int(
    os.environ.get("PST_BENCH_LONGCTX_THRESHOLD", "4096")
)
LONGCTX_CHUNK = int(os.environ.get("PST_BENCH_LONGCTX_CHUNK", "2048"))
LONGCTX_LENS = [
    int(x)
    for x in os.environ.get(
        "PST_BENCH_LONGCTX_LENS", "8192,16384,32768,65536,131072"
    ).split(",")
    if x.strip()
]
LONGCTX_ANSWER_TOK = int(
    os.environ.get("PST_BENCH_LONGCTX_ANSWER_TOK", "16")
)
CPU_OFFLOAD_MB = int(os.environ.get("PST_BENCH_CPU_OFFLOAD_MB", "2048"))
DISK_OFFLOAD_DIR = os.environ.get(
    "PST_BENCH_DISK_DIR", "/tmp/pst-bench-kv"
)
# pre-compile the packed-prefill buckets the timed run will hit so no
# XLA compile lands inside a TTFT measurement
PRECOMPILE = os.environ.get("PST_BENCH_PRECOMPILE", "1") == "1"
# Published HBM bandwidth of one chip in GB/s, keyed by the
# `device_kind` jax reports (source: Google Cloud documentation, "TPU
# v5e": 16 GB of HBM at 819 GB/s). A device that is not listed is an
# error, not a default.
HBM_BW_GBPS_BY_DEVICE_KIND = {"TPU v5 lite": 819.0}
QPS = float(os.environ.get("PST_BENCH_QPS", "2.0"))  # arrival pacing


def _hbm_bw_gbps() -> float:
    """HBM GB/s of the device this run measures; refuses anything but
    a TPU whose `device_kind` is in the peaks table."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; jax initialised {dev.platform!r} "
            "(a CPU run is not a measurement — use the tests for "
            "correctness)"
        )
    if dev.device_kind not in HBM_BW_GBPS_BY_DEVICE_KIND:
        raise SystemExit(
            f"device_kind {dev.device_kind!r} has no entry in "
            "HBM_BW_GBPS_BY_DEVICE_KIND; add its published peak with "
            "the source"
        )
    return HBM_BW_GBPS_BY_DEVICE_KIND[dev.device_kind]


def main() -> None:
    if os.environ.get("PST_BENCH_SWEEP", "0") == "1":
        # the sweep parent imports no jax and never touches the chip:
        # each config runs in its own subprocess (below), and a chip
        # belongs to one process at a time
        _run_sweep()
        return

    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    _hbm_bw_gbps()  # refuse a non-TPU backend before any work
    print(json.dumps(run_config(
        SCHED_STEPS, PREFILL_SEQS, ASYNC_DECODE,
        os.environ.get("PST_BENCH_LABEL", "default"),
    )))


def _parse_sweep_labels(spec: str) -> list[tuple]:
    """Parse the sweep config list. Base labels are
    k<N>-{sync|async}-{packed|nopack}; optional @-suffixes override the
    per-config workload env (the reference's run.sh sweeps QPS across
    one deployment — this lets one chip session walk the serving
    curve): k8-sync-packed@qps4@u32@r1 -> QPS=4, USERS=32, ROUNDS=1;
    @chunk<N> sets the prefill chunk; @nopfx disables h2d prefetch.
    Returns (label, k, prefill_seqs, async, env_overrides) tuples."""
    configs: list[tuple] = []
    for label in [x.strip() for x in spec.split(",") if x.strip()]:
        base, *mods = label.split("@")
        overrides: dict[str, str] = {}
        for m in mods:
            # exact-keyword modifiers FIRST: @ragged would otherwise
            # match the r<N> rounds prefix rule below
            if m == "ragged":
                overrides["PST_BENCH_RAGGED"] = "1"
            elif m == "noragged":
                overrides["PST_BENCH_RAGGED"] = "0"
            elif m == "rpa":
                overrides["PST_BENCH_RAGGED_KERNEL"] = "1"
            elif m == "norpakernel":
                overrides["PST_BENCH_RAGGED_KERNEL"] = "0"
            elif m == "remotekv":  # before the r<N> rounds prefix rule
                overrides["PST_BENCH_KV_REMOTE"] = "1"
            elif m == "noremotekv":
                overrides["PST_BENCH_KV_REMOTE"] = "0"
            elif m.startswith("qps"):
                overrides["PST_BENCH_QPS"] = str(float(m[3:]))
            elif m.startswith("chunk"):
                overrides["PST_BENCH_PREFILL_CHUNK"] = str(int(m[5:]))
            elif m.startswith("u"):
                overrides["PST_BENCH_USERS"] = str(int(m[1:]))
            elif m.startswith("r"):
                overrides["PST_BENCH_ROUNDS"] = str(int(m[1:]))
            elif m == "nopfx":
                overrides["PST_BENCH_PREFETCH"] = "0"
            elif m == "nopfpipe":
                overrides["PST_BENCH_PREFILL_PIPELINE"] = "0"
            elif m == "trace":
                overrides["PST_BENCH_TRACE"] = "1"
            elif m == "elastic":
                overrides["PST_BENCH_ELASTIC"] = "1"
            elif m == "noelastic":
                overrides["PST_BENCH_ELASTIC"] = "0"
            elif m == "kvoff":
                overrides["PST_BENCH_KV_OFFLOAD"] = "1"
            elif m == "synckv":
                overrides["PST_BENCH_SYNC_KV"] = "1"
            elif m == "pd":
                overrides["PST_BENCH_PD"] = "1"
            elif m == "nopd":
                overrides["PST_BENCH_PD"] = "0"
            elif m == "longctx":
                # long-context scenario: 8k -> 128k prompt-length sweep
                # served by the context-parallel ring lane
                overrides["PST_BENCH_LONGCTX"] = "1"
            elif m == "nolongctx":
                # same sweep on the chunked-prefill control (the A/B)
                overrides["PST_BENCH_LONGCTX"] = "1"
                overrides["PST_BENCH_LONGCTX_RING"] = "0"
            else:
                raise ValueError(
                    f"bad sweep label modifier {m!r} in {label!r}: want "
                    "qps<F> | u<N> | r<N> | chunk<N> | nopfx | nopfpipe "
                    "| trace | elastic | noelastic | ragged | noragged "
                    "| rpa | norpakernel | kvoff | synckv | remotekv "
                    "| noremotekv | pd | nopd | longctx | nolongctx"
                )
        if ("PST_BENCH_SYNC_KV" in overrides
                and "PST_BENCH_KV_OFFLOAD" not in overrides):
            # fail fast: @synckv without @kvoff would silently measure a
            # NO-tiering config as the "sync control" — chip time
            # must not burn on a corrupted A/B
            raise ValueError(
                f"{label!r}: @synckv requires @kvoff (the sync path "
                "only differs once the KV tiers are enabled)"
            )
        if (overrides.get("PST_BENCH_KV_REMOTE") == "1"
                and "PST_BENCH_KV_OFFLOAD" not in overrides):
            # same honesty gate: the remote tier only sees traffic once
            # the capped-HBM eviction workload is on
            raise ValueError(
                f"{label!r}: @remotekv requires @kvoff (shared-cache "
                "traffic only exists under the capped-HBM workload)"
            )
        kpart, mode, pack = base.split("-")
        # fail fast on typos: chip time must not silently run the
        # sync path under an "asynch" label
        if (not kpart.startswith("k") or mode not in ("sync", "async")
                or pack not in ("packed", "nopack")):
            raise ValueError(
                f"bad sweep config label {label!r}: want "
                "k<N>-{sync|async}-{packed|nopack}[@qps<F>|@u<N>|@r<N>"
                "|@chunk<N>|@nopfx|@nopfpipe|@trace|@elastic"
                "|@noelastic|@ragged|@noragged|@rpa|@norpakernel"
                "|@kvoff|@synckv|@remotekv|@noremotekv|@pd|@nopd"
                "|@longctx|@nolongctx]"
            )
        configs.append((
            label,
            int(kpart[1:]),
            PREFILL_SEQS if pack == "packed" else 1,
            mode == "async",
            overrides,
        ))
    return configs


def _run_sweep() -> None:
    """The full measurement matrix: K=1 control, K=8, packing on/off,
    async on/off — ONE SUBPROCESS PER CONFIG, so each config starts
    on an empty chip (an in-process engine.shutdown() can leave the
    old engine's params+KV live long enough that the next config's
    allocations RESOURCE_EXHAUST it) and this parent never touches the
    device. Results stream into BENCH_SWEEP.json after EVERY config so
    a mid-sweep failure still leaves evidence; the best row is the
    driver-contract stdout line."""
    # config labels are self-describing ("k{K}-{sync|async}-{packed|nopack}")
    # and the list is env-overridable so a short chip budget can run
    # the highest-value measurements first:
    #   PST_BENCH_SWEEP_CONFIGS=k8-sync-packed,k16-sync-packed,... bench.py
    spec = os.environ.get(
        "PST_BENCH_SWEEP_CONFIGS",
        "k1-sync-nopack,k{K}-sync-nopack,k{K}-sync-packed,k{K}-async-packed"
    ).replace("{K}", str(SCHED_STEPS))
    configs = _parse_sweep_labels(spec)
    out_path = os.environ.get("PST_BENCH_SWEEP_OUT", "BENCH_SWEEP.json")
    per_config_timeout = float(
        os.environ.get("PST_BENCH_CONFIG_TIMEOUT", "1500")
    )
    results: list[dict] = []
    for label, k, ps, ad, overrides in configs:
        env = dict(os.environ)
        env.pop("PST_BENCH_SWEEP", None)
        env.update(overrides)
        env.update({
            "PST_BENCH_SCHED_STEPS": str(k),
            "PST_BENCH_PREFILL_SEQS": str(ps),
            "PST_BENCH_ASYNC": "1" if ad else "0",
            "PST_BENCH_LABEL": label,
        })
        r, wedged = _run_one_config(label, env, per_config_timeout)
        # every row records whether the config actually measured;
        # a config that hit its watchdog is recorded as {"ok": false,
        # "watchdog": true} and the sweep continues with the rest
        r["ok"] = (not r.get("watchdog")
                   and r.get("value", 0.0) > 0.0)
        print(f"# sweep {label}: {json.dumps(r)}", file=sys.stderr)
        results.append(r)
        with open(out_path, "w") as f:
            json.dump({"ts": time.strftime("%FT%TZ", time.gmtime()),
                       "model": MODEL, "results": results}, f, indent=1)
        if wedged:
            break
    best = max(results, key=lambda r: r.get("value", 0.0))
    print(json.dumps(best))


def _run_one_config(
    label: str, env: dict, timeout: float
) -> tuple[dict, bool]:
    """Run ONE sweep config in its own subprocess (one process per
    chip). Returns (driver-contract row, child_wedged);
    `child_wedged` means the child outlived SIGTERM and still holds
    the chip, so the caller must abort the sweep. Rows from a fired
    watchdog (the child's 1200 s run deadline, or the parent timeout
    here) carry `watchdog: true`; the parent-timeout row additionally
    carries `parent_timeout: true` (child emitted nothing at all).
    Factored out of _run_sweep so the watchdog-continue contract is
    testable without a chip."""
    import subprocess

    timed_out = False
    wedged = False
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env, stdout=subprocess.PIPE, text=True,
    )
    try:
        stdout, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        timed_out = True
        proc.terminate()
        try:
            stdout, _ = proc.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            # the child outlived SIGTERM: it still holds the chip, so
            # any further config would fail in backend init — abort
            # the sweep instead of recording those as measurements
            stdout = ""
            wedged = True
    # even on timeout, a graceful SIGTERM shutdown (or the child's
    # teardown guard) may have emitted a COMPLETED measurement —
    # prefer it over a synthetic failure row
    r = _last_json(stdout)
    if r is None and timed_out:
        r = {"metric": f"sweep-config-timeout: {label}",
             "value": 0.0, "unit": "gen_tokens/s/chip",
             "vs_baseline": 0.0, "watchdog": True,
             # parent_timeout: the CHILD emitted nothing at all (its
             # own watchdog never even fired) — kept as a distinct
             # marker for sweep-JSON forensics
             "parent_timeout": True,
             "error": f"no result after {timeout:.0f}s"
                      + ("; child unresponsive to SIGTERM, sweep "
                         "aborted" if wedged else "")}
    elif r is None:
        r = {"metric": f"sweep-config-failed: {label}",
             "value": 0.0, "unit": "gen_tokens/s/chip",
             "vs_baseline": 0.0,
             "error": f"exit={proc.returncode}, no JSON line"}
    return r, wedged


def _last_json(stdout: str | None) -> dict | None:
    """Parse the last driver-contract JSON line from a child's stdout."""
    lines = [ln for ln in (stdout or "").splitlines()
             if ln.startswith("{")]
    try:
        return json.loads(lines[-1])
    except (IndexError, ValueError):
        return None


def _arm_watchdog(seconds: float, label: str):
    """Abort (with the driver-contract JSON line) if the run wedges.

    A device call that never returns leaves the main thread blocked
    inside C code no Python exception can interrupt. A daemon timer
    prints the abort row and hard-exits with os._exit."""
    import threading

    def fire() -> None:
        print(json.dumps({
            "metric": f"bench-aborted: watchdog ({label})",
            "value": 0.0,
            "unit": "gen_tokens/s/chip",
            "vs_baseline": 0.0,
            # explicit marker: the sweep parent records this row as
            # {"ok": false, "watchdog": true} and CONTINUES with the
            # remaining configs
            "watchdog": True,
            "error": f"{label} exceeded {seconds:.0f}s — chip wedged?",
        }), flush=True)
        os._exit(2)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def _cache_server_box():
    """@remotekv bench mode: an in-process `kv.cache_server` standing
    in for the cluster's shared cache pod (colocated on this host — the
    wire cost is loopback, so the A/B measures the
    framing/serialization machinery, understating a real network's
    latency but not its protocol overhead)."""
    from production_stack_tpu.kv.cache_server import InProcessCacheServer

    return InProcessCacheServer(capacity_bytes=8 * 2**30)


class _PDPrefiller:
    """@pd bench mode: a colocated prefill-role engine with its own
    step thread and an in-process KVTransferServer, so the measured
    decode engine exercises the REAL PD data plane (phase-1 prefill
    here, chain pull through the decode engine's PeerTier staged
    restore). Both engines share the one chip — device work serializes
    and weights sit in HBM twice, which understates the multi-chip win
    but measures the transfer machinery honestly."""

    def __init__(self, config):
        import queue as _queue

        from production_stack_tpu.engine.llm_engine import LLMEngine
        from production_stack_tpu.engine.sampling_params import (
            SamplingParams,
        )
        from production_stack_tpu.kv.transfer import KVTransferServer

        self.engine = LLMEngine(config)
        self._lock = threading.Lock()
        self._sp1 = SamplingParams(
            max_tokens=1, temperature=0.0, ignore_eos=True
        )
        self._stop = threading.Event()
        self._finished: _queue.Queue = _queue.Queue()
        self._prompts: dict[str, list[int]] = {}
        self._inflight = 0  # guarded by: self._lock
        self.submitted = 0

        # the transfer server wants an AsyncLLMEngine-alike: .engine +
        # ._lock (the lock our step thread holds per step)
        holder: dict = {"ready": threading.Event()}
        outer = self

        class _FakeAsync:
            engine = self.engine
            _lock = outer._lock

        def serve():
            async_mod = __import__("asyncio")

            async def run():
                srv = KVTransferServer(_FakeAsync())
                await srv.start("127.0.0.1", 0)
                holder["srv"] = srv
                holder["port"] = srv.port
                holder["loop"] = async_mod.get_running_loop()
                holder["stop"] = async_mod.Event()
                holder["ready"].set()
                await holder["stop"].wait()
                await srv.stop()

            async_mod.run(run())

        self._srv_thread = threading.Thread(target=serve, daemon=True)
        self._srv_thread.start()
        assert holder["ready"].wait(10), "kv transfer server stalled"
        self._holder = holder
        self.port = holder["port"]
        self.server = holder["srv"]
        self._step_thread = threading.Thread(
            target=self._run, name="pd-prefill-step", daemon=True
        )
        self._step_thread.start()

    def warmup(self, prompts) -> None:
        from production_stack_tpu.engine.sampling_params import (
            SamplingParams,
        )

        with self._lock:
            self.engine.generate(
                prompts,
                SamplingParams(
                    max_tokens=1, temperature=0.0, ignore_eos=True
                ),
            )

    def submit(self, rid: str, tokens: list[int]) -> None:
        with self._lock:
            self._prompts[rid] = tokens
            self.engine.add_request(
                rid, prompt_token_ids=tokens, sampling_params=self._sp1
            )
            self._inflight += 1
            self.submitted += 1

    def drain(self) -> list[tuple[str, list[int]]]:
        """Finished phase-1 requests, ready for the decode engine."""
        import queue as _queue

        out = []
        while True:
            try:
                out.append(self._finished.get_nowait())
            except _queue.Empty:
                return out

    def busy(self) -> bool:
        """True while phase-1 work is in flight OR finished results
        await drain — _inflight decrements at the same moment the
        result is enqueued, so checking it alone would let the bench
        loop exit with undrained requests (dropping them, and every
        later round of their sessions, from the measurement)."""
        with self._lock:
            if self._inflight > 0:
                return True
        return not self._finished.empty()

    def _run(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                busy = self.engine.has_unfinished()
                outs = self.engine.step() if busy else []
                for o in outs:
                    if o.finished:
                        self._inflight -= 1
                        self._finished.put(
                            (o.request_id,
                             self._prompts.pop(o.request_id))
                        )
            if not busy:
                self._stop.wait(0.002)

    def close(self) -> None:
        self._stop.set()
        self._step_thread.join(timeout=5)
        self._holder["loop"].call_soon_threadsafe(
            self._holder["stop"].set
        )
        self._srv_thread.join(timeout=5)
        self.engine.shutdown()


def _run_longctx(label: str) -> dict:
    """@longctx scenario: serve ONE prompt per length over the 8k ->
    128k sweep, recording TTFT vs prompt length, the long-prefill
    per-phase attribution, and the HBM high-water mark. The ring lane
    is on by default (@longctx); @nolongctx pins the chunked-prefill
    control for the A/B."""
    import gc

    import jax

    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.llm_engine import LLMEngine
    from production_stack_tpu.engine.sampling_params import SamplingParams
    from production_stack_tpu.models.config import get_model_config

    watchdog = _arm_watchdog(
        float(os.environ.get("PST_BENCH_RUN_DEADLINE", "1200")),
        f"longctx[{label}]",
    )
    mc = get_model_config(MODEL)
    lens = [x for x in LONGCTX_LENS
            if x + LONGCTX_ANSWER_TOK <= mc.max_model_len]
    if not lens:
        raise SystemExit(
            f"model {MODEL} (max_model_len={mc.max_model_len}) admits "
            f"none of the sweep lengths {LONGCTX_LENS}"
        )
    ring = LONGCTX_RING
    config = EngineConfig(
        model=MODEL,
        tokenizer="byte",
        dtype="bfloat16",
        cache_dtype="bfloat16",
        block_size=32,
        hbm_utilization=0.85,
        max_model_len=max(lens) + LONGCTX_ANSWER_TOK,
        max_num_seqs=4,
        max_prefill_chunk=PREFILL_CHUNK,
        tensor_parallel_size=TP,
        num_scheduler_steps=SCHED_STEPS,
        device_stop=ELASTIC,
        adaptive_decode_k=ELASTIC,
        long_prefill_threshold=LONGCTX_THRESHOLD if ring else None,
        context_parallel_size=LONGCTX_SP if ring else 0,
        long_prefill_chunk=LONGCTX_CHUNK,
        seed=0,
    )
    t_setup = time.time()
    engine = LLMEngine(config)
    ring_live = engine.long_prefill is not None
    print(
        f"# longctx engine up in {time.time() - t_setup:.1f}s, ring "
        f"{'LIVE' if ring_live else 'OFF'}, "
        f"{engine.runner.num_blocks} KV blocks",
        file=sys.stderr,
    )
    rng = np.random.RandomState(0)
    vocab = engine.runner.model_config.vocab_size
    sp = SamplingParams(
        max_tokens=LONGCTX_ANSWER_TOK, temperature=0.0, ignore_eos=True
    )
    # warm the small buckets so the first sweep point is not all compile
    engine.generate(
        [rng.randint(0, vocab, 256).tolist()],
        SamplingParams(max_tokens=2, temperature=0.0, ignore_eos=True),
    )

    def _peak_bytes() -> int:
        try:
            return int(
                (jax.devices()[0].memory_stats() or {}).get(
                    "peak_bytes_in_use", 0
                )
            )
        except Exception:  # noqa: BLE001 — CPU backends have no stats
            return 0

    rows = []
    pool_tokens = engine.runner.num_blocks * config.block_size
    for L in lens:
        rid = f"lc{L}"
        if L + LONGCTX_ANSWER_TOK > pool_tokens:
            rows.append({
                "prompt_tokens": L, "admitted": False,
                "reason": f"KV pool holds {pool_tokens} tokens",
            })
            continue
        prompt = rng.randint(0, vocab, L).tolist()
        snap = engine.stats()
        hbm_hw = 0.0
        ttft = None
        t0 = time.time()
        engine.add_request(rid, prompt_token_ids=prompt,
                           sampling_params=sp)
        while engine.has_unfinished():
            outs = engine.step()
            hbm_hw = max(hbm_hw, engine.block_manager.usage)
            if ttft is None and any(
                o.request_id == rid and o.token_ids for o in outs
            ):
                ttft = time.time() - t0
        e2e = time.time() - t0
        st = engine.stats()
        rows.append({
            "prompt_tokens": L,
            "admitted": True,
            "ttft_s": round(ttft, 3) if ttft is not None else -1,
            "e2e_s": round(e2e, 3),
            # a ring claim that FAILED back to chunked prefill must not
            # pollute the ring-vs-chunked A/B rows as "ring"
            "served_via": (
                "chunked"
                if st.long_prefill_requests_total
                == snap.long_prefill_requests_total
                else "ring"
                if st.long_prefill_fallbacks_total
                == snap.long_prefill_fallbacks_total
                else "ring-fallback"
            ),
            "hbm_highwater_frac": round(hbm_hw, 4),
            "hbm_peak_bytes": _peak_bytes(),
            "phase_s": {
                "ring": round(
                    st.long_prefill_ring_seconds_total
                    - snap.long_prefill_ring_seconds_total, 3),
                "d2h": round(
                    st.long_prefill_d2h_seconds_total
                    - snap.long_prefill_d2h_seconds_total, 3),
                "land": round(
                    st.long_prefill_land_seconds_total
                    - snap.long_prefill_land_seconds_total, 3),
                "overflow": round(
                    st.long_prefill_overflow_seconds_total
                    - snap.long_prefill_overflow_seconds_total, 3),
            },
        })
        print(f"# longctx {L}: {rows[-1]}", file=sys.stderr)
    st = engine.stats()
    served = [r for r in rows if r.get("admitted")]
    result = {
        "metric": (
            f"long-context TTFT sweep ({mc.name}, "
            f"{lens[0]}-{lens[-1]} tok prompts, "
            f"{'ring sp=' + str(LONGCTX_SP) if ring_live else 'chunked'}"
            f", {TP} chip(s))"
        ),
        "value": served[-1]["ttft_s"] if served else -1,
        "unit": f"s_ttft@{served[-1]['prompt_tokens']}tok"
        if served else "s_ttft",
        "vs_baseline": -1,
        "detail": {
            "config_label": label,
            "sweep": rows,
            "long_prefill": {
                "enabled": ring,
                "live": ring_live,
                "sp": LONGCTX_SP if ring_live else 0,
                "threshold": LONGCTX_THRESHOLD if ring_live else None,
                "chunk_tokens": (
                    engine.long_prefill.chunk if ring_live else None
                ),
                "requests": st.long_prefill_requests_total,
                "chunks": st.long_prefill_chunks_total,
                "fallbacks": st.long_prefill_fallbacks_total,
                "phase_s": {
                    "ring": round(st.long_prefill_ring_seconds_total, 3),
                    "d2h": round(st.long_prefill_d2h_seconds_total, 3),
                    "land": round(st.long_prefill_land_seconds_total, 3),
                    "overflow": round(
                        st.long_prefill_overflow_seconds_total, 3),
                },
            },
            "compiles": {
                "total": engine.runner.compile_events_total,
                "by_kind": dict(sorted(
                    engine.runner.compile_events.items()
                )),
            },
        },
    }
    watchdog.cancel()
    engine.shutdown()
    del engine
    gc.collect()
    return result


def run_config(sched_steps: int, prefill_seqs: int, async_decode: bool,
               label: str) -> dict:
    import gc

    import jax  # noqa: F401 — backend already initialized

    if LONGCTX:
        # @longctx replaces the multi-round QA workload with the
        # prompt-length sweep (the base k/pack label still selects the
        # decode config the answers run under)
        return _run_longctx(label)

    watchdog = _arm_watchdog(
        float(os.environ.get("PST_BENCH_RUN_DEADLINE", "1200")),
        f"run_config[{label}]",
    )

    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.llm_engine import LLMEngine
    from production_stack_tpu.engine.sampling_params import SamplingParams

    t_setup = time.time()
    # final-round sequence length: round-1 prompt plus per-round growth
    # (answer fed back into the session + the next question)
    final_len = (
        SYSTEM_PROMPT_TOK + HISTORY_TOK
        + (ROUNDS - 1) * (ANSWER_TOK + QUESTION_TOK) + ANSWER_TOK
    )
    # @kvoff: cap the KV pool so finished sessions' prefixes spill into
    # the cpu/disk tiers between rounds while every ACTIVE lane still
    # fits (peak active = NUM_USERS x final_len; 1.15x slack covers the
    # +1 generation block and pinned-export transients)
    kv_blocks = None
    kv_kwargs: dict = {}
    cache_server_box = None
    if KV_OFFLOAD:
        kv_blocks = KV_BLOCKS or int(
            1.15 * NUM_USERS * -(-final_len // 32)
        )
        import shutil

        shutil.rmtree(DISK_OFFLOAD_DIR, ignore_errors=True)
        kv_kwargs = dict(
            num_kv_blocks=kv_blocks,
            cpu_offload_bytes=CPU_OFFLOAD_MB * 2**20,
            disk_offload_dir=DISK_OFFLOAD_DIR,
            sync_kv_offload=SYNC_KV,
        )
        if KV_REMOTE:
            # @remotekv: LMCache-like topology — capped cpu buffer +
            # in-process shared cache server, NO local disk tier
            # (overflow past host RAM restores over the wire as ONE
            # chain pull; write-behind batched PUTs ship every export)
            cache_server_box = _cache_server_box()
            kv_kwargs["disk_offload_dir"] = None
            kv_kwargs["remote_cache_url"] = (
                f"127.0.0.1:{cache_server_box.port}"
            )
    config = EngineConfig(
        model=MODEL,
        tokenizer="byte",
        dtype="bfloat16",
        cache_dtype="bfloat16",
        block_size=32,
        hbm_utilization=0.85,
        **kv_kwargs,
        max_model_len=max(4096, 32 * (-(-(final_len + 64) // 32))),
        max_num_seqs=NUM_USERS,
        max_prefill_chunk=PREFILL_CHUNK,
        max_prefill_seqs=prefill_seqs,
        tensor_parallel_size=TP,
        num_scheduler_steps=sched_steps,
        # elastic fused decode A/B: @noelastic pins the fixed-trip
        # fixed-K control (the pre-elastic behavior) for attribution
        device_stop=ELASTIC,
        adaptive_decode_k=ELASTIC,
        # unified ragged dispatch A/B: @noragged pins the split
        # alternating prefill/decode rounds for attribution
        ragged_dispatch=RAGGED,
        # single-kernel ragged attention A/B: @norpakernel pins the
        # composed per-lane kernels for attribution (pallas impl only)
        ragged_kernel=RAGGED_KERNEL,
        async_decode=async_decode,
        prefetch_decode=PREFETCH,
        prefill_pipeline=PREFILL_PIPELINE,
        # tracing A/B: @trace turns the full recording path on (timeline
        # + memory span exporter); the default control has every hook
        # compiled down to one boolean check
        request_timeline=TRACE,
        tracing_exporter="memory" if TRACE else "none",
        seed=0,
    )
    pd_prefiller = None
    if PD:
        import dataclasses as _dc

        # @pd: a separate prefill-role engine (own step thread + KV
        # transfer server) takes every round-1 prompt at max_tokens=1;
        # the measured decode engine pulls the chain through its
        # PeerTier staged restore. Colocated on the one chip: size the
        # prefill engine's pool small (it only holds in-flight phase-1
        # chains until they are pulled) and leave the decode engine
        # the rest. The prefill engine needs no offload tiers.
        pf_blocks = 4 * max(
            1, -(-(SYSTEM_PROMPT_TOK + HISTORY_TOK) // 32)
        ) * max(2, min(8, NUM_USERS))
        pd_prefiller = _PDPrefiller(_dc.replace(
            config,
            kv_role="prefill",
            hbm_utilization=0.2,
            num_kv_blocks=pf_blocks,
            cpu_offload_bytes=0,
            disk_offload_dir=None,
            request_timeline=False,
            tracing_exporter="none",
        ))
        config = _dc.replace(
            config,
            kv_role="decode",
            kv_transfer_config={
                "peer": f"127.0.0.1:{pd_prefiller.port}"
            },
            hbm_utilization=0.6,
        )
    engine = LLMEngine(config)
    mc = engine.runner.model_config
    print(
        f"# engine up in {time.time() - t_setup:.1f}s on "
        f"{jax.devices()[0].platform}, {engine.runner.num_blocks} KV blocks",
        file=sys.stderr,
    )

    rng = np.random.RandomState(0)
    vocab = mc.vocab_size
    shared_prefix = rng.randint(0, vocab, SYSTEM_PROMPT_TOK).tolist()
    prompts = [
        shared_prefix + rng.randint(0, vocab, HISTORY_TOK).tolist()
        for _ in range(NUM_USERS)
    ]
    # the user's next message for each later round, fixed up front so the
    # workload is deterministic across configs
    questions = [
        [rng.randint(0, vocab, QUESTION_TOK).tolist()
         for _ in range(ROUNDS - 1)]
        for _ in range(NUM_USERS)
    ]
    sp = SamplingParams(
        max_tokens=ANSWER_TOK, temperature=0.0, ignore_eos=True
    )

    # -- warmup: compile the buckets the timed run will hit, so no XLA
    # compile lands inside the measurement: full-length prompts select the
    # same prefill/decode ctx buckets as the real pass
    t0 = time.time()
    if pd_prefiller is not None:
        # compile the prefill engine's full-prompt buckets FIRST, so
        # the decode engine's warmup below pulls real chains — warming
        # the transfer link and the staged-import scatter compile
        # before the timed run
        pd_prefiller.warmup(prompts[:2])
    engine.generate(
        prompts[:2],
        SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True),
    )
    print(f"# warmup/compile {time.time() - t0:.1f}s", file=sys.stderr)

    if PRECOMPILE:
        # compile every prefill program the QPS-paced run can reach so no
        # XLA compile lands inside a TTFT/ITL measurement: lone arrivals
        # take the SINGLE-sequence path (warmup packs its two prompts, so
        # singles would otherwise first compile mid-run), bursts take the
        # packed path at pow2 group sizes, and a fully prefix-cached
        # prompt resumes with a 1-token tail chunk (see
        # ModelRunner.precompile_prefill)
        t0 = time.time()
        rnr = engine.runner
        plen = SYSTEM_PROMPT_TOK + HISTORY_TOK
        chunk = config.max_prefill_chunk
        # walk the actual chunking: each sub-chunk is min(chunk, plen-p)
        # tokens at total p+len — a short FINAL sub-chunk (plen % chunk)
        # lands in its own smaller t_pad bucket and must be precompiled
        # too, or its compile lands inside a live TTFT measurement
        pieces = sorted({
            (min(chunk, plen - p), rnr._ctx_bucket(p + min(chunk, plen - p)))
            for p in range(0, plen, chunk)
        })
        tail_ctx = rnr._ctx_bucket(plen)
        # a fully prefix-cached prompt resumes past the last whole-block
        # boundary, so its tail chunk is plen - floor((plen-1)/bs)*bs
        # tokens (in [1, block_size]) — use the exact length so the tail
        # lands in the same t_pad bucket the timed run will reach
        bs = config.block_size
        tail_len = plen - ((plen - 1) // bs) * bs
        singles = pieces + [(tail_len, tail_ctx)]
        groups = []
        s = 2
        while s <= min(prefill_seqs, NUM_USERS):
            groups += [(s, cl, t) for cl, t in pieces]
            s *= 2
        if prefill_seqs > 1:
            groups.append((2, tail_len, tail_ctx))
        if ROUNDS > 1:
            # rounds 2+ resume from the prefix cache at the last cached
            # whole-block boundary of the previous round's sequence; with
            # greedy + ignore_eos every length is deterministic, so each
            # round's resume tail compiles ahead of the timed run. Fused
            # K-step rounds finish whole lane groups together, so
            # resubmissions arrive in BURSTS — the packed variants of
            # each tail are reachable too. Dedup by bucket so shared
            # (t_pad, c_pad) programs cost one trash dispatch, not one
            # per round.
            seen = {
                (rnr._prefill_bucket(cl), t) for cl, t in singles
            }
            seen_g = {
                (gs, rnr._prefill_bucket(cl), t) for gs, cl, t in groups
            }
            L = plen
            for r in range(ROUNDS - 1):
                prev_total = L + ANSWER_TOK
                L = prev_total + QUESTION_TOK
                cached = (prev_total // bs) * bs
                rtail = L - cached
                cb = rnr._ctx_bucket(L)
                if (rnr._prefill_bucket(rtail), cb) not in seen:
                    seen.add((rnr._prefill_bucket(rtail), cb))
                    singles.append((rtail, cb))
                gs = 2
                while gs <= min(prefill_seqs, NUM_USERS):
                    key = (gs, rnr._prefill_bucket(rtail), cb)
                    if key not in seen_g:
                        seen_g.add(key)
                        groups.append((gs, rtail, cb))
                    gs *= 2
        ndisp = rnr.precompile_prefill(singles, groups)
        if ROUNDS > 1:
            # later rounds also cross decode ctx buckets (pow2 block
            # counts) the warmup never reached; elastic serving also
            # dispatches the pow2 K buckets below the cap (adaptive K)
            # and the prefetch-chained device-stop variant
            grow = ANSWER_TOK + QUESTION_TOK
            decode_ctxs = [
                plen + r * grow + ANSWER_TOK for r in range(ROUNDS)
            ]
            from production_stack_tpu.engine.scheduler import (
                decode_precompile_variants,
            )

            # the ONE variant-selection policy precompile_serving uses
            # too — the warmed (k, chained, stop) set must match what
            # pick_decode_k + the dispatch gates select at runtime
            for kk, chained, stop in decode_precompile_variants(
                sched_steps, ELASTIC,
                overlap=async_decode or PREFETCH,
                async_chained=async_decode,
                device_stop=ELASTIC,
            ):
                ndisp += rnr.precompile_decode(
                    decode_ctxs, kk, chained=chained, stop=stop,
                )
            if RAGGED and not async_decode:
                # mixed rounds here pair resume-tail prefill lanes with
                # decode lanes in the same session-length regime: warm
                # the small lane-mix buckets on the decode-ctx diagonal
                # (resubmission bursts are mostly 1-2 lanes; bigger
                # mixes and off-diagonal ctx pairs compile on first use
                # and are cheap on restart via JAX_COMPILATION_CACHE_DIR)
                from production_stack_tpu.engine.scheduler import (
                    decode_k_buckets,
                )

                ndisp += rnr.precompile_ragged(
                    [max(1, c - sched_steps + 1) for c in decode_ctxs],
                    decode_k_buckets(sched_steps, ELASTIC),
                    min(2, prefill_seqs),
                    PREFILL_CHUNK,
                    stop=ELASTIC,
                    chained=PREFETCH,
                )
        print(
            f"# prefill precompile: {ndisp} dispatches in "
            f"{time.time() - t0:.1f}s",
            file=sys.stderr,
        )

    # -- timed run ---------------------------------------------------------
    # QPS-paced arrivals, like the reference harness (multi-round-qa.py
    # drives a target QPS): TTFT is measured from each request's own
    # arrival, not from the start of a burst
    ttfts: dict[str, float] = {}
    t_start = time.time()
    # request ids are "u<i>:r<round>"; round-1 arrivals are QPS-paced,
    # rounds 2+ resubmit the grown session the moment the previous
    # answer lands (reference sessions chat continuously)
    arrivals = [(f"u{i}:r1", t_start + i / QPS, p)
                for i, p in enumerate(prompts)]
    submit_t: dict[str, float] = {}
    pending = list(arrivals)
    session_prompt = list(prompts)  # per-user, grows each round
    session_round = [1] * NUM_USERS

    gen_tokens = 0
    decode_time = 0.0
    last_token_t: dict[str, float] = {}
    itls: list[float] = []  # inter-token gaps across all streams
    while (pending or engine.has_unfinished()
           or (pd_prefiller is not None and pd_prefiller.busy())):
        now = time.time()
        while pending and pending[0][1] <= now:
            rid, due, p = pending.pop(0)
            if pd_prefiller is not None:
                # @pd: the cold prompt's phase 1 runs on the prefill
                # engine; the decode engine admits it after the chain
                # pull (TTFT still counts from the scheduled arrival —
                # the whole disaggregated path is the measurement)
                pd_prefiller.submit(rid, p)
            else:
                engine.add_request(
                    rid, prompt_token_ids=p, sampling_params=sp
                )
            # TTFT counts from the SCHEDULED arrival: admission delay past
            # `due` is queueing the system caused and must stay in the
            # measurement (avoiding coordinated omission)
            submit_t[rid] = due
        if pd_prefiller is not None:
            for rid, toks in pd_prefiller.drain():
                engine.add_request(
                    rid, prompt_token_ids=toks, sampling_params=sp
                )
        if not engine.has_unfinished():
            if pending:
                time.sleep(
                    max(0.0, min(0.002, pending[0][1] - time.time()))
                    if pd_prefiller is not None
                    else max(0.0, pending[0][1] - time.time())
                )
            elif pd_prefiller is not None:
                time.sleep(0.001)  # phase-1 in flight on the prefiller
            continue
        st = time.time()
        outs = engine.step()
        dt = time.time() - st
        now = time.time()
        for out in outs:
            if out.request_id not in ttfts and out.token_ids:
                ttfts[out.request_id] = now - submit_t[out.request_id]
            if out.new_token_ids:
                prev = last_token_t.get(out.request_id)
                if prev is not None:
                    itls.append(now - prev)
                last_token_t[out.request_id] = now
            if out.finished:
                uid = int(out.request_id.split(":")[0][1:])
                r = session_round[uid]
                if r < ROUNDS:
                    session_prompt[uid] = (
                        session_prompt[uid] + list(out.token_ids)
                        + questions[uid][r - 1]
                    )
                    session_round[uid] = r + 1
                    nrid = f"u{uid}:r{r + 1}"
                    engine.add_request(
                        nrid,
                        prompt_token_ids=session_prompt[uid],
                        sampling_params=sp,
                    )
                    submit_t[nrid] = now
        if engine.last_step_kind in ("decode", "ragged"):
            # ragged rounds generate decode tokens too; their wall time
            # includes the fused prefill lanes BY DESIGN (the unified
            # round is the thing being measured)
            gen_tokens += sum(len(o.new_token_ids) for o in outs)
            decode_time += dt
    total_time = time.time() - t_start

    all_gen = NUM_USERS * ANSWER_TOK * ROUNDS
    decode_tps = gen_tokens / decode_time if decode_time > 0 else 0.0
    overall_tps = all_gen / total_time
    ttft_arr = np.asarray(sorted(ttfts.values()))
    p50_ttft = float(np.percentile(ttft_arr, 50)) if len(ttft_arr) else -1
    itl_arr = np.asarray(itls)
    itl_p = (
        {
            "p50_itl_s": round(float(np.percentile(itl_arr, 50)), 4),
            "p90_itl_s": round(float(np.percentile(itl_arr, 90)), 4),
            "p99_itl_s": round(float(np.percentile(itl_arr, 99)), 4),
        }
        if len(itl_arr)
        else {}
    )

    model_bytes = mc.num_params() * 2  # bf16
    # each of the TP chips holds model_bytes/TP and streams it per decode
    # step at HBM_BW, so the aggregate roofline scales with TP; reported
    # value and vs_baseline are both per-chip so TP runs stay comparable
    roofline_tps = NUM_USERS * TP * _hbm_bw_gbps() * 1e9 / model_bytes

    r1 = np.asarray(
        [v for k, v in ttfts.items() if k.endswith(":r1")]
    )
    resume = np.asarray(
        [v for k, v in ttfts.items() if not k.endswith(":r1")]
    )
    result = {
        "metric": (
            f"multi-round-qa-style serving throughput "
            f"({mc.name}, {NUM_USERS} users x {ROUNDS} rounds, "
            f"{SYSTEM_PROMPT_TOK}+{HISTORY_TOK} tok prompts, "
            f"{ANSWER_TOK} tok answers, {TP} chip(s))"
        ),
        "value": round(overall_tps / TP, 1),
        "unit": "gen_tokens/s/chip",
        "vs_baseline": round(decode_tps / roofline_tps, 3),
        "detail": {
            "tensor_parallel_size": TP,
            "arrival_qps": QPS,
            "num_scheduler_steps": sched_steps,
            "prefill_seqs": prefill_seqs,
            "async_decode": async_decode,
            "prefetch_decode": PREFETCH,
            "prefill_pipeline": PREFILL_PIPELINE,
            "trace": TRACE,
            "config_label": label,
            "rounds": ROUNDS,
            "decode_tokens_per_s_aggregate": round(decode_tps, 1),
            "p50_ttft_s": round(p50_ttft, 3),
            # round-1 TTFT pays the full prefill; rounds 2+ resume from
            # the prefix cache and re-prefill only the session tail
            "p50_ttft_round1_s": round(
                float(np.percentile(r1, 50)), 3
            ) if len(r1) else -1,
            "p50_ttft_resume_s": round(
                float(np.percentile(resume, 50)), 3
            ) if len(resume) else -1,
            "preemptions": engine.stats().num_preemptions_total,
            # h2d-prefetch effectiveness: hits dispatched on a staged
            # buffer (no serial upload); misses staged but invalidated
            "staged_hits": engine._staged_hits_total,
            "staged_misses": engine._staged_misses_total,
            # the round's phase attribution (tracing/phases.py): where
            # the step thread's wall time went (schedule / pack / h2d /
            # dispatch / fetch / apply) + staging and cold-prompt
            # chaining effectiveness
            "prefill_phase_s": {
                k: round(v, 3)
                for k, v in engine.phases.seconds().items()
            },
            # per-phase sample counts: phase_s / phase_n = mean wall
            # time per observation of that phase
            "prefill_phase_n": engine.phases.counts(),
            "prefill_staged_hits": engine._pf_staged_hits_total,
            "prefill_staged_misses": engine._pf_staged_misses_total,
            "prefill_chained_chunks": engine._pf_chained_chunks_total,
            # elastic fused decode attribution: chosen-K distribution
            # (adaptive sizing), host-discarded overshoot slots (the
            # K=32 waste mode — ~0 under device stops), and whole-round
            # device early exits
            "elastic_decode": {
                "device_stop": ELASTIC,
                "adaptive_decode_k": ELASTIC,
                "decode_rounds": engine._decode_rounds_total,
                "decode_k_hist": {
                    str(kk): v
                    for kk, v in sorted(engine._decode_k_hist.items())
                },
                "overshoot_tokens":
                    engine._decode_overshoot_tokens_total,
                "early_exit_rounds":
                    engine._decode_early_exit_rounds_total,
            },
            # unified ragged dispatch attribution (@ragged/@noragged):
            # fused lane-typed rounds, their lane-mix distribution
            # ("p<prefill>+d<decode>" per fused round), the share of
            # rounds that carried prefill lanes, split-execution
            # fallbacks (exotic lanes), and ragged h2d-staging
            # effectiveness
            "ragged_dispatch": {
                "enabled": RAGGED,
                "ragged_rounds": engine._ragged_rounds_total,
                "split_rounds": engine._ragged_split_rounds_total,
                "lane_mix_hist": dict(sorted(
                    engine._ragged_lane_mix_hist.items()
                )),
                # of all rounds that decoded, how many also carried
                # prefill lanes (ragged rounds tick decode_rounds too)
                "prefill_lane_share": round(
                    engine._ragged_rounds_total
                    / max(1, engine._decode_rounds_total), 3,
                ),
                "staged_hits": engine._ragged_staged_hits_total,
                "staged_misses": engine._ragged_staged_misses_total,
            },
            # compile-count attribution (@rpa/@norpakernel): program-
            # variant builds per builder kind — the cold-start compile
            # tax the single-kernel row-bucket variants shrink. Reads
            # the same counters as tpu:compile_events_total.
            "compiles": {
                "ragged_kernel": RAGGED_KERNEL,
                "total": engine.runner.compile_events_total,
                "by_kind": dict(sorted(
                    engine.runner.compile_events.items()
                )),
            },
            # zero-stall KV tiering attribution (@kvoff): export time is
            # offload-worker wall (overlapped), restore time is
            # enqueue->landed (overlaps queue wait); tier counters show
            # which tier actually served the resumes
            # disaggregated prefill/decode attribution (@pd): phase-1
            # count on the prefill engine, peer pull counters on the
            # decode engine (hits = blocks transferred, fallbacks =
            # failed pulls), staged-restore landings, and what the
            # transfer server actually served
            **({
                "pd_transfer": {
                    "colocated_same_chip": True,
                    "phase1_requests": pd_prefiller.submitted,
                    "peer": engine.kv_peer.counters(),
                    "restore_blocks": engine._kv_restore_blocks_total,
                    "restore_fallbacks":
                        engine._kv_restore_fallbacks_total,
                    "transfer_server": {
                        "chains": pd_prefiller.server.chains_served,
                        "blocks": pd_prefiller.server.blocks_served,
                    },
                },
            } if PD else {}),
            **({
                "kv_offload": {
                    "kv_blocks": kv_blocks,
                    "sync_kv_offload": SYNC_KV,
                    "export_blocks": engine._kv_export_blocks_total,
                    "export_s": round(
                        engine._kv_export_seconds_total, 3),
                    "restore_blocks": engine._kv_restore_blocks_total,
                    "restore_s": round(
                        engine._kv_restore_seconds_total, 3),
                    "restore_fallbacks":
                        engine._kv_restore_fallbacks_total,
                    "export_sync_fallbacks":
                        engine._kv_export_sync_fallbacks_total,
                    "tiers": engine.offload.counters()
                    if engine.offload is not None else {},
                },
            } if KV_OFFLOAD else {}),
            # shared-cache attribution (@remotekv): engine-side
            # RemoteTier counters (write-behind frames shipped, chain
            # pull hits/misses, wire bytes) + the server's own
            # occupancy/hit-rate stats
            **({
                "kv_remote": {
                    "remote": engine.offload.remote.counters()
                    if engine.offload is not None
                    and engine.offload.remote is not None else {},
                    "server": cache_server_box.stats(),
                },
            } if KV_REMOTE and cache_server_box is not None else {}),
            "mean_ttft_s": round(float(ttft_arr.mean()), 3)
            if len(ttft_arr)
            else -1,
            "total_wall_s": round(total_time, 1),
            "roofline_decode_tokens_per_s": round(roofline_tps, 1),
            "prefix_cache_hit_rate": round(
                engine.stats().prefix_cache_hit_rate, 3
            ),
            **itl_p,
        },
    }
    # the measurement is complete: disarm the abort watchdog BEFORE
    # teardown, which can itself block — a hung
    # shutdown must not overwrite a successful result with an abort
    # row. Arm a teardown guard instead that EMITS the result and exits
    # cleanly, so the measurement survives a wedged shutdown.
    import threading

    watchdog.cancel()

    def emit_and_exit() -> None:
        print(json.dumps(result), flush=True)
        os._exit(0)

    teardown_guard = threading.Timer(120.0, emit_and_exit)
    teardown_guard.daemon = True
    teardown_guard.start()
    # free the engine (params + KV cache) before the next sweep config
    # allocates its own — two live engines would OOM the chip's HBM
    if pd_prefiller is not None:
        pd_prefiller.close()
        del pd_prefiller
    engine.shutdown()
    del engine
    if cache_server_box is not None:
        cache_server_box.close()
    gc.collect()
    teardown_guard.cancel()
    return result


if __name__ == "__main__":
    main()
