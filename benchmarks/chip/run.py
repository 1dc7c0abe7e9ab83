#!/usr/bin/env python3
"""The benchmark: one cell, client -> router -> engine -> TPU.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

This process imports no jax. It starts one engine child per replica
(`engine_child.py`, with `JAX_PLATFORMS=tpu`, never the caller's value)
and one router child (`python -m production_stack_tpu.router`), checks
through `/version` that the engine runs on platform `tpu`, on a
`device_kind` that `peaks.json` knows and on as many devices as the cell
asks for, and otherwise prints no result line and exits non-zero. Then:
set-up (the reference check, cold against cached, turn 0 of the session
pool, a warm phase of the cell's own traffic), the measured window of
`--seconds`, both `/metrics` scraped at its edges, the children stopped
with SIGTERM, and ONE JSON object as the last line of standard output.
`correct` is false when the served log-probabilities leave the
reference's tolerance, when cold and cached disagree, when a request of
the window is malformed, or when a program was built inside the window.
With `--trace 0` its metrics are the cell's end-to-end metrics; with
`--trace 1` a 5 s `jax.profiler` trace is taken inside the window and
the metrics are the cell's per-layer metrics.

`--rehearse` runs the same control flow on the CPU at the tiny debug
widths; it prints its device as `cpu` and a line that is NOT the
contract's result line (no `metrics` key a driver could read).
`--sweep r1,r2,...` finds the knee of an open-loop cell once: one
set-up, then each rate for `--seconds`, and a table; not a driver
command.
"""

from __future__ import annotations

import argparse
import asyncio
import http.client
import json
import os
import random
import signal
import socket
import subprocess
import sys
import time

T_PROCESS_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import loadgen  # noqa: E402
import manifest  # noqa: E402

ENGINE_READY_S = 1100
ROUTER_READY_S = 60
SHUTDOWN_S = 60
TRACE_START_S = 2.0      # into the window
TRACE_SECONDS = 5.0
REF_PROMPTS = 2
REF_PROMPT_TOKENS = 256
REF_MAX_TOKENS = 8
# cold against cached (chip_smoke.py's check (c)): the cached run
# recomputes the prompt's tail in another row bucket, so bf16 rounding
# may move a log-probability in its second digit
CACHED_LOGPROB_ATOL = 0.1


class Refused(Exception):
    """The run cannot give a result; no result line is printed."""


def log(*a) -> None:
    print(*a, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def request(port: int, method: str, path: str, body: dict | None = None,
            timeout: float = 30.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path,
                     body=None if body is None else json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def get_json(port: int, path: str, method: str = "GET",
             body: dict | None = None, timeout: float = 30.0) -> dict:
    status, data = request(port, method, path, body, timeout)
    if status != 200:
        raise Refused(f"{method} {path} -> {status}: {data[:300]!r}")
    return json.loads(data)


class Child:
    def __init__(self, name: str, argv: list[str], env: dict, out_dir: str):
        self.name = name
        self.log_path = os.path.join(out_dir, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, *argv], cwd=ROOT, env=env,
            stdout=self._log, stderr=subprocess.STDOUT)

    def log_tail(self, n: int = 40) -> str:
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def wait_http(self, port: int, path: str, deadline_s: float,
                  ready=lambda status, data: status == 200) -> None:
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            rc = self.proc.poll()
            if rc is not None:
                raise Refused(f"{self.name} exited with code {rc} before "
                              f"it answered {path}")
            try:
                if ready(*request(port, "GET", path, timeout=5.0)):
                    return
            except OSError:
                pass
            time.sleep(0.25)
        raise Refused(f"{self.name} did not answer {path} within "
                      f"{deadline_s:.0f}s")

    def stop(self) -> int | None:
        if self.proc.poll() is None:
            self.proc.terminate()
        try:
            rc = self.proc.wait(timeout=SHUTDOWN_S)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            rc = None
        self._log.close()
        return rc


def start_children(cell, args, out_dir: str, children: list) -> dict:
    """Engines (one per replica, each on its own devices) behind one
    router. Returns the ports and the engines' device report."""
    if not os.path.isdir(os.path.join(ROOT, "production_stack_tpu")):
        raise Refused("the production_stack_tpu package is not in this "
                      "checkout: there is no system to measure")
    replicas = int(cell.config.get("replicas", 1))
    platform = "cpu" if args.rehearse else "tpu"
    engines = []
    for r in range(replicas):
        port, control = free_port(), free_port()
        env = dict(os.environ, JAX_PLATFORMS=platform, PYTHONUNBUFFERED="1")
        if replicas > 1 and not args.rehearse:
            # one replica, one slice of the host's chips (not proved on
            # the chip: no cell has two replicas yet)
            per = cell.chips // replicas
            env["TPU_VISIBLE_DEVICES"] = ",".join(
                str(r * per + i) for i in range(per))
        argv = [os.path.join(HERE, "engine_child.py"),
                "--config-file", cell.config_file,
                "--config-name", cell.config_name,
                "--family-file", cell.family_file,
                "--seed", str(args.seed), "--port", str(port),
                "--control-port", str(control), "--out-dir", out_dir,
                "--context-floor-tokens",
                str(int(cell.traffic.get("shared_prefix_tokens", 0)))]
        if args.rehearse:
            argv.append("--rehearse")
        child = Child(f"engine{r}", argv, env, out_dir)
        children.append(child)
        engines.append({"port": port, "control": control, "child": child})
    for e in engines:
        e["child"].wait_http(e["port"], "/health", ENGINE_READY_S)
    versions = [get_json(e["port"], "/version") for e in engines]
    ver = versions[0]
    count = sum(v["device_count"] for v in versions)
    if args.rehearse:
        if ver["platform"] != "cpu":
            raise Refused(f"a rehearsal runs on the CPU, not on "
                          f"{ver['platform']!r}")
    else:
        if ver["platform"] != "tpu":
            raise Refused(f"platform is {ver['platform']!r}, not 'tpu'")
        if ver["device_kind"] not in cell.peaks:
            raise Refused(f"device_kind {ver['device_kind']!r} is not in "
                          "peaks.json")
        if count != cell.chips:
            raise Refused(f"{count} devices, the cell asks for "
                          f"{cell.chips}")
    router_port = free_port()
    router = Child("router", [
        "-m", "production_stack_tpu.router",
        "--host", "127.0.0.1", "--port", str(router_port),
        "--service-discovery", "static",
        "--static-backends", ",".join(
            f"http://127.0.0.1:{e['port']}" for e in engines),
        "--static-models", ",".join([cell.config_name] * replicas),
        *cell.config.get("router_args", []),
    ], dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1"), out_dir)
    children.append(router)
    router.wait_http(
        router_port, "/v1/models", ROUTER_READY_S,
        ready=lambda s, d: s == 200 and cell.config_name.encode() in d)
    return {"engines": engines, "router_port": router_port,
            "device": {"platform": ver["platform"],
                       "kind": ver["device_kind"], "count": count}}


# -- correctness, outside the window ---------------------------------------
def completion(port: int, model: str, prompt: str, max_tokens: int) -> dict:
    out = get_json(port, "/v1/completions", "POST", {
        "model": model, "prompt": prompt, "max_tokens": max_tokens,
        "temperature": 0, "ignore_eos": True, "logprobs": 5,
    }, timeout=1100)
    choice = out["choices"][0]
    return {"text": choice["text"],
            "logprobs": choice["logprobs"]["token_logprobs"]}


def reference_check(sys_, cell, seed: int) -> dict:
    """(a) served log-probabilities against the plain float32 reference,
    teacher-forced on the ids the served path chose; (b) cold against
    cached on the same prompt."""
    import reference  # its constants only; jax is not imported here

    rng = random.Random(seed ^ 0x5EED)
    engine = sys_["engines"][0]
    worst = {"ok": True, "max_abs_diff": 0.0, "mean_abs_diff": 0.0}
    cached_diff = 0.0
    for i in range(REF_PROMPTS):
        prompt = loadgen.words(REF_PROMPT_TOKENS, rng)
        served = completion(sys_["router_port"], cell.config_name, prompt,
                            REF_MAX_TOKENS)
        prompt_ids = get_json(engine["port"], "/tokenize", "POST",
                              {"prompt": prompt})["tokens"]
        gen_ids = [ord(ch) - 0x10000 for ch in served["text"]]
        if len(gen_ids) != REF_MAX_TOKENS or min(gen_ids) < 0:
            raise Refused(f"reference prompt {i}: the stream did not "
                          f"carry {REF_MAX_TOKENS} token ids")
        ref = get_json(engine["control"], "/reference", "POST",
                       {"prompt_ids": prompt_ids, "generated_ids": gen_ids},
                       timeout=1100)
        cmp_ = reference.compare(served["logprobs"], ref["logprobs"])
        log(json.dumps({"reference_check": i, **cmp_,
                        "reference_s": ref["seconds"]}))
        worst["ok"] = worst["ok"] and cmp_["ok"]
        worst["max_abs_diff"] = max(worst["max_abs_diff"],
                                    cmp_["max_abs_diff"])
        worst["mean_abs_diff"] = max(worst["mean_abs_diff"],
                                     cmp_["mean_abs_diff"])
        if i == 0:
            again = completion(sys_["router_port"], cell.config_name,
                               prompt, REF_MAX_TOKENS)
            cached_diff = max(abs(a - b) for a, b in zip(
                served["logprobs"], again["logprobs"]))
            same = again["text"] == served["text"]
            log(json.dumps({"cold_vs_cached": {
                "same_tokens": same, "max_abs_diff": cached_diff}}))
            worst["ok"] = (worst["ok"] and same
                           and cached_diff <= CACHED_LOGPROB_ATOL)
    worst["cached_max_abs_diff"] = cached_diff
    return worst


# -- one run -------------------------------------------------------------------
async def measure(sys_, cell, plan, trace: bool):
    """Set-up traffic, then warm phase + window. Returns what the
    window saw."""
    engine = sys_["engines"][0]
    driver = loadgen.Driver(plan, sys_["router_port"], cell.config_name)
    loop = asyncio.get_running_loop()
    scr: dict = {}

    def scrape(tag: str) -> None:
        t = time.monotonic()
        scr["engine_" + tag] = manifest.parse_prometheus(
            request(engine["port"], "GET", "/metrics")[1].decode())
        scr["router_" + tag] = manifest.parse_prometheus(
            request(sys_["router_port"], "GET", "/metrics")[1].decode())
        scr["scrape_s_" + tag] = time.monotonic() - t

    setup_recs = await driver.run_setup()
    bad = [r for r in setup_recs if not r.ok()]
    if bad:
        raise Refused(f"set-up: {len(bad)} of {len(setup_recs)} turn-0 "
                      f"requests failed: {bad[0].as_json()}")

    # with --trace 1 the counters are read at the window's edges. With
    # --trace 0 nothing touches /metrics between the warm phase's start
    # and the end of the drain: a scrape waits for the step in flight
    # on the engine's event loop and stalls every stream meanwhile.
    async def on_start():
        if trace:
            await loop.run_in_executor(None, scrape, "before")

    async def on_end():
        if trace:
            await loop.run_in_executor(None, scrape, "after")

    if not trace:
        scrape("before")
    trace_task = None
    if trace:
        async def do_trace():
            await asyncio.sleep(plan.warm_seconds + TRACE_START_S)
            span = min(TRACE_SECONDS, max(1.0, plan.seconds - 3.0))
            await loop.run_in_executor(
                None, get_json, engine["control"], "/trace/start", "POST",
                {}, 120)
            await asyncio.sleep(span)
            return await loop.run_in_executor(
                None, get_json, engine["control"], "/trace/stop", "POST",
                {}, 600)
        trace_task = asyncio.create_task(do_trace())
    t0, t1 = await driver.run_timed(on_start, on_end)
    if not trace:
        scrape("after")
    reduced = None
    if trace_task:
        # stopped inside the window; reduced only after it, so that the
        # reduction does not take the engine's interpreter from it
        log(json.dumps({"trace_stop": await trace_task}))
        reduced = get_json(engine["control"], "/trace/reduce", "POST", {},
                           timeout=900)
    return driver, scr, t0, t1, reduced


def end_to_end_values(cell, driver, t0: float, t1: float) -> dict:
    """Every end-to-end metric this harness knows, from the records."""
    recs = driver.records
    if cell.traffic["loop"] == "open":
        window = [r for r in recs if r.phase == "window"]
    else:
        window = [r for r in recs
                  if r.end is not None and r.end >= t0 and r.phase != "setup"]
    ttfts = [v for v in map(loadgen.ttft_ms, window) if v is not None]
    gaps = [g for r in window for g in loadgen.token_gaps_ms(r)]
    values = {
        "norm_latency_mean_ms": loadgen.mean(
            [v for v in map(loadgen.norm_latency_ms, window)
             if v is not None]),
        "request_mean_ms": loadgen.mean(
            [v for v in map(loadgen.request_ms, window) if v is not None]),
        "tpot_mean_ms": loadgen.mean(
            [v for v in map(loadgen.tpot_ms, window) if v is not None]),
        "ttft_p50_ms": loadgen.percentile(ttfts, 50),
        "ttft_p95_ms": loadgen.percentile(ttfts, 95),
        "itl_p95_ms": loadgen.percentile(gaps, 95),
        "output_tok_per_s": loadgen.tokens_in_window(recs, t0, t1)
        / (t1 - t0),
    }
    failed = [r for r in window if not r.ok() and not r.cut]
    return {"values": values, "window": window, "failed": failed,
            "n_ttft": len(ttfts), "n_gaps": len(gaps)}


def run_cell(args, cell, out_dir: str, children: list) -> dict | None:
    # the cell's family, for its counts (readers divide by its bytes);
    # a family file that is not there stops the run before any child
    family = manifest.load_family(cell.family_file)
    sys_ = start_children(cell, args, out_dir, children)
    engine = sys_["engines"][0]
    with open(engine["child"].log_path, errors="replace") as f:
        ready = [ln for ln in f if ln.startswith('{"engine_child"')]
    log("engine:", ready[-1].strip() if ready else "(no ready line)")
    check = reference_check(sys_, cell, args.seed)
    traffic = dict(cell.traffic)
    if args.rehearse:
        traffic["setup"] = {**traffic.get("setup", {}), "warm_seconds": 2}
        if "rate_rps" in traffic:
            traffic["rate_rps"] = min(traffic["rate_rps"], 1.0)
        if "clients" in traffic:
            traffic["clients"] = 4
        traffic["session_pool"] = min(traffic.get("session_pool", 0), 6)

    if args.sweep:
        return sweep(args, sys_, cell, traffic, out_dir)

    plan = loadgen.build_plan(traffic, args.seed, args.seconds)
    driver, scr, t0, t1, reduced = asyncio.run(
        measure(sys_, cell, plan, bool(args.trace)))
    setup_s = t0 - T_PROCESS_START
    e2e = end_to_end_values(cell, driver, t0, t1)
    mem = get_json(engine["control"], "/")
    ver_after = get_json(engine["port"], "/version")
    with open(os.path.join(out_dir, f"records_{args.seed}.json"), "w") as f:
        json.dump([r.as_json() for r in driver.records], f)
    if reduced is not None:
        with open(os.path.join(out_dir, f"trace_{args.seed}.json"), "w") as f:
            json.dump(reduced, f)

    n = e2e["n_ttft"]
    compiles = (scr["engine_after"].get("tpu:compile_events_total", 0)
                - scr["engine_before"].get("tpu:compile_events_total", 0))
    log(json.dumps({
        "window": {"requests": len(e2e["window"]), "with_ttft": n,
                   "token_gaps": e2e["n_gaps"],
                   "highest_supported_percentile":
                       loadgen.supported_percentile(n),
                   "failed": len(e2e["failed"]),
                   "first_failure": (e2e["failed"][0].as_json()
                                     if e2e["failed"] else None),
                   "compiles_in_window": compiles,
                   "scrape_s": [scr["scrape_s_before"],
                                scr["scrape_s_after"]],
                   "all_values": e2e["values"]},
        "reference": check}))
    if compiles:
        # the contract: nothing compiles inside the measured window. A
        # build stalls every stream for seconds, so the run's numbers
        # are not the cell's; it is reported, and it is not correct
        log(f"FAULT: {compiles:.0f} program builds landed between the "
            "warm phase and the end of the window (engine log: "
            "'compiling'); set-up did not warm what this traffic reaches")

    peak_kind = cell.peaks.get(sys_["device"]["kind"], {})
    values = dict(e2e["values"], setup_s=setup_s)
    if args.trace:
        ctx = {
            "engine_before": scr["engine_before"],
            "engine_after": scr["engine_after"],
            "router_before": scr["router_before"],
            "router_after": scr["router_after"],
            "records": e2e["window"], "trace": reduced,
            "config": cell.config, "chips": cell.chips, "peak": peak_kind,
            "family": family, "loadgen": loadgen,
            "window_s": t1 - t0,
        }
        metrics = manifest.read_layer_metrics(cell, ctx)
        left_out = [m["name"] for m in cell.per_layer
                    if m["name"] not in metrics]
        if left_out:
            log("NOTE: per-layer metrics whose readers found nothing to "
                "read, left out of the result: " + ", ".join(left_out))
    else:
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}
    device = dict(sys_["device"])
    peaks_seen = [b for b in mem["peak_bytes_in_use"] if b]
    device["memory_peak_bytes"] = max(peaks_seen) if peaks_seen else None
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    correct = bool(check["ok"]) and not e2e["failed"] and not compiles
    if ver_after["platform"] != sys_["device"]["platform"]:
        raise Refused("the platform changed under the run")
    result = {"correct": correct, "attempted": len(e2e["window"]),
              "failed": len(e2e["failed"]), "metrics": metrics,
              "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    return result


def sweep(args, sys_, cell, traffic: dict, out_dir: str) -> None:
    """Each rate for --seconds after ONE set-up; prints a table. The
    knee is the highest rate at which nothing fails and the backlog does
    not grow: the median TTFT of the step's second half is at most 1.5
    times that of its first half (plus 50 ms). Nothing is polled while a
    step runs: the engine's /metrics waits for the step in flight ON
    its event loop."""
    engine = sys_["engines"][0]
    rates = [float(r) for r in args.sweep.split(",")]
    rows = []
    base = loadgen.build_plan(
        {**traffic, "rate_rps": rates[0]}, args.seed, args.seconds)
    sessions, spares = base.sessions, base.spares
    first = True
    for rate in rates:
        t = {**traffic, "rate_rps": rate,
             "setup": {**traffic.get("setup", {}), "warm_seconds": 0}}
        plan = loadgen.build_plan(t, args.seed + int(rate * 1000),
                                  args.seconds)
        plan.sessions, plan.spares = sessions, spares
        if not first:
            plan.setup_turns = []
        first = False
        driver, scr, t0, t1, _ = asyncio.run(
            measure(sys_, cell, plan, False))
        e2e = end_to_end_values(cell, driver, t0, t1)
        mid = (t0 + t1) / 2
        halves = [[], []]
        for r in e2e["window"]:
            v = loadgen.ttft_ms(r)
            if v is not None and r.due is not None:
                halves[r.due >= mid].append(v)
        unfinished = sum(1 for r in e2e["window"] if not r.done)
        row = {
            "rate_rps": rate, "requests": len(e2e["window"]),
            "failed": len(e2e["failed"]), "unfinished": unfinished,
            "ttft_p50_first_half_ms": loadgen.percentile(halves[0], 50),
            "ttft_p50_second_half_ms": loadgen.percentile(halves[1], 50),
            **e2e["values"],
            "compiles": scr["engine_after"].get(
                "tpu:compile_events_total", 0) - scr["engine_before"].get(
                "tpu:compile_events_total", 0),
        }
        a, b = (row["ttft_p50_first_half_ms"],
                row["ttft_p50_second_half_ms"])
        row["sustained"] = bool(
            row["failed"] == 0 and a and b and b <= 1.5 * a + 50.0)
        rows.append(row)
        log(json.dumps({"sweep": row}))
        time.sleep(2.0)
    with open(os.path.join(out_dir, "sweep.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default=None,
                    help="comma list of rates (req/s); open-loop cells")
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload)
    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            args.seconds = float(json.load(f)["run_seconds"])
    out_dir = os.path.join(ROOT, "chiprun_out", "bench", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    children: list[Child] = []
    result = None
    rc = 0
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        result = run_cell(args, cell, out_dir, children)
    except Refused as e:
        print(f"benchmark REFUSED: {e}", file=sys.stderr)
        rc = 1
    except Exception:  # noqa: BLE001 - every failure ends the same way
        import traceback

        traceback.print_exc()
        rc = 1
    finally:
        for child in reversed(children):
            code = child.stop()
            if code != 0 and rc == 0:
                print(f"{child.name} exited {code} after SIGTERM",
                      file=sys.stderr)
                rc = 1
        if rc != 0:
            for child in children:
                print(f"--- tail of {child.log_path} ---\n"
                      f"{child.log_tail()}", file=sys.stderr)
    if rc != 0 or result is None:
        return rc
    if args.rehearse:
        # not a measurement: no `metrics` key, and the device says cpu
        print(json.dumps({"rehearsal": True, "device": result["device"],
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"],
                          "names": sorted(result["metrics"])}))
        return 0
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
