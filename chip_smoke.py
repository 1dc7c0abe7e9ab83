#!/usr/bin/env python3
"""Chip smoke: serve real traffic through router -> engine -> TPU, once.

    python chip_smoke.py          # one chip:  llama-3.2-3b, full width+depth
    python chip_smoke.py --tp 4   # four chips: mistral-7b, full width+depth

The quickest proof that the system still starts and answers on the
accelerator. This process imports neither jax nor the engine package: it
starts ONE chip-owning child (`python -m production_stack_tpu.engine`,
with `JAX_PLATFORMS=tpu` — never the caller's value) and one router child
(`python -m production_stack_tpu.router`, which imports no jax), sends
three phases of greedy `ignore_eos` traffic through the router, checks
what came back, and stops both children with SIGTERM.

Weights are random from seed 0 and the tokenizer is the hermetic byte
tokenizer, so nothing is downloaded. Programs compile on first use; the
engine-ready and first-response times printed on a pass are SET-UP
times (compilation included), not serving latencies.

Exit code 0 and a last stdout line
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`
only when every check passed on platform `tpu`. Any failure — no chip,
a missing package, a failed request, a fallback in the engine log —
exits 1 with the failed check and the tail of the engine log on stderr,
and prints no result line.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# tp -> (model preset, extra engine flags). Both presets keep their
# published width AND depth: llama-3.2-3b (6.4 GB bf16) is the one
# head_dim-128 preset that fits a 16 GB chip whole; mistral-7b
# (14.5 GB) only fits split four ways (3.6 GB of weights per chip).
CONFIGS = {
    1: ("llama-3.2-3b", []),
    4: ("mistral-7b", ["--tensor-parallel-size", "4"]),
}
ENGINE_FLAGS = [
    "--tokenizer", "byte", "--max-model-len", "8192",
    "--max-num-seqs", "16", "--num-scheduler-steps", "8",
]
# device_kind strings this repo has run on (jax.devices()[0].device_kind)
KNOWN_DEVICE_KINDS = ("TPU v5 lite",)

DEADLINE_S = 1140          # whole run, under the driver's 1200 s
ENGINE_READY_S = 600       # weights + KV cache + kernel compile checks
ROUTER_READY_S = 60
REQUEST_S = 600            # one request, first-use compiles included
SHUTDOWN_S = 60            # SIGTERM -> exit 0

LONG_PROMPT_CHARS = 700    # byte tokenizer: chars ~ tokens; > 512 chunk
LONG_MAX_TOKENS = 32
SYSTEM_PROMPT_CHARS = 300
CHAT_STREAMS = 8
CHAT_MAX_TOKENS = 64
CHAT_STAGGER_S = 0.25      # second half arrives while the first decodes
# (c) repeats (a) over the prefix cache and must give the same answer.
# Random weights over a 32k-128k vocabulary decode to ids the byte
# tokenizer renders as "", so the text alone cannot tell; the top-5
# candidates at every position can ("token_id:N" keys of top_logprobs).
# The cached run recomputes the prompt's tail in another row bucket, so
# bf16 rounding may reorder near-ties at the edge of the top 5 and move
# a logprob in its third digit: most candidates must be shared at every
# position (a diverged sequence shares none), chosen logprobs within
# LOGPROB_ATOL.
TOP_LOGPROBS = 5
MIN_SHARED_CANDIDATES = 3
LOGPROB_ATOL = 0.1


class SmokeFailure(Exception):
    pass


def check(ok: bool, name: str, detail: str = "") -> None:
    if not ok:
        raise SmokeFailure(f"{name}: {detail}" if detail else name)
    print(f"  ok  {name}" + (f" ({detail})" if detail else ""), flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def text_of(n_chars: int, rng: random.Random) -> str:
    words = []
    while sum(len(w) + 1 for w in words) < n_chars:
        words.append("".join(
            rng.choice("abcdefghijklmnopqrstuvwxyz")
            for _ in range(rng.randint(2, 9))
        ))
    return " ".join(words)[:n_chars]


# -- http ------------------------------------------------------------------
def request(port: int, method: str, path: str, body: dict | None = None,
            timeout: float = 10.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            method, path,
            body=None if body is None else json.dumps(body),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def get_json(port: int, path: str) -> dict:
    status, data = request(port, "GET", path)
    if status != 200:
        raise SmokeFailure(f"GET {path} -> {status}: {data[:200]!r}")
    return json.loads(data)


def stream_chat(port: int, body: dict) -> dict:
    """One streaming chat completion; returns what the stream carried."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=REQUEST_S)
    got = {"status": None, "done": False, "finish_reason": None,
           "completion_tokens": None, "events": 0}
    try:
        conn.request("POST", "/v1/chat/completions", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        got["status"] = resp.status
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            payload = line[len("data:"):].strip()
            if payload == "[DONE]":
                got["done"] = True
                break
            event = json.loads(payload)
            got["events"] += 1
            if "error" in event:
                got["error"] = event["error"]
            for choice in event.get("choices", []):
                if choice.get("finish_reason"):
                    got["finish_reason"] = choice["finish_reason"]
            if event.get("usage"):
                got["completion_tokens"] = (
                    event["usage"]["completion_tokens"]
                )
    finally:
        conn.close()
    return got


def metric_by(metrics_text: str, name: str, label: str) -> dict:
    """One Prometheus family's samples, keyed by the value of `label`."""
    out: dict = {}
    for line in metrics_text.splitlines():
        if line.startswith(name) and line[len(name):][:1] in ("{", " "):
            key = line.partition(f'{label}="')[2].partition('"')[0]
            out[key] = out.get(key, 0.0) + float(line.rsplit(" ", 1)[1])
    return out


def metric_sum(metrics_text: str, name: str) -> float:
    """Sum of a Prometheus family's samples over all label sets."""
    samples = metric_by(metrics_text, name, "model_name")
    if not samples:
        raise SmokeFailure(f"/metrics has no {name}")
    return sum(samples.values())


# -- children ----------------------------------------------------------------
class Child:
    def __init__(self, name: str, argv: list[str], env: dict):
        self.name = name
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, *argv], cwd=HERE, env=env,
            stdout=self._log, stderr=subprocess.STDOUT,
        )

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def log_tail(self, n: int = 60) -> str:
        return "".join(self.log_text().splitlines(keepends=True)[-n:])

    def wait_http(self, port: int, path: str, deadline_s: float,
                  ready=lambda status, data: status == 200) -> None:
        """Poll until `ready`; fails as soon as the child has exited."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            rc = self.proc.poll()
            if rc is not None:
                raise SmokeFailure(
                    f"{self.name} exited with code {rc} before it "
                    "answered" + self._why_dead()
                )
            try:
                if ready(*request(port, "GET", path, timeout=5.0)):
                    return
            except OSError:
                pass
            time.sleep(0.5)
        raise SmokeFailure(
            f"{self.name} did not answer {path} within {deadline_s:.0f}s"
        )

    def _why_dead(self) -> str:
        log = self.log_text()
        if "Unable to initialize backend 'tpu'" in log:
            return (": NO ACCELERATOR — jax could not initialise the tpu "
                    "backend on this machine")
        if "No module named" in log:
            return ": the production_stack_tpu package is not beside " \
                   "chip_smoke.py"
        return ""

    def stop(self) -> int | None:
        """SIGTERM, bounded wait; SIGKILL only past the bound. Returns
        the exit code of a graceful stop, None if it had to be killed."""
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


# -- the run -----------------------------------------------------------------
def run(tp: int, children: list[Child]) -> dict:
    model, extra = CONFIGS[tp]
    engine_port, router_port = free_port(), free_port()
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache"
    )
    os.makedirs(LOG_DIR, exist_ok=True)

    # the chip-owning child: the device is named here, not inherited
    # (a sandbox exports JAX_PLATFORMS=cpu); JAX_COMPILATION_CACHE_DIR
    # passes through untouched when set
    engine_env = dict(os.environ, JAX_PLATFORMS="tpu", PYTHONUNBUFFERED="1")
    t_start = time.monotonic()
    engine = Child("engine", [
        "-m", "production_stack_tpu.engine", "--model", model,
        *ENGINE_FLAGS, *extra,
        "--host", "127.0.0.1", "--port", str(engine_port),
    ], engine_env)
    children.append(engine)
    # the router imports no jax; pinning it to cpu keeps it off the
    # chip even if that ever regresses
    router = Child("router", [
        "-m", "production_stack_tpu.router",
        "--host", "127.0.0.1", "--port", str(router_port),
        "--service-discovery", "static",
        "--static-backends", f"http://127.0.0.1:{engine_port}",
        "--static-models", model,
        "--routing-logic", "roundrobin",
    ], dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1"))
    children.append(router)

    print(f"engine: {model} tp={tp} (log {engine.log_path})", flush=True)
    engine.wait_http(engine_port, "/health", ENGINE_READY_S)
    engine_ready_s = time.monotonic() - t_start

    # what the engine runs on, asked of the engine itself
    ver = get_json(engine_port, "/version")
    check(ver["platform"] == "tpu", "platform is tpu", ver["platform"])
    check(ver["device_kind"] in KNOWN_DEVICE_KINDS, "device_kind known",
          ver["device_kind"])
    check(ver["device_count"] == tp, "device count",
          f"{ver['device_count']}")
    check(ver["attention_impl"] == "pallas" and ver["ragged_kernel"] is True,
          "pallas attention with the ragged kernel",
          f"{ver['attention_impl']}, ragged_kernel={ver['ragged_kernel']}")

    router.wait_http(
        router_port, "/v1/models", ROUTER_READY_S,
        ready=lambda status, data: status == 200 and model.encode() in data,
    )

    rng = random.Random(0)
    long_prompt = text_of(LONG_PROMPT_CHARS, rng)
    system_prompt = text_of(SYSTEM_PROMPT_CHARS, rng)
    received_tokens = 0

    def long_completion(label: str) -> dict:
        nonlocal received_tokens
        status, data = request(router_port, "POST", "/v1/completions", {
            "model": model, "prompt": long_prompt,
            "max_tokens": LONG_MAX_TOKENS, "temperature": 0,
            "ignore_eos": True, "logprobs": TOP_LOGPROBS,
        }, timeout=REQUEST_S)
        check(status == 200, f"({label}) status 200", data[:300].decode(
            errors="replace") if status != 200 else "")
        out = json.loads(data)
        choice = out["choices"][0]
        received_tokens += out["usage"]["completion_tokens"]
        check(out["usage"]["completion_tokens"] == LONG_MAX_TOKENS
              and choice["finish_reason"] == "length",
              f"({label}) {LONG_MAX_TOKENS} tokens, finish_reason length",
              f"prompt_tokens={out['usage']['prompt_tokens']}")
        lps = choice["logprobs"]["token_logprobs"]
        # greedy picks the arg-max, whose probability is >= 1/vocab
        check(len(lps) == LONG_MAX_TOKENS
              and all(isinstance(x, float) and math.isfinite(x)
                      and -20.0 < x <= 0.0 for x in lps),
              f"({label}) chosen-token logprobs finite and in range",
              f"min {min(lps):.3f} max {max(lps):.3f}")
        return {"text": choice["text"], "logprobs": lps,
                "candidates": [set(top) for top in
                               choice["logprobs"]["top_logprobs"]]}

    # (a) one long prompt: crosses the 512-token prefill chunk boundary
    print("traffic (a): one long prompt", flush=True)
    t0 = time.monotonic()
    first = long_completion("a")
    first_response_s = time.monotonic() - t0

    # (b) concurrent streaming chats over a shared system prompt: packed
    # prefill, mixed prefill+decode rounds, continuous batching, SSE
    print(f"traffic (b): {CHAT_STREAMS} concurrent streaming chats",
          flush=True)
    results: list = [None] * CHAT_STREAMS

    def one_chat(i: int) -> None:
        try:
            results[i] = stream_chat(router_port, {
                "model": model,
                "messages": [
                    {"role": "system", "content": system_prompt},
                    {"role": "user",
                     "content": f"question {i}: " + text_of(
                         40, random.Random(100 + i))},
                ],
                "max_tokens": CHAT_MAX_TOKENS, "temperature": 0,
                "ignore_eos": True, "stream": True,
                "stream_options": {"include_usage": True},
            })
        except Exception as e:  # noqa: BLE001 — reported by the check below
            results[i] = {"exception": repr(e)}

    threads = [threading.Thread(target=one_chat, args=(i,), daemon=True)
               for i in range(CHAT_STREAMS)]
    for i, t in enumerate(threads):
        if i == CHAT_STREAMS // 2:
            time.sleep(CHAT_STAGGER_S)
        t.start()
    for t in threads:
        t.join(timeout=REQUEST_S)
    for i, got in enumerate(results):
        ok = (got is not None and got.get("status") == 200 and got["done"]
              and got["finish_reason"] == "length"
              and got["completion_tokens"] == CHAT_MAX_TOKENS
              and "error" not in got)
        if not ok:
            raise SmokeFailure(f"(b) stream {i}: {got}")
        received_tokens += got["completion_tokens"]
    check(True, f"(b) {CHAT_STREAMS} streams: 200, {CHAT_MAX_TOKENS} "
          "tokens each, finish_reason length, [DONE]")

    # (c) request (a) again: served over the prefix cache, same answer
    print("traffic (c): the long prompt again", flush=True)
    _, before = request(engine_port, "GET", "/metrics")
    hits_before = metric_sum(before.decode(),
                             "vllm:gpu_prefix_cache_hits_total")
    again = long_completion("c")
    _, after = request(engine_port, "GET", "/metrics")
    metrics = after.decode()
    hits = metric_sum(metrics, "vllm:gpu_prefix_cache_hits_total")
    check(hits > hits_before, "(c) prefix-cache hit counter moved",
          f"{hits_before:.0f} -> {hits:.0f} tokens")
    worst = max(abs(x - y)
                for x, y in zip(first["logprobs"], again["logprobs"]))
    shared = min(len(x & y) for x, y in
                 zip(first["candidates"], again["candidates"]))
    check(again["text"] == first["text"] and worst <= LOGPROB_ATOL
          and shared >= MIN_SHARED_CANDIDATES,
          "(c) same answer as (a)",
          f"top-{TOP_LOGPROBS} candidates shared at every position >= "
          f"{shared}, max |dlogprob| {worst:.4f}")

    # counters agree with what the client received
    generated = metric_sum(metrics, "vllm:generation_tokens_total")
    check(generated == received_tokens,
          "generation-token counter equals tokens received",
          f"{generated:.0f} == {received_tokens}")
    compiles = metric_by(metrics, "tpu:compile_events_total", "kind")
    check(sum(compiles.values()) > 0, "tpu:compile_events_total > 0",
          "programs built: " + ", ".join(
              f"{k}={v:.0f}" for k, v in sorted(compiles.items())))

    ver = get_json(engine_port, "/version")
    in_use = ver["bytes_in_use"]
    check(len(in_use) == tp and all(b and b > 0 for b in in_use),
          "per-device bytes_in_use reported",
          ", ".join(f"{(b or 0) / 2**30:.2f} GiB" for b in in_use))
    if tp > 1:
        check((max(in_use) - min(in_use)) <= 0.10 * max(in_use),
              "per-device memory within 10% of each other")

    log = engine.log_text()
    check("Traceback" not in log, "engine log holds no traceback")
    check("falling back" not in log.lower(),
          "engine log holds no 'falling back'")
    n_cache = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    check(n_cache > 0, "compile cache directory is not empty",
          f"{n_cache} entries in {cache_dir}")

    # SIGTERM: the router first, then the chip owner
    for child in (router, engine):
        rc = child.stop()
        check(rc == 0, f"{child.name} exits 0 after SIGTERM",
              "killed after the bound" if rc is None else f"exit code {rc}")

    print(f"set-up: engine ready in {engine_ready_s:.1f} s, first "
          f"response in {first_response_s:.1f} s (compilation included; "
          "not a serving latency)", flush=True)
    return {"platform": ver["platform"], "kind": ver["device_kind"],
            "count": ver["device_count"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1, choices=sorted(CONFIGS),
                    help="chips: 1 serves llama-3.2-3b, 4 serves mistral-7b")
    args = ap.parse_args()

    def on_alarm(signum, frame):  # noqa: ARG001
        raise SmokeFailure(f"deadline: not done within {DEADLINE_S}s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(DEADLINE_S)
    children: list[Child] = []
    try:
        device = run(args.tp, children)
    except Exception as e:  # noqa: BLE001 — every failure ends the same way
        if not isinstance(e, SmokeFailure):
            traceback.print_exc()
        print(f"chip_smoke FAILED: {e!r}", file=sys.stderr)
        for child in children:
            print(f"--- tail of {child.log_path} ---\n"
                  f"{child.log_tail()}", file=sys.stderr)
        return 1
    finally:
        signal.alarm(0)
        for child in reversed(children):
            child.stop()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
