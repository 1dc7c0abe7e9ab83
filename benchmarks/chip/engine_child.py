#!/usr/bin/env python3
"""The chip-owning child of the benchmark: one engine replica.

The only place where the benchmark touches the program's Python objects.
It builds the SAME `EngineServer` that `python -m production_stack_tpu.
engine` builds, from the same argument parser, and differs from that
command in four things a depth-cut model with seeded weights needs and
the CLI cannot give:

1. the model configuration comes from the benchmark's data file, through
   the program's own `from_hf_config`, registered as a preset. What of
   that file is written to the `config.json` is the family's to say
   (`hf_config`), less the keys every configuration has
   (`manifest.COMMON_KEYS`); whether the file and the resulting
   `ModelConfig` agree is the family's `check`, and the tiny widths of a
   rehearsal its `rehearsal_config`;
2. the weights are made on the device from `--seed` in ONE jitted call.
   Common, here: the `rbg` key from the seed, the one `jax.jit` with the
   engine's own tensor-parallel layout where there is a mesh, the wait
   for the arrays. The family's (`init_params`): the parameter tree,
   layer by layer, in the serving type, with every term non-zero that a
   dropped term should show in the reference check;
3. the tokenizer stand-in renders every token id as one reversible
   character (`ReversibleByteTokenizer`): with random weights the plain
   byte tokenizer renders nearly every id as "", so a stream would carry
   no chunk per token and a chat history would not re-encode to the ids
   that were generated — both of which any real tokenizer gives;
4. every program the cell's traffic can reach is compiled before the
   server listens, through the runner's own `precompile_*` entry points
   (`warm_programs`). Which programs those are is asked of the engine's
   own configuration and bucket functions, between the context at which
   the traffic's prefills start (`--context-floor-tokens`, the traffic
   file's shared prefix) and `--max-model-len`; no list of buckets is
   kept with the benchmark. The parent fails a run in whose window a
   program was built all the same (`run.py`).

A control thread (plain HTTP on a localhost port) serves the parent:
start/stop a `jax.profiler` trace and reduce it, evaluate the family's
plain reference on the engine's own parameter arrays, report device
memory. Only the process that holds the chip can do those.

Nothing here names a matrix of a parameter tree or branches on a field
of an architecture: the configuration's file names its family, and the
family is a file (`manifest.py` says what it gives).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import manifest  # noqa: E402

ID_BASE = 0x10000   # first code point past the surrogates and the BMP


def hf_config_of(config: dict, family) -> dict:
    """The `config.json` the program reads: what the family makes of the
    configuration's file, less the benchmark's own keys."""
    return {k: v for k, v in family.hf_config(config).items()
            if k not in manifest.COMMON_KEYS}


def make_tokenizer():
    from production_stack_tpu.engine.tokenizer import ByteTokenizer

    class ReversibleByteTokenizer(ByteTokenizer):
        """Byte tokenizer whose decode renders EVERY id as exactly one
        character (code point ID_BASE + id) and whose encode maps such a
        character back to its id; any other text encodes to its UTF-8
        bytes as before. So a stream carries one character per token,
        and generated text re-encodes to the ids that were generated."""

        def encode(self, text: str, add_bos: bool = True) -> list[int]:
            ids: list[int] = []
            for ch in text:
                o = ord(ch)
                if o >= ID_BASE:
                    ids.append(o - ID_BASE)
                else:
                    ids.extend(ch.encode("utf-8"))
            return ([self.BOS] + ids) if add_bos else ids

        def decode(self, token_ids: list[int]) -> str:
            return "".join(chr(ID_BASE + t) for t in token_ids)

    return ReversibleByteTokenizer()


def model_config(config: dict, family, name: str, rehearse: bool,
                 tp: int = 1):
    """The ModelConfig, by the program's own `from_hf_config`, checked
    by the family against the configuration's file."""
    from production_stack_tpu.models import config as mcfg

    hf = hf_config_of(config, family)
    tmp = tempfile.mkdtemp(prefix="chipbench-cfg-")
    try:
        with open(os.path.join(tmp, "config.json"), "w") as f:
            json.dump(hf, f)
        mc = mcfg.from_hf_config(tmp, name=name)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    family.check(config, mc)
    if rehearse:
        mc = family.rehearsal_config(mc, tp)
    return mcfg._register(mc)


def make_params(family, mc, seed: int, dtype, mesh):
    """All weights from the seed, on the device, under one jit."""
    import jax

    # the hardware generator: threefry would spend most of set-up here
    key = jax.random.fold_in(
        jax.random.key(seed % (1 << 31), impl="rbg"), seed >> 31)

    def init(k):
        return family.init_params(mc, k, dtype)

    kw = {}
    if mesh is not None:
        from production_stack_tpu.parallel import sharding

        kw["out_shardings"] = sharding.param_shardings(mesh, mc)
    params = jax.jit(init, **kw)(key)
    jax.block_until_ready(params)
    return params


def _ladder(bucket, top: int) -> list[int]:
    """Every value the runner's bucket function takes from 1 up to the
    bucket of `top` (as `LLMEngine.precompile_serving` walks them)."""
    out = [bucket(1)]
    while out[-1] < bucket(top):
        out.append(bucket(out[-1] + 1))
    return out


def warm_programs(engine, context_floor: int, rehearse: bool) -> int:
    """Compile (or read from the cache) every program the cell's traffic
    can reach, before the server listens: in each context bucket between
    the traffic's floor and `max_model_len`, each prefill chunk bucket
    alone and in packed groups, the fused decode round, and each of
    those prefill shapes beside a decode round. The buckets are the
    runner's own; nothing here knows their values."""
    rnr = engine.runner
    cfg = engine.config
    chunks = _ladder(rnr._prefill_bucket, cfg.max_prefill_chunk)
    ctxs = [c for c in _ladder(rnr._ctx_bucket, cfg.max_model_len)
            if c > context_floor]
    sizes = [s for s in _ladder(lambda n: 1 << (n - 1).bit_length(),
                                cfg.max_prefill_seqs) if s >= 2]
    if rehearse:
        # control flow only: two chunk buckets, pairs, the top context
        chunks, ctxs, sizes = [chunks[3], chunks[-1]], ctxs[-1:], [2]
    k = cfg.num_scheduler_steps
    rows_mode = rnr.ragged_kernel and rnr.prefill_pipeline
    n = 0
    for ctx in ctxs:
        if rows_mode:
            # packed programs key on the padded ROW count: two lanes of
            # each chunk bucket, then the full chunk at every group size
            groups = [(2, c, ctx) for c in chunks]
            groups += [(s, chunks[-1], ctx) for s in sizes if s > 2]
        else:
            groups = [(s, c, ctx) for s in sizes for c in chunks]
        n += rnr.precompile_prefill([(c, ctx) for c in chunks], groups)
        n += rnr.precompile_decode(
            [ctx - k + 1], k,
            chained=engine._async_decode or engine._prefetch_decode,
            stop=engine._device_stop)
        if engine._ragged_dispatch:
            for c in chunks:
                n += rnr.precompile_ragged(
                    [ctx - k + 1], [k], 1, c,
                    stop=engine._device_stop,
                    chained=engine._prefetch_decode)
            n += rnr.precompile_ragged(
                [ctx - k + 1], [k], max(sizes or [1]), chunks[-1],
                stop=engine._device_stop, chained=engine._prefetch_decode)
    return n


class Control:
    """What the parent may ask of the process that holds the chip."""

    def __init__(self, family, mc, params, out_dir: str):
        self.family, self.mc, self.params = family, mc, params
        self.out_dir = out_dir
        self.trace_dir: str | None = None

    def memory(self) -> dict:
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        return {
            "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
            "bytes_in_use": [s.get("bytes_in_use") for s in stats],
            "bytes_limit": [s.get("bytes_limit") for s in stats],
        }

    def trace_start(self) -> dict:
        import jax

        self.trace_dir = os.path.join(self.out_dir, "trace")
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        return {"started": time.monotonic()}

    def trace_stop(self) -> dict:
        import jax

        t0 = time.monotonic()
        jax.profiler.stop_trace()
        return {"stop_s": time.monotonic() - t0}

    def trace_reduce(self) -> dict:
        import trace_reduce

        t0 = time.monotonic()
        profile = trace_reduce.load(self.trace_dir)
        reduced = trace_reduce.reduce(profile)
        reduced["planes"] = [
            {"name": p.name, "lines": [ln.name for ln in p.lines]}
            for p in profile.planes]
        reduced["reduce_s"] = time.monotonic() - t0
        return reduced

    def reference(self, body: dict) -> dict:
        import reference

        t0 = time.monotonic()
        lps = reference.teacher_forced_logprobs(
            self.family, self.mc, self.params, body["prompt_ids"],
            body["generated_ids"])
        return {"logprobs": lps, "seconds": time.monotonic() - t0}


def serve_control(control: Control, port: int) -> ThreadingHTTPServer:
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):  # noqa: D102 - quiet
            pass

        def _reply(self, fn):
            try:
                out, code = fn(), 200
            except Exception as e:  # noqa: BLE001 - reported to the parent
                import traceback

                traceback.print_exc()
                out, code = {"error": repr(e)}, 500
            data = json.dumps(out).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):  # noqa: N802
            self._reply(control.memory)

        def do_POST(self):  # noqa: N802
            n = int(self.headers.get("Content-Length") or 0)
            body = json.loads(self.rfile.read(n) or b"{}")
            fn = {
                "/trace/start": control.trace_start,
                "/trace/stop": control.trace_stop,
                "/trace/reduce": control.trace_reduce,
                "/reference": lambda: control.reference(body),
            }.get(self.path)
            self._reply(fn if fn else lambda: {"error": "unknown path"})

    httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True,
                     name="bench-control").start()
    return httpd


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config-file", required=True)
    ap.add_argument("--config-name", required=True)
    ap.add_argument("--family-file", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--control-port", type=int, required=True)
    ap.add_argument("--out-dir", required=True)
    ap.add_argument("--context-floor-tokens", type=int, default=0,
                    help="no measured prefill chunk ends at or below "
                    "this context (the traffic's shared prefix)")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    t_start = time.monotonic()

    from production_stack_tpu.engine.__main__ import (
        build_parser, config_from_args, require_accelerator,
    )
    from production_stack_tpu.engine.server import EngineServer
    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    with open(a.config_file) as f:
        config = json.load(f)
    engine_args = list(config["engine_args"])
    if a.rehearse:
        engine_args += ["--dtype", "float32", "--kv-cache-dtype", "float32",
                        "--num-kv-blocks", "4096"]
    args = build_parser().parse_args([
        "--model", a.config_name, *engine_args,
        "--host", "127.0.0.1", "--port", str(a.port),
    ])
    configure_compile_cache()
    require_accelerator()
    import jax
    import jax.numpy as jnp

    if a.rehearse != (jax.default_backend() == "cpu"):
        raise SystemExit(
            f"backend {jax.default_backend()!r} with rehearse={a.rehearse}: "
            "a rehearsal runs on the CPU and nothing else does")
    family = manifest.load_family(a.family_file)
    mc = model_config(config, family, a.config_name, a.rehearse,
                      args.tensor_parallel_size)
    ecfg = config_from_args(args)
    mesh = None
    if ecfg.tensor_parallel_size > 1 or ecfg.pipeline_parallel_size > 1:
        from production_stack_tpu.parallel import sharding

        mesh = sharding.make_serving_mesh(
            ecfg.tensor_parallel_size, ecfg.pipeline_parallel_size)
    t0 = time.monotonic()
    params = make_params(family, mc, a.seed, jnp.dtype(ecfg.dtype), mesh)
    t_params = time.monotonic() - t0
    server = EngineServer(ecfg, params=params)
    engine = server.engine.engine
    engine.tokenizer = make_tokenizer()
    t0 = time.monotonic()
    n_warm = warm_programs(engine, a.context_floor_tokens, a.rehearse)
    print(json.dumps({
        "engine_child": a.config_name, "params_s": round(t_params, 3),
        "warm_dispatches": n_warm,
        "warm_s": round(time.monotonic() - t0, 3),
        "compile_events": dict(engine.runner.compile_events),
        "ready_s": round(time.monotonic() - t_start, 3),
        "num_kv_blocks": engine.runner.num_blocks,
    }), flush=True)
    os.makedirs(a.out_dir, exist_ok=True)
    httpd = serve_control(
        Control(family, mc, engine.runner.params, a.out_dir), a.control_port)
    try:
        server.run(host="127.0.0.1", port=a.port)
    finally:
        httpd.shutdown()


if __name__ == "__main__":
    main()
