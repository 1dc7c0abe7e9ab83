"""CLI: `python -m production_stack_tpu.engine` — serve a model.

Flag names mirror `vllm serve` where the capability matches (the reference's
helm chart builds exactly these flags, reference:
helm/templates/deployment-vllm-multi.yaml:104-181), so existing deployment
configs translate mechanically.
"""

from __future__ import annotations

import argparse
import os

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.server import EngineServer
from production_stack_tpu.utils.compile_cache import configure_compile_cache


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pst-engine", description="TPU-native LLM serving engine"
    )
    p.add_argument("--model", default="pst-tiny-debug",
                   help="preset name or local HF checkpoint dir")
    p.add_argument("--tokenizer", default=None,
                   help="tokenizer dir, or 'byte' for the hermetic tokenizer")
    p.add_argument("--served-model-name", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--kv-cache-dtype", default="bfloat16")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--block-size", type=int, default=32)
    p.add_argument("--num-kv-blocks", type=int, default=None)
    p.add_argument("--gpu-memory-utilization", "--hbm-utilization",
                   dest="hbm_utilization", type=float, default=0.9)
    p.add_argument("--max-model-len", type=int, default=None)
    p.add_argument("--max-num-seqs", type=int, default=8)
    p.add_argument("--max-num-batched-tokens", "--max-prefill-chunk",
                   dest="max_prefill_chunk", type=int, default=512)
    p.add_argument("--enable-chunked-prefill", action="store_true",
                   default=True)
    p.add_argument("--no-enable-chunked-prefill",
                   dest="enable_chunked_prefill", action="store_false")
    p.add_argument("--max-prefill-seqs", type=int, default=8,
                   help="cross-sequence prefill packing: chunks from up "
                        "to this many sequences share one dispatch "
                        "(1 = no packing)")
    p.add_argument("--scheduling-policy", default="fcfs",
                   choices=["fcfs", "priority"],
                   help="priority: requests carry an integer 'priority' "
                        "(lower = served first); preemption evicts the "
                        "lowest-priority victim")
    p.add_argument("--decode-interleave", type=int, default=1,
                   help="max consecutive prefill chunks while decodes "
                        "wait (0 = prefill always wins)")
    p.add_argument("--num-scheduler-steps", type=int, default=1,
                   help="fused decode+sample iterations per dispatch "
                        "(on-device sampling; amortises host RTT); "
                        "every round has this size")
    p.add_argument("--device-stop", action="store_true", default=True,
                   help="evaluate EOS/stop-token/max-token stops INSIDE "
                        "the fused decode scan: finished lanes freeze "
                        "mid-round, the host takes exactly the "
                        "generated tokens")
    p.add_argument("--no-device-stop", dest="device_stop",
                   action="store_false",
                   help="fixed-trip fused scan; overshoot discarded on "
                        "the host (the tests' reference)")
    p.add_argument("--no-adaptive-decode-k", action="store_true",
                   help="parsed and ignored: a fused round is always "
                        "--num-scheduler-steps long. Kept only while "
                        "the benchmark's configurations pass it "
                        "(ROADMAP Queue 3)")
    p.add_argument("--num-speculative-tokens", type=int, default=0,
                   help="ngram prompt-lookup speculative decoding: "
                        "draft up to this many tokens and verify them "
                        "in one forward (greedy batch-1 decode; 0=off)")
    p.add_argument("--ngram-prompt-lookup-max", type=int, default=3)
    p.add_argument("--ngram-prompt-lookup-min", type=int, default=1)
    p.add_argument("--prefetch-decode", action="store_true", default=True,
                   help="speculative h2d prefetch: upload the next fused "
                        "round's inputs while the current one executes")
    p.add_argument("--no-prefetch-decode", dest="prefetch_decode",
                   action="store_false")
    p.add_argument("--prefill-pipeline", action="store_true",
                   default=True,
                   help="pipelined prefill: one fused h2d buffer per "
                        "prefill dispatch, chunk N+1 staged while chunk "
                        "N computes, cold multi-chunk prompts chained "
                        "without host round-trips")
    p.add_argument("--no-prefill-pipeline", dest="prefill_pipeline",
                   action="store_false",
                   help="serial per-array prefill uploads (what "
                        "multihost staging takes; the tests' reference)")
    p.add_argument("--ragged-dispatch", action="store_true",
                   default=True,
                   help="unified ragged prefill+decode rounds: when "
                        "prefill chunks and decode lanes are both "
                        "ready, dispatch them as ONE lane-typed device "
                        "program — no prefill/decode interleave wait")
    p.add_argument("--no-ragged-dispatch", dest="ragged_dispatch",
                   action="store_false",
                   help="split alternating prefill/decode rounds (what "
                        "multihost and meshed engines always run; the "
                        "tests' reference)")
    p.add_argument("--precompile-serving", action="store_true",
                   default=False,
                   help="compile every steady-state prefill/decode "
                        "program shape at startup so no XLA compile "
                        "lands inside a live request (minutes of "
                        "startup the first time; cheap on restart with "
                        "JAX_COMPILATION_CACHE_DIR)")
    p.add_argument("--enable-prefix-caching", action="store_true",
                   default=True)
    p.add_argument("--no-enable-prefix-caching",
                   dest="enable_prefix_caching", action="store_false")
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--pipeline-parallel-size", type=int, default=1)
    p.add_argument("--context-parallel-size", type=int, default=0,
                   help="sp mesh axis for the long-prefill ring "
                   "(tp x sp devices; 0 = no ring)")
    p.add_argument("--long-prefill-threshold", type=int, default=None,
                   help="prompts whose uncached remainder exceeds this "
                   "many tokens run as context-parallel ring prefill "
                   "(requires --context-parallel-size > 1)")
    p.add_argument("--long-prefill-chunk", type=int, default=2048,
                   help="ring prefill chunk length in tokens")
    p.add_argument("--enable-lora", action="store_true")
    p.add_argument("--max-loras", type=int, default=4)
    p.add_argument("--enable-sleep-mode", action="store_true",
                   help="advertise sleep/wake support (endpoints always on)")
    p.add_argument("--enable-auto-tool-choice", action="store_true",
                   help="honor OpenAI `tools` with tool_choice=auto "
                        "(engine/tools.py)")
    p.add_argument("--tool-call-parser", default="hermes",
                   choices=["hermes"],
                   help="tool-call output format to parse")
    p.add_argument("--api-key", default=os.environ.get("PST_API_KEY"),
                   help="require `Authorization: Bearer <key>` on /v1/* "
                        "(default: $PST_API_KEY, so k8s can mount the key "
                        "as a Secret env instead of exposing it on argv)")
    p.add_argument("--chat-template", default=None,
                   help="Jinja chat-template override: a template string "
                        "or a path to a template file")
    p.add_argument("--attention-impl", default="auto",
                   choices=["auto", "xla", "pallas"])
    # observability: per-request lifecycle timelines + span export
    p.add_argument("--request-timeline", action="store_true",
                   default=True,
                   help="record per-request lifecycle timelines "
                        "(enqueue/admit/prefill-chunks/first-token/"
                        "decode-rounds/preempt/finish) served by "
                        "/debug/requests")
    p.add_argument("--no-request-timeline", dest="request_timeline",
                   action="store_false",
                   help="disable timeline recording (every hook "
                        "degrades to one boolean check)")
    p.add_argument("--timeline-ring-size", type=int, default=256,
                   help="finished timelines kept for /debug/requests")
    p.add_argument("--tracing-exporter", default="none",
                   choices=["none", "log", "memory", "otlp"],
                   help="engine-side span export: one engine_request "
                        "span per request (child of the router span "
                        "via the propagated traceparent header)")
    # disaggregated prefill / KV transfer
    p.add_argument("--kv-role", default=None,
                   choices=[None, "prefill", "decode", "both",
                            "kv_producer", "kv_consumer"],
                   help="disaggregated prefill/decode role (advertised "
                        "to the router's `pd` policy via /v1/models; "
                        "kv_producer/kv_consumer are vLLM-flag-compat "
                        "aliases for prefill/decode)")
    p.add_argument("--kv-transfer-listen", default=None,
                   help="host:port to serve KV block chains on "
                        "(prefill/both roles)")
    p.add_argument("--kv-peer", default=None,
                   help="comma list of peer addresses to pull KV from "
                        "(decode/both roles): prefill engines' "
                        "--kv-transfer-listen addresses or a "
                        "kv.cache_server, address-interchangeably")
    # KV offload (LMCache-equivalent)
    p.add_argument("--max-lora-rank", type=int, default=16)
    p.add_argument("--cpu-offload-gb", type=float, default=0.0)
    p.add_argument("--disk-offload-dir", default=None)
    p.add_argument("--remote-cache-url", default=None)
    p.add_argument("--kv-controller-url", default=None)
    p.add_argument("--kv-instance-id", default="default-instance")
    p.add_argument("--sync-kv-offload", action="store_true",
                   default=False,
                   help="pre-PR-4 synchronous KV tier traffic: d2h "
                        "export inside scheduling and blocking tier "
                        "reads + whole-cache-copy import on the step "
                        "loop (what multihost engines always run; "
                        "the default is the zero-stall async "
                        "export/staged-restore path)")
    p.add_argument("--kv-restore-wait-s", type=float, default=2.0,
                   help="staged-restore admission budget: max seconds a "
                        "waiting request may hold its admission slot "
                        "while its KV tier fetch + h2d staging are in "
                        "flight before recomputing from scratch")
    p.add_argument("--multihost", action="store_true",
                   help="one engine spanning a multi-host slice: host 0 "
                        "schedules + serves HTTP, other hosts replay its "
                        "steps (jax.distributed SPMD)")
    p.add_argument("--coordinator-address", default=None,
                   help="host0:port for jax.distributed (defaults to "
                        "COORDINATOR_ADDRESS env / TPU metadata)")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    return p


def config_from_args(args: argparse.Namespace) -> EngineConfig:
    # vLLM-flag-compat aliases; prefill/decode/both pass through
    role = {
        "kv_producer": "prefill", "kv_consumer": "decode",
    }.get(args.kv_role, args.kv_role)
    return EngineConfig(
        model=args.model,
        tokenizer=args.tokenizer,
        chat_template=args.chat_template,
        dtype=args.dtype,
        cache_dtype=args.kv_cache_dtype,
        seed=args.seed,
        block_size=args.block_size,
        num_kv_blocks=args.num_kv_blocks,
        hbm_utilization=args.hbm_utilization,
        max_model_len=args.max_model_len,
        max_num_seqs=args.max_num_seqs,
        scheduling_policy=args.scheduling_policy,
        max_prefill_chunk=args.max_prefill_chunk,
        enable_chunked_prefill=args.enable_chunked_prefill,
        max_prefill_seqs=args.max_prefill_seqs,
        decode_interleave=args.decode_interleave,
        num_scheduler_steps=args.num_scheduler_steps,
        device_stop=args.device_stop,
        precompile_serving=args.precompile_serving,
        prefetch_decode=args.prefetch_decode,
        prefill_pipeline=args.prefill_pipeline,
        ragged_dispatch=args.ragged_dispatch,
        num_speculative_tokens=args.num_speculative_tokens,
        ngram_prompt_lookup_max=args.ngram_prompt_lookup_max,
        ngram_prompt_lookup_min=args.ngram_prompt_lookup_min,
        enable_prefix_caching=args.enable_prefix_caching,
        tensor_parallel_size=args.tensor_parallel_size,
        pipeline_parallel_size=args.pipeline_parallel_size,
        context_parallel_size=args.context_parallel_size,
        long_prefill_threshold=args.long_prefill_threshold,
        long_prefill_chunk=args.long_prefill_chunk,
        multihost=args.multihost,
        served_model_name=args.served_model_name,
        enable_lora=args.enable_lora,
        max_loras=args.max_loras,
        max_lora_rank=args.max_lora_rank,
        enable_auto_tool_choice=args.enable_auto_tool_choice,
        tool_call_parser=args.tool_call_parser,
        api_key=args.api_key,
        attention_impl=args.attention_impl,
        request_timeline=args.request_timeline,
        timeline_ring_size=args.timeline_ring_size,
        tracing_exporter=args.tracing_exporter,
        kv_role=role,
        kv_transfer_config={
            "listen": args.kv_transfer_listen,
            "peer": args.kv_peer,
        },
        cpu_offload_bytes=int(args.cpu_offload_gb * 2**30),
        disk_offload_dir=args.disk_offload_dir,
        remote_cache_url=args.remote_cache_url,
        kv_controller_url=args.kv_controller_url,
        kv_instance_id=args.kv_instance_id,
        sync_kv_offload=args.sync_kv_offload,
        kv_restore_wait_s=args.kv_restore_wait_s,
    )


def require_accelerator() -> None:
    """Refuse the CPU backend unless `JAX_PLATFORMS` names it.

    With `JAX_PLATFORMS` unset jax drops to the CPU when libtpu fails to
    initialise, and a 3B model would "serve" from host memory. The CPU
    is a supported backend only where it was asked for (tests, local
    servers: `JAX_PLATFORMS=cpu`)."""
    import jax

    asked = (jax.config.jax_platforms or "").lower().split(",")
    if jax.default_backend() == "cpu" and "cpu" not in asked:
        raise SystemExit(
            "no accelerator: jax initialised the CPU backend although "
            f"JAX_PLATFORMS={jax.config.jax_platforms!r} does not name "
            "it. Fix the TPU runtime, or set JAX_PLATFORMS=cpu to serve "
            "from the CPU on purpose."
        )


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    configure_compile_cache()
    if args.kv_instance_id == "default-instance":
        # by convention the instance id is host:port so kvaware routing can
        # map controller matches back to endpoint urls (routing_logic.py);
        # 0.0.0.0 never appears in an endpoint url, so resolve a real
        # address for the id
        host = args.host
        if host in ("0.0.0.0", "::", ""):
            import socket

            try:
                host = socket.gethostbyname(socket.gethostname())
            except OSError:
                host = "127.0.0.1"
        args.kv_instance_id = f"{host}:{args.port}"
    follower = False
    if args.multihost:
        # must run before anything touches a device (jax.distributed)
        from production_stack_tpu.parallel import multihost

        multihost.initialize(
            coordinator_address=args.coordinator_address,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
        follower = multihost.process_index() != 0
    require_accelerator()
    if follower:
        # follower host: no HTTP server, replay host 0's device steps
        from production_stack_tpu.engine.model_runner import ModelRunner
        from production_stack_tpu.engine.multihost_engine import (
            follower_loop,
            validate_multihost_config,
        )

        cfg = config_from_args(args)
        validate_multihost_config(cfg)
        follower_loop(ModelRunner(cfg))
        return
    server = EngineServer(config_from_args(args))
    server.run(host=args.host, port=args.port)


if __name__ == "__main__":
    main()
