"""Engine configuration."""

from __future__ import annotations

from dataclasses import dataclass, field

from production_stack_tpu.models.config import ModelConfig, get_model_config


@dataclass
class EngineConfig:
    model: str = "pst-tiny-debug"
    tokenizer: str | None = None  # defaults to model path; "byte" for tests
    # optional Jinja chat-template override (string or file path) applied
    # over whatever the tokenizer ships (reference: helm chatTemplate)
    chat_template: str | None = None
    dtype: str = "bfloat16"
    cache_dtype: str = "bfloat16"
    seed: int = 0

    # KV cache sizing: explicit block count, or fraction of HBM after weights
    block_size: int = 32
    num_kv_blocks: int | None = None
    hbm_utilization: float = 0.9

    # scheduling
    # vLLM --scheduling-policy: "fcfs" (arrival order) or "priority"
    # (requests carry an integer `priority`; lower = served first,
    # preemption evicts the LOWEST-priority victim)
    scheduling_policy: str = "fcfs"
    max_model_len: int | None = None  # None -> model's max
    max_num_seqs: int = 8
    max_prefill_chunk: int = 512
    enable_chunked_prefill: bool = True
    # cross-sequence prefill packing: up to this many sequences' prompt
    # chunks run in ONE dispatch (N concurrent arrivals cost ~1 program
    # instead of N — burst TTFT). 1 = round-2 behavior. Group size is
    # bucketed to powers of two, so the jit compile space grows by
    # log2(max_prefill_seqs) variants.
    max_prefill_seqs: int = 8
    enable_prefix_caching: bool = True
    # max consecutive prefill chunks while decodes wait (bounded ITL);
    # 0 = prefill always wins (round-1 behavior)
    decode_interleave: int = 1
    # fused decode iterations per dispatch (vLLM --num-scheduler-steps):
    # sampling (incl. presence/frequency/repetition penalties, whose
    # token counts ride on device through the scan) runs on device and K
    # tokens come back in ONE host fetch, amortising the dispatch/fetch
    # RTT. Must be <= block_size. Every round has this one size (one
    # decode program and one lane-typed program a context bucket): a
    # lane out of budget freezes and a round whose lanes all ended
    # exits early (device_stop), so a short tail costs what it runs.
    num_scheduler_steps: int = 1
    # device-side stop masks: EOS, the
    # request's stop_token_ids, and a remaining-max_tokens countdown
    # are evaluated INSIDE the fused K-step scan. A lane that finishes
    # mid-round freezes (sampled slot pinned to the pad token, KV-slot
    # writes redirected to the trash slot, penalty/guided state
    # updates masked) and the dispatch returns per-lane valid counts,
    # so the host applies exactly the generated tokens instead of
    # discarding overshoot after the fetch; a round whose lanes all
    # finish exits early (lax.while_loop). False (--no-device-stop)
    # keeps the fixed-trip scan, the reference the bit-identity tests
    # compare against (either side's cost on an attached chip: not
    # measured). Host-side stop STRINGS still resolve on the host (text
    # matching cannot run on device).
    # Multihost engines ignore this (the broadcast wire ships host
    # token lists, not stop matrices).
    device_stop: bool = True
    # speculative h2d prefetch: while a fused decode round executes,
    # upload the NEXT round's packed host inputs (positions/ctx/keys
    # advanced by K on the same lanes) and dispatch it chained on the
    # on-device sampled tokens when the prediction holds. Removes the
    # pack and the host->device transfer from the steady-state round's
    # critical path, and lets the staged round start when the fetch
    # returns, before the fetched tokens are applied
    # (LLMEngine._starts_at_fetch): on one v5e 59-61% of the dense
    # chat cells' decode rounds start so, and laguna's tpot_mean_ms
    # went 5.03 -> 4.77 (ledger, PR 48). At most ONE round is in
    # flight. Requires num_scheduler_steps > 1; single-device; off
    # multihost.
    prefetch_decode: bool = True
    # pipelined prefill: (1) every prefill dispatch ships ONE packed i32
    # host->device buffer (tokens/positions/write slots/tables/sampling
    # args fused, mirroring the decode pack) instead of ~8 small
    # transfers (cost of either on an attached chip: not measured);
    # (2) cold multi-chunk prompts chain their chunks back-to-back
    # without a host round-trip in between while nothing is
    # decode-ready or waiting (only the final chunk's sampled token is
    # fetched; chunk N+1's buffer is built and uploaded while chunk N
    # computes). Outputs are bit-identical to the serial path
    # (tests/test_prefill_pipeline.py). False = the
    # per-array upload path (--no-prefill-pipeline): what a multihost
    # engine's staging takes, and the tests' reference.
    prefill_pipeline: bool = True
    # unified ragged prefill+decode dispatch (Ragged Paged Attention
    # role, PAPERS.md): when a round has BOTH mid-prefill runners and
    # decode-ready lanes, the scheduler plans ONE lane-typed round
    # (scheduler.plan_ragged_round) and the engine dispatches ONE
    # device program (model_runner.ragged_dispatch) whose packed h2d
    # buffer carries prefill-chunk lanes and fused decode lanes
    # together — the prefill/decode interleave throttle dissolves, a
    # waiting prompt's chunk runs in the very next round, and the
    # decode half keeps the device stop masks + staged h2d prefetch.
    # Tokens are bit-identical to the split path
    # (tests/test_ragged_dispatch.py).
    # False (--no-ragged-dispatch) keeps the split alternating rounds:
    # the path multihost and meshed (tp/pp) engines always take, and
    # the tests' reference.
    ragged_dispatch: bool = True
    # compile every steady-state serving program shape at startup
    # (full-chunk + resume-tail prefill, packed groups, fused-K decode,
    # per ctx bucket) so no XLA compile lands inside a live request's
    # TTFT/ITL. Costs minutes of startup the FIRST time; the persistent
    # compile cache (JAX_COMPILATION_CACHE_DIR) makes later restarts
    # cheap. Multihost: broadcast so follower hosts compile ahead too.
    precompile_serving: bool = False
    # speculative decoding (vLLM --speculative-config ngram role):
    # propose up to this many draft tokens by prompt-lookup (the last
    # n-gram's previous continuation in the context) and verify them in
    # ONE prefill-shaped forward — each fully-accepted verify replaces
    # up to K sequential decode dispatches. Greedy-only (temperature 0,
    # no penalties/logprobs) and engages at decode batch 1, where the
    # per-step RTT dominates; everything else falls back to the normal
    # decode path with identical outputs. 0 = off.
    num_speculative_tokens: int = 0
    ngram_prompt_lookup_max: int = 3
    ngram_prompt_lookup_min: int = 1

    # long-context serving (context-parallel ring prefill,
    # engine/long_prefill.py): a prompt whose UNCACHED remainder
    # exceeds this many tokens leaves the chunked-prefill lane and runs
    # as sp-sharded ring chunks on a ("tp", "sp") mesh
    # (parallel/long_context.py), its layer-stacked KV landing in the
    # paged cache through the PR 4 donated-import primitives — decode
    # afterwards is the normal paged path, tokens bit-identical to a
    # chunked-prefill control (tests/test_long_context_serving.py).
    # The long lane never blocks ragged/decode rounds for other users:
    # one enqueue-only chunk dispatch (plus at most one landed block
    # batch) per engine step. None = off. Requires
    # context_parallel_size > 1; single-process engines only (multihost
    # and pipeline-parallel engines always serve chunked).
    long_prefill_threshold: int | None = None
    # ring chunk length in tokens (rounded up to a multiple of the ring
    # size and the KV block size); the padded sequence ladder is
    # chunk x pow2, so program variants stay O(log max_model_len)
    long_prefill_chunk: int = 2048
    # sp mesh axis size for the ring (0/1 = no sp mesh). The ring uses
    # tensor_parallel_size x context_parallel_size devices, preferring
    # devices past the serving one(s) when the host has spares.
    context_parallel_size: int = 0

    # parallelism (tensor-parallel size over the ICI mesh)
    tensor_parallel_size: int = 1
    # pipeline parallelism: layers (and their KV) shard over a pp mesh
    # axis; every engine step is one SPMD program with ppermute stage
    # handoffs (parallel/pp_serving.py; the reference's ray-cluster
    # pipelineParallelSize capability). Composes with tp: pp x tp chips.
    pipeline_parallel_size: int = 1
    # one engine spanning the hosts of a multi-host slice (jax.distributed
    # SPMD; host 0 schedules + serves HTTP, followers replay its steps)
    multihost: bool = False

    # serving
    served_model_name: str | None = None
    enable_lora: bool = False
    max_loras: int = 4
    max_lora_rank: int = 16
    # OpenAI tool calling (engine/tools.py; vLLM flag names, reference
    # tutorial 13): auto tool choice requires the explicit opt-in
    enable_auto_tool_choice: bool = False
    tool_call_parser: str = "hermes"
    # require `Authorization: Bearer <key>` on /v1/* (vLLM --api-key)
    api_key: str | None = None

    # attention implementation: "auto" | "xla" | "pallas"
    attention_impl: str = "auto"

    # disaggregated prefill/decode role: None (undeclared) | "prefill"
    # | "decode" | "both". Prefill/both engines serve KV chains over
    # kv_transfer_config["listen"] (kv/transfer.py); decode/both engines
    # pull through a PeerTier at kv_transfer_config["peer"] (comma list
    # of peer addresses — a prefill engine or a cache server, address-
    # interchangeably). The role is advertised on the /v1/models card so
    # the router's `pd` policy can split the fleet.
    kv_role: str | None = None
    kv_transfer_config: dict = field(default_factory=dict)

    def pd_role(self) -> str | None:
        """Resolved PD role for discovery: the explicit kv_role, else
        inferred from the transfer config ('both' when an engine both
        serves and pulls), else None (not PD-configured)."""
        if self.kv_role in ("prefill", "decode", "both"):
            return self.kv_role
        cfg = self.kv_transfer_config or {}
        listen, peer = cfg.get("listen"), cfg.get("peer")
        if listen and peer:
            return "both"
        if listen:
            return "prefill"
        if peer:
            return "decode"
        return None

    # -- observability ------------------------------------------------
    # per-request lifecycle timeline (tracing/timeline.py): enqueue ->
    # admit -> prefill chunks -> first token -> sampled decode rounds ->
    # preempt/resume -> finish, served by /debug/requests and exported
    # as `engine_request` spans. Recording is append-only host work off
    # the device-dispatch path; False makes every hook a single boolean
    # check.
    request_timeline: bool = True
    # finished timelines kept for /debug/requests (bounded ring)
    timeline_ring_size: int = 256
    # engine-side span export: "none" | "log" | "memory" | "otlp"
    # (OTLP/JSON-shaped payloads drained by a watched flush task)
    tracing_exporter: str = "none"

    # KV offload (LMCache-equivalent) tiers
    cpu_offload_bytes: int = 0
    disk_offload_dir: str | None = None
    remote_cache_url: str | None = None
    kv_controller_url: str | None = None
    kv_instance_id: str = "default-instance"
    # zero-stall KV tiering (PR 4): exports are deferred (freed blocks
    # pinned, d2h snapshot enqueued after the step's dispatch, tier IO
    # on the offload worker) and restores are staged (tier fetch + h2d
    # start while the request WAITS; admission lands once the restore
    # does, in-place donated cache update). True restores the pre-PR-4
    # synchronous path — device-sync export inside scheduling, blocking
    # tier reads + whole-cache-copy import on the step loop
    # (--sync-kv-offload): the path multihost engines always take (the
    # broadcast wire ships host arrays, not device buffers) and the
    # tests' reference.
    sync_kv_offload: bool = False
    # staged-restore admission budget: how long an admission slot may be
    # held back while the request's tier fetch + h2d staging are in
    # flight, before falling back to recompute-from-scratch. Bounds the
    # damage of a wedged tier (dead remote, slow disk) to one budget per
    # request.
    kv_restore_wait_s: float = 2.0

    def __post_init__(self) -> None:
        if self.long_prefill_threshold is not None:
            if self.long_prefill_threshold <= 0:
                raise ValueError(
                    "long_prefill_threshold must be positive (None "
                    "disables the long-prefill lane)"
                )
            if self.context_parallel_size <= 1:
                raise ValueError(
                    "long_prefill_threshold requires "
                    "context_parallel_size > 1 (the ring needs an sp "
                    "mesh axis)"
                )
        if self.scheduling_policy not in ("fcfs", "priority"):
            raise ValueError(
                "scheduling_policy must be 'fcfs' or 'priority'"
            )
        if self.kv_role not in (None, "prefill", "decode", "both"):
            raise ValueError(
                "kv_role must be one of None/'prefill'/'decode'/'both',"
                f" got {self.kv_role!r}"
            )
        # n=0 would make the prompt-lookup window match every position
        # (arr[-0:] is the whole context), degenerating drafts to noise.
        if self.num_speculative_tokens:
            if not (
                1
                <= self.ngram_prompt_lookup_min
                <= self.ngram_prompt_lookup_max
            ):
                raise ValueError(
                    "require 1 <= ngram_prompt_lookup_min <= "
                    f"ngram_prompt_lookup_max, got min="
                    f"{self.ngram_prompt_lookup_min} max="
                    f"{self.ngram_prompt_lookup_max}"
                )

    def model_config(self) -> ModelConfig:
        return get_model_config(self.model)

    def resolved_max_model_len(self) -> int:
        mc = self.model_config()
        if self.max_model_len is None:
            return mc.max_model_len
        return min(self.max_model_len, mc.max_model_len)
