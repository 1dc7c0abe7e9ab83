"""Engine step outputs returned to the serving layer."""

from __future__ import annotations

from dataclasses import dataclass, field

from production_stack_tpu.engine.sequence import RequestMetrics


@dataclass
class RequestOutput:
    request_id: str
    # the prompt and all output tokens so far. Read, never written: on
    # an unfinished output both may be the lists the running sequence
    # itself holds (token_ids then grows with it); the finished output
    # has token_ids to itself
    prompt_token_ids: list[int]
    token_ids: list[int]
    new_token_ids: list[int]  # tokens produced this step
    text: str  # full output text so far
    delta_text: str  # text produced this step
    finished: bool
    finish_reason: str | None
    metrics: RequestMetrics
    num_cached_tokens: int = 0
    # time.perf_counter() reading that closed the step thread's fetch
    # of the round this output came from, written by AsyncLLMEngine.
    # _deliver; 0.0 where no round was fetched (an abort, an engine
    # driven without the server) — tpu:token_delivery_seconds
    t_fetched: float = 0.0
    # per-token logprob entries (only when SamplingParams.logprobs set):
    # {"token_id", "logprob", "top_logprobs": [{"token_id", "logprob"}]}
    logprobs: list[dict] | None = None  # all tokens so far
    new_logprobs: list[dict] | None = None  # this step (streaming)
    # vLLM prompt_logprobs role: one entry per prompt position (None
    # first), populated on the FINAL output only
    prompt_logprobs: list[dict | None] | None = None


@dataclass
class EngineStatsSnapshot:
    """Feeds the Prometheus /metrics contract the router scrapes
    (reference: src/vllm_router/stats/engine_stats.py:63-76)."""

    num_running: int = 0
    num_waiting: int = 0
    kv_usage: float = 0.0  # -> vllm:gpu_cache_usage_perc
    prefix_cache_queries: int = 0  # -> vllm:gpu_prefix_cache_queries_total
    prefix_cache_hits: int = 0  # -> vllm:gpu_prefix_cache_hits_total
    # hash_block calls on the blocks of queried prompts: over
    # prefix_cache_queries / block_size, how often a prompt block was
    # hashed (at most once)
    # -> tpu:prefix_blocks_hashed_total
    prefix_blocks_hashed_total: int = 0
    prompt_tokens_total: int = 0
    generation_tokens_total: int = 0
    num_preemptions_total: int = 0
    requests_finished_total: int = 0
    # speculative decoding acceptance (vllm:spec_decode_* role)
    spec_draft_tokens_total: int = 0
    spec_accepted_tokens_total: int = 0
    # the round seen from inside (tracing/phases.py), each a (seconds,
    # count) pair: the step thread's phases (tracing.ENGINE_PHASES) —
    # tpu:engine_phase_*_seconds in /metrics; of the host-work ones
    # (tracing.HOST_PHASES) the seconds the thread did not run —
    # tpu:engine_phase_*_offcpu_seconds; and the event-loop thread's
    # side (tracing.LOOP_PHASES, filled in by AsyncLLMEngine.stats
    # after the engine lock is let go): its waits for that lock by site
    # — tpu:event_loop_lock_wait_seconds, tpu:admit_lock_wait_seconds —
    # and a round's hand-over — tpu:deliver_pickup_seconds,
    # tpu:server_deliver_seconds, tpu:server_send_seconds,
    # tpu:token_delivery_seconds
    engine_phases: dict = field(default_factory=dict)
    engine_phases_offcpu: dict = field(default_factory=dict)
    loop_phases: dict = field(default_factory=dict)
    # (tokens, rounds): context tokens the attention calls of the
    # dispatched rounds had to read once — tpu:attn_context_tokens
    attn_context_tokens: tuple = (0, 0)
    # (context tokens the lanes attended, each lane's own count; those
    # of them a shared pass served: a run of pages the decode lanes of
    # a row block share, which the walk streams once) —
    # tpu:attn_lane_context_tokens, tpu:attn_shared_context_tokens
    attn_lane_tokens: tuple = (0, 0)
    # (lanes x fused steps of the dispatched rounds' decode rows, those
    # the host packed as zero-row segments: lanes holding no sequence)
    # — tpu:decode_lane_steps, tpu:decode_idle_lane_steps
    decode_lane_steps: tuple = (0, 0)
    # (evaluations of the sampler by the dispatched rounds, those whose
    # rows held a temperature > 0: the only ones that build the
    # candidate window) — tpu:sampler_steps, tpu:sampler_window_steps
    sampler_steps: tuple = (0, 0)
    # passes of the layer stack by the dispatched programs (their
    # forwards x ut_steps) — tpu:loop_passes; and a looped stack's exit
    # distribution under its gate, summed on the device over the
    # sampled rows, a float a pass (empty without a gate) —
    # tpu:loop_exit_mass{pass}
    loop_passes_total: int = 0
    loop_exit_mass: tuple = ()
    # -- a model of layer groups (models/layer_groups.py); all zero or
    # empty for a model of alike layers ------------------------------
    # context tokens a LAYER of each attention kind read, by the kind's
    # name ("full", or "window" for the kind with one):
    # tpu:attn_context_tokens_<kind>
    attn_context_by_kind: dict = field(default_factory=dict)
    # routed expert layers, summed on the device over layers and fused
    # steps: pairs routed, pairs whose expert is held here, local
    # experts with at least one row — tpu:moe_routed_rows,
    # tpu:moe_local_rows, tpu:moe_active_experts
    moe_stats: tuple = (0, 0, 0)
    # blocks some sequence references, per cache group —
    # tpu:kv_blocks_in_use{group}; window-group blocks let go behind a
    # window — tpu:kv_window_blocks_released; and (window-group blocks
    # in use, running sequences) summed over the dispatched rounds —
    # tpu:kv_window_blocks_per_seq; (blocks the admitted prompts'
    # prefix hits were cut back by because the window-group blocks at
    # their end were gone, admitted prompts whose prefix hit) —
    # tpu:prefix_window_cutback_blocks
    kv_blocks_in_use: dict = field(default_factory=dict)
    kv_window_blocks_released_total: int = 0
    kv_window_blocks_per_seq: tuple = (0, 0)
    prefix_window_cutback_blocks: tuple = (0, 0)
    # a model with recurrent state (state-space layers): state slots
    # that running sequences hold and snapshots resident in the pool
    # (gauges: tpu:ssm_state_slots_in_use, tpu:ssm_snapshots_resident);
    # snapshots saved, prefix hits restored from one, snapshots evicted,
    # hit tokens given up because no snapshot stood at or under the
    # hit's end, and one-token state updates of decode lanes x
    # state-space layers (counters: tpu:ssm_snapshot_saves, _restores,
    # _evictions, tpu:prefix_state_cutback_tokens,
    # tpu:ssm_lane_layer_steps), and the update kernel's calls
    # (tpu:state_update_calls). Empty for any other model
    ssm_stats: dict = field(default_factory=dict)
    # the stages of building a program, from jax's monitoring events of
    # this process: trace / lower / compile -> (seconds, count), and the
    # persistent compile cache's hits — tpu:program_*_seconds,
    # tpu:program_cache_hits
    program_stages: dict = field(default_factory=dict)
    program_cache_hits_total: int = 0
    # chunks a cold prompt's chain dispatched after a step's first —
    # tpu:prefill_chained_chunks in /metrics
    prefill_chained_chunks_total: int = 0
    # long-prefill lane (context-parallel ring prefill, engine/
    # long_prefill.py): requests served by the ring, ring chunks
    # dispatched, ring failures that fell back to chunked prefill, and
    # the per-phase TTFT attribution — ring compute, device->host KV
    # materialization, paged-cache landing, and tier-export overflow
    # seconds that ran while long jobs were in flight —
    # tpu:prefill_ring/d2h/land/overflow_* in /metrics
    long_prefill_requests_total: int = 0
    long_prefill_chunks_total: int = 0
    long_prefill_fallbacks_total: int = 0
    long_prefill_ring_seconds_total: float = 0.0
    long_prefill_d2h_seconds_total: float = 0.0
    long_prefill_land_seconds_total: float = 0.0
    long_prefill_overflow_seconds_total: float = 0.0
    # elastic fused decode: rounds dispatched, sampled-then-discarded
    # overshoot tokens (~0 with device stops, except host-resolved stop
    # strings), whole-round device early exits, and rounds dispatched
    # at the fetch's return, before the round before them was applied
    # — tpu:decode_* in /metrics
    decode_rounds_total: int = 0
    decode_early_dispatch_total: int = 0
    decode_overshoot_tokens_total: int = 0
    decode_early_exit_rounds_total: int = 0
    # unified ragged dispatch: fused lane-typed rounds, rounds a mixed
    # plan ran split (exotic lanes), and per-side lane totals —
    # tpu:ragged_* in /metrics
    ragged_rounds_total: int = 0
    ragged_split_rounds_total: int = 0
    ragged_prefill_lanes_total: int = 0
    ragged_decode_lanes_total: int = 0
    # compile-count observability: program-variant builds (jit cache
    # misses on the runner's step builders) since boot, total and per
    # builder kind — tpu:compile_events_total in /metrics. The
    # cold-start compile cost
    # (and the single-kernel variant-space shrink) read directly off
    # this instead of being inferred from compile logs.
    compile_events_total: int = 0
    # kind -> count, e.g. {"decode_multi": 3, "ragged_rows": 2}
    compile_events: dict = field(default_factory=dict)
    # zero-stall KV tiering attribution: deferred-export batches (wall
    # seconds measured ON THE OFFLOAD WORKER — overlapped activity, not
    # step-loop stalls) and staged restores (enqueue -> landed), plus
    # per-tier hit/miss/byte counters — tpu:kv_* in /metrics
    kv_export_seconds_total: float = 0.0
    kv_export_blocks_total: int = 0
    kv_export_bytes_total: int = 0
    kv_restore_seconds_total: float = 0.0
    kv_restore_blocks_total: int = 0
    kv_restore_bytes_total: int = 0
    kv_restore_fallbacks_total: int = 0
    # tier name -> {hits, misses, read_bytes, write_bytes}
    kv_tier_counters: dict = field(default_factory=dict)
    # disaggregated-prefill peer pulls (PeerTier): blocks served by /
    # missing from the PD peer, bytes pulled over the transfer link,
    # and failed pulls (dead peer, corrupt frame) — tpu:kv_peer_* in
    # /metrics
    kv_peer_hits_total: int = 0
    kv_peer_misses_total: int = 0
    kv_peer_read_bytes_total: int = 0
    kv_peer_fallbacks_total: int = 0
    # shared cache server (RemoteTier): blocks served by / missing from
    # the cluster-wide cache, bytes over the wire in each direction,
    # write-behind put_batch frames shipped, and failed flushes/pulls
    # (dead server) — tpu:kv_remote_* in /metrics
    kv_remote_hits_total: int = 0
    kv_remote_misses_total: int = 0
    kv_remote_read_bytes_total: int = 0
    kv_remote_write_bytes_total: int = 0
    kv_remote_flushes_total: int = 0
    kv_remote_fallbacks_total: int = 0

    @property
    def prefix_cache_hit_rate(self) -> float:
        if self.prefix_cache_queries == 0:
            return 0.0
        return self.prefix_cache_hits / self.prefix_cache_queries
