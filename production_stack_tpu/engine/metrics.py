"""Engine Prometheus metrics.

Gauge names follow the exact contract the reference router scrapes
(reference: src/vllm_router/stats/engine_stats.py:63-76 parses
`vllm:num_requests_running`, `vllm:num_requests_waiting`,
`vllm:gpu_cache_usage_perc`, `vllm:gpu_prefix_cache_hit_rate`,
`vllm:gpu_prefix_cache_{hits,queries}_total`), so any router/dashboard built
for vLLM engines scrapes ours unchanged. On TPU the "gpu_" prefix is kept for
drop-in compatibility; tpu:* aliases are exported alongside.
"""

from __future__ import annotations

from prometheus_client import (
    REGISTRY,
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
)

from prometheus_client.core import SummaryMetricFamily

from production_stack_tpu.engine.outputs import EngineStatsSnapshot
from production_stack_tpu.tracing import (
    ENGINE_PHASES,
    HOST_PHASES,
    LOCK_WAITS,
)

# the event loop's side of a round's hand-over: a name of
# tracing.LOOP_PHASES -> the sample its (seconds, count) pair is
LOOP_HANDOVER_SAMPLES = {
    "deliver": "tpu:server_deliver_seconds",
    "send": "tpu:server_send_seconds",
    "deliver_pickup": "tpu:deliver_pickup_seconds",
    "token_delivery": "tpu:token_delivery_seconds",
}

_LATENCY_BUCKETS = (
    0.01, 0.025, 0.05, 0.1, 0.2, 0.4, 0.8, 1.5, 3.0, 6.0, 12.0, 30.0, 60.0,
)


class _PairTotals:
    """Running (sum, count) totals exposed as `<name>_sum` and
    `<name>_count`: a Summary that is SET from totals kept where the
    work happens, not observed sample by sample."""

    def __init__(self, model_name: str, docs: dict[str, str]):
        self.model_name = model_name
        self.docs = docs
        self.values: dict[str, tuple] = {n: (0.0, 0) for n in docs}

    def set(self, name: str, pair) -> None:
        self.values[name] = (pair[0], pair[1])

    def collect(self):
        for name, doc in self.docs.items():
            total, count = self.values[name]
            fam = SummaryMetricFamily(name, doc, labels=["model_name"])
            fam.add_metric([self.model_name], count_value=count,
                           sum_value=total)
            yield fam


class EngineMetrics:
    def __init__(
        self,
        model_name: str,
        registry: CollectorRegistry | None = None,
    ):
        self.model_name = model_name
        reg = registry or REGISTRY
        label = ["model_name"]

        def gauge(name, doc):
            return Gauge(name, doc, label, registry=reg)

        self.num_running = gauge(
            "vllm:num_requests_running", "Requests currently being decoded"
        )
        self.num_waiting = gauge(
            "vllm:num_requests_waiting", "Requests waiting to be scheduled"
        )
        self.cache_usage = gauge(
            "vllm:gpu_cache_usage_perc", "KV-cache usage (1 = full)"
        )
        self.prefix_hit_rate = gauge(
            "vllm:gpu_prefix_cache_hit_rate",
            "Prefix-cache hit rate over engine lifetime",
        )
        self.prefix_hits = gauge(
            "vllm:gpu_prefix_cache_hits_total",
            "Prefix-cache token hits (total)",
        )
        self.prefix_queries = gauge(
            "vllm:gpu_prefix_cache_queries_total",
            "Prefix-cache token queries (total)",
        )
        self.prefix_blocks_hashed = Counter(
            "tpu:prefix_blocks_hashed",
            "Prompt blocks the engine's block manager hashed (one per "
            "hash_block call on a block of a queried prompt). Over "
            "vllm:gpu_prefix_cache_queries_total / block_size: how "
            "often a prompt block was hashed, at most once (match, "
            "restore and registration share one chain per sequence); "
            "above 1 a caller hashes what another already did",
            label, registry=reg,
        )
        self.prompt_tokens = Counter(
            "vllm:prompt_tokens", "Prefill tokens processed",
            label, registry=reg,
        )
        self.generation_tokens = Counter(
            "vllm:generation_tokens", "Tokens generated",
            label, registry=reg,
        )
        self.preemptions = Counter(
            "vllm:num_preemptions", "Sequence preemptions",
            label, registry=reg,
        )
        self.spec_drafts = Counter(
            "vllm:spec_decode_num_draft_tokens",
            "Speculative draft tokens proposed", label, registry=reg,
        )
        self.spec_accepted = Counter(
            "vllm:spec_decode_num_accepted_tokens",
            "Speculative draft tokens accepted", label, registry=reg,
        )
        # the round seen from inside (tracing/phases.py): every sample
        # below is a (seconds, count) pair exposed as `<name>_sum` /
        # `<name>_count`, so a mean over a window is delta-sum over
        # delta-count as for the histograms. What a dashboard or the
        # benchmark must tell apart has a NAME of its own, not a label
        # value (readers sum a sample over its label sets).
        self.pairs = _PairTotals(model_name, {
            **{
                f"tpu:engine_phase_{p}_seconds":
                    f"Wall time of the engine round's `{p}` phase "
                    f"(tracing/phases.py; `engine.{p}` in a profiler "
                    "trace)"
                for p in ENGINE_PHASES
            },
            **{
                f"tpu:engine_phase_{p}_offcpu_seconds":
                    f"Of tpu:engine_phase_{p}_seconds, what the step "
                    "thread did not run (wall less time.thread_time() "
                    "over each span): it stood in the phase while "
                    "another thread held the interpreter or a lock"
                for p in HOST_PHASES
            },
            "tpu:server_deliver_seconds":
                "Event loop: the callback that puts a round's outputs "
                "on their requests' queues (`server.deliver` in a "
                "profiler trace), once a round",
            "tpu:server_send_seconds":
                "Event loop: an output taken off its queue -> its "
                "content chunk serialised and written to the socket "
                "(`server.send`), per chunk",
            "tpu:deliver_pickup_seconds":
                "The step thread queued a round's delivery callback "
                "-> the event loop ran it: how far the loop is behind, "
                "seen once a round",
            "tpu:token_delivery_seconds":
                "The step thread's fetch of a round returned -> a "
                "content chunk of that round was written to its "
                "socket, per chunk",
            "tpu:event_loop_lock_wait_seconds":
                "Time the server's event-loop thread spent acquiring "
                "the engine lock (admission, abort, stats): every SSE "
                "stream of the replica stands still meanwhile",
            "tpu:admit_lock_wait_seconds":
                "Per request: event-loop wait for the engine lock "
                "before add_request (not in tpu:request_queue_seconds, "
                "which starts after it)",
            "tpu:attn_context_tokens":
                "Context tokens the attention calls of a dispatched "
                "round had to read once (sum) per round (count): each "
                "decode lane's context at each fused step, each "
                "prefill chunk's end context; a run of pages that the "
                "decode lanes of a row block share counts once for the "
                "block (tpu:attn_lane_context_tokens counts every lane)",
            "tpu:loop_exit_pass":
                "A looped stack with an exit gate: the pass at which a "
                "sampled row would leave under the gate (sum: pass x "
                "tpu:loop_exit_mass over the passes, 1-based) and the "
                "sampled rows (count: the mass of all passes); their "
                "ratio is the mean exit pass, out of ut_steps. The "
                "per-pass counter under ONE name for readers that sum "
                "a sample over its label sets",
            "tpu:kv_window_blocks_per_seq":
                "A model with a windowed cache group: window-group "
                "blocks some sequence holds (sum) and running "
                "sequences (count), both summed over the dispatched "
                "rounds; their ratio stays near (window + chunk) / "
                "block_size however long the contexts grow",
            "tpu:prefix_window_cutback_blocks":
                "A model with a windowed cache group: blocks the "
                "admitted prompts' prefix hits were shortened by "
                "because the window-group blocks at the hit's end were "
                "no longer resident (sum) and admitted prompts whose "
                "prefix hit (count); 0 = every hit ended where its "
                "window blocks still were",
            **{
                f"tpu:program_{st}_seconds": doc
                for st, doc in (
                    ("trace", "jaxpr tracing of programs (jax.monitoring)"),
                    ("lower", "lowering of programs to MLIR"),
                    ("compile", "backend compile of programs, or their "
                     "retrieval from the persistent cache"),
                )
            },
        })
        reg.register(self.pairs)
        # a model of layer groups (models/layer_groups.py); zero for
        # a model of alike layers. Names, not label values, tell the
        # kinds apart (readers sum a sample over its label sets)
        self.attn_context_kind = {
            kind: Counter(
                f"tpu:attn_context_tokens_{kind}",
                f"Context tokens a `{kind}` attention layer of a "
                "layer-group model read (the window kind cut to its "
                "window; a latent kind's tokens are one cached row "
                "each): tpu:attn_context_tokens, per kind and layer",
                label, registry=reg)
            for kind in ("full", "window", "latent")
        }
        self.moe_rows = {
            name: Counter(f"tpu:moe_{name}", doc, label, registry=reg)
            for name, doc in (
                ("routed_rows", "Routed expert layers: (row, expert) "
                 "pairs routed, over all layers and fused steps"),
                ("local_rows", "Routed expert layers: pairs whose "
                 "expert is held by this engine (its expert-parallel "
                 "rank's slice); over tpu:moe_routed_rows about "
                 "1 / ep_size"),
                ("active_experts", "Routed expert layers: local experts "
                 "with at least one row, summed over layers and steps"),
            )
        }
        self.kv_blocks_in_use = Gauge(
            "tpu:kv_blocks_in_use",
            "KV blocks some sequence references, per cache group",
            ["model_name", "group"], registry=reg)
        self.kv_window_released = Counter(
            "tpu:kv_window_blocks_released",
            "Window-group KV blocks a sequence let go because every "
            "position in them lay behind its window", label, registry=reg)
        # a model with recurrent state (ops/ssm.py or ops/kda.py fills
        # the state group, engine/block_manager.StateBlockManager keeps
        # it: the tpu:ssm_* names are the GROUP's, whichever recurrence);
        # zero for any other
        self.ssm_gauges = {
            key: Gauge(name, doc, label, registry=reg)
            for key, name, doc in (
                ("state_slots_in_use", "tpu:ssm_state_slots_in_use",
                 "State slots (the recurrent state of every state-space "
                 "layer, one a running sequence) that a sequence holds"),
                ("snapshots_resident", "tpu:ssm_snapshots_resident",
                 "Snapshots of a sequence's recurrent state at a token "
                 "boundary resident in the pool a prefix hit restores "
                 "from"),
            )
        }
        self.ssm_counters = {
            key: Counter(name, doc, label, registry=reg)
            for key, name, doc in (
                ("snapshot_saves", "tpu:ssm_snapshot_saves",
                 "Snapshots of the recurrent state saved at a boundary "
                 "and registered under the boundary block's hash"),
                ("snapshot_restores", "tpu:ssm_snapshot_restores",
                 "Admitted prompts whose prefix hit starts from a "
                 "snapshot of the recurrent state"),
                ("snapshot_evictions", "tpu:ssm_snapshot_evictions",
                 "Snapshots dropped from the pool to make room"),
                ("prefix_state_cutback_tokens",
                 "tpu:prefix_state_cutback_tokens",
                 "Tokens of the admitted prompts' prefix hits given up "
                 "because no snapshot of the recurrent state stood at "
                 "the hit's end: recomputed from the deepest snapshot "
                 "under it (or from nothing)"),
                ("lane_layer_steps", "tpu:ssm_lane_layer_steps",
                 "One-token updates of the recurrent state by the "
                 "dispatched rounds: decode lanes that hold a sequence "
                 "x fused steps x state layers"),
                ("update_calls", "tpu:state_update_calls",
                 "Calls of the decode lanes' state-update kernel "
                 "dispatched: fused steps x state layers, whatever the "
                 "lanes hold (lane-layer steps over calls = live lanes "
                 "a call)"),
            )
        }
        self.program_cache_hits = Counter(
            "tpu:program_cache_hits",
            "Programs served by jax's persistent compilation cache",
            label, registry=reg,
        )
        # HTTP handler entry -> first content chunk of a streamed
        # completion/chat written, on the server's clock: parse,
        # template, tokenize, lock wait, queue, prefill, the hop back to
        # the loop, detokenize, write (vllm:time_to_first_token_seconds
        # starts inside the engine lock and ends on the step thread)
        self.server_ttft = Histogram(
            "tpu:server_ttft_seconds",
            "Streamed request: handler entry -> first content chunk "
            "written", label, buckets=_LATENCY_BUCKETS, registry=reg,
        )
        self.server_ttft.labels(model_name)  # exported from boot
        self.prefill_chained_chunks = Counter(
            "tpu:prefill_chained_chunks",
            "Prefill chunks dispatched via cold-prompt chaining "
            "(no host round-trip between chunks)", label, registry=reg,
        )
        # long-prefill lane (context-parallel ring prefill): per-phase
        # TTFT attribution for prompts served by the sp-sharded ring —
        # ring compute, device->host KV materialization, paged-cache
        # landing, and the tier-export overflow that ran under the job
        self.long_prefill_requests = Counter(
            "tpu:long_prefill_requests",
            "Prompts served via the context-parallel ring prefill lane",
            label, registry=reg,
        )
        self.long_prefill_chunks = Counter(
            "tpu:long_prefill_chunks",
            "Ring prefill chunk dispatches", label, registry=reg,
        )
        self.long_prefill_fallbacks = Counter(
            "tpu:long_prefill_fallbacks",
            "Long prefills that failed back to chunked prefill",
            label, registry=reg,
        )
        self.prefill_ring_s = Counter(
            "tpu:prefill_ring_seconds",
            "Long-prefill ring compute wall time (job start -> ring "
            "drained; overlaps other users' decode rounds)",
            label, registry=reg,
        )
        self.prefill_ring_d2h_s = Counter(
            "tpu:prefill_ring_d2h_seconds",
            "Long-prefill device->host KV materialization wall time "
            "(on the long-prefill worker)", label, registry=reg,
        )
        self.prefill_kv_land_s = Counter(
            "tpu:prefill_kv_land_seconds",
            "Long-prefill paged-cache landing wall time (first parked "
            "batch -> last donated import enqueued)",
            label, registry=reg,
        )
        self.prefill_overflow_export_s = Counter(
            "tpu:prefill_overflow_export_seconds",
            "Tier-export seconds attributed to in-flight long prefills "
            "(HBM headroom the landed chain displaced)",
            label, registry=reg,
        )
        # zero-stall KV tiering (PR 4): deferred-export batch wall time
        # (measured ON THE OFFLOAD WORKER — overlapped activity, never a
        # step-loop stall), staged-restore enqueue->landed time, and
        # per-tier traffic so a dashboard can see WHICH tier serves and
        # whether eviction cascades are healthy
        _kv_buckets = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                       0.25, 0.5, 1.0, 2.5, 5.0)
        self.kv_export_s = Histogram(
            "tpu:kv_export_seconds",
            "Deferred KV export batch wall time (d2h materialization + "
            "tier store, on the offload worker)",
            label, buckets=_kv_buckets, registry=reg,
        )
        self.kv_restore_s = Histogram(
            "tpu:kv_restore_seconds",
            "Staged KV restore wall time (enqueue -> blocks landed in "
            "HBM; overlaps the request's queue wait)",
            label, buckets=_kv_buckets, registry=reg,
        )
        tier_label = ["model_name", "tier"]
        self.kv_tier_hits = Counter(
            "tpu:kv_tier_hits", "KV tier read hits",
            tier_label, registry=reg,
        )
        self.kv_export_blocks = Counter(
            "tpu:kv_export_blocks", "KV blocks exported to the offload "
            "tiers", label, registry=reg,
        )
        self.kv_restore_blocks = Counter(
            "tpu:kv_restore_blocks", "KV blocks restored from the "
            "offload tiers into HBM", label, registry=reg,
        )
        self.kv_restore_fallbacks = Counter(
            "tpu:kv_restore_fallbacks", "Staged restores that fell back "
            "to recompute (broken chain, timeout, or full HBM)",
            label, registry=reg,
        )
        # disaggregated prefill/decode transfer (PeerTier pulls):
        # blocks the PD peer served / could not serve, bytes over the
        # transfer link, and failed pulls (dead peer, corrupt frame)
        self.kv_peer_hits = Counter(
            "tpu:kv_peer_hits",
            "KV blocks pulled from the disaggregated-prefill peer",
            label, registry=reg,
        )
        self.kv_peer_misses = Counter(
            "tpu:kv_peer_misses",
            "KV blocks requested from the PD peer but not served "
            "(chain evicted or never prefilled there)",
            label, registry=reg,
        )
        self.kv_peer_read_bytes = Counter(
            "tpu:kv_peer_read_bytes",
            "Bytes pulled over the inter-engine KV transfer link",
            label, registry=reg,
        )
        self.kv_peer_fallbacks = Counter(
            "tpu:kv_peer_fallbacks",
            "Failed PD peer pulls (dead peer / mid-frame death / "
            "corrupt payload) that degraded to local recompute",
            label, registry=reg,
        )
        # cluster-wide shared KV cache (RemoteTier <-> kv.cache_server):
        # cross-engine chain hits/misses, wire bytes each direction,
        # write-behind put_batch frames, and failed flushes/pulls
        self.kv_remote_hits = Counter(
            "tpu:kv_remote_hits",
            "KV blocks served by the shared cache server",
            label, registry=reg,
        )
        self.kv_remote_misses = Counter(
            "tpu:kv_remote_misses",
            "KV blocks requested from the shared cache server but not "
            "held there (cold chain or evicted/expired)",
            label, registry=reg,
        )
        self.kv_remote_read_bytes = Counter(
            "tpu:kv_remote_read_bytes",
            "Bytes pulled from the shared cache server",
            label, registry=reg,
        )
        self.kv_remote_write_bytes = Counter(
            "tpu:kv_remote_write_bytes",
            "Bytes shipped to the shared cache server (write-behind "
            "batched puts)",
            label, registry=reg,
        )
        self.kv_remote_flushes = Counter(
            "tpu:kv_remote_flushes",
            "Write-behind put_batch frames shipped to the shared cache",
            label, registry=reg,
        )
        self.kv_remote_fallbacks = Counter(
            "tpu:kv_remote_fallbacks",
            "Failed shared-cache flushes/pulls (dead server / corrupt "
            "frame) that degraded without stalling the engine",
            label, registry=reg,
        )
        # fused decode: rounds, host-discarded overshoot tokens (~0
        # under device stops), and whole-round device early exits
        self.decode_rounds = Counter(
            "tpu:decode_rounds", "Decode rounds dispatched",
            label, registry=reg,
        )
        self.decode_early_dispatch = Counter(
            "tpu:decode_early_dispatch",
            "Decode rounds whose program was dispatched when the fetch "
            "of the round before them returned, before that round's "
            "tokens were applied (over tpu:decode_rounds: the share of "
            "rounds between which the device waited for one dispatch)",
            label, registry=reg,
        )
        self.decode_lane_steps = Counter(
            "tpu:decode_lane_steps",
            "Lanes x fused steps of every dispatched round's decode "
            "rows (the program runs max_num_seqs lanes whatever the "
            "live batch)",
            label, registry=reg,
        )
        self.decode_idle_lane_steps = Counter(
            "tpu:decode_idle_lane_steps",
            "Of tpu:decode_lane_steps, those the host packed as "
            "zero-row segments of the attention walk (lanes holding "
            "no sequence: no KV block read). Lanes a device stop "
            "freezes mid-round skip their walk too and are NOT "
            "counted here",
            label, registry=reg,
        )
        self.attn_lane_context_tokens = Counter(
            "tpu:attn_lane_context_tokens",
            "Context tokens the decode lanes and prefill chunks of the "
            "dispatched rounds attended, each lane's own count "
            "(tpu:attn_context_tokens counts what the walk streams: a "
            "run of pages that the lanes of a row block share, once)",
            label, registry=reg,
        )
        self.attn_shared_context_tokens = Counter(
            "tpu:attn_shared_context_tokens",
            "Of tpu:attn_lane_context_tokens, those a shared pass "
            "served: the leading keys that every decode lane of a row "
            "block reads from the same pages (one cached prefix), "
            "walked once for the block",
            label, registry=reg,
        )
        self.sampler_steps = Counter(
            "tpu:sampler_steps",
            "Evaluations of the sampler by the dispatched rounds: a "
            "round's fused decode steps, one more where it samples "
            "prefill rows' first tokens, one for a batch sampled on the "
            "host path",
            label, registry=reg,
        )
        self.sampler_window_steps = Counter(
            "tpu:sampler_window_steps",
            "Of tpu:sampler_steps, those whose rows held a temperature "
            "> 0: the device builds the 64-candidate window (top-k "
            "over the vocabulary) only there, a greedy evaluation "
            "takes its argmax",
            label, registry=reg,
        )
        self.loop_passes = Counter(
            "tpu:loop_passes",
            "Passes of the layer stack by the dispatched programs: "
            "their forwards (a round's fused decode steps, a prefill "
            "beside them riding the first) x the model's ut_steps (1 "
            "for a stack that runs once a token); each pass reads "
            "every layer's weights",
            label, registry=reg,
        )
        self.loop_exit_mass = Counter(
            "tpu:loop_exit_mass",
            "A looped stack's exit distribution under its learned "
            "gate, summed on the device over the rows that were "
            "sampled: the probability that a row would leave after "
            "`pass` (the last pass takes the rest). Served at a "
            "threshold of 1 no row leaves; sum(pass x mass) / sum(mass) "
            "is the mean pass a lower threshold could stop at",
            ["model_name", "pass"], registry=reg,
        )
        self.decode_overshoot = Counter(
            "tpu:decode_overshoot_tokens",
            "Sampled decode slots discarded by the host past a stop "
            "condition (device stops freeze these lanes on device "
            "instead; stop STRINGS still resolve host-side)",
            label, registry=reg,
        )
        self.decode_early_exits = Counter(
            "tpu:decode_early_exit_rounds",
            "Fused decode rounds whose device loop exited before the "
            "trip count because every lane had finished",
            label, registry=reg,
        )
        # unified ragged dispatch: fused lane-typed rounds and their
        # lane mix (prefill lanes per fused round — pure rounds are not
        # observed, so rate(tpu:ragged_rounds) over
        # rate(tpu:decode_rounds) is the mixed-round share)
        self.ragged_lane_mix = Histogram(
            "tpu:ragged_lane_mix",
            "Prefill-chunk lanes fused into a ragged round (each "
            "observation is one mixed prefill+decode dispatch)",
            label, buckets=(1, 2, 4, 8, 16), registry=reg,
        )
        self.ragged_rounds = Counter(
            "tpu:ragged_rounds",
            "Lane-typed ragged rounds dispatched fused (prefill chunks "
            "+ decode steps in one device program)",
            label, registry=reg,
        )
        self.ragged_split_rounds = Counter(
            "tpu:ragged_split_rounds",
            "Planned mixed rounds executed as split prefill+decode "
            "dispatches (prompt_logprobs / host-sampled finals / "
            "near-budget guided lanes)",
            label, registry=reg,
        )
        self.compile_events = Counter(
            "tpu:compile_events_total",
            "Program-variant builds (jit cache misses on the model "
            "runner's step builders) — the cold-start compile tax, "
            "labeled by builder kind (decode_multi, ragged_rows, ...)",
            ["model_name", "kind"], registry=reg,
        )
        self.request_success = Counter(
            "vllm:request_success", "Finished requests",
            ["model_name", "finished_reason"], registry=reg,
        )
        self.ttft = Histogram(
            "vllm:time_to_first_token_seconds", "TTFT",
            label, buckets=_LATENCY_BUCKETS, registry=reg,
        )
        self.tpot = Histogram(
            "vllm:time_per_output_token_seconds", "Inter-token latency",
            label, buckets=(0.002, 0.005, 0.01, 0.02, 0.04, 0.08, 0.16,
                            0.32, 0.64, 1.28), registry=reg,
        )
        self.e2e_latency = Histogram(
            "vllm:e2e_request_latency_seconds", "End-to-end request latency",
            label, buckets=_LATENCY_BUCKETS, registry=reg,
        )
        # request-lifecycle attribution (fed from RequestMetrics at
        # finish): TTFT = queue-wait + scheduling delay + prefill, and
        # these split the first two out so a TTFT regression is
        # attributable without reading per-request timelines
        self.queue_time = Histogram(
            "tpu:request_queue_seconds",
            "Enqueue -> scheduler admission (waiting-queue wait)",
            label, buckets=_LATENCY_BUCKETS, registry=reg,
        )
        self.sched_delay = Histogram(
            "tpu:scheduling_delay_seconds",
            "Scheduler admission -> first prefill dispatch",
            label, buckets=_LATENCY_BUCKETS, registry=reg,
        )
        self.preempt_stall = Histogram(
            "tpu:preemption_stall_seconds",
            "Wall time spent preempted (preempt -> re-admission), "
            "summed per request; observed only for preempted requests",
            label, buckets=_LATENCY_BUCKETS, registry=reg,
        )
        self._counter_state = EngineStatsSnapshot()

    def update_from_snapshot(self, s: EngineStatsSnapshot) -> None:
        m = self.model_name
        self.num_running.labels(m).set(s.num_running)
        self.num_waiting.labels(m).set(s.num_waiting)
        self.cache_usage.labels(m).set(s.kv_usage)
        self.prefix_hit_rate.labels(m).set(s.prefix_cache_hit_rate)
        self.prefix_hits.labels(m).set(s.prefix_cache_hits)
        self.prefix_queries.labels(m).set(s.prefix_cache_queries)
        prev = self._counter_state
        self.prefix_blocks_hashed.labels(m).inc(max(
            0, s.prefix_blocks_hashed_total
            - prev.prefix_blocks_hashed_total))
        self.prompt_tokens.labels(m).inc(
            max(0, s.prompt_tokens_total - prev.prompt_tokens_total)
        )
        self.generation_tokens.labels(m).inc(
            max(0, s.generation_tokens_total - prev.generation_tokens_total)
        )
        self.preemptions.labels(m).inc(
            max(0, s.num_preemptions_total - prev.num_preemptions_total)
        )
        self.spec_drafts.labels(m).inc(
            max(0, s.spec_draft_tokens_total
                - prev.spec_draft_tokens_total)
        )
        self.spec_accepted.labels(m).inc(
            max(0, s.spec_accepted_tokens_total
                - prev.spec_accepted_tokens_total)
        )
        for name, pair in s.engine_phases.items():
            self.pairs.set(f"tpu:engine_phase_{name}_seconds", pair)
        for name, pair in s.engine_phases_offcpu.items():
            self.pairs.set(
                f"tpu:engine_phase_{name}_offcpu_seconds", pair)
        if s.loop_phases:
            waits = [s.loop_phases[n] for n in LOCK_WAITS]
            self.pairs.set("tpu:event_loop_lock_wait_seconds", (
                sum(p[0] for p in waits), sum(p[1] for p in waits)))
            self.pairs.set("tpu:admit_lock_wait_seconds",
                           s.loop_phases["admit_lock_wait"])
            for name, sample in LOOP_HANDOVER_SAMPLES.items():
                self.pairs.set(sample, s.loop_phases[name])
        self.pairs.set("tpu:attn_context_tokens", s.attn_context_tokens)
        for kind, tokens in s.attn_context_by_kind.items():
            self.attn_context_kind[kind].labels(m).inc(max(
                0, tokens - prev.attn_context_by_kind.get(kind, 0)))
        for name, now, was in zip(
                ("routed_rows", "local_rows", "active_experts"),
                s.moe_stats, prev.moe_stats):
            self.moe_rows[name].labels(m).inc(max(0, now - was))
        for group, blocks in s.kv_blocks_in_use.items():
            self.kv_blocks_in_use.labels(m, group).set(blocks)
        self.kv_window_released.labels(m).inc(max(
            0, s.kv_window_blocks_released_total
            - prev.kv_window_blocks_released_total))
        self.pairs.set("tpu:kv_window_blocks_per_seq",
                       s.kv_window_blocks_per_seq)
        self.pairs.set("tpu:prefix_window_cutback_blocks",
                       s.prefix_window_cutback_blocks)
        for key, gauge in self.ssm_gauges.items():
            gauge.labels(m).set(s.ssm_stats.get(key, 0))
        for key, counter in self.ssm_counters.items():
            counter.labels(m).inc(max(0, s.ssm_stats.get(key, 0)
                                      - prev.ssm_stats.get(key, 0)))
        for stage, pair in s.program_stages.items():
            self.pairs.set(f"tpu:program_{stage}_seconds", pair)
        self.program_cache_hits.labels(m).inc(max(
            0, s.program_cache_hits_total
            - prev.program_cache_hits_total))
        self.prefill_chained_chunks.labels(m).inc(max(
            0, s.prefill_chained_chunks_total
            - prev.prefill_chained_chunks_total))
        self.long_prefill_requests.labels(m).inc(max(
            0, s.long_prefill_requests_total
            - prev.long_prefill_requests_total))
        self.long_prefill_chunks.labels(m).inc(max(
            0, s.long_prefill_chunks_total
            - prev.long_prefill_chunks_total))
        self.long_prefill_fallbacks.labels(m).inc(max(
            0, s.long_prefill_fallbacks_total
            - prev.long_prefill_fallbacks_total))
        self.prefill_ring_s.labels(m).inc(max(
            0.0, s.long_prefill_ring_seconds_total
            - prev.long_prefill_ring_seconds_total))
        self.prefill_ring_d2h_s.labels(m).inc(max(
            0.0, s.long_prefill_d2h_seconds_total
            - prev.long_prefill_d2h_seconds_total))
        self.prefill_kv_land_s.labels(m).inc(max(
            0.0, s.long_prefill_land_seconds_total
            - prev.long_prefill_land_seconds_total))
        self.prefill_overflow_export_s.labels(m).inc(max(
            0.0, s.long_prefill_overflow_seconds_total
            - prev.long_prefill_overflow_seconds_total))
        self.decode_rounds.labels(m).inc(max(
            0, s.decode_rounds_total - prev.decode_rounds_total))
        self.decode_early_dispatch.labels(m).inc(max(
            0, s.decode_early_dispatch_total
            - prev.decode_early_dispatch_total))
        for counter, now, was in zip(
                (self.decode_lane_steps, self.decode_idle_lane_steps,
                 self.sampler_steps, self.sampler_window_steps,
                 self.attn_lane_context_tokens,
                 self.attn_shared_context_tokens),
                s.decode_lane_steps + s.sampler_steps + s.attn_lane_tokens,
                prev.decode_lane_steps + prev.sampler_steps
                + prev.attn_lane_tokens):
            counter.labels(m).inc(max(0, now - was))
        self.loop_passes.labels(m).inc(max(
            0, s.loop_passes_total - prev.loop_passes_total))
        for t, now in enumerate(s.loop_exit_mass):
            was = prev.loop_exit_mass[t] if prev.loop_exit_mass else 0.0
            self.loop_exit_mass.labels(m, str(t + 1)).inc(
                max(0.0, now - was))
        self.pairs.set("tpu:loop_exit_pass", (
            sum((t + 1) * x for t, x in enumerate(s.loop_exit_mass)),
            sum(s.loop_exit_mass)))
        self.decode_overshoot.labels(m).inc(max(
            0, s.decode_overshoot_tokens_total
            - prev.decode_overshoot_tokens_total))
        self.decode_early_exits.labels(m).inc(max(
            0, s.decode_early_exit_rounds_total
            - prev.decode_early_exit_rounds_total))
        self.ragged_rounds.labels(m).inc(max(
            0, s.ragged_rounds_total - prev.ragged_rounds_total))
        self.ragged_split_rounds.labels(m).inc(max(
            0, s.ragged_split_rounds_total
            - prev.ragged_split_rounds_total))
        for kind, n in (s.compile_events or {}).items():
            pn = (prev.compile_events or {}).get(kind, 0)
            self.compile_events.labels(m, kind).inc(max(0, n - pn))
        self.kv_export_blocks.labels(m).inc(max(
            0, s.kv_export_blocks_total - prev.kv_export_blocks_total))
        self.kv_restore_blocks.labels(m).inc(max(
            0, s.kv_restore_blocks_total - prev.kv_restore_blocks_total))
        self.kv_restore_fallbacks.labels(m).inc(max(
            0, s.kv_restore_fallbacks_total
            - prev.kv_restore_fallbacks_total))
        self.kv_peer_hits.labels(m).inc(max(
            0, s.kv_peer_hits_total - prev.kv_peer_hits_total))
        self.kv_peer_misses.labels(m).inc(max(
            0, s.kv_peer_misses_total - prev.kv_peer_misses_total))
        self.kv_peer_read_bytes.labels(m).inc(max(
            0, s.kv_peer_read_bytes_total
            - prev.kv_peer_read_bytes_total))
        self.kv_peer_fallbacks.labels(m).inc(max(
            0, s.kv_peer_fallbacks_total
            - prev.kv_peer_fallbacks_total))
        self.kv_remote_hits.labels(m).inc(max(
            0, s.kv_remote_hits_total - prev.kv_remote_hits_total))
        self.kv_remote_misses.labels(m).inc(max(
            0, s.kv_remote_misses_total - prev.kv_remote_misses_total))
        self.kv_remote_read_bytes.labels(m).inc(max(
            0, s.kv_remote_read_bytes_total
            - prev.kv_remote_read_bytes_total))
        self.kv_remote_write_bytes.labels(m).inc(max(
            0, s.kv_remote_write_bytes_total
            - prev.kv_remote_write_bytes_total))
        self.kv_remote_flushes.labels(m).inc(max(
            0, s.kv_remote_flushes_total
            - prev.kv_remote_flushes_total))
        self.kv_remote_fallbacks.labels(m).inc(max(
            0, s.kv_remote_fallbacks_total
            - prev.kv_remote_fallbacks_total))
        for tier, c in (s.kv_tier_counters or {}).items():
            pc = (prev.kv_tier_counters or {}).get(tier, {})
            self.kv_tier_hits.labels(m, tier).inc(
                max(0, c.get("hits", 0) - pc.get("hits", 0)))
        self._counter_state = s

    def observe_kv(
        self,
        export_seconds: list[float],
        restore_seconds: list[float],
    ) -> None:
        """Feed drained engine observations (LLMEngine.
        drain_kv_observations) into the tpu:kv_*_seconds histograms."""
        m = self.model_name
        for s in export_seconds:
            self.kv_export_s.labels(m).observe(max(0.0, s))
        for s in restore_seconds:
            self.kv_restore_s.labels(m).observe(max(0.0, s))

    def observe_ragged(self, lane_counts: list[int]) -> None:
        """Feed drained ragged lane-mix observations (LLMEngine.
        drain_ragged_observations — prefill lanes per fused round)
        into the tpu:ragged_lane_mix histogram."""
        m = self.model_name
        for n in lane_counts:
            self.ragged_lane_mix.labels(m).observe(n)

    def observe_request(
        self,
        finish_reason: str,
        ttft_s: float | None,
        e2e_s: float | None,
        n_output_tokens: int,
        queue_s: float | None = None,
        sched_delay_s: float | None = None,
        preempt_stall_s: float | None = None,
    ) -> None:
        m = self.model_name
        self.request_success.labels(m, finish_reason).inc()
        if ttft_s is not None:
            self.ttft.labels(m).observe(ttft_s)
        if e2e_s is not None:
            self.e2e_latency.labels(m).observe(e2e_s)
            if ttft_s is not None and n_output_tokens > 1:
                self.tpot.labels(m).observe(
                    (e2e_s - ttft_s) / (n_output_tokens - 1)
                )
        if queue_s is not None:
            self.queue_time.labels(m).observe(max(0.0, queue_s))
        if sched_delay_s is not None:
            self.sched_delay.labels(m).observe(max(0.0, sched_delay_s))
        if preempt_stall_s is not None:
            # only preempted requests observe (a zero-flood would bury
            # the signal); panels rate() over preemption events
            self.preempt_stall.labels(m).observe(max(0.0, preempt_stall_s))
