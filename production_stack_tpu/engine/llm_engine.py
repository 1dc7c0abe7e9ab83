"""LLMEngine: ties scheduler + block manager + model runner + sampler into
the step loop. One step == one prefill chunk OR one decode batch (static
shapes, see model_runner.py).

TPU-native equivalent of the serving engine the reference stack deploys as
external `vllm serve` pods (reference: helm/templates/deployment-vllm-multi.yaml:104-126);
the OpenAI/metrics HTTP surface lives in engine/server.py.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.block_manager import (
    BlockManager,
    StateBlockManager,
    WindowedBlockManager,
)
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.model_runner import ModelRunner
from production_stack_tpu.engine.outputs import (
    EngineStatsSnapshot,
    RequestOutput,
)
from production_stack_tpu.engine.sampler import (
    apply_penalties,
    sample_tokens,
)
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.engine.scheduler import (
    PrefillWork,
    Scheduler,
    SchedulerConfig,
    decode_precompile_variant,
)
from production_stack_tpu.engine.sequence import (
    PromptIds,
    Sequence,
    SequenceStatus,
)
from production_stack_tpu.engine.tokenizer import get_tokenizer
from production_stack_tpu.tracing import phases
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)


class LLMEngine:
    # read by benchmarks/chip/engine_child.warm_programs; goes when the
    # harness gets a public precompile (ROADMAP D3)
    _async_decode = False

    def __init__(self, config: EngineConfig, params: dict | None = None):
        self.config = config
        if config.multihost:
            from production_stack_tpu.engine.multihost_engine import (
                validate_multihost_config,
            )

            validate_multihost_config(config)
        self.tokenizer = get_tokenizer(
            config.tokenizer, config.model,
            chat_template=config.chat_template,
        )
        self.runner = ModelRunner(config, params=params)
        if config.multihost:
            from production_stack_tpu.engine.multihost_engine import (
                wrap_engine_for_multihost,
            )
            from production_stack_tpu.parallel import multihost

            if multihost.is_multihost():
                # host 0 only: followers never construct an LLMEngine,
                # they run multihost_engine.follower_loop on a bare runner
                wrap_engine_for_multihost(self)
        if self.runner.num_window_blocks:
            # a model with a windowed cache group: the second pool hangs
            # on the first through one block map, which the runner
            # uploads when it moved (block_manager.WindowedBlockManager)
            from production_stack_tpu.models import layer_groups

            mc = self.runner.model_config
            self.block_manager = WindowedBlockManager(
                self.runner.num_blocks, config.block_size,
                config.enable_prefix_caching,
                window=mc.attn_kinds[layer_groups.mapped_kind(mc)].window,
                num_window_blocks=self.runner.num_window_blocks,
            )
            self.runner.block_map_source = self.block_manager
        elif self.runner.num_state_slots:
            # a model with recurrent state: a state slot a sequence and
            # a pool of snapshots beside the pages, found on the device
            # through maps the runner uploads when they moved
            # (block_manager.StateBlockManager)
            self.block_manager = StateBlockManager(
                self.runner.num_blocks, config.block_size,
                config.enable_prefix_caching,
                num_state_slots=self.runner.num_state_slots,
                num_snapshots=self.runner.num_snapshots,
                interval_blocks=self.runner.snapshot_interval_blocks,
            )
            self.runner.state_map_source = self.block_manager
        else:
            self.block_manager = BlockManager(
                num_blocks=self.runner.num_blocks,
                block_size=config.block_size,
                enable_prefix_caching=config.enable_prefix_caching,
            )
        self.scheduler = Scheduler(
            SchedulerConfig(
                max_num_seqs=config.max_num_seqs,
                max_prefill_chunk=config.max_prefill_chunk,
                max_model_len=config.resolved_max_model_len(),
                enable_chunked_prefill=config.enable_chunked_prefill,
                max_prefill_seqs=config.max_prefill_seqs,
                scheduling_policy=config.scheduling_policy,
                decode_interleave=config.decode_interleave,
                decode_lookahead=max(0, config.num_scheduler_steps - 1),
            ),
            self.block_manager,
        )
        self._seqs: dict[str, Sequence] = {}
        self.last_step_kind = "idle"  # "prefill" | "decode" | "idle"
        # -- request-lifecycle timeline (tracing/timeline.py) -------------
        # one recorder per engine: scheduler admission/preemption events,
        # per-chunk prefill attribution, first token, sampled decode
        # rounds, finish. Appends only — no locks or device syncs on the
        # step path; disabled = a single boolean check per hook (the
        # per-step call sites additionally guard on _tl_enabled so the
        # calls themselves vanish).
        from production_stack_tpu.tracing import (
            NULL_RECORDER,
            RequestTracer,
            TimelineRecorder,
        )

        exporter = config.tracing_exporter
        if not config.request_timeline and exporter != "none":
            # engine spans are DERIVED from timelines (_export_span):
            # with recording off the exporter would sit silently dead —
            # degrade loudly instead (same contract as init_sentry) and
            # drop to "none" so no pointless flush loop spawns either
            logger.warning(
                "engine span export DISABLED: tracing_exporter=%r "
                "requires request timelines (drop "
                "--no-request-timeline to export engine_request spans)",
                exporter,
            )
            exporter = "none"
        self.tracer = RequestTracer(
            exporter,
            service_name=config.served_model_name or config.model,
        )
        if config.request_timeline:
            self.timeline = TimelineRecorder(
                maxlen=config.timeline_ring_size, tracer=self.tracer
            )
        else:
            self.timeline = NULL_RECORDER
        self._tl_enabled = self.timeline.enabled
        self.scheduler.timeline = self.timeline
        # -- the round seen from inside (tracing/phases.py) ---------------
        # one timer for the whole round: the runner feeds pack / h2d /
        # dispatch, the engine schedule / fetch / apply (and the server's
        # step loop idle / deliver). `_round` numbers the dispatched
        # rounds; the `engine.step` annotation of a profiler trace
        # carries it as `round` and the request timeline's prefill_chunk
        # / first_token / decode_round events as `engine_round` (a
        # decode_round's own `round` counts that REQUEST's rounds), so a
        # request in /debug/requests joins a round in a trace by that
        # number and not by clock
        self.phases = self.runner.phases
        self._round = 0
        # (window-group blocks in use, running sequences) summed over
        # the dispatched rounds: their ratio is the blocks a sequence
        # holds there, the proof in a run that the release works
        self._window_blocks_per_seq = [0, 0]
        self._step_ann = None     # live only inside a profiler session
        self._step_tagged = False
        # device-side stop masks (elastic fused decode): EOS / stop-id /
        # remaining-budget checks ride INSIDE the fused scan, a finished
        # lane freezes mid-round and the dispatch returns per-lane valid
        # counts. Multihost is out (the broadcast wire ships host token
        # lists, not stop matrices)
        self._device_stop = (
            config.device_stop
            and config.num_scheduler_steps > 1
            and not config.multihost
        )
        # decode round accounting: rounds, host-discarded overshoot
        # tokens (~0 under device stops except for host-resolved stop
        # STRINGS), and whole-round early exits
        self._decode_rounds_total = 0
        self._decode_overshoot_tokens_total = 0
        self._decode_early_exit_rounds_total = 0
        # speculative h2d prefetch (stage_decode_multi): upload the NEXT
        # fused round's packed host inputs while the current round is
        # still executing, then dispatch it chained on the on-device
        # tokens — the pack and its h2d leave the round's critical
        # path, and a staged round can start at the fetch's return
        # (`_starts_at_fetch`). Multihost is out: the broadcast wire
        # ships host token lists, not device arrays.
        self._prefetch_decode = (
            config.prefetch_decode
            and config.num_scheduler_steps > 1
            and not config.multihost
        )
        self._staged_decode: dict | None = None
        self._staged_hits_total = 0
        self._staged_misses_total = 0
        # a staged round starts when the fetch returns: where the
        # fetched arrays show that the stage's prediction holds, round
        # N+1 is dispatched BEFORE round N's tokens are applied
        # (`_starts_at_fetch`), and is in flight when step() returns.
        # The next step() finds it here and goes to its fetch. At most
        # one round is on the device at any time, as without a stage
        self._inflight: dict | None = None
        self._early_dispatch_total = 0
        # callers that stand at the lock around step() with a request
        # or an abort (AsyncLLMEngine counts them): they get in before
        # the next round is chosen, so a round never starts early
        # past one of them
        self.callers_waiting = 0
        # pipelined prefill: a dispatch ships ONE packed buffer, and
        # cold multi-chunk prompts chain their chunks back-to-back in
        # one engine round when nothing is decode-ready or waiting
        # (`_chain_next_prefill`: each chunk's upload overlaps the
        # chunk before it on the device). Multihost is out for the
        # chaining and the ragged stage (the broadcast wire ships host
        # argument lists, not device buffers) — the fused-buffer
        # dispatch itself works everywhere.
        self._prefill_pipeline = (
            config.prefill_pipeline and not config.multihost
        )
        self._pf_chained_chunks_total = 0
        # unified ragged prefill+decode dispatch: mixed rounds run as
        # ONE lane-typed device program (model_runner.ragged_dispatch);
        # the scheduler plans them (plan_ragged_round) instead of
        # alternating behind the interleave. Multihost is out (the
        # broadcast wire ships host argument lists), and meshed engines
        # are out (the fused buffer is a committed single-device
        # transfer — same rule as the prefill pipeline / decode
        # prefetch staging).
        self._ragged_dispatch = (
            config.ragged_dispatch
            and not config.multihost
            and self.runner.mesh is None
        )
        self.scheduler.config.ragged_dispatch = self._ragged_dispatch
        # staged NEXT ragged round (h2d prefetch): fingerprint-validated
        # like _staged_decode; a lane-mix change between
        # stage and dispatch is a counted miss, never a dispatch error
        self._staged_ragged: dict | None = None
        self._ragged_staged_hits_total = 0
        self._ragged_staged_misses_total = 0
        # ragged accounting: rounds dispatched fused, rounds a mixed
        # plan had to run split (exotic lanes: prompt_logprobs,
        # host-sampled finals, near-budget guided), per-round lane-mix
        # observations (prefill lanes per fused round — drained into
        # the tpu:ragged_lane_mix histogram), and lane totals
        self._ragged_rounds_total = 0
        self._ragged_split_rounds_total = 0
        self._ragged_prefill_lanes_total = 0
        self._ragged_decode_lanes_total = 0
        self._ragged_lane_mix_hist: dict[str, int] = {}
        # long-prefill lane (context-parallel ring prefill,
        # engine/long_prefill.py): prompts past long_prefill_threshold
        # ring on a ("tp", "sp") mesh while decode/ragged rounds keep
        # running, and their KV lands through the PR 4 import
        # primitives. Multihost and pipeline-parallel engines are out
        # (the ring manager drives single-process device enqueues); a
        # host without tp*sp devices degrades loudly to chunked-only.
        self.long_prefill = None
        if (
            config.long_prefill_threshold is not None
            and config.context_parallel_size > 1
            and not config.multihost
            and config.pipeline_parallel_size == 1
        ):
            from production_stack_tpu.engine.long_prefill import (
                LongPrefillManager,
            )

            try:
                self.long_prefill = LongPrefillManager(
                    self.runner,
                    chunk_tokens=config.long_prefill_chunk,
                )
            except Exception as e:  # noqa: BLE001 — not enough devices
                # for the ring mesh, or a mesh build failure: serve
                # every prompt chunked instead of refusing to boot
                logger.warning(
                    "long-prefill lane DISABLED (%s); prompts past "
                    "%d tokens will serve via chunked prefill",
                    e, config.long_prefill_threshold,
                )
            else:
                self.scheduler.config.long_prefill_threshold = (
                    config.long_prefill_threshold
                )
                self.scheduler.long_prefill = self._begin_long_prefill
        # speculative decoding works under multihost too: verify_batch
        # is part of the broadcast protocol (multihost_engine.py), so
        # followers replay the same packed verify host 0 dispatches
        self._spec_enabled = config.num_speculative_tokens > 0
        # lifetime counters for /metrics
        self._prompt_tokens_total = 0
        self._generation_tokens_total = 0
        self._preemptions_total = 0
        self._finished_total = 0
        self._spec_drafts_total = 0
        self._spec_accepted_total = 0

        # -- KV offload tiers + controller reporting (LMCache-equivalent) --
        self.kv_reporter = None
        self.offload = None
        if config.kv_controller_url:
            from production_stack_tpu.kv.controller import ControllerReporter

            self.kv_reporter = ControllerReporter(
                config.kv_controller_url,
                instance_id=config.kv_instance_id,
                url=config.kv_instance_id,
                block_size=config.block_size,
                snapshot_fn=self._kv_snapshot,
            )
        from production_stack_tpu.kv.offload import build_offload_manager

        # -- disaggregated-prefill consumer side (reference capability:
        # decode pod pulls KV produced by the prefill pod via NIXL; ours
        # pulls content-addressed chains through a PeerTier that rides
        # the offload manager's pending-READ map — the transport-
        # agnostic fetch interface — so the staged-restore path below
        # handles peer pulls with ZERO blocking socket IO on the
        # scheduler thread, kv/peer.py) -----------------------------------
        self.kv_peer = None
        _peer_spec = (config.kv_transfer_config or {}).get("peer")
        if _peer_spec and config.kv_role != "prefill":
            from production_stack_tpu.kv.peer import PeerTier

            self.kv_peer = PeerTier(_peer_spec)
        self.offload = build_offload_manager(
            config, self.kv_reporter, peer=self.kv_peer
        )
        if self.kv_reporter is not None:
            bm = self.block_manager
            bm.on_admit = lambda hs: self.kv_reporter.admit("hbm", hs)
            bm.on_evict = lambda hs: self.kv_reporter.evict("hbm", hs)
        # zero-stall KV tiering: deferred export (freed blocks pinned,
        # d2h snapshot enqueued after the step's dispatch, tier IO on
        # the offload worker) + staged restore (tier fetch + h2d start
        # while the request WAITS; admission lands once the restore
        # does). sync_kv_offload keeps the pre-PR-4 synchronous path:
        # multihost always takes it (the broadcast wire ships host
        # arrays, not device buffers), and the tests compare against it.
        self._kv_async = (
            self.offload is not None
            and not config.sync_kv_offload
            and not config.multihost
        )
        # deferred-export queue: (block_id, hash) pairs pinned against
        # reuse until _flush_kv_exports enqueues their device snapshot
        self._kv_export_pending: list[tuple[int, int]] = []
        self._kv_export_queued: set[int] = set()
        # staged restores by request_id (see _begin_kv_restore)
        self._kv_restores: dict[str, dict] = {}
        # histogram observations drained by the server's stats loop
        # (deque appends/pops are GIL-atomic: the export side appends
        # from the offload worker thread)
        from collections import deque as _deque

        self._kv_export_obs: _deque = _deque(maxlen=1024)
        self._kv_restore_obs: _deque = _deque(maxlen=1024)
        # prefill-lane count per fused ragged round, drained into the
        # tpu:ragged_lane_mix histogram (appends/pops GIL-atomic)
        self._ragged_obs: _deque = _deque(maxlen=4096)
        self._kv_export_seconds_total = 0.0
        self._kv_export_blocks_total = 0
        self._kv_export_bytes_total = 0
        self._kv_restore_seconds_total = 0.0
        self._kv_restore_blocks_total = 0
        self._kv_restore_bytes_total = 0
        self._kv_restore_fallbacks_total = 0
        # wall seconds spent in SYNCHRONOUS tier exports (backlog-cap
        # degradations + --sync-kv-offload): the overflow-export slice
        # of a long prefill's TTFT attribution reads the delta of this
        # + the worker-side export seconds over the job's lifetime
        self._kv_export_sync_seconds_total = 0.0
        # high-water anchor for that attribution: overlapping long
        # jobs must not each claim the SAME export seconds (the
        # cumulative tpu:prefill_overflow_export_seconds would outgrow
        # the actual export wall) — each finalize claims only the
        # window past the last claim
        self._long_overflow_anchor = 0.0
        if self.offload is not None and (
            self.offload.tiers or self.offload.remote is not None
        ):
            # export hooks only where there is somewhere to export TO
            # (local tiers or the shared cache server's write-through):
            # a peer-only manager (pure PD decode engine) must not pin
            # and d2h-snapshot freed blocks into an empty cascade
            if self._kv_async:
                self.block_manager.on_freed_cached = (
                    self._queue_freed_exports
                )
                self.scheduler.kv_flush = self._flush_kv_exports
            else:
                self.block_manager.on_freed_cached = (
                    self._offload_freed_blocks
                )

        if self.offload is not None:
            self.scheduler.kv_restore = self._restore_from_offload

    # -- KV offload integration -------------------------------------------
    def _kv_snapshot(self) -> dict[str, list[int]]:
        """Full tier->hashes state for controller (re)registration replay."""
        out = {"hbm": list(self.block_manager.cached_blocks.keys())}
        if self.offload is not None:
            out.update(self.offload.snapshot())
        return out

    def _offload_freed_blocks(self, pairs: list[tuple[int, int]]) -> None:
        """SYNCHRONOUS export path (--sync-kv-offload / multihost):
        cached blocks just became evictable -> batched d2h export inside
        scheduling -> tiers."""
        pairs = [(bid, h) for bid, h in pairs if not self.offload.contains(h)]
        self._export_sync(pairs)

    def _export_sync(self, pairs: list[tuple[int, int]]) -> None:
        """Blocking export of (block_id, hash) pairs on the CALLING
        thread: the --sync-kv-offload path and the async path's
        backlog-cap degradation share this one copy of the wire-layout
        slicing."""
        if not pairs:
            return
        t0 = time.monotonic()
        data = self.runner.export_blocks([bid for bid, _ in pairs])
        # per-block contiguous copies: a view of the batched export array
        # would pin the WHOLE export alive in the CPU tier until every
        # sibling block is evicted, blowing the tier's byte accounting
        self.offload.put_batch(
            [
                (h, np.ascontiguousarray(data[:, :, i]))
                for i, (_, h) in enumerate(pairs)
            ]
        )
        self._kv_export_sync_seconds_total += time.monotonic() - t0

    def _queue_freed_exports(self, pairs: list[tuple[int, int]]) -> None:
        """Deferred export (the zero-stall path): freed-but-cached
        blocks are PINNED against reuse and queued; _flush_kv_exports
        enqueues their device snapshot at the end of the step (after
        the dispatch, so the d2h overlaps compute) and the blocking
        materialization + tier IO run on the offload worker."""
        fresh: list[tuple[int, int]] = []
        pin: list[int] = []
        for bid, h in pairs:
            if h in self._kv_export_queued:
                pin.append(bid)  # re-freed before the snapshot: re-pin
                continue
            if self.offload.contains(h):
                continue
            fresh.append((bid, h))
            pin.append(bid)
            self._kv_export_queued.add(h)
        if pin:
            self.block_manager.pin_for_export(pin)
        self._kv_export_pending.extend(fresh)

    # in-flight deferred-export batches before the flush degrades to a
    # synchronous (stalling, counted) export: device gather buffers
    # queued behind a slow tier must not OOM HBM
    KV_EXPORT_BACKLOG_CAP = 4

    # stackcheck: hot-path — runs on the step thread between/after
    # device dispatches: may only ENQUEUE the device-side snapshot; the
    # blocking d2h + tier IO happen on the offload worker (the
    # backlog-cap branch is the deliberate, counted exception)
    def _flush_kv_exports(self) -> bool:
        """Enqueue the deferred-export snapshot and release the pins.
        Device ops execute in enqueue order, so later dispatches cannot
        overwrite the snapshot — unpinning here is safe. Returns True
        when anything was flushed (scheduler retry contract)."""
        pending = self._kv_export_pending
        if not pending:
            return False
        self._kv_export_pending = []
        self._kv_export_queued.clear()
        bids = [bid for bid, _ in pending]
        try:
            if self.offload.export_backlog() >= self.KV_EXPORT_BACKLOG_CAP:
                # backpressure: each queued batch pins DEVICE gather
                # buffers until the worker materializes it — under
                # eviction churn faster than tier IO, HBM must not
                # become the overflow buffer. Materialize THIS batch on
                # the step thread (a bounded stall — the old
                # synchronous behavior) instead of growing the queue.
                self._export_sync(pending)
                return True
            handle = self.runner.stage_export_blocks(bids)
            self.offload.put_batch_async(
                [h for _, h in pending], handle,
                self.runner.materialize_export, self._note_kv_export,
            )
        except Exception:  # noqa: BLE001 — export is best-effort: a
            # failed gather (e.g. device OOM sizing the snapshot) drops
            # the batch, it must not kill the step or leak the pins
            logger.exception("kv export staging failed; batch dropped")
        finally:
            # pins release even on failure — a leaked pin would shrink
            # the KV pool permanently (the snapshot, when it succeeded,
            # is already enqueued, so release stays ordering-safe)
            self.block_manager.unpin_exported(bids)
        return True

    def _note_kv_export(
        self, seconds: float, blocks: int, nbytes: int
    ) -> None:
        """Offload-worker callback when a deferred export batch lands
        (GIL-atomic appends/adds only; no locks shared with the step
        thread)."""
        self._kv_export_obs.append(seconds)
        self._kv_export_seconds_total += seconds
        self._kv_export_blocks_total += blocks
        self._kv_export_bytes_total += nbytes

    # -- staged restore ----------------------------------------------------
    # outstanding restore records (fetching or staged) before new
    # enqueue-time restores stop being started: each record's completed
    # reads park host arrays in the offload manager until consumed, so
    # a deep waiting queue must not buffer every request's chain in
    # host RAM at once. The admission head bypasses the cap (force) —
    # it consumes its record next.
    KV_RESTORE_FETCH_CAP = 8

    def _begin_kv_restore(
        self, seq: Sequence, force: bool = False
    ) -> tuple[dict | None, list[int] | None]:
        """Start the async restore for a request: find the offload-tier
        chain continuation past the resident HBM prefix (cheap host-map
        probes only) and queue its tier reads on the offload worker.
        Called when the request enters the waiting queue, so the fetch
        (and then the h2d staging) overlaps the queue wait. Returns
        (record, hashes) — hashes also on a no-restore miss, so the PD
        pull never re-hashes the prompt."""
        if not force and len(self._kv_restores) >= \
                self.KV_RESTORE_FETCH_CAP:
            # the admission hook re-begins with force=True
            return None, None
        bm = self.block_manager
        if seq.sampling_params.prompt_logprobs is not None:
            # the scheduler allocates these with reuse_cache=False
            # (every position must COMPUTE) — a restored prefix would
            # be ignored, so fetching + deferring for it is pure waste
            return None, None
        # ONE hashing pass per admission: the chain is computed here,
        # reused by staging, finalize and the PD pull, and left on the
        # sequence for admission's prefix match and the registration
        hashes = bm.block_hashes_for(
            seq.prompt_token_ids, seq.hash_seed, seq.block_hashes
        )
        if not hashes:
            return None, hashes
        # cap the fetch at what could ever be adopted: the pool's usable
        # blocks (minus the null block) and the model-length ceiling.
        # Beyond that the blocks cannot land in HBM anyway, and the cap
        # keeps the staged width inside precompile_kv_import's warmed
        # pow2 diagonal (no XLA compile inside a live admission)
        cap = min(
            bm.num_blocks - 1,
            self.scheduler.config.max_model_len // bm.block_size,
        )
        has_chain = self.offload.has_chain_source()
        i = 0
        want: list[int] = []   # ordered fetch list (local + chain)
        local: list[int] = []  # hashes a local tier claims to hold
        remote: list[int] = []  # tail a chain source may hold (1 pull)
        while i < len(hashes) and len(want) < cap:
            h = hashes[i]
            if bm.contains_hash(h):
                i += 1  # already resident: nothing to fetch
                continue
            if self.offload.contains_local(h):
                # per-block local tier reads (pending/cpu/disk); blocks
                # this engine pushed to the shared cache deliberately
                # fall through to the chain branch — one get_chain pull
                # beats a per-block network get each
                want.append(h)
                local.append(h)
            elif has_chain:
                # past the local continuation the PD peer or the shared
                # cache server may still hold the chain (a peer just
                # prefilled this prompt, or a sibling engine pushed the
                # prefix) — the whole tail rides ONE get_chain pull on
                # the offload worker
                want.append(h)
                remote.append(h)
            else:
                break  # chain continuation ends here
            i += 1
        if not want:
            return None, hashes
        if local:
            self.offload.request_reads(local)
        if remote:
            self.offload.request_chain_reads(remote)
        rec = {
            "rid": seq.request_id,
            "hashes": hashes,
            "want": want,
            # pure-chain records (no local tier claimed anything) that
            # come back empty are COLD PROMPTS neither the PD peer nor
            # the shared cache ever held (e.g. a resume's new tail) —
            # finalize must not count them as restore fallbacks
            # (kv_peer_misses / kv_remote_misses already carry that
            # signal)
            "peer_only": bool(remote) and not local,
            "state": "fetching",
            "t0": time.monotonic(),
            "handle": None,
            "cols": {},
            "col_bytes": [],
            "col_tiers": [],
        }
        self._kv_restores[seq.request_id] = rec
        return rec, hashes

    # staged (device-buffer-holding) restores allowed at once: the
    # restore mirror of KV_EXPORT_BACKLOG_CAP — a burst of waiting
    # requests must not land every chain's wire-format KV in HBM at
    # once. Dict order is insertion order (enqueue ≈ FIFO), so the
    # oldest records stage first; the admission head bypasses the cap
    # via _restore_from_offload (it lands and frees its buffer next).
    KV_RESTORE_STAGED_CAP = 4

    def _poll_kv_restores(self) -> None:
        """Advance in-flight restores (start the h2d for completed
        fetches) so uploads overlap whatever the engine is doing — not
        just the owning request's admission attempts."""
        staged = sum(
            1 for r in self._kv_restores.values()
            if r["state"] == "staged"
        )
        for rec in list(self._kv_restores.values()):
            if rec["state"] != "fetching":
                continue  # already staged/failed: not a cap candidate
                # (counting it again would halve the effective cap)
            if staged >= self.KV_RESTORE_STAGED_CAP:
                break
            try:
                self._advance_kv_restore(rec)
                if rec["state"] == "staged":
                    staged += 1
            except Exception:  # noqa: BLE001 — same contract as the
                # scheduler's kv_restore guard: a staging failure
                # (device_put OOM, corrupt tier read shape) must never
                # kill the step loop — this request simply recomputes
                logger.exception(
                    "kv restore staging failed for %s; recomputing",
                    rec["rid"],
                )
                self._mark_restore_failed(rec)

    # stackcheck: hot-path — restore staging on the step thread:
    # assemble the host batch and START its h2d (device_put enqueue);
    # no device fetch, no tier IO (reads completed on the worker)
    def _advance_kv_restore(self, rec: dict) -> None:
        if rec["state"] != "fetching":
            return
        done = self.offload.poll_reads(rec["want"])
        if len(done) < len(rec["want"]):
            return  # worker still fetching
        usable: list[tuple[int, np.ndarray, str]] = []
        for h in rec["want"]:
            arr, tier = done[h]
            if arr is None:
                break  # mid-restore failure: the tail recomputes
            usable.append((h, arr, tier))
        self.offload.discard_reads(rec["want"])
        # references are released: leave "fetching" NOW so a staging
        # exception below cannot make _drop_kv_restore discard a second
        # time (which would strip a concurrent shared-prefix restore's
        # references and starve it)
        rec["state"] = "failed"
        if not usable:
            rec["nothing_fetched"] = True
            return
        data = np.stack([a for _, a, _ in usable], axis=2)
        rec["handle"] = self.runner.stage_import_blocks(data)
        rec["cols"] = {h: j for j, (h, _, _) in enumerate(usable)}
        # per-column attribution so finalize can report what was
        # ADOPTED, not what was staged (partial adoption must not
        # inflate bytes-per-block)
        rec["col_bytes"] = [int(a.nbytes) for _, a, _ in usable]
        rec["col_tiers"] = [tier for _, _, tier in usable]
        rec["state"] = "staged"

    def _finalize_kv_restore(self, seq: Sequence, rec: dict) -> None:
        """Admission-time landing: re-validate the staged window against
        the CURRENT cache (the chain must still connect from the
        resident prefix — content-addressed hashes ARE the fingerprint;
        any break falls back to recompute from the break) and scatter
        the adopted blocks in place via the donated import."""
        self._kv_restores.pop(rec["rid"], None)
        if rec["state"] != "staged":
            if not (rec.get("peer_only") and rec.get("nothing_fetched")):
                # an empty PURE-CHAIN fetch is a cold prompt neither
                # the peer nor the shared cache held, not a failed
                # restore (kv_peer_*/kv_remote_* carry that signal);
                # everything else — local chain break, staging error,
                # timeout — still counts
                self._kv_restore_fallbacks_total += 1
            return
        bm = self.block_manager
        if self._kv_export_pending:
            # release export pins so adoption can claim free blocks
            self._flush_kv_exports()
        cols = rec["cols"]
        hashes = rec["hashes"]  # computed once at _begin_kv_restore
        bids: list[int] = []
        src: list[int] = []
        adopted: list[int] = []
        i = 0
        while i < len(hashes):
            h = hashes[i]
            if bm.contains_hash(h):
                i += 1
                continue
            j = cols.get(h)
            if j is None:
                break  # staged window over (or chain moved): recompute
            if not bm.can_adopt_another(len(bids)):
                rec["hbm_full"] = True  # only OUR adoptions left to
                break  # evict: adopting more would cannibalize them
            bid = bm.adopt_cached_block(h)
            if bid is None:
                rec["hbm_full"] = True  # pool exhausted: partial
                break
            bids.append(bid)
            src.append(j)
            adopted.append(h)
            i += 1
        if bids and not self._import_restored(bids, adopted,
                                              rec["handle"], src):
            bids = []
            src = []  # nothing landed: no tier-served attribution
            rec["import_failed"] = True
        seconds = time.monotonic() - rec["t0"]
        tiers: dict[str, int] = {}
        for j in src:
            t = rec["col_tiers"][j]
            tiers[t] = tiers.get(t, 0) + 1
        if bids:
            self._kv_restore_obs.append(seconds)
            self._kv_restore_seconds_total += seconds
            self._kv_restore_blocks_total += len(bids)
            self._kv_restore_bytes_total += sum(
                rec["col_bytes"][j] for j in src
            )
        elif i < len(hashes) or rec.get("import_failed"):
            # adoption was CUT SHORT (chain break / full HBM) or the
            # import failed — a walk that reached the end restoring
            # nothing means everything was already resident (e.g. a
            # shared prefix another request landed first): best case,
            # not a fallback
            self._kv_restore_fallbacks_total += 1
        if self._tl_enabled:
            self.timeline.event(
                seq.request_id, "kv_restore",
                {
                    "tiers": tiers,
                    "blocks": len(bids),
                    "seconds": round(seconds, 6),
                },
            )

    def _import_restored(
        self, bids: list[int], adopted: list[int], handle: tuple,
        src: list[int],
    ) -> bool:
        """Land adopted blocks via the donated scatter; on failure
        UN-ADOPT them — a cache entry whose KV contents were never
        written would silently serve garbage to every later prefix hit
        on its hash. Returns True when the import landed."""
        try:
            self.runner.import_staged_blocks(bids, handle, src)
            return True
        except Exception:  # noqa: BLE001 — e.g. stale wrong-shape tier
            # data after a model swap; the request just recomputes
            logger.exception(
                "kv import failed; dropping %d adopted blocks", len(bids)
            )
            for h in adopted:
                self.block_manager.drop_cached_block(h)
            return False

    def _drop_kv_restore(self, request_id: str) -> None:
        """Forget a request's staged restore (abort / admission abort)."""
        rec = self._kv_restores.pop(request_id, None)
        if rec is not None and rec["state"] == "fetching":
            self.offload.discard_reads(rec["want"])

    def _mark_restore_failed(self, rec: dict) -> None:
        """Park a failed restore as state='failed' but KEEP the record:
        the owning request's next admission attempt consumes it (one
        fallback, recompute, proceed). Dropping the record instead
        would let _begin_kv_restore re-create it fresh each step — a
        deterministically failing restore (e.g. stale wrong-shape tier
        files after a model swap) would then defer the FIFO head
        forever on a renewed wait budget."""
        if rec["state"] == "fetching":
            self.offload.discard_reads(rec["want"])
        rec["state"] = "failed"

    def _restore_from_offload(self, seq: Sequence):
        """Scheduler admission hook. Async mode: poll/stage/land the
        request's staged restore — returns False to keep the request
        WAITING while its tier fetch + h2d are in flight (bounded by
        kv_restore_wait_s, then recompute). Sync mode: the original
        blocking restore. Always returns truthy once admission may
        proceed."""
        if not self._kv_async:
            self._restore_sync(seq)
            return True
        bm = self.block_manager
        if not bm.enable_prefix_caching:
            return True
        rec = self._kv_restores.get(seq.request_id)
        if rec is None:
            # no record (preempted requeue, fetch-cap skip, or blocks
            # offloaded after enqueue): begin the ASYNC fetch now —
            # still no tier IO on this thread (fallback paths go
            # through the worker's pending-read map too, and PD peer
            # pulls ride the same staged restore as chain reads).
            # _kv_async guarantees self.offload is set here.
            rec, _hashes = self._begin_kv_restore(seq, force=True)
            if rec is None:
                return True
        try:
            self._advance_kv_restore(rec)
        except Exception:  # noqa: BLE001 — staging failure (device_put
            # OOM, corrupt tier shape): recompute, never kill the step.
            # The record parks as 'failed' and finalize consumes it
            # below — recreating it would retry a deterministic failure
            # forever (see _mark_restore_failed)
            logger.exception(
                "kv restore staging failed for %s; recomputing",
                seq.request_id,
            )
            self._mark_restore_failed(rec)
        if rec["state"] == "fetching":
            # the wait budget covers how long the request HOLDS its
            # admission slot, not its whole queue life — a fetch that
            # ran concurrently with a long queue wait (or a priority
            # displacement from the head) must not arrive back with
            # its budget already spent. Consecutive deferrals of the
            # SAME request are one scheduling round apart; gaps beyond
            # that mean the request was not blocking anyone, so they
            # don't bill the budget.
            now = time.monotonic()
            last = rec.get("last_defer")
            if last is not None:
                # bill at most ~one engine round per deferral: a long
                # gap means the request was displaced from the head
                # (not holding anyone up) — but it must still accrue
                # SOMETHING, or rounds slower than the cap would let a
                # wedged tier defer the FIFO head forever
                rec["held_s"] = (
                    rec.get("held_s", 0.0) + min(now - last, 1.0)
                )
            rec["last_defer"] = now
            if rec.get("held_s", 0.0) < self.config.kv_restore_wait_s:
                return False
            # wedged/slow tier or dead PD peer: recompute rather than
            # stall admission (the peer pull already rode the staged
            # fetch — no second, blocking pull happens here)
            logger.warning(
                "kv restore for %s held admission %.1fs; recomputing",
                seq.request_id, self.config.kv_restore_wait_s,
            )
            self._drop_kv_restore(seq.request_id)
            self._kv_restore_fallbacks_total += 1
            return True
        self._finalize_kv_restore(seq, rec)
        return True

    def _restore_sync(self, seq: Sequence) -> None:
        """Pre-PR-4 synchronous restore: blocking tier reads on the
        scheduler thread (--sync-kv-offload and
        multihost engines)."""
        bm = self.block_manager
        if not bm.enable_prefix_caching:
            return
        hashes = bm.block_hashes_for(
            seq.prompt_token_ids, seq.hash_seed, seq.block_hashes
        )
        matched, _ = bm.match_prefix(
            seq.prompt_token_ids, seq.hash_seed, seq.block_hashes
        )
        restore: list[tuple[int, np.ndarray]] = []  # (block_id, data)
        adopted: list[int] = []
        i = len(matched)
        hbm_full = False
        if self.offload is not None:
            while i < len(hashes):
                h = hashes[i]
                if bm.contains_hash(h):
                    break  # already back in HBM (another seq restored it)
                arr = self.offload.get(h)
                if arr is None:
                    break  # local chain broken; try the PD peer below
                if not bm.can_adopt_another(len(restore)):
                    hbm_full = True  # see can_adopt_another
                    break
                bid = bm.adopt_cached_block(h)
                if bid is None:
                    hbm_full = True  # no room: a network pull is pointless
                    break
                restore.append((bid, arr))
                adopted.append(h)
                i += 1
        self._import_restored_host(restore, adopted)
        if not hbm_full:
            self._pd_transfer_restore(seq, hashes)

    def _pd_transfer_restore(
        self, seq: Sequence, hashes: list[int] | None = None
    ) -> None:
        """SYNC-MODE chain-source pull: one batched blocking round-trip
        from the PD peer (then the shared cache server) for whatever
        the local tiers could not supply. Only reachable from
        _restore_sync (--sync-kv-offload and
        multihost engines) — the zero-stall async path routes chain
        pulls through the staged restore's pending-READ map instead
        (request_chain_reads), so no socket ever runs on the scheduler
        thread there. `hashes` is the precomputed chain when the caller
        already has it (one hashing pass per admission)."""
        if self.offload is None or not self.offload.has_chain_source():
            return
        bm = self.block_manager
        if hashes is None:
            hashes = bm.block_hashes_for(
                seq.prompt_token_ids, seq.hash_seed, seq.block_hashes
            )
        i = 0
        while i < len(hashes) and bm.contains_hash(hashes[i]):
            i += 1
        if i >= len(hashes):
            return
        blocks: list[np.ndarray] = []
        for source in self.offload.chain_sources():
            if i + len(blocks) >= len(hashes):
                break
            # a source serving only a short prefix hands the UNSERVED
            # TAIL to the next one — same contract as the async path's
            # _do_chain_read (a peer that evicted most of a chain the
            # shared cache still holds must not force a recompute)
            got, _addr = source.get_chain(hashes[i + len(blocks):])
            blocks.extend(got)
        if not blocks:
            return
        restore: list[tuple[int, np.ndarray]] = []
        adopted: list[int] = []
        for j, arr in enumerate(blocks):
            if not bm.can_adopt_another(len(restore)):
                break  # see can_adopt_another
            bid = bm.adopt_cached_block(hashes[i + j])
            if bid is None:
                break
            restore.append((bid, arr))
            adopted.append(hashes[i + j])
        self._import_restored_host(restore, adopted)

    def _import_restored_host(
        self, restore: list[tuple[int, np.ndarray]], adopted: list[int]
    ) -> None:
        """import_blocks with the same un-adopt-on-failure contract as
        _import_restored (sync restore + PD pull paths)."""
        if not restore:
            return
        try:
            self.runner.import_blocks(
                [bid for bid, _ in restore],
                np.stack([a for _, a in restore], axis=2),
            )
        except Exception:  # noqa: BLE001 — see _import_restored
            logger.exception(
                "kv import failed; dropping %d adopted blocks",
                len(restore),
            )
            for h in adopted:
                self.block_manager.drop_cached_block(h)

    def drain_kv_observations(self) -> tuple[list[float], list[float]]:
        """(export_seconds, restore_seconds) observations accumulated
        since the last drain — feeds the server's tpu:kv_export_seconds
        / tpu:kv_restore_seconds histograms. Deque pops are GIL-atomic
        vs the worker's appends."""
        exp: list[float] = []
        rst: list[float] = []
        while True:
            try:
                exp.append(self._kv_export_obs.popleft())
            except IndexError:
                break
        while True:
            try:
                rst.append(self._kv_restore_obs.popleft())
            except IndexError:
                break
        return exp, rst

    # -- long-prefill lane (context-parallel ring prefill) ------------------
    def _begin_long_prefill(self, seq: Sequence) -> bool:
        """Scheduler admission hook: claim an admitted long prompt for
        the ring lane. Declines (-> chunked path) for adapter requests
        (the ring runs base weights only) and prompt_logprobs (the ring
        fetches only the final row's logits)."""
        mgr = self.long_prefill
        if mgr is None:
            return False
        if seq.lora_name is not None:
            return False
        if seq.sampling_params.prompt_logprobs is not None:
            return False
        # anchor for the overflow-export attribution: tier-export
        # seconds that accrue while this job is in flight are the HBM
        # headroom the landed chain displaced
        export_s0 = (
            self._kv_export_seconds_total
            + self._kv_export_sync_seconds_total
        )
        if not mgr.start(seq, export_s0=export_s0):
            return False
        seq.long_prefill_active = True
        if seq.metrics.first_scheduled_time is None:
            seq.metrics.first_scheduled_time = time.time()
        return True

    def _advance_long_prefills(self) -> tuple[list[Sequence], bool]:
        """One engine step's worth of long-prefill progress (chunk
        dispatch / batch landing — see LongPrefillManager.advance) plus
        finalization of completed jobs: the sequence's chain is fully
        landed in the paged cache, so sample its first token host-side
        and hand it to the normal decode path. Returns (stepped
        sequences, progressed)."""
        mgr = self.long_prefill
        done, failed, progressed = mgr.advance()
        stepped: list[Sequence] = []
        for rec in failed:
            seq = rec["seq"]
            if seq.request_id in self._seqs and not seq.finished:
                # the block table is already allocated; the chunked
                # planners pick the sequence up next schedule()
                seq.long_prefill_active = False
                logger.warning(
                    "long prefill failed for %s; serving via chunked "
                    "prefill", seq.request_id,
                )
        for rec in done:
            seq = rec["seq"]
            if (
                seq.finished
                or seq.request_id not in self._seqs
                or not seq.long_prefill_active
            ):
                continue  # aborted/preempted while the last batch landed
            seq.long_prefill_active = False
            new_tokens = seq.num_prompt_tokens - seq.num_computed_tokens
            seq.num_computed_tokens = seq.num_prompt_tokens
            self._prompt_tokens_total += max(0, new_tokens)
            export_now = (
                self._kv_export_seconds_total
                + self._kv_export_sync_seconds_total
            )
            # claim only the export window past BOTH this job's start
            # and the last claim — overlapping jobs share the seconds
            # instead of each counting them (see _long_overflow_anchor)
            anchor = max(
                rec.get("export_s0", export_now),
                self._long_overflow_anchor,
            )
            overflow_s = max(0.0, export_now - anchor)
            self._long_overflow_anchor = export_now
            mgr.phase_s["overflow"] += overflow_s
            # first token: host-sampled from the ring's final-row
            # logits (the same host path post-preemption penalty
            # finals take in _run_prefill_works)
            sampled, used_logits = self._sample(
                [seq], rec["logits"][None], return_logits=True
            )
            entry = None
            n_lp = seq.sampling_params.logprobs
            if n_lp is not None:
                entry = self._host_logprob_entry(
                    # stackcheck: disable=device-sync-transitive — the
                    # long-prefill first-token logprob row materializes
                    # only when the request asked for logprobs
                    np.asarray(used_logits)[0], int(sampled[0]), n_lp
                )
            if self._tl_enabled:
                self.timeline.event(
                    seq.request_id, "long_prefill",
                    {
                        "prompt_tokens": rec["n"],
                        "chunk_tokens": mgr.chunk,
                        "chunks": rec["ring_end"] // mgr.chunk,
                        "blocks_landed": rec["landed_blocks"],
                        "cached_prompt_tokens": (
                            rec["start_block"] * mgr.block_size
                        ),
                        "ring_s": round(rec["ring_s"], 6),
                        "d2h_s": round(rec["d2h_s"], 6),
                        "land_s": round(rec["land_s"], 6),
                        "overflow_s": round(overflow_s, 6),
                    },
                )
            self._append_token(seq, int(sampled[0]), entry)
            stepped.append(seq)
        return stepped, progressed

    def _cancel_long_prefill(self, seq: Sequence) -> None:
        """Drop a sequence's ring job (abort / preemption)."""
        if self.long_prefill is not None:
            self.long_prefill.cancel(seq.request_id)
        seq.long_prefill_active = False

    # -- request lifecycle ------------------------------------------------
    def add_request(
        self,
        request_id: str,
        prompt: str | None = None,
        prompt_token_ids: list[int] | None = None,
        sampling_params: SamplingParams | None = None,
        arrival_time: float | None = None,
        lora_name: str | None = None,
        priority: int = 0,
        traceparent: str | None = None,
    ) -> None:
        if request_id in self._seqs:
            raise ValueError(f"duplicate request_id {request_id!r}")
        if prompt_token_ids is None:
            if prompt is None:
                raise ValueError("need prompt or prompt_token_ids")
            prompt_token_ids = self.tokenizer.encode(prompt)
        if not prompt_token_ids:
            raise ValueError("empty prompt")
        # validated BEFORE admission (a ValueError, the server's 400);
        # already done where the caller came through the async engine
        prompt_token_ids = PromptIds.of(prompt_token_ids)
        sp0 = sampling_params or SamplingParams()
        if sp0.truncate_prompt_tokens is not None:
            from production_stack_tpu.engine.sampling_params import (
                truncate_prompt,
            )

            prompt_token_ids = truncate_prompt(
                prompt_token_ids, sp0, self.scheduler.config.max_model_len
            )
        if sp0.prompt_logprobs is not None:
            from production_stack_tpu.engine.sampler import LOGPROB_CAP

            if sp0.prompt_logprobs > LOGPROB_CAP:
                raise ValueError(
                    f"prompt_logprobs > {LOGPROB_CAP} unsupported"
                )
        if sp0.logit_bias:
            vocab = self.runner.model_config.vocab_size
            bad = [t for t in sp0.logit_bias if t >= vocab]
            if bad:
                raise ValueError(
                    f"logit_bias token ids {bad[:5]} out of range for "
                    f"vocab size {vocab}"
                )
        if sp0.logprobs is not None:
            from production_stack_tpu.engine.sampler import LOGPROB_CAP

            if not 0 <= sp0.logprobs <= LOGPROB_CAP:
                # same DoS class: the fused path slices a CAP-sized axis
                raise ValueError(
                    f"logprobs must be in [0, {LOGPROB_CAP}]"
                )
        if lora_name is not None:
            if self.runner.lora_manager is None:
                raise ValueError(
                    "request names a LoRA adapter but the engine was "
                    "started without --enable-lora"
                )
            self.runner.lora_manager.slot_of(lora_name)  # raises if unknown
        sp = sampling_params or SamplingParams()
        hash_seed = None
        if self.runner.lora_manager is not None:
            hash_seed = self.runner.lora_manager.hash_seed_of(lora_name)
        seq = Sequence(
            request_id=request_id,
            prompt_token_ids=prompt_token_ids,
            sampling_params=sp,
            eos_token_id=self.tokenizer.eos_token_id,
            arrival_time=arrival_time,
            lora_name=lora_name,
            hash_seed=hash_seed,
            priority=int(priority),
        )
        if sp.guided_choice is not None:
            if not sp.guided_choice or not all(
                isinstance(c, str) and c for c in sp.guided_choice
            ):
                raise ValueError(
                    "guided_choice must be a non-empty list of "
                    "non-empty strings"
                )
            try:
                choice_ids = [
                    self.tokenizer.encode(c, add_bos=False)
                    for c in sp.guided_choice
                ]
            except TypeError:  # tokenizer without the add_bos kwarg
                choice_ids = [
                    self.tokenizer.encode(c) for c in sp.guided_choice
                ]
            if any(not ids for ids in choice_ids):
                raise ValueError("guided_choice entries must tokenize "
                                 "to at least one token")
            seq._guided_choices = choice_ids  # type: ignore[attr-defined]
        if (sp.guided_json is not None or sp.guided_regex is not None
                or sp.guided_grammar is not None):
            from production_stack_tpu.engine import structured

            if self.tokenizer.eos_token_id is None:
                # the mask offers EOS as the stop-here move at accepting
                # states; without one a finished constraint would leave
                # the lane unstoppable (and unmaskable at dead ends)
                raise ValueError(
                    "guided decoding requires a tokenizer with an EOS "
                    "token"
                )
            # compile (or fetch cached) the constraint machine; schema/
            # pattern/grammar errors surface here as ValueError -> 400
            kind, spec = (
                ("json", sp.guided_json)
                if sp.guided_json is not None
                else ("regex", sp.guided_regex)
                if sp.guided_regex is not None
                else ("grammar", sp.guided_grammar)
            )
            machine = structured.get_machine(kind, spec)
            seq._guided_machine = machine  # type: ignore[attr-defined]
            seq._guided_state = machine.initial()  # type: ignore[attr-defined]
        self._seqs[request_id] = seq
        self.scheduler.add_seq(seq)
        if self._kv_async and self.block_manager.enable_prefix_caching:
            # staged restore starts the moment the request enters the
            # waiting queue: the tier fetch (offload worker) and then
            # the h2d upload (_poll_kv_restores) overlap the queue wait
            try:
                self._begin_kv_restore(seq)
            except Exception:  # noqa: BLE001 — restore is best-effort;
                # a failure here must not reject the request (admission
                # simply recomputes the prefix)
                logger.exception("kv restore staging failed for %s",
                                 request_id)
        self.timeline.start(
            request_id,
            arrival_time=seq.metrics.arrival_time,
            traceparent=traceparent,
            prompt_tokens=seq.num_prompt_tokens,
            priority=seq.priority,
        )

    def abort_request(self, request_id: str) -> bool:
        seq = self._seqs.pop(request_id, None)
        if seq is None:
            return False
        if self._kv_restores:
            self._drop_kv_restore(request_id)
        if self.long_prefill is not None:
            self._cancel_long_prefill(seq)
        rnd = self._inflight
        if rnd is not None and any(s is seq for s in rnd["seqs"]):
            # the round on the device still writes this sequence's
            # blocks (and its state slot): they are its own until that
            # round's fetch (`_finish_inflight`), where its tokens of
            # the round are dropped and counted as overshoot
            seq.status = SequenceStatus.FINISHED_ABORTED
            rnd["aborted"].append(seq)
            aborted = True
        else:
            aborted = self.scheduler.abort(request_id)
        self.timeline.finish(request_id, "abort")
        return aborted

    def has_request(self, request_id: str) -> bool:
        """True while `request_id` is in flight (GIL-atomic dict probe;
        the server uses it to de-conflict router-supplied ids)."""
        return request_id in self._seqs

    def has_request_prefix(self, request_id: str) -> bool:
        """True while any `<request_id>-c<i>` multi-choice sub-request
        is in flight. list() snapshots the key view atomically so the
        scan never races the step thread's pops; the dict is bounded by
        max_num_seqs + waiting, so the scan is tiny."""
        pref = f"{request_id}-c"
        return any(k.startswith(pref) for k in list(self._seqs))

    def has_unfinished(self) -> bool:
        # a round that started at the last fetch's return is on the
        # device between two step() calls (`drain_round`)
        return self.scheduler.has_unfinished() or self._inflight is not None

    # -- the staged next round (h2d prefetch) ------------------------------
    def _reserve_next_round(self, seqs: list[Sequence], k: int) -> bool:
        """Bounds + block reservation for staging a SECOND fused round
        before the first one's tokens are applied: every lane at least 2K
        tokens from its max_tokens/max_model_len bounds, and tables
        grown to cover both rounds. All-or-nothing growth: allocate
        only after EVERY lane passed its checks, so a late refusal
        never leaves earlier lanes holding speculatively grown block
        tables (advisor r3: the predicate must not have partial side
        effects)."""
        bs = self.block_manager.block_size
        grow = 0
        for s in seqs:
            sp = s.sampling_params
            remaining = sp.max_tokens - s.num_generated - k
            if remaining < (1 if self._device_stop else k):
                # the lane ends in this round. One that ends INSIDE the
                # staged round rides it where the device stops it (the
                # stage ships its budget less K); without device stops
                # final rounds run synchronously
                return False
            if s.num_tokens + 2 * k >= self.scheduler.config.max_model_len:
                return False
            # blocks needed to cover this round + the next one
            need = (s.num_tokens + 2 * k + bs - 1) // bs - len(s.block_table)
            if need > 0:
                grow += need
        if grow > self.block_manager.num_free_blocks:
            return False  # needs preemption: go through schedule()
        for s in seqs:
            ok = self.block_manager.ensure_capacity(
                s.num_tokens + 2 * k, s.block_table
            )
            assert ok  # guaranteed by the free-block precheck above
        return True

    def _can_stage(self, seqs: list[Sequence], k: int) -> bool:
        """True when the NEXT fused round on these same lanes can be
        speculatively staged (h2d prefetch): single device, no waiting
        admission work, no guided lanes, every lane at least 2K tokens
        from its bounds, and block tables growable to cover this round
        plus the staged one (all-or-nothing, _reserve_next_round)."""
        if self.runner.mesh is not None:
            return False  # the staged put is a committed single-device
            # transfer; under a mesh jit would have to reshard it
        if self.scheduler.waiting:
            return False  # admission will change the lane set
        if any(
            not s.prefill_done and not s.long_prefill_active
            for s in self.scheduler.running
        ):
            return False  # the next round carries prefill rows (a
            # lane-typed round, which the ragged stage covers, or the
            # split path's prefill round, which comes before any
            # decode): a pure-decode stage would only be dropped at
            # the next schedule(). A long-lane runner is NOT such a
            # lane — its ring runs outside the round, so pure-decode
            # staging stays live under it
        if any(self._is_guided(s) for s in seqs):
            # the chained dispatch carries no DFA tables; guided lanes
            # resolve each round so their device states re-initialize
            return False
        return self._reserve_next_round(seqs, k)

    def _stage_fingerprint(
        self, seqs: list[Sequence], advance: int = 0
    ) -> tuple:
        """State the staged buffer was built for, as observed at the
        NEXT dispatch: same lanes in the same order, every lane exactly
        `advance` tokens further, block tables untouched since the
        stage's growth, and NO free() anywhere in between (the free
        epoch) — freed block ids can be re-handed to another sequence,
        making a same-length table reference someone else's KV. At
        stage time `advance` is the round's K (the tokens of the round
        on the device are not yet applied).

        The lanes need no entry of their own: a sequence's lane
        (`ModelRunner.decode_lanes`) is a function of the first pages
        of the round's tables in order, and the same sequences in the
        same order with no table freed hold the same first pages. So
        the stage was laid out over the lanes this round takes, and
        the chained `toks_dev[-1]` of the round before sits in them
        already. The dispatch still compares the two maps, and counts
        a stage laid out otherwise as a miss."""
        return (
            tuple(s.request_id for s in seqs),
            tuple(s.num_tokens + advance for s in seqs),
            tuple(len(s.block_table) for s in seqs),
            self.block_manager.free_epoch,
        )

    # stackcheck: not-hot — host-side token bookkeeping over numpy
    # arrays every caller already fetched at its metered fetch point
    def _apply_multi_tokens(
        self, seqs: list[Sequence], toks: np.ndarray, k: int,
        lps: tuple | None = None,
        valid: np.ndarray | None = None,
        round_attrs: dict | None = None,
        lanes: np.ndarray | None = None,
    ) -> None:
        """Apply a fused-K round's (k, b) sampled tokens: sequence j's
        are lane `lanes[j]`'s (`ModelRunner.decode_lanes`; None: lane
        j), in `toks`, `lps` and `valid` alike.
        `lps` = (chosen (k,b), top_vals (k,b,CAP), top_ids (k,b,CAP))
        host arrays when any lane requested logprobs. `valid` = the
        device-stop per-lane valid counts ((b,) int32, full-lane
        padded): rows >= valid[lane] were frozen ON DEVICE (pinned pad,
        no KV/state writes, never sampled) and are skipped without
        touching the overshoot counter — the host takes exactly the
        generated tokens."""
        with self.phases.span("apply"):
            if lanes is None:
                lanes = slice(len(seqs))
            # from here on column j is sequence j's lane
            toks = toks[:, lanes]
            if lps is not None:
                lps = tuple(a[:, lanes] for a in lps)
            # one numpy->python conversion per lane, not one per k*b slot
            vcounts = valid[lanes].tolist() if valid is not None else None
            if vcounts and max(vcounts) < k:
                # every lane froze before the trip count: the device round
                # exited early instead of paying the all-finished tail
                self._decode_early_exit_rounds_total += 1
            # a lane whose tokens can only end it at the last of them
            # takes them in one call; any other lane token by token
            by_token: list[int] = []
            rows = toks.T.tolist() if vcounts is not None else None
            for j, seq in enumerate(seqs):
                if (rows is None or seq.finished
                        or not self._applies_in_one(seq)):
                    by_token.append(j)
                    continue
                tokens = rows[j][:vcounts[j]]
                if tokens:
                    seq.num_computed_tokens = (
                        seq.num_tokens + len(tokens) - 1
                    )
                    self._append_tokens(seq, tokens)
            for i in range(k if by_token else 0):
                for j in by_token:
                    seq = seqs[j]
                    if vcounts is not None and i >= vcounts[j]:
                        continue  # device-frozen rows: pad, never sampled
                    if seq.finished:
                        # host-side stop (stop strings, guided completion,
                        # or the fixed-trip --no-device-stop control): this
                        # slot WAS sampled on device and is now discarded —
                        # the waste class device stops exist to eliminate
                        self._decode_overshoot_tokens_total += 1
                        continue
                    seq.num_computed_tokens = seq.num_tokens
                    entry = None
                    n = seq.sampling_params.logprobs
                    if lps is not None and n is not None:
                        chosen, tv, ti = lps
                        entry = {
                            "token_id": int(toks[i, j]),
                            "logprob": float(chosen[i, j]),
                            "top_logprobs": [
                                {"token_id": int(ti[i, j, m]),
                                 "logprob": float(tv[i, j, m])}
                                for m in range(n)
                            ],
                        }
                    self._append_token(seq, int(toks[i, j]), entry)
            self._note_decode_round(seqs, k, extra_attrs=round_attrs)

    def _note_decode_round(
        self, seqs: list[Sequence], k: int,
        extra_attrs: dict | None = None,
    ) -> None:
        """Per-round decode accounting — the ONE copy shared by the
        fused path (_apply_multi_tokens) and the host-sampled
        single-step branch: tpu:decode_rounds, and one SAMPLED timeline
        tick per request per round (tracing.DECODE_EVENT_EVERY), not
        per token — the k_chosen/lanes_done fields ride the same
        append-only event."""
        self._decode_rounds_total += 1
        if self._tl_enabled:
            lanes_done = sum(1 for s in seqs if s.finished)
            # lane-mix attribution: a split-path decode round carries
            # no prefill lanes; ragged rounds override via extra_attrs
            attrs = {
                "k_chosen": k, "lanes_done": lanes_done,
                "prefill_lanes": 0, "decode_lanes": len(seqs),
                "engine_round": self._round,
            }
            if extra_attrs:
                attrs.update(extra_attrs)
            for seq in seqs:
                if not seq.finished:
                    self.timeline.decode_round(
                        seq.request_id, k, attrs=attrs
                    )

    # -- the step loop ----------------------------------------------------
    # stackcheck: hot-path — may only enqueue (flush = device-snapshot
    # enqueue; the d2h runs on the offload worker)
    def step(self) -> list[RequestOutput]:
        # one `engine.step` per call in a profiler trace, the phases its
        # leaves; outside a profiler session no annotation is made
        ann = None
        if phases.profiling():
            ann = self._step_ann = phases.TraceAnnotation("engine.step")
            self._step_tagged = False
            ann.__enter__()
        try:
            return self._step_impl()
        finally:
            # deferred KV exports flush at the END of every step — after
            # the dispatch, so the d2h snapshot overlaps device compute;
            # on idle/final steps this is the draining path that keeps
            # freed blocks from staying pinned forever
            if self._kv_export_pending:
                self._flush_kv_exports()
            if ann is not None:
                self._step_ann = None
                ann.__exit__(None, None, None)

    def _begin_round(self, kind: str, k: int, lanes: int, rows: int) -> None:
        """Number the round about to be dispatched and, inside a
        profiler session, tag this step's `engine.step` with it: `kind`
        (decode / ragged / prefill / verify), fused steps `k`, live
        decode `lanes` and prefill `rows`. A step that dispatches more
        than one round (chained prefill chunks) is tagged with its
        first; every round still takes a number."""
        self._round += 1
        if self.runner.num_window_blocks:
            held = self._window_blocks_per_seq
            held[0] += self.block_manager.window_blocks_in_use
            held[1] += self.scheduler.num_running
        self._tag_step(self._round, kind, k, lanes, rows)

    def _tag_step(self, number: int, kind: str, k: int, lanes: int,
                  rows: int) -> None:
        """Inside a profiler session, tag this step's `engine.step`
        with a round, unless it carries one already."""
        ann = self._step_ann
        if ann is not None and not self._step_tagged:
            self._step_tagged = True
            ann.set_metadata(round=number, kind=kind, k=k,
                             lanes=lanes, rows=rows)

    # stackcheck: hot-path — a round is dispatched and fetched inside
    # the step that scheduled it; the fetches are the metered
    # `phases.span("fetch")` seams of the round runners below
    def _step_impl(self) -> list[RequestOutput]:
        if self._inflight is not None:
            # the round this step would choose was chosen and started
            # at the last fetch's return
            return self._finish_inflight()
        with self.phases.span("schedule"):
            if self._kv_restores:
                # start h2d uploads for restores whose tier fetch landed
                # while their requests sit in the waiting queue (the
                # upload then overlaps this step's compute)
                self._poll_kv_restores()
            # long-prefill lane: advance ring chunks / KV landing BEFORE
            # scheduling, so a job whose chain just finished landing is
            # decode-ready in THIS round's plan (its first token rides
            # the same step). One enqueue per job per step — never a
            # device fetch — so the decode/ragged rounds below keep
            # their cadence.
            long_stepped: list[Sequence] = []
            long_progress = True
            if self.long_prefill is not None and self.long_prefill.active:
                long_stepped, long_progress = self._advance_long_prefills()
            sched_out = self.scheduler.schedule()
        if sched_out.preempted or sched_out.prefills or sched_out.aborted:
            # any table free/reassignment or lane-set change invalidates
            # the staged prefetch (the epoch in the fingerprint already
            # guarantees this; dropping early frees the device buffer).
            # Exception: a RAGGED round's staged buffer expects prefill
            # lanes — it is validated (or miss-counted) in _step_ragged
            self._staged_decode = None
        if self._staged_ragged is not None and (
            sched_out.preempted or sched_out.aborted
            or not sched_out.is_ragged
        ):
            # the staged lane mix did not materialize (a table was
            # freed, prefill drained, or the round went pure): a COUNTED
            # staging miss — the fingerprint/total-length checks would
            # refuse the buffer anyway, never a dispatch error
            self._ragged_staged_misses_total += 1
            self._staged_ragged = None
        if sched_out.preempted and self.long_prefill is not None:
            # a preempted long-lane sequence lost its block table: its
            # ring job is stale — drop it (reset_for_recompute already
            # cleared the lane flag). A sequence preempted AND
            # re-admitted inside this same schedule() carries the flag
            # again with a FRESH job (manager.start replaced the stale
            # record) — that one must not be cancelled.
            for seq in sched_out.preempted:
                if not seq.long_prefill_active:
                    self.long_prefill.cancel(seq.request_id)
        self._preemptions_total += len(sched_out.preempted)
        self.last_step_kind = (
            "ragged"
            if sched_out.is_ragged
            else "prefill"
            if sched_out.prefills
            else "decode"
            if sched_out.decode is not None
            else "idle"
        )
        if sched_out.is_empty:
            if long_stepped:
                # a long prefill finished with nothing else scheduled:
                # emit its first-token output now
                return self._finalize_stepped(long_stepped)
            if self._kv_restores and not self.scheduler.running:
                # every waiting request is restore-deferred and nothing
                # is dispatchable: yield briefly instead of pegging the
                # step thread (and the async-engine lock) at 100%
                # against the offload worker doing the actual fetch
                # stackcheck: disable=blocking-hot — deliberate 1ms idle
                # yield on the no-dispatchable-work branch (see above)
                time.sleep(0.001)
            elif (
                self.long_prefill is not None
                and self.long_prefill.active
                and not long_progress
            ):
                # only long-prefill work exists and it is waiting on
                # the materialization worker: yield instead of pegging
                # the step thread against the worker's d2h
                # stackcheck: disable=blocking-hot — deliberate 0.5ms
                # idle yield while the worker owns the d2h (see above)
                time.sleep(0.0005)
            return []

        outputs: list[RequestOutput] = []
        for seq in sched_out.aborted:
            seq.metrics.finished_time = time.time()
            self._finished_total += 1
            outputs.append(self._make_output(seq))
            self._seqs.pop(seq.request_id, None)
            if self._kv_restores:
                self._drop_kv_restore(seq.request_id)
            self.timeline.finish(seq.request_id, seq.finish_reason)

        stepped: list[Sequence] = list(long_stepped)
        if sched_out.is_ragged:
            # unified ragged dispatch: prefill-chunk lanes + the decode
            # batch in ONE lane-typed device round (split execution for
            # lane sets the fused program cannot express)
            stepped.extend(
                self._step_ragged(sched_out.prefills, sched_out.decode)
            )
        elif sched_out.prefills:
            # pipelined prefill: a cold group's remaining chunks chain
            # back-to-back in THIS engine round while nothing is
            # decode-ready or waiting (`_chain_next_prefill`)
            works = sched_out.prefills
            stepped.extend(self._run_prefill_works(works))
            for _ in range(self.MAX_CHAINED_PREFILLS):
                works = self._chain_next_prefill(works)
                if works is None:
                    break
                self._pf_chained_chunks_total += len(works)
                stepped.extend(
                    self._run_prefill_works(works, chained=True))
        elif sched_out.decode is not None:
            seqs = sched_out.decode.seqs
            if self._spec_enabled:
                spec = self._try_spec_decode_batch(seqs)
                if spec is not None:
                    stepped.extend(spec)
                    outputs.extend(self._finalize_stepped(stepped))
                    return outputs
            stepped.extend(self._run_decode_round(seqs))

        if long_stepped and len(stepped) > len(long_stepped):
            # a just-finalized long prefill may ALSO have ridden this
            # round's decode batch (its first token made it
            # decode-ready before schedule()): finalize it once
            seen: set[int] = set()
            stepped = [
                s for s in stepped
                if not (id(s) in seen or seen.add(id(s)))
            ]
        outputs.extend(self._finalize_stepped(stepped))
        return outputs

    def _run_decode_round(self, seqs: list[Sequence]) -> list[Sequence]:
        """Dispatch one decode round over `seqs` (the body of the
        decode step, shared by the split path and the ragged round's
        split-execution fallback): the fused K-step on-device path when
        the batch supports it, the host-sampled single-step path
        otherwise. Returns the stepped sequences."""
        stepped: list[Sequence] = []
        k_steps = self.config.num_scheduler_steps
        with self.phases.span("pack"):
            tokens = [s.last_token_id for s in seqs]
            positions = [s.num_tokens - 1 for s in seqs]
            tables = [s.block_table for s in seqs]
            ctx_lens = [s.num_tokens for s in seqs]
            # guided lanes ride the fused multi-step scan via on-device
            # TokenDFA tables (structured.TokenDFA — outlines-style
            # FSM-index compilation); only constraints too large to
            # compile under budget fall back to the host-masked
            # single-step path below
            guided_tables = None
            needs_guided = any(self._is_guided(s) for s in seqs)
            if needs_guided and k_steps > 1:
                # leave the fused path when any guided lane is close to
                # its token budget: the final steps need budget-aware
                # completion steering (_steer_allowed), which only the
                # host-masked path evaluates. Parity with K=1 holds —
                # unsteered steps mask identically on both paths.
                near_budget = any(
                    self._is_guided(s)
                    and (s.sampling_params.max_tokens
                         - s.num_generated)
                    <= k_steps + self.GUIDED_STEER_BOUND
                    for s in seqs
                )
                if not near_budget:
                    guided_tables = self._device_guided_tables(seqs)
        if k_steps > 1 and (not needs_guided
                            or guided_tables is not None):
            with self.phases.span("pack"):
                temps, top_ps, top_ks, min_ps, keys, needs_pen = (
                    self._sampling_arrays(seqs)
                )
                # token-count state rides on device through the scan; only
                # the compact generated-id lists cross the bus
                penalties = self._penalty_args(seqs) if needs_pen else None
                want_lp = any(
                    s.sampling_params.logprobs is not None for s in seqs
                )
                bias = self._bias_arrays(seqs)
                stop = (
                    self._stop_arrays(seqs) if self._device_stop else None
                )
                # where each sequence sits among the round's lanes:
                # everything the pack ships a lane goes there, and
                # everything that comes back a lane is read there
                lanes = self.runner.decode_lanes(tables)
                staged = None
                st = self._staged_decode
                self._staged_decode = None
                if st is not None:
                    if (penalties is None and bias is None
                            and guided_tables is None
                            and st["fp"] == self._stage_fingerprint(seqs)
                            and np.array_equal(st["lanes"], lanes)):
                        # the prediction held: dispatch chained on the
                        # previous round's on-device tokens with the
                        # pre-uploaded packed buffer — zero serial h2d
                        staged = st["handle"]
                        tokens = st["chain_tokens"]
                        self._staged_hits_total += 1
                    else:
                        self._staged_misses_total += 1
            rnd = self._dispatch_decode(
                seqs, k_steps, tokens, positions, ctx_lens,
                (temps, top_ps, top_ks, min_ps, keys), stop, lanes,
                staged=staged, penalties=penalties,
                guided=guided_tables, bias=bias,
            )
            stepped.extend(self._finish_decode_round(rnd))
        else:
            self._begin_round("decode", 1, len(seqs), 0)
            logits = self.runner.decode(
                tokens, positions, tables, ctx_lens,
                lora_slots=[self._lora_slot(s) for s in seqs],
            )
            # host sampling reads the logits: the round's fetch
            with self.phases.span("fetch"):
                sampled, used_logits = self._sample(
                    seqs, logits[: len(seqs)], return_logits=True
                )
                # stackcheck: disable=device-sync-transitive — the ONE
                # intended per-round materialization of the sampled-from
                # logits; logprob entries below index into it row by row
                used_logits = np.asarray(used_logits)
            with self.phases.span("apply"):
                for i, (seq, token) in enumerate(zip(seqs, sampled)):
                    seq.num_computed_tokens = seq.num_tokens
                    entry = None
                    if seq.sampling_params.logprobs is not None:
                        entry = self._host_logprob_entry(
                            used_logits[i], int(token),
                            seq.sampling_params.logprobs,
                        )
                    self._append_token(seq, int(token), entry)
                    stepped.append(seq)
                self._note_decode_round(seqs, 1)
        return stepped

    # -- a fused decode round: dispatch, and what follows its fetch ---------
    def _dispatch_decode(
        self, seqs: list[Sequence], k: int, tokens, positions: list[int],
        ctx_lens: list[int], sampling: tuple, stop: tuple | None,
        lanes: np.ndarray, *, staged: tuple | None = None,
        penalties: tuple | None = None, guided: tuple | None = None,
        bias: tuple | None = None,
    ) -> dict:
        """Number and dispatch ONE fused K-step decode round and return
        its record: what `_finish_decode_round` fetches and applies,
        and what the round after it is staged from. `sampling` =
        (temps, top_ps, top_ks, min_ps, keys) and `stop` (the device
        stop arrays or None) AS THIS ROUND SHIPS THEM. `tokens` is the
        host list of last tokens, or the device array a staged round
        chains on."""
        temps, top_ps, top_ks, min_ps, keys = sampling
        want_lp = any(
            s.sampling_params.logprobs is not None for s in seqs
        )
        # fused on-device decode+sample loop: K tokens per
        # dispatch, ONE device->host fetch
        # stop and staged ride conditional kwargs: the multihost
        # runner wrapper replays host token lists and knows neither
        # stop masks nor a staged buffer (both are off there)
        stop_kw = {"stop": stop} if stop is not None else {}
        staged_kw = {"staged": staged} if staged is not None else {}
        self._begin_round("decode", k, len(seqs), 0)
        ys = self.runner.decode_multi(
            tokens, positions, [s.block_table for s in seqs], ctx_lens, k,
            temps, top_ps, top_ks, keys, min_ps=min_ps,
            lora_slots=[self._lora_slot(s) for s in seqs],
            penalties=penalties,
            want_logprobs=want_lp,
            guided=guided,
            logit_bias=bias,
            lanes=lanes,
            **stop_kw,
            **staged_kw,
        )  # (k, b) on device [+ logprob arrays] [+ valid]
        valid_dev = None
        if stop is not None:
            toks_dev = ys[0]
            valid_dev = ys[-1]
            lps_dev = ys[1:-1] if want_lp else None
        else:
            toks_dev, lps_dev = (
                (ys[0], ys[1:]) if want_lp else (ys, None)
            )
        for out in (toks_dev, valid_dev, *(lps_dev or ())):
            # the results start for the host when the program ends, not
            # when the fetch asks: the fetch's tail lies in the gap
            # before a round that starts at that fetch's return
            if out is not None:
                out.copy_to_host_async()
        return {
            "seqs": seqs, "k": k, "round": self._round, "lanes": lanes,
            "sampling": sampling, "stop": stop,
            "toks": toks_dev, "lps": lps_dev, "valid": valid_dev,
            # per-round host state (penalty counts, DFA tables, bias)
            # does not chain: such a round stages no successor
            "chains": penalties is None and guided is None and bias is None,
            # sequences aborted while this round was in flight across
            # two step() calls: freed at its fetch (`abort_request`)
            "aborted": [],
        }

    def _stage_next_decode(self, rnd: dict) -> None:
        """Upload the PREDICTED inputs of the round after `rnd` now —
        the transfer rides out `rnd`'s fetch; validated by fingerprint
        before any dispatch uses it. `rnd` is dispatched and its tokens
        are not applied: every lane is taken to advance by its K."""
        seqs, k_steps, lanes = rnd["seqs"], rnd["k"], rnd["lanes"]
        temps, top_ps, top_ks, min_ps, keys = rnd["sampling"]
        stop = rnd["stop"]
        nk = keys.copy()
        nk[:, 1] += k_steps
        stage_stop = None
        if stop is not None:
            # the countdowns advance with the k tokens this
            # round will apply (a lane that freezes earlier
            # breaks the fingerprint, so the stale stage is
            # never dispatched)
            stage_stop = (
                stop[0],
                np.maximum(stop[1] - k_steps, 0),
                stop[2] - k_steps,
                stop[3],
            )
        positions = [s.num_tokens - 1 + k_steps for s in seqs]
        ctx_lens = [s.num_tokens + k_steps for s in seqs]
        self._staged_decode = {
            "fp": self._stage_fingerprint(seqs, advance=k_steps),
            "handle": self.runner.stage_decode_multi(
                positions, [s.block_table for s in seqs], ctx_lens,
                k_steps, temps, top_ps, top_ks, nk,
                min_ps=min_ps, stop=stage_stop, lanes=lanes,
            ),
            "lanes": lanes,
            "chain_tokens": rnd["toks"][-1],
            # what the stage was packed from: a round that starts at
            # the fetch's return is dispatched with exactly these
            "positions": positions, "ctx_lens": ctx_lens,
            "sampling": (temps, top_ps, top_ks, min_ps, nk),
            "stop": stage_stop,
        }

    def _starts_at_fetch(
        self, rnd: dict, st: dict, toks: np.ndarray,
        valid: np.ndarray | None,
    ) -> bool:
        """Decided on round `rnd`'s FETCHED arrays, before they are
        applied: will the staged round `st` be the one the next step()
        would dispatch? Then it can start now. The stage itself was
        made a fetch ago under this hold of the caller's lock, so what
        `_can_stage` refused (a mesh, multihost, penalties, logit bias,
        guided lanes, a waiting request, a prefill lane, a lane at its
        bounds) never gets here; a KV restore belongs to a waiting
        request, and deferred KV exports are of blocks that stay
        pinned until their snapshot is enqueued, whichever side of a
        dispatch that falls. What is left to observe:"""
        seqs, k = rnd["seqs"], rnd["k"]
        if self.callers_waiting:
            return False  # an arrival or an abort stands at the lock:
            # it gets in at this step's end, before the next round is
            # chosen, as it always did
        if self._spec_enabled or (
            self.long_prefill is not None and self.long_prefill.active
        ):
            return False  # the next step() may choose a verify round,
            # and advances the long lane's ring by one enqueue: both
            # happen in schedule order, which a round in flight skips
        if any(s.sampling_params.stop for s in seqs):
            return False  # stop strings: a rule only the host
            # evaluates, after rendering, may end a lane in this round
            # (the other such rule, a guided lane, stages nothing)
        if st["fp"] != self._stage_fingerprint(seqs, advance=k):
            return False  # a free since the stage (the epoch)
        lanes = rnd["lanes"]
        if valid is not None and (valid[lanes] != k).any():
            return False  # a lane froze on the device: it ended
        # and no token of the round ends its sequence at the last step
        # (or anywhere, without device stops). An EOS under min_tokens
        # would not: refusing it too costs one early start
        eos, _, _, stop_ids = rnd["stop"] or self._stop_arrays(seqs)
        mine = toks[:, lanes]
        if (mine == eos).any():
            return False
        return stop_ids is None or not (
            mine[:, :, None] == stop_ids[None]).any()

    def _finish_decode_round(self, rnd: dict) -> list[Sequence]:
        """What follows a fused decode round's dispatch, in the step
        that dispatched it or (a round that started at the fetch before
        it, `_inflight`) in the next: stage its successor, fetch it,
        START THE SUCCESSOR where the fetched arrays show the stage
        holds, apply it. Returns the stepped sequences."""
        seqs, k_steps = rnd["seqs"], rnd["k"]
        if (self._prefetch_decode and rnd["chains"]
                and not rnd["aborted"] and self._can_stage(seqs, k_steps)):
            self._stage_next_decode(rnd)
        # materialize the round's results in one place so the d2h
        # cost lands in the fetch phase like other fetches
        with self.phases.span("fetch"):
            # stackcheck: disable=device-sync-transitive — the ONE
            # metered multi-token fetch for this decode round
            toks_np = np.asarray(rnd["toks"])
            lps_np = (
                # stackcheck: disable=device-sync-transitive —
                # logprob arrays exist only when lanes requested
                # them; they ride this round's metered fetch with
                # the tokens
                tuple(np.asarray(a) for a in rnd["lps"])
                if rnd["lps"] else None
            )
            valid_np = (
                # stackcheck: disable=device-sync-transitive —
                # validity mask rides the same metered fetch as the
                # tokens it gates
                np.asarray(rnd["valid"])
                if rnd["valid"] is not None else None
            )
        st = self._staged_decode
        if st is not None and self._starts_at_fetch(
                rnd, st, toks_np, valid_np):
            # the device has every input of the next round: it starts
            # now, and this round's tokens are applied under it
            self._staged_decode = None
            self._staged_hits_total += 1
            self._early_dispatch_total += 1
            self._inflight = self._dispatch_decode(
                seqs, k_steps, st["chain_tokens"], st["positions"],
                st["ctx_lens"], st["sampling"], st["stop"], st["lanes"],
                staged=st["handle"],
            )
        self._apply_multi_tokens(
            seqs, toks_np, k_steps, lps=lps_np, valid=valid_np,
            round_attrs={"engine_round": rnd["round"]},
            lanes=rnd["lanes"],
        )
        return seqs

    def _finish_inflight(self) -> list[RequestOutput]:
        """The step of a round that was dispatched at the fetch before
        it: nothing to schedule, pack or dispatch; its fetch, and what
        `_finish_decode_round` hangs on a fetch."""
        rnd, self._inflight = self._inflight, None
        # the step carries the number of the round it fetches, not of
        # one it may start at that fetch
        self._tag_step(rnd["round"], "decode", rnd["k"], len(rnd["seqs"]), 0)
        self.last_step_kind = "decode"
        for seq in rnd["seqs"]:
            # what the schedule this round did without does for a lane
            # it has chosen: a second cache group lets go of what lies
            # behind the window of the lane's first query of THIS round
            # (no-op with one pool, and for a lane aborted under it)
            self.block_manager.release_behind(
                seq.block_table, seq.num_computed_tokens)
        try:
            stepped = self._finish_decode_round(rnd)
        finally:
            # the round is off the device: what was aborted under it
            # lets its blocks and its state slot go
            for seq in rnd["aborted"]:
                self.scheduler.free_finished(seq)
        gone = {id(s) for s in rnd["aborted"]}
        return self._finalize_stepped(
            [s for s in stepped if id(s) not in gone])

    def drain_round(self) -> list[RequestOutput]:
        """Fetch and apply the round in flight, if there is one, and
        dispatch nothing: before a sleep, a shutdown, or the abort of
        everything after a failed step."""
        if self._inflight is None:
            return []
        # no stage behind the round, so nothing to start at its fetch
        staging, self._prefetch_decode = self._prefetch_decode, False
        try:
            return self._finish_inflight()
        finally:
            self._prefetch_decode = staging

    # -- unified ragged prefill+decode rounds -------------------------------
    def _penalty_args(self, seqs: list[Sequence]) -> tuple:
        """(gen_lists, presence, frequency, repetition) penalty inputs
        for the fused decode scan — shared by _run_decode_round and the
        ragged dispatch path."""
        pres = np.zeros((len(seqs),), np.float32)
        freq = np.zeros((len(seqs),), np.float32)
        rep = np.ones((len(seqs),), np.float32)
        for i, s in enumerate(seqs):
            pres[i] = s.sampling_params.presence_penalty
            freq[i] = s.sampling_params.frequency_penalty
            rep[i] = s.sampling_params.repetition_penalty
        return (
            [list(s.generated_token_ids) for s in seqs],
            pres, freq, rep,
        )

    def _needs_host_first_sample(self, s: Sequence) -> bool:
        """A final prefill chunk whose first token cannot be taken from
        the on-device sample: guided masks, logit_bias, or non-empty
        penalty state after a preemption recompute."""
        sp = s.sampling_params
        if self._is_guided(s):
            return True  # first token must be masked
        if sp.logit_bias:
            return True  # on-device sample knows no bias
        return s.num_generated > 0 and (
            sp.presence_penalty != 0.0
            or sp.frequency_penalty != 0.0
            or sp.repetition_penalty != 1.0
        )

    def _ragged_prefill_fusable(self, works: list[PrefillWork]) -> bool:
        """Prefill lanes the fused ragged program can serve: packed
        chunks with on-device last-row sampling. prompt_logprobs lanes
        (per-row host fetches serialize anyway) and finals needing host
        sampling run the round split instead — same outputs, two
        dispatches."""
        for w in works:
            if w.seq.sampling_params.prompt_logprobs is not None:
                return False
            if w.is_last_chunk and self._needs_host_first_sample(w.seq):
                return False
        return True

    def _step_ragged(
        self, works: list[PrefillWork], dwork
    ) -> list[Sequence]:
        """Execute one planned lane-typed round: prefill-chunk lanes +
        the decode batch in ONE device dispatch when every lane is
        fusable, else split execution of the SAME plan (both halves
        still run this engine step, so the no-interleave-wait
        scheduling contract holds either way)."""
        self._prepare_chunks(works)
        seqs = dwork.seqs
        k_steps = self.config.num_scheduler_steps
        # decode-half gates mirror _run_decode_round's fused path; the
        # ragged program additionally fuses k=1 rounds (host sampling
        # is only needed for near-budget guided steering and
        # constraints too large to compile)
        guided_tables = None
        needs_guided = any(self._is_guided(s) for s in seqs)
        fusable = True
        if needs_guided:
            near_budget = any(
                self._is_guided(s)
                and (s.sampling_params.max_tokens
                     - s.num_generated)
                <= k_steps + self.GUIDED_STEER_BOUND
                for s in seqs
            )
            if near_budget:
                fusable = False
            else:
                guided_tables = self._device_guided_tables(seqs)
                fusable = guided_tables is not None
        if fusable:
            fusable = self._ragged_prefill_fusable(works)
        if not fusable:
            self._ragged_split_rounds_total += 1
            if self._staged_ragged is not None:
                # the staged buffer expects the fused program: counted
                # staging miss, never a dispatch error
                self._ragged_staged_misses_total += 1
                self._staged_ragged = None
            stepped = self._run_prefill_works(works)
            stepped.extend(self._run_decode_round(seqs))
            return stepped
        return self._dispatch_ragged(works, seqs, k_steps, guided_tables)

    def _dispatch_ragged(
        self,
        works: list[PrefillWork],
        seqs: list[Sequence],
        k_steps: int,
        guided_tables: tuple | None,
    ) -> list[Sequence]:
        """The fused lane-typed round: one packed h2d buffer, one
        dispatch, prefill bookkeeping + the shared fused-decode
        bookkeeping afterwards. The h2d-prefetch stage for the NEXT
        round starts before any fetch so its upload overlaps."""
        now = time.time()
        for w in works:
            if w.seq.metrics.first_scheduled_time is None:
                w.seq.metrics.first_scheduled_time = now
        phase_snap = self.phases.seconds() if self._tl_enabled else None
        with self.phases.span("pack"):
            seqs_w = [w.seq for w in works]
            pf_sampling = self._sampling_arrays(seqs_w)[:5]
            pf_chunks = [
                w.seq.prompt_token_ids[
                    w.chunk_start : w.chunk_start + w.chunk_len
                ]
                for w in works
            ]
            pf_budgets = [
                w.seq.num_prompt_tokens - (w.chunk_start + w.chunk_len)
                for w in works
            ]
            temps, top_ps, top_ks, min_ps, keys, needs_pen = (
                self._sampling_arrays(seqs)
            )
            penalties = self._penalty_args(seqs) if needs_pen else None
            want_lp = any(
                s.sampling_params.logprobs is not None for s in seqs
            )
            bias = self._bias_arrays(seqs)
            stop = self._stop_arrays(seqs) if self._device_stop else None
            tokens = [s.last_token_id for s in seqs]
            # the decode sequences' lanes, as in `_run_decode_round`;
            # the prefill lanes stay in the order of `works`
            lanes = self.runner.decode_lanes(
                [s.block_table for s in seqs])
            staged_kw = {}
            st = self._staged_ragged
            self._staged_ragged = None
            if st is not None:
                if (penalties is None and bias is None
                        and guided_tables is None
                        and st["fp"] == self._ragged_fingerprint(
                            works, seqs)
                        and np.array_equal(st["lanes"], lanes)):
                    # the prediction held: chain the decode lanes on the
                    # previous round's on-device tokens with the
                    # pre-uploaded lane-typed buffer — zero serial h2d
                    staged_kw = {"staged": st["handle"]}
                    tokens = st["chain_tokens"]
                    self._ragged_staged_hits_total += 1
                else:
                    # lane-mix / state drift since the stage (and the
                    # runner additionally validates the staged buffer's
                    # total layout length): a counted staging miss — the
                    # dispatch rebuilds + uploads serially, never errors
                    self._ragged_staged_misses_total += 1
        stop_kw = {"stop": stop} if stop is not None else {}
        self._begin_round(
            "ragged", k_steps, len(seqs), sum(len(c) for c in pf_chunks))
        pf_sampled_dev, pf_logits_dev, ys = self.runner.ragged_dispatch(
            pf_chunks,
            [w.chunk_start for w in works],
            [w.seq.block_table for w in works],
            [w.chunk_start + w.chunk_len for w in works],
            tokens,
            [s.num_tokens - 1 for s in seqs],
            [s.block_table for s in seqs],
            [s.num_tokens for s in seqs],
            k_steps,
            temps, top_ps, top_ks, keys, min_ps=min_ps,
            pf_sampling=pf_sampling,
            pf_lora_slots=[self._lora_slot(w.seq) for w in works],
            lora_slots=[self._lora_slot(s) for s in seqs],
            penalties=penalties,
            want_logprobs=want_lp,
            guided=guided_tables,
            logit_bias=bias,
            pf_budgets=pf_budgets,
            lanes=lanes,
            **stop_kw,
            **staged_kw,
        )
        valid_dev = None
        if stop is not None:
            toks_dev = ys[0]
            valid_dev = ys[-1]
            lps_dev = ys[1:-1] if want_lp else None
        else:
            toks_dev, lps_dev = (
                (ys[0], ys[1:]) if want_lp else (ys, None)
            )
        # stage the predicted NEXT ragged round before any fetch below
        # so its upload overlaps this round's execution + fetch
        self._maybe_stage_ragged(
            works, seqs, k_steps, temps, top_ps, top_ks, keys, min_ps,
            stop, penalties, bias, guided_tables, toks_dev, lanes,
        )
        stepped: list[Sequence] = []
        for w in works:
            w.seq.num_computed_tokens += w.chunk_len
            self._prompt_tokens_total += w.chunk_len
        if self._tl_enabled:
            group_phases = self.phases.delta(phase_snap)
            for w in works:
                self.timeline.event(
                    w.seq.request_id, "prefill_chunk",
                    {
                        "chunk_start": w.chunk_start,
                        "chunk_len": w.chunk_len,
                        "last": w.is_last_chunk,
                        "staged_hit": len(staged_kw) > 0,
                        "chained": False,
                        "group_size": len(works),
                        "engine_round": self._round,
                        "ragged": True,
                        "prefill_lanes": len(works),
                        "decode_lanes": len(seqs),
                        **(
                            {"group_phase_s": group_phases}
                            if group_phases else {}
                        ),
                    },
                )
        finals = [
            (i, w) for i, w in enumerate(works) if w.is_last_chunk
        ]
        if finals:
            with self.phases.span("fetch"):
                # stackcheck: disable=device-sync-transitive — the ONE
                # metered prefill-token fetch for this ragged round
                pf_toks_np = np.asarray(pf_sampled_dev)
            with self.phases.span("apply"):
                for i, w in finals:
                    tok = int(pf_toks_np[i])
                    if tok < 0:
                        # the device pins ONLY non-real lanes to the
                        # idle sentinel; a real lane yielding it means
                        # the lane packing drifted — fail this round
                        # loudly rather than emitting a corrupt stream
                        raise RuntimeError(
                            f"ragged dispatch returned the idle-lane "
                            f"sentinel for real prefill lane {i} "
                            f"({w.seq.request_id})"
                        )
                    entry = None
                    n = w.seq.sampling_params.logprobs
                    if n is not None:
                        entry = self._host_logprob_entry(
                            # stackcheck: disable=device-sync-transitive
                            # — logprob rows materialize only for lanes
                            # that requested them; their fetch point
                            np.asarray(pf_logits_dev[i]), tok, n
                        )
                    self._append_token(w.seq, tok, entry)
                    stepped.append(w.seq)
        # materialize the decode-lane results in one place so the d2h
        # cost lands in the fetch phase like every other fetch
        with self.phases.span("fetch"):
            # stackcheck: disable=device-sync-transitive — the ONE
            # metered multi-token fetch for this ragged round's decode
            # lanes
            toks_np = np.asarray(toks_dev)
            lps_np = (
                # stackcheck: disable=device-sync-transitive — logprob
                # arrays exist only when lanes requested them; they ride
                # this round's metered fetch with the tokens
                tuple(np.asarray(a) for a in lps_dev) if lps_dev else None
            )
            valid_np = (
                # stackcheck: disable=device-sync-transitive — validity
                # mask rides the same metered fetch as the tokens it
                # gates
                np.asarray(valid_dev) if valid_dev is not None else None
            )
        self._apply_multi_tokens(
            seqs, toks_np, k_steps,
            lps=lps_np,
            valid=valid_np,
            round_attrs={
                "prefill_lanes": len(works),
                "decode_lanes": len(seqs),
            },
            lanes=lanes,
        )
        stepped.extend(seqs)
        self._note_ragged_round(len(works), len(seqs))
        return stepped

    def _predict_next_prefill_works(
        self, works: list[PrefillWork]
    ) -> list[PrefillWork]:
        """Predicted chunk set for the round AFTER `works`, computed
        BEFORE this round's bookkeeping lands (the ragged stage must
        start while the dispatch is still in flight): each non-final
        lane advances by its own chunk length."""
        nxt: list[PrefillWork] = []
        chunked = self.scheduler.config.enable_chunked_prefill
        for w in works:
            s = w.seq
            if s.sampling_params.prompt_logprobs is not None:
                continue
            start = w.chunk_start + w.chunk_len
            rem = s.num_prompt_tokens - start
            if rem <= 0:
                continue
            clen = (
                min(rem, self.scheduler.config.max_prefill_chunk)
                if chunked else rem
            )
            nxt.append(PrefillWork(
                seq=s, chunk_start=start, chunk_len=clen,
            ))
        return nxt

    def _ragged_fingerprint(
        self, works: list[PrefillWork], seqs: list[Sequence],
        advance: int = 0,
    ) -> tuple:
        """State a staged ragged buffer was built for, as observed at
        dispatch: the prefill lanes in order at the same chunk offsets,
        block tables untouched (length + the allocator's free epoch —
        freed ids can be re-handed to another sequence) and no tokens
        appended since the stage (the sampling keys depend on
        generated_len), + the decode lanes in order at exact token
        counts (`advance` further at stage time: the K tokens of the
        round on the device are not yet applied). Any lane-mix change
        — a prefill lane finishing, a new admission — breaks it,
        converting the stage into a counted miss."""
        return (
            tuple(w.seq.request_id for w in works),
            tuple(w.chunk_start for w in works),
            tuple(w.chunk_len for w in works),
            tuple(len(w.seq.block_table) for w in works),
            tuple(w.seq.num_generated for w in works),
            tuple(s.request_id for s in seqs),
            tuple(s.num_tokens + advance for s in seqs),
            tuple(len(s.block_table) for s in seqs),
            self.block_manager.free_epoch,
        )

    def _maybe_stage_ragged(
        self, works, seqs, k_steps, temps, top_ps, top_ks, keys,
        min_ps, stop, penalties, bias, guided_tables, toks_dev, lanes,
    ) -> None:
        """Stage the PREDICTED next lane-typed round (h2d prefetch —
        the PR 1/PR 5 staging pattern applied to the unified round):
        prefill lanes advance by their chunk, decode lanes chain on
        this round's on-device tokens advanced by K, in this round's
        `lanes` (`_stage_fingerprint`). Validated by fingerprint + the
        runner's total-layout check before use."""
        if not (self._prefetch_decode and self._prefill_pipeline):
            return
        if (penalties is not None or bias is not None
                or guided_tables is not None):
            return  # per-round host state does not chain
        if self.scheduler.waiting:
            return  # admission will change the lane set
        if any(w.is_last_chunk for w in works):
            # a finishing prefill lane migrates to the decode side
            # next round: the lane mix changes by construction
            return
        nxt = self._predict_next_prefill_works(works)
        if not nxt:
            return
        if not self._reserve_next_round(seqs, k_steps):
            return
        nk = keys.copy()
        nk[:, 1] += k_steps
        stage_stop = None
        if stop is not None:
            stage_stop = (
                stop[0],
                np.maximum(stop[1] - k_steps, 0),
                stop[2] - k_steps,
                stop[3],
            )
        seqs_w = [w.seq for w in nxt]
        pf_sampling = self._sampling_arrays(seqs_w)[:5]
        handle = self.runner.stage_ragged(
            [
                w.seq.prompt_token_ids[
                    w.chunk_start : w.chunk_start + w.chunk_len
                ]
                for w in nxt
            ],
            [w.chunk_start for w in nxt],
            [w.seq.block_table for w in nxt],
            [w.chunk_start + w.chunk_len for w in nxt],
            pf_sampling,
            [s.num_tokens - 1 + k_steps for s in seqs],
            [s.block_table for s in seqs],
            [s.num_tokens + k_steps for s in seqs],
            k_steps, temps, top_ps, top_ks, nk,
            min_ps=min_ps, stop=stage_stop,
            pf_budgets=[
                w.seq.num_prompt_tokens
                - (w.chunk_start + w.chunk_len)
                for w in nxt
            ],
            lanes=lanes,
        )
        self._staged_ragged = {
            "fp": self._ragged_fingerprint(nxt, seqs, advance=k_steps),
            "handle": handle,
            "lanes": lanes,
            "chain_tokens": toks_dev[-1],
        }

    def _note_ragged_round(self, n_pf: int, n_dec: int) -> None:
        """Fused lane-typed round accounting: tpu:ragged_rounds, the
        lane-mix histogram feed, and the lane totals."""
        self._ragged_rounds_total += 1
        self._ragged_prefill_lanes_total += n_pf
        self._ragged_decode_lanes_total += n_dec
        self._ragged_obs.append(n_pf)
        key = f"p{n_pf}+d{n_dec}"
        self._ragged_lane_mix_hist[key] = (
            self._ragged_lane_mix_hist.get(key, 0) + 1
        )

    def drain_ragged_observations(self) -> list[int]:
        """Prefill-lane counts of fused ragged rounds since the last
        drain — feeds the server's tpu:ragged_lane_mix histogram
        (deque pops GIL-atomic)."""
        out: list[int] = []
        while True:
            try:
                out.append(self._ragged_obs.popleft())
            except IndexError:
                break
        return out

    # -- pipelined prefill --------------------------------------------------
    def _next_prefill_works(
        self, works: list[PrefillWork]
    ) -> list[PrefillWork]:
        """Predicted next chunk set after `works` completes: the same
        sequences (order kept) that still have prompt left. prompt_
        logprobs sequences are excluded — their per-chunk host fetches
        serialize anyway."""
        nxt: list[PrefillWork] = []
        chunked = self.scheduler.config.enable_chunked_prefill
        for w in works:
            s = w.seq
            if s.finished or s not in self.scheduler.running:
                continue
            if s.sampling_params.prompt_logprobs is not None:
                continue
            rem = s.num_uncomputed_prompt_tokens
            if rem <= 0:
                continue
            clen = (
                min(rem, self.scheduler.config.max_prefill_chunk)
                if chunked else rem
            )
            nxt.append(PrefillWork(
                seq=s, chunk_start=s.num_computed_tokens, chunk_len=clen,
            ))
        return nxt

    # chain cap: one engine.step() holds the server's step lock, so an
    # unbounded chain would freeze add_request/abort (and with them the
    # whole HTTP loop) for a very long prompt's entire prefill. Past
    # the cap the next step() schedules the following chunk and chains
    # again.
    MAX_CHAINED_PREFILLS = 8

    def _chain_next_prefill(
        self, works: list[PrefillWork]
    ) -> list[PrefillWork] | None:
        """Chained multi-chunk dispatch: when every scheduled chunk was
        non-final and NOTHING is decode-ready or waiting, the group's
        next chunks run in this same engine round — the host round-trip
        (scheduler pass + an interleaved decode's blocking fetch)
        between consecutive chunks of a cold prompt disappears, and each
        chunk's packed upload overlaps the previous chunk's device
        compute (the dispatches are async enqueues). Only the final
        chunk's sampled token is ever fetched."""
        if not self._prefill_pipeline:
            return None
        if any(w.is_last_chunk for w in works):
            return None  # finals made their seqs decode-ready
        if any(
            w.seq.sampling_params.prompt_logprobs is not None
            for w in works
        ):
            return None
        if self.scheduler.waiting:
            return None  # admission may pack new arrivals into the group
        if any(
            s.prefill_done and not s.finished
            for s in self.scheduler.running
        ):
            return None  # a decode stream would be starved: interleave
        nxt = self._next_prefill_works(works)
        return nxt or None

    def _prepare_chunks(self, works: list[PrefillWork]) -> None:
        """Every prefill chunk passes here right before its dispatch,
        scheduled, chained or split alike: a block manager with a second
        cache group releases what the chunk's sequence left behind its
        window and makes room for the chunk's positions there; one with
        recurrent state readies a snapshot slot for a chunk that ends
        at a boundary (a no-op with one pool)."""
        if not (self.runner.num_window_blocks
                or self.runner.num_state_slots):
            return
        with phases.annotation("engine.kv_release", chunks=len(works)):
            for w in works:
                # blocks that chunks chained earlier in this step
                # filled: content-addressed BEFORE their twins are let
                # go, so that the twins stay for a later prefix hit
                self._register_full_blocks(w.seq)
                self.block_manager.prepare_chunk(
                    w.seq.block_table, w.chunk_start,
                    w.chunk_start + w.chunk_len,
                )

    def _run_prefill_works(
        self, works: list[PrefillWork], chained: bool = False,
    ) -> list[Sequence]:
        """Dispatch one scheduled prefill chunk group (the body of the
        prefill step): prompt_logprobs sequences on the single-sequence
        program variant, everything else in one packed dispatch, first
        tokens appended for final chunks. Returns the stepped sequences.
        `chained` marks groups
        dispatched by cold-prompt chaining (no host round-trip since the
        previous group) for the timeline."""
        self._prepare_chunks(works)
        stepped: list[Sequence] = []
        now = time.time()
        for w in works:
            if w.seq.metrics.first_scheduled_time is None:
                w.seq.metrics.first_scheduled_time = now
        phase_snap = self.phases.seconds() if self._tl_enabled else None
        self._begin_round(
            "prefill", 0, 0, sum(w.chunk_len for w in works))
        # prompt_logprobs requests take the single-sequence program
        # variant (every row's distribution scored on device); they
        # never pack — their per-row outputs are per-sequence
        plp_works = [
            (i, w) for i, w in enumerate(works)
            if w.seq.sampling_params.prompt_logprobs is not None
        ]
        std_works = [
            (i, w) for i, w in enumerate(works)
            if w.seq.sampling_params.prompt_logprobs is None
        ]
        last_logits: dict[int, object] = {}
        tok_of: dict[int, int] = {}  # original idx -> sampled token
        for i, w in plp_works:
            seq = w.seq
            chunk = seq.prompt_token_ids[
                w.chunk_start : w.chunk_start + w.chunk_len
            ]
            # row j scores the NEXT prompt token; the final chunk's
            # last row has none (its continuation is generated)
            tgts = seq.prompt_token_ids[
                w.chunk_start + 1 : w.chunk_start + w.chunk_len + 1
            ]
            t1, p1, k1, m1, keys1, _ = self._sampling_arrays([seq])
            token_dev, logits, chosen, tv, ti = self.runner.prefill(
                chunk,
                start_pos=w.chunk_start,
                block_table=seq.block_table,
                total_len=w.chunk_start + w.chunk_len,
                lora_slot=self._lora_slot(seq),
                sampling=(t1, p1, k1, m1, keys1),
                prompt_lp_targets=[int(x) for x in tgts],
            )
            with self.phases.span("fetch"):
                # stackcheck: disable=device-sync-transitive — the
                # metered guided/bias lane fetch: token + prompt-logprob
                # triplet
                tok_of[i] = int(np.asarray(token_dev))
                chosen, tv, ti = (
                    # stackcheck: disable=device-sync-transitive — same
                    # metered fetch, prompt-logprob arrays for this lane
                    np.asarray(chosen), np.asarray(tv), np.asarray(ti)
                )
            last_logits[i] = logits
            self._accumulate_prompt_lps(
                seq, w.chunk_start, tgts, chosen, tv, ti,
            )
        if std_works:
            sworks = [w for _, w in std_works]
            seqs_w = [w.seq for w in sworks]
            temps, top_ps, top_ks, min_ps, keys, _ = (
                self._sampling_arrays(seqs_w)
            )
            sampling = (temps, top_ps, top_ks, min_ps, keys)
            if len(sworks) == 1:
                # single-sequence path keeps the round-2 buckets
                w = sworks[0]
                seq = w.seq
                chunk = seq.prompt_token_ids[
                    w.chunk_start : w.chunk_start + w.chunk_len
                ]
                token_dev, logits = self.runner.prefill(
                    chunk,
                    start_pos=w.chunk_start,
                    block_table=seq.block_table,
                    total_len=w.chunk_start + w.chunk_len,
                    lora_slot=self._lora_slot(seq),
                    sampling=sampling,
                )
                tokens_dev = token_dev[None]
                last_logits[std_works[0][0]] = logits
            else:
                # packed cross-sequence prefill: one dispatch covers
                # every scheduled chunk (burst-TTFT fix)
                tokens_dev, logits = self.runner.prefill_batch(
                    [
                        w.seq.prompt_token_ids[
                            w.chunk_start : w.chunk_start + w.chunk_len
                        ]
                        for w in sworks
                    ],
                    start_positions=[w.chunk_start for w in sworks],
                    block_tables=[w.seq.block_table for w in sworks],
                    total_lens=[
                        w.chunk_start + w.chunk_len for w in sworks
                    ],
                    lora_slots=[
                        self._lora_slot(w.seq) for w in sworks
                    ],
                    sampling=sampling,
                )
                for j, (i, _) in enumerate(std_works):
                    last_logits[i] = logits[j]
            # ONE fetch for the whole std group's sampled tokens
            if any(w.is_last_chunk for w in sworks):
                with self.phases.span("fetch"):
                    # stackcheck: disable=device-sync-transitive — the
                    # ONE metered fetch for the std prefill group (see
                    # above)
                    toks_np = np.asarray(tokens_dev)
                for j, (i, _) in enumerate(std_works):
                    tok_of[i] = int(toks_np[j])
        for i, w in enumerate(works):
            w.seq.num_computed_tokens += w.chunk_len
            self._prompt_tokens_total += w.chunk_len
        if self._tl_enabled:
            # one event per chunk, attributed with the dispatch group's
            # per-phase wall time (delta over the runner's tpu:prefill_*
            # counters — the group shares one dispatch, so the phases
            # are group-level, tagged with the group size)
            group_phases = self.phases.delta(phase_snap)
            for w in works:
                self.timeline.event(
                    w.seq.request_id, "prefill_chunk",
                    {
                        "chunk_start": w.chunk_start,
                        "chunk_len": w.chunk_len,
                        "last": w.is_last_chunk,
                        "chained": chained,
                        "group_size": len(works),
                        "engine_round": self._round,
                        # lane-mix attribution (unified-round contract:
                        # every prefill event says what rode with it —
                        # the split path rides alone)
                        "prefill_lanes": len(works),
                        "decode_lanes": 0,
                        **(
                            {"group_phase_s": group_phases}
                            if group_phases else {}
                        ),
                    },
                )
        finals = [
            (i, w) for i, w in enumerate(works) if w.is_last_chunk
        ]
        if finals:
            with self.phases.span("apply"):
                # first tokens were sampled ON DEVICE inside the prefill
                # program — the host fetches (s_pad,) int32 instead of
                # (s_pad, vocab) f32 logits. Only a post-preemption
                # sequence with active penalties (its generated history
                # is folded into the prompt, so penalty counts are
                # non-empty at the "first" token) needs the logits
                # (_needs_host_first_sample — shared with the ragged
                # round's fusability gate).
                pen = [(i, w) for i, w in finals
                       if self._needs_host_first_sample(w.seq)]
                clean = [(i, w) for i, w in finals
                         if not self._needs_host_first_sample(w.seq)]
                if clean:
                    for i, w in clean:
                        entry = None
                        n = w.seq.sampling_params.logprobs
                        if n is not None:
                            entry = self._host_logprob_entry(
                                # stackcheck: disable=device-sync-transitive
                                # — logprob rows materialize only for lanes
                                # that requested them; their fetch point
                                np.asarray(last_logits[i]),
                                tok_of[i], n,
                            )
                        self._append_token(w.seq, tok_of[i], entry)
                        stepped.append(w.seq)
                if pen:
                    fl = jnp.stack([last_logits[i] for i, _ in pen])
                    sampled, used_logits = self._sample(
                        [w.seq for _, w in pen], fl, return_logits=True
                    )
                    # stackcheck: disable=device-sync-transitive — the ONE
                    # intended materialization of penalized-lane logits;
                    # logprob entries below index into it row by row
                    used_logits = np.asarray(used_logits)
                    for j, ((i, w), token) in enumerate(
                        zip(pen, sampled)
                    ):
                        entry = None
                        n = w.seq.sampling_params.logprobs
                        if n is not None:
                            entry = self._host_logprob_entry(
                                used_logits[j], int(token), n
                            )
                        self._append_token(w.seq, int(token), entry)
                        stepped.append(w.seq)
        return stepped

    # -- speculative decoding (prompt-lookup n-gram drafts) ----------------
    # haystack bound for prompt-lookup: the scan runs per lane per step
    # on the step-loop critical path, so cap it to a recent suffix —
    # beyond this, matches are stale context anyway
    NGRAM_SCAN_WINDOW = 8192

    # stackcheck: not-hot — pure host-side n-gram matching over python
    # token lists; no device arrays ever enter this helper
    def _ngram_drafts(self, seq: Sequence, k: int) -> list[int]:
        """Draft tokens from the LAST previous occurrence of the
        context's trailing n-gram (vLLM's ngram prompt-lookup role): no
        draft model, pure host-side memory of the sequence itself —
        strongest on repetitive/structured text."""
        end = seq.num_tokens
        context = seq.token_ids(
            max(0, end - self.NGRAM_SCAN_WINDOW), end
        )
        arr = np.asarray(context, np.int32)
        cfg = self.config
        for n in range(cfg.ngram_prompt_lookup_max,
                       cfg.ngram_prompt_lookup_min - 1, -1):
            if len(arr) <= n:
                continue
            pattern = arr[-n:]
            win = np.lib.stride_tricks.sliding_window_view(arr, n)
            matches = np.nonzero((win == pattern).all(axis=1))[0]
            matches = matches[matches + n < len(arr)]  # need continuation
            if len(matches):
                i = int(matches[-1])
                return [int(t) for t in context[i + n: i + n + k]]
        return []

    def _try_spec_decode_batch(
        self, seqs: list[Sequence]
    ) -> list[Sequence] | None:
        """One speculative round over the whole decode batch; returns
        the stepped list, or None to fall back to the normal path.

        All lanes' draft chunks [last_token, d_1..d_k_i] (ragged per
        lane; zero-draft lanes feed just their last token) verify in ONE
        packed forward, and every row is sampled ON DEVICE with the key
        autoregressive decode would have used — the engine's keys depend
        only on (seed, generated_len), so acceptance-by-equality keeps
        outputs bit-identical to sequential decode at ANY temperature,
        not just greedy (parity asserted by tests/test_spec_decode.py).
        Eligibility is whole-batch: lanes needing per-step logit edits
        (logprobs, guided masks, logit penalties, logit_bias) fall the
        batch back to the normal path."""
        for s in seqs:
            sp = s.sampling_params
            if (
                sp.logprobs is not None
                or self._is_guided(s)
                or sp.logit_bias
                or sp.presence_penalty != 0.0
                or sp.frequency_penalty != 0.0
                or sp.repetition_penalty != 1.0
            ):
                return None
        k_cfg = self.config.num_speculative_tokens
        drafts_by_lane: list[list[int]] = []
        any_drafts = False
        for s in seqs:
            n0 = s.num_tokens
            # drafts must fit the KV layout and the generation budget
            k = min(
                k_cfg,
                self.scheduler.config.max_model_len - n0,
                s.sampling_params.max_tokens - s.num_generated - 1,
                # verify feeds k+1 tokens through the prefill buckets
                self.config.max_prefill_chunk - 1,
            )
            d = self._ngram_drafts(s, k) if k > 0 else []
            if d and not self.block_manager.ensure_capacity(
                n0 + len(d), s.block_table
            ):
                d = []  # no room to grow: this lane rides draft-free
            drafts_by_lane.append(d)
            any_drafts = any_drafts or len(d) > 0
        if not any_drafts:
            return None
        chunks = [
            [s.last_token_id] + d
            for s, d in zip(seqs, drafts_by_lane)
        ]
        temps, top_ps, top_ks, min_ps, _keys, _pen = (
            self._sampling_arrays(seqs)
        )
        # stackcheck: disable=device-sync-transitive — host staging:
        # np.asarray over a python list, no device array involved
        seeds = np.asarray(
            [self._seq_seed(s) & 0xFFFFFFFF for s in seqs], np.uint32
        )
        # stackcheck: disable=device-sync-transitive — host staging:
        # np.asarray over a python list, no device array involved
        starts = np.asarray(
            [s.num_generated for s in seqs], np.int64
        )
        self._begin_round(
            "verify", 1, len(seqs), sum(len(c) for c in chunks))
        sampled = self.runner.verify_batch(
            chunks,
            start_positions=[s.num_tokens - 1 for s in seqs],
            block_tables=[s.block_table for s in seqs],
            total_lens=[
                s.num_tokens - 1 + len(c) for s, c in zip(seqs, chunks)
            ],
            row_sampling=(temps, top_ps, top_ks, min_ps, seeds, starts),
            lora_slots=[self._lora_slot(s) for s in seqs],
        )
        stepped: list[Sequence] = []
        for i, (seq, drafts) in enumerate(zip(seqs, drafts_by_lane)):
            row = sampled[i]
            accepted = 0
            for d in drafts:
                if int(row[accepted]) == d:
                    accepted += 1
                else:
                    break
            self._spec_drafts_total += len(drafts)
            self._spec_accepted_total += accepted
            # accepted drafts + the verify forward's own next token (the
            # correction on mismatch, the bonus token on full acceptance)
            new_tokens = drafts[:accepted] + [int(row[accepted])]
            for t in new_tokens:
                if seq.finished:
                    break  # EOS/stop fired mid-acceptance; drop the rest
                seq.num_computed_tokens = seq.num_tokens
                self._append_token(seq, int(t))
            if self._tl_enabled and not seq.finished:
                self.timeline.decode_round(
                    seq.request_id, len(new_tokens),
                    attrs={"engine_round": self._round},
                )
            stepped.append(seq)
        self.last_step_kind = "decode"
        return stepped

    def _finalize_stepped(
        self, stepped: list[Sequence]
    ) -> list[RequestOutput]:
        with self.phases.span("apply"):
            outputs: list[RequestOutput] = []
            for seq in stepped:
                self._register_full_blocks(seq)
                out = self._make_output(seq)
                outputs.append(out)
                if seq.finished:
                    seq.metrics.finished_time = time.time()
                    self._finished_total += 1
                    self.scheduler.free_finished(seq)
                    self._seqs.pop(seq.request_id, None)
                    self.timeline.finish(
                        seq.request_id, seq.finish_reason,
                        {
                            "generated_tokens": seq.num_generated,
                            "preemptions": seq.metrics.num_preemptions,
                        } if self._tl_enabled else None,
                    )
            return outputs

    # -- internals ---------------------------------------------------------
    def _sampling_arrays(
        self, seqs: list[Sequence], b: int | None = None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray,
               np.ndarray, bool]:
        """Per-lane sampling parameter arrays + whether any sequence
        needs logit penalties (multi-step then carries token counts on
        device; single-step applies them host-side in _apply_penalties).

        Key = (seed, generated_len): multi-step derives iteration i's key
        as (seed, generated_len + i), bit-identical to i single steps."""
        b = b if b is not None else len(seqs)
        temps = np.zeros((b,), np.float32)
        top_ps = np.ones((b,), np.float32)
        top_ks = np.full((b,), -1, np.int32)
        min_ps = np.zeros((b,), np.float32)
        keys = np.zeros((b, 2), np.uint32)
        needs_penalties = False
        for i, s in enumerate(seqs):
            sp = s.sampling_params
            temps[i] = sp.temperature
            top_ps[i] = sp.top_p
            top_ks[i] = sp.top_k
            min_ps[i] = sp.min_p
            if (
                sp.presence_penalty != 0.0
                or sp.frequency_penalty != 0.0
                or sp.repetition_penalty != 1.0
            ):
                needs_penalties = True
            keys[i] = (
                np.uint32(self._seq_seed(s) & 0xFFFFFFFF),
                np.uint32(s.num_generated),
            )
        return temps, top_ps, top_ks, min_ps, keys, needs_penalties

    # stackcheck: hot-path — host-array build feeding the fused decode
    # dispatch: one pass over the batch, no device work, no blocking IO
    def _stop_arrays(
        self, seqs: list[Sequence]
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray | None]:
        """Per-lane device-stop arrays for ModelRunner.decode_multi:
        (eos, min_rem, budget, stop_ids|None). eos ships -1 under
        ignore_eos (or an EOS-less tokenizer) so the device check never
        fires; min_rem/budget are THIS-ROUND countdowns of the host's
        min_tokens / max_tokens+max_model_len gates (Sequence.check_stop
        semantics); stop_ids pads each lane's stop_token_ids to the
        batch's pow2 cap with -1 (token ids are non-negative, the
        sentinel never matches). Stop STRINGS stay host-resolved — text
        matching cannot run on device — so their overshoot is discarded
        exactly as on the fixed-trip path."""
        b = len(seqs)
        eos = np.full((b,), -1, np.int32)
        min_rem = np.zeros((b,), np.int32)
        budget = np.zeros((b,), np.int32)
        mml = self.scheduler.config.max_model_len
        max_ids = 0
        for i, s in enumerate(seqs):
            sp = s.sampling_params
            if not sp.ignore_eos and s.eos_token_id is not None:
                eos[i] = int(s.eos_token_id)
            gen = s.num_generated
            min_rem[i] = max(0, sp.min_tokens - gen)
            # scheduled lanes are unfinished, so both terms are >= 1
            budget[i] = max(
                1, min(sp.max_tokens - gen, mml - s.num_tokens)
            )
            if sp.stop_token_ids:
                max_ids = max(max_ids, len(sp.stop_token_ids))
        stop_ids = None
        if max_ids:
            # pow2 cap (>= 4) keeps the program-variant space tiny
            cap = max(4, 1 << (max_ids - 1).bit_length())
            stop_ids = np.full((b, cap), -1, np.int32)
            for i, s in enumerate(seqs):
                ids = list(s.sampling_params.stop_token_ids or ())
                if ids:
                    stop_ids[i, : len(ids)] = ids
        return eos, min_rem, budget, stop_ids

    @staticmethod
    def _bias_arrays(
        seqs: list[Sequence],
    ) -> tuple[np.ndarray, np.ndarray] | None:
        """Per-lane OpenAI logit_bias as dense (b, cap) id/value arrays
        for the fused decode scan, or None when no lane has a bias.
        cap is the pow2 bucket of the largest bias map (>= 8) so the
        program variant space stays tiny; padding rows add 0.0 to token
        0 — a no-op."""
        maxn = max(
            len(s.sampling_params.logit_bias or {}) for s in seqs
        )
        if maxn == 0:
            return None
        cap = max(8, 1 << (maxn - 1).bit_length())
        ids = np.zeros((len(seqs), cap), np.int32)
        vals = np.zeros((len(seqs), cap), np.float32)
        for i, sq in enumerate(seqs):
            for j, (t, v) in enumerate(
                (sq.sampling_params.logit_bias or {}).items()
            ):
                ids[i, j] = t
                vals[i, j] = v
        return ids, vals

    def _seq_seed(self, s: Sequence) -> int:
        sp = s.sampling_params
        return (
            sp.seed
            if sp.seed is not None
            else (self.config.seed ^ (hash(s.request_id) & 0x7FFFFFFF))
        )

    # -- structured output (guided choice/json/regex) ----------------------
    @staticmethod
    def _is_guided(seq: Sequence) -> bool:
        return (
            getattr(seq, "_guided_choices", None) is not None
            or getattr(seq, "_guided_machine", None) is not None
        )

    def _mask_cache(self):
        """Lazy per-engine vocab trie for constraint masking."""
        mc = getattr(self, "_token_mask_cache", None)
        if mc is None:
            from production_stack_tpu.engine.structured import (
                TokenMaskCache,
            )

            mc = TokenMaskCache(self.tokenizer)
            self._token_mask_cache = mc
        return mc

    def _guided_allowed(self, seq: Sequence) -> set[int] | None:
        """Tokens the constraint allows next, or None when the sequence
        is unconstrained."""
        machine = getattr(seq, "_guided_machine", None)
        if machine is not None:
            if getattr(seq, "_guided_dead", False):
                # constraint evaluation blew up earlier for THIS request
                # (e.g. an ambiguous grammar whose closure diverges only
                # mid-generation): the only legal move is to stop
                return (
                    {int(seq.eos_token_id)}
                    if seq.eos_token_id is not None else set()
                )
            states = seq._guided_state
            try:
                allowed = set(self._mask_cache().allowed(machine, states))
            except ValueError as e:
                # fail ONLY this request: a per-lane constraint blow-up
                # must never abort the whole engine step (and with it
                # every other in-flight stream)
                logger.warning(
                    "guided constraint diverged for %s mid-generation "
                    "(%s); ending the stream", seq.request_id, e,
                )
                seq._guided_dead = True  # type: ignore[attr-defined]
                return (
                    {int(seq.eos_token_id)}
                    if seq.eos_token_id is not None else set()
                )
            # budget-aware completion steering: with only a few budget
            # tokens left, keep only moves from which the machine can
            # still reach an accepting state within what remains —
            # otherwise a greedy model rides a repeatable construct
            # ("ab+c", ("," [0-9])*) straight past max_tokens and the
            # stream ends non-conforming
            remaining = (seq.sampling_params.max_tokens
                         - seq.num_generated)
            if 0 < remaining <= self.GUIDED_STEER_BOUND:
                steered = self._steer_allowed(
                    machine, states, allowed, remaining
                )
                if steered is not None:
                    allowed = steered
            if machine.accepting(states) and seq.eos_token_id is not None:
                allowed.add(int(seq.eos_token_id))
            if not allowed and seq.eos_token_id is not None:
                # dead end (should not happen for live machines): the
                # only legal move is to stop
                allowed.add(int(seq.eos_token_id))
            return allowed
        choices = getattr(seq, "_guided_choices", None)
        if choices is None:
            return None
        g = list(seq.generated_token_ids)
        allowed: set[int] = set()
        complete = False
        for ids in choices:
            if len(ids) > len(g) and list(ids[: len(g)]) == g:
                allowed.add(int(ids[len(g)]))
            elif list(ids) == g:
                complete = True
        if complete and allowed and seq.eos_token_id is not None:
            # one choice is complete but a longer one still extends it
            # ("go" vs "gone"): let the MODEL decide by offering EOS as
            # the stop-here option instead of silently making the longer
            # choice unreachable
            allowed.add(int(seq.eos_token_id))
        return allowed

    # budget window (tokens) in which constraint steering engages; also
    # the margin by which guided lanes leave the fused device path so
    # their final steered steps run host-masked (K-step parity holds:
    # unsteered steps mask identically on both paths)
    GUIDED_STEER_BOUND = 8
    # frontier cap for the completion-distance search: a node offering
    # more distinct next strings than this (e.g. a JSON machine inside a
    # free-form string) is too wide to steer — give up rather than burn
    # the step loop
    GUIDED_STEER_FANOUT = 128

    def _dist_to_accept(self, machine, states, cap: int) -> int | None:
        """Shortest number of further tokens from `states` to an
        accepting state (token-level BFS, deduped by token STRING), or
        None when no accepting state is reachable within `cap` tokens
        or the frontier is too wide to search. Memoized per LIVE
        machine object (weak-keyed, so a finished request's machine
        takes its entries with it and a recycled address can never
        serve another grammar's distances); steering only runs in the
        final GUIDED_STEER_BOUND tokens of a request, so each
        machine's memo stays tiny."""
        import weakref

        memos = getattr(self, "_guided_dist_memo", None)
        if memos is None:
            memos = weakref.WeakKeyDictionary()
            self._guided_dist_memo = memos
        memo = memos.get(machine)
        if memo is None:
            memo = {}
            memos[machine] = memo
        cached = memo.get(states)
        if cached is not None:
            dist, searched_cap = cached
            if dist is not None or cap <= searched_cap:
                return dist
        mc = self._mask_cache()
        if machine.accepting(states):
            memo[states] = (0, cap)
            return 0
        seen = {states}
        frontier = [states]
        for d in range(1, cap + 1):
            nxt = []
            for st in frontier:
                try:
                    allowed = mc.allowed(machine, st)
                except ValueError:
                    continue  # diverging constraint: unsearchable here
                strs = {mc.token_str(t) for t in allowed}
                strs.discard("")
                if len(strs) > self.GUIDED_STEER_FANOUT:
                    memo[states] = (None, cap)
                    return None
                for s in strs:
                    try:
                        ns = machine.step_str(st, s)
                    except ValueError:
                        continue
                    if not ns or ns in seen:
                        continue
                    if machine.accepting(ns):
                        memo[states] = (d, cap)
                        return d
                    seen.add(ns)
                    nxt.append(ns)
            if not nxt:
                break
            frontier = nxt
        memo[states] = (None, cap)
        return None

    def _steer_allowed(
        self, machine, states, allowed: set[int], remaining: int,
    ) -> set[int] | None:
        """Subset of `allowed` whose successor states can still accept
        within `remaining - 1` further tokens, or None when steering is
        infeasible (search too wide / nothing completes) — the caller
        then keeps the unsteered mask."""
        mc = self._mask_cache()
        by_str: dict[str, list[int]] = {}
        for t in allowed:
            by_str.setdefault(mc.token_str(t), []).append(t)
        by_str.pop("", None)
        if len(by_str) > self.GUIDED_STEER_FANOUT:
            return None
        keep: set[int] = set()
        for s, ids in by_str.items():
            try:
                ns = machine.step_str(states, s)
            except ValueError:
                continue
            if not ns:
                continue
            d = self._dist_to_accept(machine, ns, remaining - 1)
            if d is not None and d <= remaining - 1:
                keep.update(ids)
        return keep or None

    def _device_guided_tables(self, seqs: list[Sequence]):
        """Assemble TokenDFA tables for a batch with guided lanes so the
        fused multi-step scan can evaluate the constraints ON DEVICE
        (fixes the guided-vs-multistep cliff: guided lanes previously
        forced the whole batch onto the single-step host-mask path).

        Returns the `guided` tuple ModelRunner.decode_multi takes, or
        None when any guided lane's constraint is too large to compile
        under budget (the caller keeps the host path). Unguided lanes
        ride a shared trivial allow-everything machine."""
        from production_stack_tpu.engine.structured import get_token_dfa

        vocab = self.runner.model_config.vocab_size
        mask_cache = self._mask_cache()
        lane_dfas: list = []
        for s in seqs:
            machine = getattr(s, "_guided_machine", None)
            choices = getattr(s, "_guided_choices", None)
            if machine is None and choices is None:
                lane_dfas.append(None)
                continue
            # a missing EOS id is legal for guided_choice (the machine
            # kinds reject it at request admission); -1 simply never
            # lands in the vocab-range EOS column
            eos = (int(s.eos_token_id)
                   if s.eos_token_id is not None else -1)
            # a diverging machine returns None here (the failure is
            # negative-cached inside get_token_dfa, same as over-budget
            # constraints); the host path's per-lane containment
            # (_guided_allowed) then winds the request down
            dfa = get_token_dfa(
                machine if machine is not None else choices,
                mask_cache, vocab, eos,
            )
            if dfa is None:
                return None  # over budget: host path
            lane_dfas.append(dfa)

        distinct: list = []
        for d in lane_dfas:
            if d is not None and all(d is not x for x in distinct):
                distinct.append(d)
        # order-invariant identity: a mere reordering of running lanes
        # (preemption/requeue) must not invalidate the host tables, the
        # device upload, or (multihost) trigger a table rebroadcast
        distinct.sort(key=lambda d: d.serial)
        # machine row M-1 (after padding: the last REAL row) is the
        # trivial allow-all machine for unguided lanes
        n_real = len(distinct) + 1
        offsets: dict[int, int] = {}
        off = 0
        for d in distinct:
            offsets[id(d)] = off
            off += d.num_states
        free_state = off
        s_total = off + 1
        c_max = max([d.num_classes for d in distinct] + [1])
        s_pad = 1 << (s_total - 1).bit_length()
        c_pad = 1 << (c_max - 1).bit_length()
        m_pad = 1 << (n_real - 1).bit_length()
        # identity via TokenDFA.serial, NOT id(): ids recycle once the
        # structured-module LRU evicts a DFA, which would silently serve
        # a stale constraint's device tables
        cache_token = (
            tuple(d.serial for d in distinct), s_pad, c_pad, m_pad,
        )

        cached = getattr(self, "_guided_host_tables", None)
        if cached is not None and cached[0] == cache_token:
            _, token_class, class_mask, class_trans = cached
        else:
            token_class = np.zeros((m_pad, vocab), np.int32)
            class_mask = np.zeros((s_pad, c_pad), bool)
            class_trans = np.tile(
                np.arange(s_pad, dtype=np.int32)[:, None], (1, c_pad)
            )
            for mi, d in enumerate(distinct):
                token_class[mi] = d.token_class
                o = offsets[id(d)]
                S, C = d.class_mask.shape
                class_mask[o:o + S, :C] = d.class_mask
                class_trans[o:o + S, :C] = d.class_trans + o
            # allow-all for unguided lanes
            class_mask[free_state, :] = True
            self._guided_host_tables = (
                cache_token, token_class, class_mask, class_trans,
            )

        init_states = np.zeros((len(seqs),), np.int32)
        lane_map = np.zeros((len(seqs),), np.int32)
        for i, (s, d) in enumerate(zip(seqs, lane_dfas)):
            if d is None:
                init_states[i] = free_state
                lane_map[i] = n_real - 1
                continue
            machine = getattr(s, "_guided_machine", None)
            host_state = (
                s._guided_state if machine is not None
                else tuple(s.generated_token_ids)
            )
            idx = d.state_index.get(host_state)
            if idx is None:
                # a frozen/strayed state the DFA never enumerated: keep
                # the host path for this batch
                return None
            init_states[i] = offsets[id(d)] + idx
            lane_map[i] = distinct.index(d)
        return (cache_token, init_states, lane_map, token_class,
                class_mask, class_trans)

    def _apply_guided_mask(self, seqs: list[Sequence], logits):
        """-inf everything outside each lane's allowed-token set."""
        if not any(self._is_guided(s) for s in seqs):
            return logits
        logits = np.array(logits, np.float32, copy=True)
        for i, s in enumerate(seqs):
            allowed = self._guided_allowed(s)
            if allowed:
                mask = np.full(logits.shape[-1], -np.inf, np.float32)
                mask[list(allowed)] = 0.0
                logits[i] = logits[i] + mask
        return logits

    # stackcheck: not-hot — the single-step HOST sampling seam: its
    # contract is to materialize logits and tokens for penalty / bias /
    # guided math (the multi-step on-device path exists to avoid it)
    def _sample(self, seqs: list[Sequence], logits,
                return_logits: bool = False):
        b = logits.shape[0]
        temps, top_ps, top_ks, min_ps, keys, needs_penalties = (
            self._sampling_arrays(seqs, b)
        )
        if needs_penalties:
            logits = self._apply_penalties(seqs, np.asarray(logits))
        if any(s.sampling_params.logit_bias for s in seqs):
            logits = np.array(logits, np.float32, copy=True)
            vocab = logits.shape[-1]
            for i, sq in enumerate(seqs):
                for t, v in (sq.sampling_params.logit_bias or {}).items():
                    if t < vocab:
                        logits[i, t] += v
        logits = self._apply_guided_mask(seqs, logits)
        self.runner.note_sampler(1, temps)
        out = sample_tokens(logits, temps, top_ps, top_ks, keys,
                            min_p=min_ps)
        sampled = np.asarray(out)[: len(seqs)]
        if return_logits:
            # the (penalized) logits the sample came from — what
            # logprob entries must be computed against for parity with
            # the on-device multi-step path
            return sampled, logits
        return sampled

    @staticmethod
    # stackcheck: not-hot — host-side accounting over arrays the caller
    # already fetched at its metered fetch point
    def _accumulate_prompt_lps(
        seq: Sequence, chunk_start: int, tgts: list[int],
        chosen: np.ndarray, tv: np.ndarray, ti: np.ndarray,
    ) -> None:
        """Collect this chunk's per-position prompt logprobs (device
        arrays already fetched). Capped at the ORIGINAL prompt length:
        preemption-by-recomputation folds generated tokens into the
        prompt, and re-prefilling must not extend the prompt logprobs
        past the real prompt."""
        n = seq.sampling_params.prompt_logprobs
        entries = getattr(seq, "_prompt_lp_entries", None)
        if entries is None:
            entries = []
            seq._prompt_lp_entries = entries  # type: ignore[attr-defined]
        limit = seq.orig_prompt_len - 1
        for j, t in enumerate(tgts):
            pos = chunk_start + 1 + j  # prompt position this row scores
            if pos > limit:
                break  # folded-in generated tokens are NOT prompt
            if pos - 1 < len(entries):
                continue  # recompute replays earlier chunks
            entries.append({
                "token_id": int(t),
                "logprob": float(chosen[j]),
                "top_logprobs": [
                    {"token_id": int(ti[j, m]),
                     "logprob": float(tv[j, m])}
                    for m in range(n)
                ],
            })

    @staticmethod
    # stackcheck: not-hot — host-side logprob math over a row the
    # caller already fetched at its metered fetch point
    def _host_logprob_entry(
        logits_row: np.ndarray, token: int, n: int
    ) -> dict:
        """Host-side mirror of sampler.token_logprobs for the
        single-step / prefill paths."""
        row = np.asarray(logits_row, np.float32)
        m = float(np.max(row))
        row = row - (m + np.log(np.sum(np.exp(row - m))))
        if n > 0:
            top = np.argpartition(-row, min(n, row.shape[0] - 1))[:n]
            top = top[np.argsort(-row[top])]
        else:
            top = np.array([], np.int64)
        return {
            "token_id": int(token),
            "logprob": float(row[token]),
            "top_logprobs": [
                {"token_id": int(t), "logprob": float(row[t])}
                for t in top
            ],
        }

    def _apply_penalties(
        self, seqs: list[Sequence], logits: np.ndarray
    ) -> np.ndarray:
        vocab = logits.shape[-1]
        b = logits.shape[0]
        counts = np.zeros((b, vocab), np.float32)
        presence = np.zeros((b,), np.float32)
        frequency = np.zeros((b,), np.float32)
        repetition = np.ones((b,), np.float32)
        for i, s in enumerate(seqs):
            sp = s.sampling_params
            presence[i] = sp.presence_penalty
            frequency[i] = sp.frequency_penalty
            repetition[i] = sp.repetition_penalty
            gen = s.generated_token_ids
            if gen:
                counts[i] = np.bincount(
                    np.asarray(gen) % vocab, minlength=vocab
                ).astype(np.float32)
        return np.asarray(
            apply_penalties(
                logits, counts > 0, counts, presence, frequency, repetition
            )
        )

    def _append_token(self, seq: Sequence, token: int,
                      logprob_entry: dict | None = None) -> None:
        """One sampled token: what only a token alone can be given (a
        step of the lane's guided machine, its logprob entry), then
        `_append_tokens`."""
        machine = getattr(seq, "_guided_machine", None)
        if machine is not None and int(token) != (
            seq.eos_token_id if seq.eos_token_id is not None else -1
        ):
            ts = self._mask_cache().token_str(int(token))
            if ts:
                try:
                    ns = machine.step_str(seq._guided_state, ts)
                except ValueError:
                    # per-lane containment: see _guided_allowed
                    ns = frozenset()
                    seq._guided_dead = True  # type: ignore[attr-defined]
                if ns:
                    seq._guided_state = ns  # type: ignore[attr-defined]
                # empty set = the token strayed off-machine (only
                # possible via an unmasked path); freeze the state so
                # masking stays well-defined
        if seq.sampling_params.logprobs is not None:
            entries = getattr(seq, "_logprob_entries", None)
            if entries is None:
                entries = []
                seq._logprob_entries = entries  # type: ignore[attr-defined]
            entries.append(logprob_entry or {
                "token_id": int(token), "logprob": float("nan"),
                "top_logprobs": [],
            })
            pend = getattr(seq, "_pending_lps", None)
            if pend is None:
                pend = []
                seq._pending_lps = pend  # type: ignore[attr-defined]
            pend.append(entries[-1])
        self._append_tokens(seq, [int(token)])

    @staticmethod
    def _applies_in_one(seq: Sequence) -> bool:
        """True where no token in the MIDDLE of a fused round's tokens
        can end the lane on the host, given that the device froze the
        lane at its own stops (eos, stop ids, min/max tokens, the
        context limit): no stop strings to find in the text, no guided
        machine or choices to step, no logprob entry a token. Read off
        the request; such a lane's tokens go in together."""
        sp = seq.sampling_params
        return (
            not sp.stop and sp.logprobs is None
            and getattr(seq, "_guided_machine", None) is None
            and getattr(seq, "_guided_choices", None) is None
        )

    def _append_tokens(self, seq: Sequence, tokens: list[int]) -> None:
        """Append sampled tokens, of which only the LAST may end the
        sequence (one token; or a fused round's, `_applies_in_one`): one
        detokenizer call, one stop test."""
        if seq.metrics.first_token_time is None:
            seq.metrics.first_token_time = time.time()
            if self._tl_enabled:
                self.timeline.event(
                    seq.request_id, "first_token",
                    {"ttft_s": round(
                        seq.metrics.first_token_time
                        - seq.metrics.arrival_time, 6,
                    ), "engine_round": self._round},
                )
        # incremental detokenization: O(1) amortised per token instead of
        # re-decoding the whole stream (engine/detokenizer.py); output is
        # bit-identical to decode(generated_token_ids)
        detok = getattr(seq, "_detok", None)
        if detok is None:
            from production_stack_tpu.engine.detokenizer import (
                IncrementalDetokenizer,
            )

            detok = IncrementalDetokenizer(self.tokenizer)
            # post-preemption replay: what was generated before these
            for t in seq.token_ids(seq.orig_prompt_len, seq.num_tokens):
                detok.append(t)
            seq._detok = detok  # type: ignore[attr-defined]
        seq.append_tokens(tokens)
        self._generation_tokens_total += len(tokens)
        new_text = detok.extend(tokens)
        seq.output_text = new_text
        # deltas ACCUMULATE until _make_output drains them: a multi-step
        # dispatch appends K tokens before one output is built, and a
        # last-token-only delta would stream 1/K of the text.
        # Trailing U+FFFD chars are WITHHELD from the stream: a partial
        # UTF-8 character spanning tokens re-renders once completed, and
        # a delta already sent cannot be rewritten (they flush on finish
        # if the byte sequence really was invalid).
        prev_emitted = getattr(seq, "_emitted_chars", 0)
        stable = len(new_text)
        while stable > 0 and new_text[stable - 1] == "�":
            stable -= 1
        stable = max(stable, prev_emitted)  # never retract sent text
        seq._pending_delta = (
            getattr(seq, "_pending_delta", "")
            + new_text[prev_emitted:stable]
        )  # type: ignore[attr-defined]
        seq._emitted_chars = stable  # type: ignore[attr-defined]
        seq._pending_ids = (
            getattr(seq, "_pending_ids", []) + tokens
        )  # type: ignore[attr-defined]
        seq.check_stop(new_text)
        if (
            not seq.finished
            and getattr(seq, "_guided_choices", None) is not None
        ):
            g = list(seq.generated_token_ids)
            complete = any(list(ids) == g for ids in seq._guided_choices)
            extendable = any(
                len(ids) > len(g) and list(ids[: len(g)]) == g
                for ids in seq._guided_choices
            )
            # finish when a choice completed and nothing longer extends
            # it, or when no choice matches any more (the model chose
            # EOS at a complete-but-extendable prefix — the appended EOS
            # ends the stream like any other stop)
            if (complete and not (
                extendable and seq.eos_token_id is not None
            )) or (not complete and not extendable):
                seq.status = SequenceStatus.FINISHED_STOPPED
        # hard cap: the KV layout cannot hold more than max_model_len
        # positions, so stop at the context limit regardless of max_tokens
        if (
            not seq.finished
            and seq.num_tokens >= self.scheduler.config.max_model_len
        ):
            seq.status = SequenceStatus.FINISHED_LENGTH

    def _register_full_blocks(self, seq: Sequence) -> None:
        """Content-address the blocks whose tokens are all computed
        since the last call: past those adopted at admission, and
        without hashing again what the prefix match hashed."""
        bm = self.block_manager
        bs = bm.block_size
        n_full = min(seq.num_computed_tokens // bs, len(seq.block_table))
        hashes = seq.block_hashes
        for i in range(seq.num_registered_blocks, n_full):
            if i < len(hashes):
                bm.register_hash(hashes[i], seq.block_table[i])
            else:
                end = (i + 1) * bs
                hashes.append(bm.register_block(
                    hashes[-1] if hashes else seq.hash_seed,
                    seq.token_ids(i * bs, end), seq.block_table[i],
                    of_prompt=end <= seq.num_prompt_tokens,
                ))
            seq.num_registered_blocks = i + 1
            bm.note_saved(seq.block_table, i)

    def _make_output(self, seq: Sequence) -> RequestOutput:
        new_ids = getattr(seq, "_pending_ids", [])
        delta = getattr(seq, "_pending_delta", "")
        if seq.finished:
            # flush any withheld trailing U+FFFD (incomplete final char)
            # on EVERY finish path — stop, length, AND abort — so
            # concatenated deltas always equal the final text; a
            # stop-string-truncated output_text is shorter than the
            # emitted count and flushes nothing
            emitted = getattr(seq, "_emitted_chars", 0)
            if emitted < len(seq.output_text):
                delta += seq.output_text[emitted:]
                seq._emitted_chars = len(seq.output_text)  # type: ignore[attr-defined]
        seq._pending_ids = []  # type: ignore[attr-defined]
        seq._pending_delta = ""  # type: ignore[attr-defined]
        lp_all = lp_new = None
        if seq.sampling_params.logprobs is not None:
            lp_new = getattr(seq, "_pending_lps", [])
            seq._pending_lps = []  # type: ignore[attr-defined]
            # the full list is only materialised on the final output —
            # copying it per streamed step would be O(T^2) per request
            if seq.finished:
                lp_all = list(getattr(seq, "_logprob_entries", []))
        plp = None
        if seq.sampling_params.prompt_logprobs is not None and seq.finished:
            # vLLM shape: one entry per prompt position, None first
            # (no context scores position 0)
            plp = [None] + list(getattr(seq, "_prompt_lp_entries", []))
        # an output carries no copy of what the sequence holds: a round
        # makes one output a lane, and copying a lane's prompt and
        # answer into each would cost the step thread (and, when the
        # event loop drops them, the GIL) in proportion to the context.
        # The prompt is the sequence's own list unless a preemption
        # folded generated tokens into it; the cumulative ids are the
        # sequence's own list while it runs and, like `logprobs`, a
        # list of the output's own once it has finished.
        n_prompt = seq.orig_prompt_len
        folded = len(seq.prompt_token_ids) != n_prompt
        return RequestOutput(
            request_id=seq.request_id,
            prompt_token_ids=(
                seq.prompt_token_ids[:n_prompt] if folded
                else seq.prompt_token_ids
            ),
            token_ids=(
                seq.token_ids(n_prompt, seq.num_tokens)
                if folded or seq.finished else seq.output_token_ids
            ),
            new_token_ids=new_ids,
            text=seq.output_text,
            delta_text=delta,
            finished=seq.finished,
            finish_reason=seq.finish_reason,
            metrics=seq.metrics,
            num_cached_tokens=seq.metrics.num_cached_prompt_tokens,
            logprobs=lp_all,
            new_logprobs=lp_new,
            prompt_logprobs=plp,
        )

    # -- LoRA hot-load (adapters applied in the jitted steps; engine/lora.py)
    def load_lora(self, name: str, path: str) -> None:
        if self.runner.lora_manager is None:
            raise RuntimeError(
                "LoRA is disabled; start the engine with --enable-lora"
            )
        self.runner.lora_manager.load(name, path)

    def unload_lora(self, name: str) -> None:
        if self.runner.lora_manager is not None:
            self.runner.lora_manager.unload(name)

    def list_loras(self) -> list[str]:
        if self.runner.lora_manager is None:
            return []
        return self.runner.lora_manager.list_adapters()

    def _lora_slot(self, seq: Sequence) -> int:
        if self.runner.lora_manager is None:
            return 0
        try:
            return self.runner.lora_manager.slot_of(seq.lora_name)
        except KeyError:
            # adapter unloaded mid-request: degrade to the base model
            # rather than killing the step loop
            logger.warning(
                "request %s: LoRA %r no longer loaded; using base model",
                seq.request_id, seq.lora_name,
            )
            seq.lora_name = None
            return 0

    def shutdown(self) -> None:
        self.drain_round()
        if hasattr(self.runner, "shutdown_followers"):
            self.runner.shutdown_followers()
        if self.long_prefill is not None:
            self.long_prefill.close()
        if self.offload is not None:
            self.offload.close()  # also closes the PD PeerTier
        if self.kv_reporter is not None:
            self.kv_reporter.close()

    # -- embeddings (stateless one-shots, /v1/embeddings) -------------------
    def embed_one(
        self, text: str, lora_name: str | None = None
    ) -> tuple[np.ndarray, int]:
        """Embed one text -> (vector, token_count). One text per call so
        the server can release the step-loop lock between items."""
        ids = self.tokenizer.encode(text)
        if not ids:
            ids = [self.tokenizer.eos_token_id or 0]
        lora_slot = 0
        if lora_name is not None:
            if self.runner.lora_manager is None:
                raise ValueError(
                    "embeddings for a LoRA adapter require --enable-lora"
                )
            lora_slot = self.runner.lora_manager.slot_of(lora_name)
        return self.runner.embed(ids, lora_slot=lora_slot), len(ids)

    def embed(self, texts: list[str],
              lora_name: str | None = None) -> list[np.ndarray]:
        return [self.embed_one(t, lora_name)[0] for t in texts]

    # -- stats for /metrics -------------------------------------------------
    def _layer_group_stats(self) -> dict:
        """The snapshot fields of a model of layer groups (none for a
        model of alike layers). All host memory: nothing here waits
        for the device. The caller holds the step lock."""
        mc = self.runner.model_config
        if not mc.layer_groups:
            return {}
        names = ["window" if ak.window else
                 "latent" if ak.latent_dim else "full"
                 for ak in mc.attn_kinds]
        bm = self.block_manager
        in_use = {names[0]: round(bm.usage * (bm.num_blocks - 1))}
        out = {}
        if self.runner.num_window_blocks:
            in_use["window"] = bm.window_blocks_in_use
            out = {
                "kv_window_blocks_released_total":
                    bm.window_blocks_released,
                "kv_window_blocks_per_seq":
                    tuple(self._window_blocks_per_seq),
                "prefix_window_cutback_blocks": tuple(bm.prefix_cutback),
            }
        if self.runner.num_state_slots:
            out["ssm_stats"] = {
                "state_slots_in_use": bm.state_slots_in_use,
                "snapshots_resident": bm.snapshots_resident,
                "snapshot_saves": bm.snapshot_saves,
                "snapshot_restores": bm.snapshot_restores,
                "snapshot_evictions": bm.snapshot_evictions,
                "prefix_state_cutback_tokens": bm.cutback_tokens,
                "lane_layer_steps": self.runner.ssm_lane_layer_steps,
                "update_calls": self.runner.state_update_calls,
            }
        return {
            "attn_context_by_kind": {
                n: c[0] for n, c in zip(
                    names, self.runner.attn_context_by_kind)},
            "moe_stats": self.runner.moe_stats(),
            "kv_blocks_in_use": in_use,
            **out,
        }

    def stats(self) -> EngineStatsSnapshot:
        _remote = self.offload.remote if self.offload is not None else None
        return EngineStatsSnapshot(
            num_running=self.scheduler.num_running,
            num_waiting=self.scheduler.num_waiting,
            kv_usage=self.block_manager.usage,
            prefix_cache_queries=self.block_manager.prefix_queries,
            prefix_cache_hits=self.block_manager.prefix_hits,
            prefix_blocks_hashed_total=self.block_manager.blocks_hashed,
            prompt_tokens_total=self._prompt_tokens_total,
            generation_tokens_total=self._generation_tokens_total,
            num_preemptions_total=self._preemptions_total,
            requests_finished_total=self._finished_total,
            spec_draft_tokens_total=self._spec_drafts_total,
            spec_accepted_tokens_total=self._spec_accepted_total,
            engine_phases=self.phases.pairs(),
            engine_phases_offcpu=self.phases.offcpu_pairs(),
            attn_context_tokens=tuple(self.runner.attn_context_tokens),
            attn_lane_tokens=tuple(self.runner.attn_lane_tokens),
            decode_lane_steps=tuple(self.runner.decode_lane_steps),
            sampler_steps=tuple(self.runner.sampler_steps),
            loop_passes_total=self.runner.loop_passes,
            loop_exit_mass=self.runner.loop_exit_mass(),
            **self._layer_group_stats(),
            program_stages=phases.program_stage_pairs(),
            program_cache_hits_total=phases.PROGRAM_CACHE_HITS[0],
            prefill_chained_chunks_total=self._pf_chained_chunks_total,
            long_prefill_requests_total=(
                self.long_prefill.requests_total
                if self.long_prefill is not None else 0
            ),
            long_prefill_chunks_total=(
                self.long_prefill.chunks_total
                if self.long_prefill is not None else 0
            ),
            long_prefill_fallbacks_total=(
                self.long_prefill.fallbacks_total
                if self.long_prefill is not None else 0
            ),
            long_prefill_ring_seconds_total=(
                self.long_prefill.phase_s["ring"]
                if self.long_prefill is not None else 0.0
            ),
            long_prefill_d2h_seconds_total=(
                self.long_prefill.phase_s["d2h"]
                if self.long_prefill is not None else 0.0
            ),
            long_prefill_land_seconds_total=(
                self.long_prefill.phase_s["land"]
                if self.long_prefill is not None else 0.0
            ),
            long_prefill_overflow_seconds_total=(
                self.long_prefill.phase_s["overflow"]
                if self.long_prefill is not None else 0.0
            ),
            decode_rounds_total=self._decode_rounds_total,
            decode_early_dispatch_total=self._early_dispatch_total,
            decode_overshoot_tokens_total=(
                self._decode_overshoot_tokens_total
            ),
            decode_early_exit_rounds_total=(
                self._decode_early_exit_rounds_total
            ),
            ragged_rounds_total=self._ragged_rounds_total,
            ragged_split_rounds_total=self._ragged_split_rounds_total,
            ragged_prefill_lanes_total=(
                self._ragged_prefill_lanes_total
            ),
            ragged_decode_lanes_total=self._ragged_decode_lanes_total,
            compile_events_total=self.runner.compile_events_total,
            compile_events=dict(self.runner.compile_events),
            kv_export_seconds_total=self._kv_export_seconds_total,
            kv_export_blocks_total=self._kv_export_blocks_total,
            kv_export_bytes_total=self._kv_export_bytes_total,
            kv_restore_seconds_total=self._kv_restore_seconds_total,
            kv_restore_blocks_total=self._kv_restore_blocks_total,
            kv_restore_bytes_total=self._kv_restore_bytes_total,
            kv_restore_fallbacks_total=self._kv_restore_fallbacks_total,
            kv_tier_counters=(
                self.offload.counters()
                if self.offload is not None else {}
            ),
            kv_peer_hits_total=(
                self.kv_peer.hits if self.kv_peer is not None else 0
            ),
            kv_peer_misses_total=(
                self.kv_peer.misses if self.kv_peer is not None else 0
            ),
            kv_peer_read_bytes_total=(
                self.kv_peer.read_bytes
                if self.kv_peer is not None else 0
            ),
            kv_peer_fallbacks_total=(
                self.kv_peer.fallbacks
                if self.kv_peer is not None else 0
            ),
            kv_remote_hits_total=(
                _remote.hits if _remote is not None else 0
            ),
            kv_remote_misses_total=(
                _remote.misses if _remote is not None else 0
            ),
            kv_remote_read_bytes_total=(
                _remote.read_bytes if _remote is not None else 0
            ),
            kv_remote_write_bytes_total=(
                _remote.write_bytes if _remote is not None else 0
            ),
            kv_remote_flushes_total=(
                _remote.flushes if _remote is not None else 0
            ),
            kv_remote_fallbacks_total=(
                _remote.fallbacks if _remote is not None else 0
            ),
        )

    # -- offline convenience (tests, benchmarks) ---------------------------
    def generate(
        self,
        prompts: list[str] | list[list[int]],
        sampling_params: SamplingParams | list[SamplingParams] | None = None,
    ) -> list[RequestOutput]:
        """Synchronous batch generation; returns final outputs in order."""
        finals: dict[str, RequestOutput] = {}
        for i, p in enumerate(prompts):
            sp = (
                sampling_params[i]
                if isinstance(sampling_params, list)
                else sampling_params
            )
            kwargs = (
                {"prompt_token_ids": p}
                if isinstance(p, list)
                else {"prompt": p}
            )
            self.add_request(f"gen-{i}", sampling_params=sp, **kwargs)
        while self.has_unfinished():
            for out in self.step():
                if out.finished:
                    finals[out.request_id] = out
        return [finals[f"gen-{i}"] for i in range(len(prompts))]

    def precompile_serving(self) -> int:
        """Compile every config-derivable serving program shape: the
        FULL grid of prefill programs (every pow2 chunk bucket — final
        tail chunks land anywhere below max_prefill_chunk — x every
        reachable ctx bucket x every pow2 packed-group size), the
        fused-K decode program per ctx bucket (+ the chained variant
        a staged round dispatches), and, with spec decode on, the
        packed verify programs.
        Servers call this at startup (--precompile-serving) so no XLA
        compile lands inside a live request's TTFT/ITL. Returns the
        number of trash dispatches executed.

        Out of scope (request-dependent, not config-derivable): the
        penalties / logprobs / guided-table variants of the decode
        program — requests using those sampling features may pay one
        compile per variant. First-boot cost is minutes (the grid is
        O(log^2) programs); with JAX_COMPILATION_CACHE_DIR restarts
        reuse every program."""
        rnr = self.runner
        cfg = self.config
        bs = self.block_manager.block_size
        # reachable ctx buckets: pow2 block counts from one block up to
        # the smaller of max_model_len and what the pool can hold
        cap = min(cfg.max_model_len, rnr.num_blocks * bs)
        ctxs: list[int] = []
        c = rnr._ctx_bucket(1)
        while True:
            ctxs.append(c)
            if c >= cap:
                break
            c = rnr._ctx_bucket(c + 1)
        # chunk-length buckets: every pow2 t_pad bucket up to the full
        # chunk (a prompt of any length puts its final tail chunk in
        # any of them)
        tbs: list[int] = []
        t = rnr._prefill_bucket(1)
        while True:
            tbs.append(t)
            if t >= rnr._prefill_bucket(cfg.max_prefill_chunk):
                break
            t = rnr._prefill_bucket(t + 1)
        singles: list[tuple[int, int]] = []
        groups: list[tuple[int, int, int]] = []
        for c in ctxs:
            for t in tbs:
                if t > c:
                    continue
                singles.append((t, c))
                # every pow2 group size: the packed program key is
                # s_pad = next_pow2(n_actual), so a 2-seq burst is a
                # different program than the max group
                s = 2
                while s <= cfg.max_prefill_seqs:
                    groups.append((s, t, c))
                    s *= 2
        if rnr.ragged_kernel and rnr.prefill_pipeline:
            # single-kernel mode: the packed-prefill program keys on
            # the padded ROW bucket (r_pad, pc_pad), so (group, chunk)
            # pairs with equal row counts share one variant — warm
            # each row bucket once instead of the full lane-mix grid
            # (chunk buckets are pow2 >= RAGGED_TQ, so s * t IS the
            # packed row count)
            seen_rows: set[tuple[int, int]] = set()
            deduped: list[tuple[int, int, int]] = []
            for s, t, c in groups:
                rkey = (rnr._rows_bucket(s * t), c)
                if rkey in seen_rows:
                    continue
                seen_rows.add(rkey)
                deduped.append((s, t, c))
            groups = deduped
        n = rnr.precompile_prefill(singles, groups)
        # decode: pick context lens that land IN each bucket after the
        # +K-1 lookahead shift (passing the bucket boundary itself would
        # shift every program one bucket up and leave the smallest
        # bucket cold). Every round is K steps; device stops select a
        # distinct program variant.
        kk, chained, stop = decode_precompile_variant(
            cfg.num_scheduler_steps,
            overlap=self._prefetch_decode,
            device_stop=self._device_stop,
        )
        n += rnr.precompile_decode(
            [max(1, c - kk + 1) for c in ctxs], kk,
            chained=chained, stop=stop,
        )
        if self._ragged_dispatch:
            # unified ragged rounds: warm the pow2 lane-mix buckets —
            # every prefill-lane group size x each ctx bucket, prefill
            # context matched to the decode bucket (sessions in one workload share a length regime;
            # off-diagonal prefill/decode context pairs are
            # request-dependent and compile on first use, cached by
            # JAX_COMPILATION_CACHE_DIR across restarts)
            n += rnr.precompile_ragged(
                [max(1, c - kk + 1) for c in ctxs],
                [kk],
                cfg.max_prefill_seqs,
                cfg.max_prefill_chunk,
                stop=self._device_stop,
                chained=self._prefetch_decode,
            )
        if cfg.num_speculative_tokens > 0:
            n += rnr.precompile_verify(
                ctxs, cfg.num_speculative_tokens + 1, cfg.max_num_seqs
            )
        if self.offload is not None:
            # staged restores (tier AND PD peer pulls) dispatch the
            # donated import scatter; warm its pow2 buckets so no XLA
            # compile lands inside a live admission (a restore chain is
            # at most max_model_len blocks)
            n += rnr.precompile_kv_import(cap // bs)
        return n
