"""Continuous-batching scheduler with chunked prefill and preemption.

The capability contract mirrors what the reference stack's engines provide
(continuous batching + chunked prefill flags in reference:
helm/templates/deployment-vllm-multi.yaml:140-146), re-shaped for TPU/XLA:
each engine step is either ONE packed prefill dispatch (chunks from up to
max_prefill_seqs sequences, each bucketed to a static length) or ONE decode
batch (fixed lane count), so every device program has a static shape and
jit traces a handful of bucket variants total. Prefill is prefill-priority
(lowest TTFT, the benchmark's headline metric) with a token budget per
chunk; decode packs all running sequences into one batch.

Queues: waiting (FIFO admission) -> running; preemption-by-recomputation
pushes the youngest running sequence back to the front of waiting when KV
blocks run out (vLLM v0 semantics).

Prefill/decode interleaving: a long multi-chunk prefill must not starve
running decodes (the reference stack's engines mix chunked prefill with
decode in one step — reference: helm/templates/deployment-vllm-multi.yaml:140-146;
our static-shape design alternates instead). `decode_interleave = K` caps
consecutive prefill DISPATCHES at K while any decode-ready sequence exists
(a packed dispatch of up to max_prefill_seqs chunks spends ONE unit of
that budget — through a remote chip the dispatch RTT, not the chunk
count, dominates its wall cost), so the inter-token gap of a running
stream is bounded by ~K prefill dispatches + one decode step regardless
of how many new users are admitted.

Unified ragged dispatch (`ragged_dispatch=True`): the alternation above
disappears entirely. `plan_ragged_round` packs every mid-prefill
runner's next chunk AND the decode-ready batch into ONE lane-typed
round (the engine dispatches both halves in a single device program —
model_runner.ragged_dispatch), so a waiting prefill claims a lane in
the very next round instead of queueing behind the interleave streak.
The streak counter stays in place for the split path
(`--no-ragged-dispatch`, multihost, meshed engines). A fused decode
round has one size everywhere, the operator's `--num-scheduler-steps`:
the scheduler only reserves its lookahead (`decode_lookahead`).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

from production_stack_tpu.engine.block_manager import BlockManager
from production_stack_tpu.engine.sequence import Sequence, SequenceStatus
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)


@dataclass
class PrefillWork:
    seq: Sequence
    chunk_start: int  # == seq.num_computed_tokens at schedule time
    chunk_len: int

    @property
    def is_last_chunk(self) -> bool:
        return (
            self.chunk_start + self.chunk_len >= self.seq.num_prompt_tokens
        )


@dataclass
class DecodeWork:
    seqs: list[Sequence]


@dataclass
class SchedulerOutput:
    # one step runs EVERY listed prefill chunk in a single packed
    # dispatch (cross-sequence prefill packing); empty list = no prefill
    prefills: list[PrefillWork] = field(default_factory=list)
    decode: DecodeWork | None = None
    preempted: list[Sequence] = field(default_factory=list)
    # sequences rejected at admission (e.g. prompt too long); the engine
    # must emit a final aborted output for these so clients don't hang
    aborted: list[Sequence] = field(default_factory=list)

    @property
    def prefill(self) -> PrefillWork | None:
        """First scheduled prefill chunk (single-chunk-era accessor)."""
        return self.prefills[0] if self.prefills else None

    @property
    def is_empty(self) -> bool:
        return (
            not self.prefills
            and self.decode is None
            and not self.aborted
        )

    @property
    def is_ragged(self) -> bool:
        """True for a lane-typed mixed round (unified ragged dispatch):
        prefill-chunk lanes AND a decode batch planned together. The
        split path never produces one — prefills and decode are mutually
        exclusive there."""
        return bool(self.prefills) and self.decode is not None


@dataclass
class SchedulerConfig:
    max_num_seqs: int = 8
    max_prefill_chunk: int = 512
    max_model_len: int = 8192
    enable_chunked_prefill: bool = True
    # cross-sequence prefill packing: chunks from up to this many
    # sequences share one dispatch. Packing needs chunked prefill (each
    # chunk is bounded by max_prefill_chunk, so a packed program is at
    # most max_prefill_seqs x max_prefill_chunk tokens); with chunking
    # off, groups stay at 1.
    max_prefill_seqs: int = 8
    # "fcfs" | "priority" (see EngineConfig.scheduling_policy)
    scheduling_policy: str = "fcfs"
    # max consecutive prefill dispatches (each packing up to
    # max_prefill_seqs chunks) while decode-ready sequences wait;
    # 0 disables interleaving (prefill runs to completion first)
    decode_interleave: int = 1
    # extra decode positions to reserve per scheduled sequence so a
    # multi-step dispatch (num_scheduler_steps - 1 lookahead) never runs
    # off the end of its block table mid-scan
    decode_lookahead: int = 0
    # unified ragged dispatch (EngineConfig.ragged_dispatch, gated by
    # the engine for multihost/async/mesh): plan ONE lane-typed round
    # carrying prefill-chunk lanes AND the decode batch together —
    # dissolves the interleave streak for in-round prefill work
    # (plan_ragged_round)
    ragged_dispatch: bool = False
    # long-prefill lane (EngineConfig.long_prefill_threshold, set by
    # the engine only when its ring manager actually built): an
    # admitted prompt whose uncached remainder exceeds this many
    # tokens is handed to the `long_prefill` hook instead of the
    # chunked lanes — the engine drives its ring chunks and KV landing
    # itself, one enqueue per step, so decode/ragged rounds for other
    # users keep running. 0 = off.
    long_prefill_threshold: int = 0


def decode_precompile_variant(
    k: int, *, overlap: bool, device_stop: bool,
) -> tuple[int, bool, bool]:
    """(k, chained, stop): the decode program a serving config
    dispatches — what LLMEngine.precompile_serving warms, kept beside
    the scheduler so it cannot silently warm another program than the
    runtime selects (a missed variant = a mid-request XLA compile).
    `overlap` = the h2d prefetch, whose staged round dispatches the
    chained program; K=1 is the single step, which has neither."""
    k = max(1, k)
    return k, overlap and k > 1, device_stop and k > 1


class Scheduler:
    def __init__(self, config: SchedulerConfig, block_manager: BlockManager):
        self.config = config
        self.block_manager = block_manager
        self.waiting: deque[Sequence] = deque()
        self.running: list[Sequence] = []
        # optional hook (LLMEngine._restore_from_offload): pull offloaded
        # KV blocks back into HBM before prompt allocation. Returns
        # truthy to proceed with admission; falsy to DEFER this request
        # (its staged restore — tier fetch + h2d upload — is still in
        # flight; admission order is preserved, so the loop breaks and
        # retries next step while decode keeps running)
        self.kv_restore = None
        # optional hook (LLMEngine._flush_kv_exports): enqueue the
        # deferred-export device snapshot NOW, releasing export-pinned
        # blocks back to the pool. Returns True when anything was
        # flushed — callers retry the failed allocation once before
        # falling back to preemption. The flush is enqueue-only (the
        # snapshot is device-ordered before any later dispatch's
        # writes), so calling it mid-schedule costs no stall.
        self.kv_flush = None
        # optional hook (LLMEngine._begin_long_prefill): claim an
        # admitted sequence for the long-prefill lane (context-parallel
        # ring prefill). The hook marks seq.long_prefill_active and
        # returns truthy when it takes the sequence; a declined
        # sequence (LoRA, prompt_logprobs, ring unavailable) serves on
        # the ordinary chunked lanes. Long-lane sequences are skipped
        # by BOTH prefill planners below — the engine drives their
        # chunks outside schedule().
        self.long_prefill = None
        # optional request-lifecycle recorder (tracing.TimelineRecorder,
        # set by LLMEngine): admit/resume/preempt events for the
        # per-request timeline; None/disabled costs one check
        self.timeline = None
        self._prefill_streak = 0  # consecutive prefill steps scheduled

    # -- queue introspection (feeds the vllm:num_requests_* gauges) -------
    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    @property
    def num_running(self) -> int:
        return len(self.running)

    def has_unfinished(self) -> bool:
        return bool(self.waiting or self.running)

    # -- entry points -----------------------------------------------------
    def add_seq(self, seq: Sequence) -> None:
        seq.status = SequenceStatus.WAITING
        self.waiting.append(seq)

    def abort(self, request_id: str) -> bool:
        for i, seq in enumerate(self.waiting):
            if seq.request_id == request_id:
                seq.status = SequenceStatus.FINISHED_ABORTED
                del self.waiting[i]
                return True
        for seq in list(self.running):
            if seq.request_id == request_id:
                seq.status = SequenceStatus.FINISHED_ABORTED
                self.free_finished(seq)
                return True
        return False

    def free_finished(self, seq: Sequence) -> None:
        if seq in self.running:
            self.running.remove(seq)
        self.block_manager.free(seq.block_table)
        seq.block_table = []

    # -- scheduling -------------------------------------------------------
    def schedule(self) -> SchedulerOutput:
        out = SchedulerOutput()
        restore_deferred = False

        # 1) admit waiting sequences while there is room
        while self.waiting and len(self.running) < self.config.max_num_seqs:
            if self.config.scheduling_policy == "priority":
                # lower priority value first, FIFO within a class; the
                # waiting queue is short (bounded by arrival rate), so a
                # linear scan beats maintaining a heap through the
                # deque's other uses (preemption pushes LEFT)
                seq = min(
                    self.waiting,
                    key=lambda s: (s.priority, s.arrival_ordinal),
                )
                if seq is not self.waiting[0]:
                    self.waiting.remove(seq)
                    self.waiting.appendleft(seq)
            seq = self.waiting[0]
            bm = self.block_manager
            min_blocks = (
                seq.num_prompt_tokens + 1 + bm.block_size - 1
            ) // bm.block_size
            if (
                seq.num_prompt_tokens + 1 > self.config.max_model_len
                or min_blocks > bm.num_blocks - 1
            ):
                logger.warning(
                    "request %s cannot fit (prompt %d tokens, "
                    "max_model_len %d, pool %d blocks); aborting",
                    seq.request_id, seq.num_prompt_tokens,
                    self.config.max_model_len, bm.num_blocks - 1,
                )
                seq.status = SequenceStatus.FINISHED_ABORTED
                self.waiting.popleft()
                out.aborted.append(seq)
                continue
            if self.kv_restore is not None:
                try:
                    proceed = self.kv_restore(seq)
                except Exception:  # noqa: BLE001 — restore is best-effort;
                    # a failure must never kill the step loop (the prompt
                    # is simply recomputed from scratch)
                    logger.exception("kv restore failed; recomputing prefix")
                    proceed = True
                if not proceed:
                    # staged restore in flight: hold this admission slot
                    # (FIFO preserved) and let decode run; the engine's
                    # wait budget bounds how long a wedged tier can
                    # defer (then the hook returns True = recompute)
                    restore_deferred = True
                    break
            alloc = None
            for _ in range(2):
                alloc = self.block_manager.allocate_prompt(
                    seq.prompt_token_ids, seed=seq.hash_seed,
                    # prompt_logprobs must COMPUTE every position; a
                    # prefix hit would skip its rows (vLLM disables
                    # reuse the same way for these requests)
                    reuse_cache=(
                        seq.sampling_params.prompt_logprobs is None
                    ),
                    hashes=seq.block_hashes,
                )
                if alloc is not None or self.kv_flush is None or \
                        not self.kv_flush():
                    break
                # export-pinned blocks just returned to the pool: retry
                # once before escalating to preemption
            if alloc is None:
                if self._priority_preempt_for(seq, out):
                    continue  # blocks freed; retry this admission
                break  # out of blocks; retry next step
            table, cached = alloc
            seq.block_table = table
            seq.num_computed_tokens = cached
            # the adopted blocks are registered: theirs are the first
            # of the hashes the match left on the sequence
            seq.num_registered_blocks = (
                cached // self.block_manager.block_size
            )
            seq.metrics.num_cached_prompt_tokens = cached
            seq.status = SequenceStatus.RUNNING
            self.waiting.popleft()
            self.running.append(seq)
            self._note_admitted(seq)
            if (
                self.long_prefill is not None
                and self.config.long_prefill_threshold > 0
                and seq.num_uncomputed_prompt_tokens
                > self.config.long_prefill_threshold
            ):
                # long-prefill lane: the ring prefill computes this
                # prompt off the chunked path (admission still gated
                # the FULL chain's block allocation above — a prompt
                # the pool cannot hold was rejected/deferred, the
                # cluster-level gate is the router's context-window
                # filter on the /v1/models card)
                try:
                    self.long_prefill(seq)
                except Exception:  # noqa: BLE001 — the claim is
                    # best-effort: a ring failure must never kill the
                    # step loop; the chunked planners serve the prompt
                    logger.exception(
                        "long-prefill claim failed for %s; serving "
                        "via chunked prefill", seq.request_id,
                    )
        # priority policy: a waiting higher-priority request CLAIMS a
        # lane from a running lower-priority one (vLLM preempts for
        # priority, not just for block exhaustion) — without this,
        # priority would only reorder the waiting queue and inversion
        # under a full lane pool would be unbounded
        if (
            self.config.scheduling_policy == "priority"
            and self.waiting
            and not restore_deferred  # a deferral is not a capacity
            # shortage: evicting a runner for a request that cannot
            # admit yet would recompute the victim for nothing
            and len(self.running) >= self.config.max_num_seqs
        ):
            cand = min(
                self.waiting,
                key=lambda s: (s.priority, s.arrival_ordinal),
            )
            worst = max(
                self.running,
                key=lambda s: (s.priority, s.arrival_ordinal),
            )
            if (cand.priority, cand.arrival_ordinal) < (
                worst.priority, worst.arrival_ordinal
            ) and self._eviction_can_fit(cand):
                self._preempt(worst, out)
                # one lane per step keeps the preemption cost bounded;
                # the next schedule() admits cand through the normal
                # loop (and preempts again if more claims remain)
                return self.schedule_admit_retry(out)

        # unified ragged dispatch: no interleave arbitration — every
        # mid-prefill runner's next chunk AND the decode-ready batch
        # share ONE lane-typed round
        if self.config.ragged_dispatch:
            return self.plan_ragged_round(out)

        # 2) prefill priority: oldest running sequence with prompt left —
        # UNLESS decode-ready sequences have already waited through
        # `decode_interleave` consecutive prefill DISPATCHES (each one
        # packed group; bounded ITL)
        has_decode_ready = any(
            s.prefill_done and not s.finished for s in self.running
        )
        decode_starved = (
            self.config.decode_interleave > 0
            and has_decode_ready
            and self._prefill_streak >= self.config.decode_interleave
        )
        if not decode_starved:
            group_cap = (
                self.config.max_prefill_seqs
                if self.config.enable_chunked_prefill
                else 1
            )
            for seq in self.running:
                if seq.prefill_done or seq.long_prefill_active:
                    # long-lane sequences ring outside schedule(); a
                    # chunked dispatch for them would double-compute
                    continue
                if len(out.prefills) >= group_cap:
                    break
                chunk_len = seq.num_uncomputed_prompt_tokens
                if self.config.enable_chunked_prefill:
                    chunk_len = min(
                        chunk_len, self.config.max_prefill_chunk
                    )
                out.prefills.append(PrefillWork(
                    seq=seq,
                    chunk_start=seq.num_computed_tokens,
                    chunk_len=chunk_len,
                ))
            if out.prefills:
                # streak counts DISPATCHES, not chunks: a packed group of
                # N chunks is ONE device dispatch. Counting chunks
                # throttled admission to ONE UNPACKED chunk per decode
                # round under load (cost of either on an attached chip:
                # not measured).
                self._prefill_streak += 1
                return out
        self._prefill_streak = 0

        # 3) otherwise decode every decode-ready running sequence (mid-
        # prefill sequences sit out the interleaved decode steps)
        decode_seqs = self._collect_decode_ready(out)
        if decode_seqs:
            out.decode = DecodeWork(seqs=decode_seqs)
        return out

    def _collect_decode_ready(
        self, out: SchedulerOutput
    ) -> list[Sequence]:
        """Capacity-checked decode batch: every decode-ready running
        sequence whose block table can grow to cover this round's
        lookahead, preempting (or self-preempting) on exhaustion —
        shared by the split path's decode step and plan_ragged_round."""
        decode_seqs: list[Sequence] = []
        for seq in list(self.running):
            if seq.finished or seq not in self.running:
                # may have been preempted while scheduling an earlier seq
                continue
            if not seq.prefill_done:
                continue
            while not self.block_manager.ensure_capacity(
                seq.num_tokens + self.config.decode_lookahead,
                seq.block_table,
            ):
                if self.kv_flush is not None and self.kv_flush():
                    continue  # export pins released; retry before
                    # preempting anyone (flush empties the queue, so
                    # the second pass cannot loop here)
                victim = self._pick_preemption_victim(exclude=seq)
                if victim is None:
                    if len(self.running) == 1:
                        # a lone sequence has outgrown the entire pool;
                        # abort it rather than deadlocking the step loop
                        logger.error(
                            "request %s outgrew the KV pool (%d tokens); "
                            "aborting", seq.request_id, seq.num_tokens,
                        )
                        seq.status = SequenceStatus.FINISHED_ABORTED
                        self.free_finished(seq)
                        out.aborted.append(seq)
                        break
                    victim = seq
                self._preempt(victim, out)
                if victim in decode_seqs:
                    decode_seqs.remove(victim)
                if victim is seq:
                    break
            else:
                decode_seqs.append(seq)
                # a second cache group lets go of what lies behind the
                # window of this lane's next query (no-op with one pool)
                self.block_manager.release_behind(
                    seq.block_table, seq.num_computed_tokens
                )
        return decode_seqs

    # stackcheck: hot-path — pure host planning of the lane-typed round
    # on the scheduling path: one pass over running, no device work
    def plan_ragged_round(self, out: SchedulerOutput) -> SchedulerOutput:
        """Plan ONE lane-typed round (unified ragged dispatch): the
        decode-ready batch claims the decode lanes and every mid-prefill
        runner's next chunk claims a prefill lane IN THE SAME ROUND — a
        freshly admitted prompt is dispatched on the very next round
        with no interleave-streak wait, which is the scheduling contract
        tests/test_ragged_dispatch.py pins. The decode-capacity pass
        (with its preemption) runs FIRST so a victim never also claims a
        prefill lane."""
        decode_seqs = self._collect_decode_ready(out)
        group_cap = (
            self.config.max_prefill_seqs
            if self.config.enable_chunked_prefill
            else 1
        )
        for seq in self.running:
            if seq.prefill_done or seq.finished or seq.long_prefill_active:
                # long-lane sequences never claim a ragged prefill lane
                # (the engine rings them one enqueue per step)
                continue
            if len(out.prefills) >= group_cap:
                break
            chunk_len = seq.num_uncomputed_prompt_tokens
            if self.config.enable_chunked_prefill:
                chunk_len = min(chunk_len, self.config.max_prefill_chunk)
            out.prefills.append(PrefillWork(
                seq=seq,
                chunk_start=seq.num_computed_tokens,
                chunk_len=chunk_len,
            ))
        if decode_seqs:
            out.decode = DecodeWork(seqs=decode_seqs)
        return out

    def _note_admitted(self, seq: Sequence) -> None:
        """Queue-wait/stall bookkeeping + timeline event on each
        WAITING/PREEMPTED -> RUNNING transition. Admission is off the
        device-dispatch path, so the time.time() stamps here are free."""
        now = time.time()
        m = seq.metrics
        resumed = m.last_preempt_time is not None
        if resumed:
            m.preempt_stall_s += now - m.last_preempt_time
            m.last_preempt_time = None
        if m.admitted_time is None:
            m.admitted_time = now
        tl = self.timeline
        if tl is not None and tl.enabled:
            tl.event(
                seq.request_id,
                "resume" if resumed else "admit",
                {
                    "queue_wait_s": round(now - m.arrival_time, 6),
                    "cached_prompt_tokens": m.num_cached_prompt_tokens,
                    **(
                        {"stall_s": round(m.preempt_stall_s, 6)}
                        if resumed else {}
                    ),
                },
            )

    def schedule_admit_retry(self, out: SchedulerOutput) -> SchedulerOutput:
        """Re-run schedule() after a priority claim, merging the
        preemption bookkeeping into the same step's output."""
        nxt = self.schedule()
        nxt.preempted = out.preempted + nxt.preempted
        nxt.aborted = out.aborted + nxt.aborted
        return nxt

    def _eviction_can_fit(self, cand: Sequence) -> bool:
        """Feasibility gate before ANY priority eviction: evicting every
        strictly lower-standing runner must free enough blocks for
        `cand`'s minimum allocation — otherwise victims would lose
        their KV progress while the claimed lane sits idle (the freed
        capacity can never admit cand, and lower-priority waiters must
        not jump it under strict priority)."""
        bs = self.block_manager.block_size
        need = (cand.num_prompt_tokens + 1 + bs - 1) // bs
        if (self.block_manager.enable_prefix_caching
                and cand.sampling_params.prompt_logprobs is None):
            # shared cached prefix blocks cost no new allocation (same
            # cap as allocate_prompt: at least one token computes)
            _, cached_tokens = self.block_manager.match_prefix(
                cand.prompt_token_ids, cand.hash_seed, cand.block_hashes
            )
            cached_tokens = min(
                cached_tokens, cand.num_prompt_tokens - 1
            )
            need -= cached_tokens // bs
        avail = self.block_manager.num_free_blocks
        ck = (cand.priority, cand.arrival_ordinal)
        for s in self.running:
            if (s.priority, s.arrival_ordinal) > ck:
                avail += len(s.block_table)
        return avail >= need

    def _priority_preempt_for(
        self, seq: Sequence, out: SchedulerOutput
    ) -> bool:
        """Block-shortage variant of the priority claim: free blocks by
        evicting a strictly lower-standing RUNNING sequence so `seq`
        can allocate. Returns True when a victim was preempted."""
        if self.config.scheduling_policy != "priority" or not self.running:
            return False
        if not self._eviction_can_fit(seq):
            return False
        worst = max(
            self.running,
            key=lambda s: (s.priority, s.arrival_ordinal),
        )
        if (seq.priority, seq.arrival_ordinal) < (
            worst.priority, worst.arrival_ordinal
        ):
            self._preempt(worst, out)
            return True
        return False

    def _pick_preemption_victim(self, exclude: Sequence) -> Sequence | None:
        if self.config.scheduling_policy == "priority":
            # evict the LOWEST-priority running sequence (largest value),
            # youngest among ties — a high-priority request must not be
            # recomputed to make room for a low-priority one. If the
            # REQUESTER itself is the lowest-standing sequence, return
            # None so it self-preempts instead of evicting a
            # higher-priority neighbour.
            best = None
            for seq in self.running:
                if seq is exclude:
                    continue
                key = (seq.priority, seq.arrival_ordinal)
                if best is None or key > (best.priority,
                                          best.arrival_ordinal):
                    best = seq
            if best is not None and (
                (best.priority, best.arrival_ordinal)
                > (exclude.priority, exclude.arrival_ordinal)
            ):
                return best
            return None
        for seq in reversed(self.running):  # youngest first
            if seq is not exclude:
                return seq
        return None

    def _preempt(self, seq: Sequence, out: SchedulerOutput) -> None:
        logger.info("preempting request %s (recompute)", seq.request_id)
        self.running.remove(seq)
        self.block_manager.free(seq.block_table)
        seq.reset_for_recompute()
        self.waiting.appendleft(seq)
        out.preempted.append(seq)
        tl = self.timeline
        if tl is not None and tl.enabled:
            tl.event(
                seq.request_id, "preempt",
                {"num_preemptions": seq.metrics.num_preemptions},
            )
