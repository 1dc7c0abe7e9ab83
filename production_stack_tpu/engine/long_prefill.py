"""Long-prefill lane: context-parallel ring prefill wired into serving.

A prompt longer than `EngineConfig.long_prefill_threshold` (with an sp
mesh available) stops riding the chunked-prefill lane: its blocks are
allocated at admission like any prompt, but the prompt itself runs as
sp-sharded ring chunks on the ("tp", "sp") mesh
(parallel/long_context.py) while the engine keeps dispatching ragged /
decode rounds for everyone else. The resulting layer-stacked KV lands
in the paged cache through the SAME zero-stall primitives KV tiering
and PD transfer use (`ModelRunner.stage_import_blocks` /
`import_staged_blocks`, PR 4), so decode afterwards is the normal paged
path and the landed chain is prefix-cache-registered — eligible for
tier export (disk / shared cache server) the moment it frees, which is
the overflow path for contexts bigger than steady-state HBM headroom.

Division of labor (the kv/offload.py split, applied to prefill):

- STEP THREAD (`advance`, called once per engine step): dispatch the
  next ring chunk (enqueue-only jitted call; the NEXT chunk's token
  buffer is staged so its h2d rides out the current chunk's compute —
  the PR 1 pipelined-prefill pattern), and land at most one parked
  wire-format block batch per step via the donated import scatter
  (enqueue-only). No device fetch, no blocking IO — decode rounds for
  other users keep their cadence between chunks.
- WORKER THREAD: after the last chunk is dispatched, wait for the ring
  to finish (`block_until_ready` — the measured ring wall), pull the
  final logits + the sp-sharded KV to the host (the d2h), relayout
  rows into the wire-format `(2, L, n, nkv, bs, d)` block batches the
  import primitives eat, and park them for the step thread. The
  blocking work lives HERE, mirroring the offload worker.

Failure degrades, never wedges: a failed ring (compile reject, OOM)
parks the record as 'failed' and the engine flips the sequence back to
the ordinary chunked-prefill lane (its block table is already
allocated; nothing is lost but time), counted in `fallbacks_total`.

Per-phase TTFT attribution (the `long_prefill` timeline event and the
tpu:prefill_* metric family): `ring` = job start -> ring compute
drained (includes the chunk-dispatch rounds the engine interleaved
with other users' decode — the ring slice of TTFT), `d2h` =
device->host KV materialization, `land` = first parked batch -> last
import enqueued (step-thread wall, overlapped with decode rounds by
design), `overflow` = tier-export seconds that ran while the job was
in flight (the engine attributes these — blocks evicted or
sync-flushed to make room for the landed chain).
"""

from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np

from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)

# blocks per landing batch: each batch is one staged h2d + one donated
# import dispatch on the step thread; pow2 so every batch reuses the
# precompile_kv_import diagonal (the final partial batch pads up inside
# stage_import_blocks)
LAND_BATCH_BLOCKS = 32


class LongPrefillManager:
    """Owns the ring prefiller, the in-flight long-prefill records, and
    the materialization worker. One instance per engine; all entry
    points except the worker body run on the engine step thread."""

    def __init__(self, runner, chunk_tokens: int):
        # runner builds the ("tp", "sp") prefiller (mesh + params
        # placement are device concerns); raises if the host lacks
        # tp*sp devices — the engine degrades to chunked prefill then
        self.runner = runner
        self.prefiller = runner.build_long_prefiller()
        self.block_size = runner.block_size
        # chunk length: ring-size AND block-size aligned so the padded
        # sequence always covers whole paged blocks
        self.chunk = self.prefiller.chunk_to(
            max(chunk_tokens, self.block_size), align=self.block_size
        )
        self._jobs: dict[str, dict] = {}
        # worker handoff: deque appends/pops are GIL-atomic; the
        # condition only wakes the worker (never held by the step
        # thread across device work)
        self._queue: deque = deque()
        self._cv = threading.Condition()
        self._worker: threading.Thread | None = None
        self._closed = False
        # lifetime accounting (tpu:long_prefill_*)
        self.requests_total = 0
        self.chunks_total = 0
        self.fallbacks_total = 0
        self.phase_s = {
            "ring": 0.0, "d2h": 0.0, "land": 0.0, "overflow": 0.0,
        }

    @property
    def active(self) -> bool:
        return bool(self._jobs)

    def jobs(self) -> int:
        return len(self._jobs)

    # -- step-thread API ---------------------------------------------------
    def start(self, seq, export_s0: float = 0.0) -> bool:
        """Begin a long prefill for an admitted sequence (block table
        already allocated). `export_s0` anchors the engine's
        overflow-export attribution. Returns False when this sequence
        cannot take the lane (it then serves on the chunked path)."""
        bs = self.block_size
        cached = seq.num_computed_tokens
        if cached % bs:
            # a non-block-aligned cached prefix only happens on nearly
            # fully-cached prompts; the chunked path serves those
            return False
        n = seq.num_prompt_tokens
        pre = self.prefiller
        s_pad = pre.seq_pad(n, self.chunk)
        rec = {
            "rid": seq.request_id,
            "seq": seq,
            "ids": list(seq.prompt_token_ids),
            "n": n,
            "table": list(seq.block_table),
            "start_block": cached // bs,
            "n_blocks": -(-n // bs),
            "s_pad": s_pad,
            # only the chunks that contain real tokens dispatch; the
            # pow2 tail of the padded cache stays zero (and is never
            # attended — every real query position sits below it)
            "ring_end": -(-n // self.chunk) * self.chunk,
            "kc": None,
            "vc": None,
            "next_start": 0,
            "staged_toks": None,
            "staged_start": -1,
            "logits_dev": None,
            "logits": None,
            "batches": deque(),  # (first_block_idx, wire ndarray)
            "batches_done": False,
            "landed_blocks": 0,
            "state": "ringing",
            "cancelled": False,
            "export_s0": export_s0,
            "t0": time.monotonic(),
            "t_ring0": None,
            "t_land0": None,
            "ring_s": 0.0,
            "d2h_s": 0.0,
            "land_s": 0.0,
        }
        try:
            rec["kc"], rec["vc"] = pre.begin_cache(s_pad)
        except Exception:  # noqa: BLE001 — e.g. ring-mesh OOM sizing the
            # full-sequence cache; the chunked path still serves this
            logger.exception(
                "long prefill cache alloc failed for %s; using chunked "
                "prefill", seq.request_id,
            )
            return False
        old = self._jobs.pop(seq.request_id, None)
        if old is not None:
            # preempt-then-readmit inside one schedule(): the stale
            # job's table is gone — only the fresh record may land
            old["cancelled"] = True
        self._jobs[seq.request_id] = rec
        self.requests_total += 1
        return True

    # stackcheck: hot-path — once per engine step between device
    # dispatches: chunk dispatch + batch landing are enqueue-only; the
    # blocking ring wait / d2h live on the worker (_materialize)
    def advance(self) -> tuple[list[dict], list[dict], bool]:
        """Advance every in-flight job one step. Returns
        (done_records, failed_records, progressed): done records have
        all their blocks landed and host logits parked (the engine
        samples the first token and finalizes); failed records name
        sequences that must fall back to the chunked path; progressed
        is False when nothing moved (the engine may yield briefly)."""
        done: list[dict] = []
        failed: list[dict] = []
        progressed = False
        for rec in list(self._jobs.values()):
            # cancelled records never linger here: cancel() and
            # start()'s stale-job replacement pop them from _jobs
            # atomically with setting the flag (the flag itself is for
            # the worker thread)
            state = rec["state"]
            if state == "ringing":
                try:
                    self._dispatch_next_chunk(rec)
                except Exception:  # noqa: BLE001 — a chunk compile /
                    # dispatch failure (e.g. full-sequence cache OOM at
                    # a new S_pad) must fail ONE request back to the
                    # chunked path, never the step loop
                    logger.exception(
                        "long prefill chunk dispatch failed for %s",
                        rec["rid"],
                    )
                    rec["state"] = state = "failed"
                else:
                    progressed = True
            elif state == "landing":
                try:
                    if self._land_one_batch(rec):
                        progressed = True
                except Exception:  # noqa: BLE001 — same contract: a
                    # failed staged import recomputes via chunked
                    # prefill (partial landings are overwritten there)
                    logger.exception(
                        "long prefill landing failed for %s", rec["rid"],
                    )
                    rec["state"] = state = "failed"
            if state == "landing":
                want = rec["n_blocks"] - rec["start_block"]
                if (
                    rec["batches_done"]
                    and not rec["batches"]
                    and rec["landed_blocks"] >= want
                    and rec["logits"] is not None
                ):
                    if rec["t_land0"] is not None:
                        rec["land_s"] = (
                            time.monotonic() - rec["t_land0"]
                        )
                        self.phase_s["land"] += rec["land_s"]
                    rec["state"] = "done"
                    done.append(rec)
                    del self._jobs[rec["rid"]]
                    progressed = True
            elif state == "failed":
                self.fallbacks_total += 1
                failed.append(rec)
                del self._jobs[rec["rid"]]
                progressed = True
            # "materializing": the worker owns it; nothing to do here
        return done, failed, progressed

    def cancel(self, request_id: str) -> None:
        """Forget a job (abort / preemption). The worker checks the
        flag between batches, so a mid-materialization cancel stops
        parking new data; device buffers drop with the record."""
        rec = self._jobs.pop(request_id, None)
        if rec is not None:
            rec["cancelled"] = True

    def close(self) -> None:
        self._closed = True
        if self._worker is not None:
            with self._cv:
                self._queue.append(None)
                self._cv.notify()
            self._worker.join(timeout=2.0)

    # stackcheck: hot-path — enqueue-only: one jitted ring-chunk
    # dispatch plus the NEXT chunk's staged token h2d; no device fetch
    def _dispatch_next_chunk(self, rec: dict) -> None:
        pre = self.prefiller
        C = self.chunk
        start = rec["next_start"]
        toks = rec["staged_toks"]
        if toks is None or rec["staged_start"] != start:
            # cold first chunk (or a stage that never happened)
            toks = pre.stage_tokens(
                rec["ids"][start: start + C], C
            )
        rec["staged_toks"] = None
        # the FINAL real token's row, local to the last dispatched
        # chunk (earlier chunks pass a clamped dummy row; their logits
        # are computed but never fetched)
        last_local = min(max(rec["n"] - 1 - start, 0), C - 1)
        logits, kc, vc = pre.prefill_chunk(
            rec["kc"], rec["vc"], toks, start, last_local,
        )
        rec["kc"], rec["vc"] = kc, vc
        rec["next_start"] = start + C
        self.chunks_total += 1
        if rec["next_start"] < rec["ring_end"]:
            # stage chunk N+1's tokens while chunk N rings (its h2d
            # overlaps the in-flight compute — PR 1 staging)
            nxt = rec["next_start"]
            rec["staged_toks"] = pre.stage_tokens(
                rec["ids"][nxt: nxt + C], C
            )
            rec["staged_start"] = nxt
        else:
            rec["logits_dev"] = logits
            rec["t_ring0"] = rec["t0"]
            rec["state"] = "materializing"
            self._submit(rec)

    # stackcheck: hot-path — pop one parked host batch, START its h2d
    # (stage_import_blocks device_put) and enqueue the donated scatter
    # (import_staged_blocks); both are the PR 4 landing primitives
    def _land_one_batch(self, rec: dict) -> bool:
        try:
            b0, data = rec["batches"].popleft()
        except IndexError:
            return False
        if rec["t_land0"] is None:
            rec["t_land0"] = time.monotonic()
        nb = int(data.shape[2])
        handle = self.runner.stage_import_blocks(data)
        bids = rec["table"][b0: b0 + nb]
        self.runner.import_staged_blocks(
            bids, handle, list(range(nb))
        )
        rec["landed_blocks"] += nb
        return True

    # -- worker ------------------------------------------------------------
    def _submit(self, rec: dict) -> None:
        if self._worker is None:
            self._worker = threading.Thread(
                target=self._run, name="long-prefill-worker", daemon=True
            )
            self._worker.start()
        with self._cv:
            self._queue.append(rec)
            self._cv.notify()

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._queue:
                    self._cv.wait()
                rec = self._queue.popleft()
            if rec is None or self._closed:
                return
            try:
                self._materialize(rec)
            except Exception:  # noqa: BLE001 — a dead device / bad shape
                # must fail ONE request back to chunked prefill, not
                # kill the worker for every later long prompt
                logger.exception(
                    "long prefill materialization failed for %s",
                    rec["rid"],
                )
                rec["state"] = "failed"

    def _materialize(self, rec: dict) -> None:
        """Worker body: wait out the ring, pull logits + KV to host,
        slice rows into wire-format block batches. All the blocking
        device IO of the long-prefill path lives here."""
        import jax

        kc, vc = rec["kc"], rec["vc"]
        jax.block_until_ready(kc)
        t1 = time.monotonic()
        rec["ring_s"] = t1 - rec["t_ring0"]
        self.phase_s["ring"] += rec["ring_s"]
        if rec["cancelled"]:
            return
        logits = np.asarray(rec["logits_dev"])
        k = np.asarray(kc)
        v = np.asarray(vc)
        # release the device references before the (slow) host
        # relayout: the sp-mesh cache memory frees as soon as the
        # arrays drop, not when the record is consumed
        rec["kc"] = rec["vc"] = rec["logits_dev"] = None
        rec["d2h_s"] = time.monotonic() - t1
        self.phase_s["d2h"] += rec["d2h_s"]
        rec["logits"] = logits
        bs = self.block_size
        L = k.shape[0]
        nkv = k.shape[1]
        d = k.shape[3]
        total = rec["n_blocks"]
        b0 = rec["start_block"]
        if b0 >= total:
            # fully-cached prefix (nothing to land): degenerate done
            rec["batches_done"] = True
            rec["state"] = "landing"
            return
        for lo in range(b0, total, LAND_BATCH_BLOCKS):
            if rec["cancelled"]:
                return
            hi = min(lo + LAND_BATCH_BLOCKS, total)
            nb = hi - lo
            rows = slice(lo * bs, hi * bs)
            # head-major rows -> wire layout (2, L, n, nkv, bs, d),
            # the same frame materialize_export ships and
            # stage_import_blocks eats
            kb = k[:, :, rows].reshape(L, nkv, nb, bs, d).swapaxes(1, 2)
            vb = v[:, :, rows].reshape(L, nkv, nb, bs, d).swapaxes(1, 2)
            rec["batches"].append((lo, np.stack([kb, vb])))
            # landing may start while later batches still convert
            rec["state"] = "landing"
        rec["batches_done"] = True
