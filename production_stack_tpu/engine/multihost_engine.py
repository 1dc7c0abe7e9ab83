"""One engine spanning the hosts of a multi-host TPU slice.

TPU-native replacement for the reference's Ray pipeline-parallel
multi-host path (reference: helm/templates/ray-cluster.yaml:1-622,
tutorial 15 `pipelineParallelSize`): instead of a Ray actor tree, the
engine runs SPMD under jax.distributed — every host executes the same
jitted steps over a global (tp) mesh whose devices span the slice, and
XLA lays the collectives on ICI/DCN.

Control flow: the scheduler, HTTP server, and sampler live on host 0
only. Host 0 wraps its ModelRunner in `BroadcastingRunner`, which
publishes a step descriptor (step kind + host-side integer args) through
the jax.distributed coordinator KV store before executing it locally;
follower hosts run `follower_loop`, replaying each descriptor against
their local ModelRunner so all hosts issue identical device programs in
identical order (the SPMD contract).

Scope (documented, loudly enforced in config validation below):
base-model serving, /v1/embeddings, and speculative decoding (embed and
verify_batch steps broadcast like decode) — KV offload tiers, PD
transfer, and LoRA hot-load remain single-host features for now (each
needs its own broadcast/addressability story).
"""

from __future__ import annotations

import numpy as np

from production_stack_tpu.parallel import multihost
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)


def _b64(a) -> dict:
    """ndarray -> JSON-safe {b64, shape, dt}: the step broadcast rides
    the jax.distributed coordinator KV store as JSON, and raw-bytes
    base64 beats a Python-int list by ~10x in size and parse cost for
    the big guided tables."""
    import base64

    a = np.ascontiguousarray(a)
    return {"b64": base64.b64encode(a.tobytes()).decode("ascii"),
            "shape": list(a.shape), "dt": str(a.dtype)}


def _unb64(d: dict):
    import base64

    return np.frombuffer(
        base64.b64decode(d["b64"]), dtype=np.dtype(d["dt"])
    ).reshape(d["shape"])


def validate_multihost_config(config) -> None:
    """Reject single-host-only features early with a clear message."""
    problems = []
    if config.enable_lora:
        problems.append("--enable-lora (adapter loads are not broadcast)")
    if config.cpu_offload_bytes or config.disk_offload_dir or (
        config.remote_cache_url
    ):
        problems.append(
            "KV offload tiers (cache export needs host-0-addressable "
            "shards)"
        )
    if config.kv_role:
        problems.append("disaggregated prefill roles")
    if problems:
        raise ValueError(
            "multihost mode does not yet support: " + "; ".join(problems)
        )


class BroadcastingRunner:
    """Host-0 ModelRunner proxy: publish each device step, then run it.

    Only the methods that issue device programs are intercepted; all
    other attribute access (model_config, num_blocks, params, ...)
    delegates to the wrapped runner.
    """

    def __init__(self, runner, broadcaster: multihost.StepBroadcaster):
        self._runner = runner
        self._bc = broadcaster

    def __getattr__(self, name):
        return getattr(self._runner, name)

    @staticmethod
    def _sampling_msg(sampling):
        if sampling is None:
            return None
        temps, top_ps, top_ks, min_ps, keys = sampling
        return [
            np.asarray(temps, np.float32).tolist(),
            np.asarray(top_ps, np.float32).tolist(),
            np.asarray(top_ks, np.int32).tolist(),
            np.asarray(min_ps, np.float32).tolist(),
            np.asarray(keys, np.uint32).tolist(),
        ]

    def prefill(self, token_ids, start_pos, block_table, total_len,
                lora_slot=0, sampling=None, prompt_lp_targets=None):
        self._bc.publish({
            "kind": "prefill",
            "token_ids": [int(t) for t in token_ids],
            "start_pos": int(start_pos),
            "block_table": [int(b) for b in block_table],
            "total_len": int(total_len),
            "lora_slot": int(lora_slot),
            "sampling": self._sampling_msg(sampling),
            # followers must select the SAME program variant (the plp
            # prefill materializes every row) or SPMD desyncs
            "prompt_lp_targets": (
                [int(t) for t in prompt_lp_targets]
                if prompt_lp_targets is not None else None
            ),
        })
        return self._runner.prefill(
            token_ids, start_pos, block_table, total_len,
            lora_slot=lora_slot, sampling=sampling,
            prompt_lp_targets=prompt_lp_targets,
        )

    def prefill_batch(self, chunks, start_positions, block_tables,
                      total_lens, lora_slots=None, sampling=None):
        msg = {
            "kind": "prefill_batch",
            "chunks": [[int(t) for t in c] for c in chunks],
            "start_positions": [int(p) for p in start_positions],
            "block_tables": [[int(b) for b in t] for t in block_tables],
            "total_lens": [int(t) for t in total_lens],
            "sampling": self._sampling_msg(sampling),
        }
        if lora_slots is not None:
            msg["lora_slots"] = [int(s) for s in lora_slots]
        self._bc.publish(msg)
        return self._runner.prefill_batch(
            chunks, start_positions, block_tables, total_lens,
            lora_slots=lora_slots, sampling=sampling,
        )

    def decode(self, token_ids, positions, block_tables, context_lens,
               lora_slots=None):
        msg = {
            "kind": "decode",
            "token_ids": [int(t) for t in token_ids],
            "positions": [int(p) for p in positions],
            "block_tables": [[int(b) for b in t] for t in block_tables],
            "context_lens": [int(c) for c in context_lens],
        }
        if lora_slots is not None:
            msg["lora_slots"] = [int(s) for s in lora_slots]
        self._bc.publish(msg)
        return self._runner.decode(
            token_ids, positions, block_tables, context_lens,
            lora_slots=lora_slots,
        )

    def decode_multi(self, token_ids, positions, block_tables,
                     context_lens, steps, temps, top_ps, top_ks, keys,
                     min_ps=None, lora_slots=None, penalties=None,
                     want_logprobs=False, guided=None, logit_bias=None,
                     lanes=None):
        msg = {
            "kind": "decode_multi",
            "token_ids": [int(t) for t in token_ids],
            "positions": [int(p) for p in positions],
            "block_tables": [[int(b) for b in t] for t in block_tables],
            "context_lens": [int(c) for c in context_lens],
            "steps": int(steps),
            "temps": np.asarray(temps).tolist(),
            "top_ps": np.asarray(top_ps).tolist(),
            "top_ks": np.asarray(top_ks).tolist(),
            "min_ps": (
                np.asarray(min_ps, np.float32).tolist()
                if min_ps is not None else None
            ),
            "keys": np.asarray(keys, np.uint32).tolist(),
            # followers must compile the SAME program variant as host 0
            # (the logprobs scan has extra outputs) or SPMD desyncs
            "want_logprobs": bool(want_logprobs),
        }
        if lora_slots is not None:
            msg["lora_slots"] = [int(s) for s in lora_slots]
        if lanes is not None:
            # every host packs the sequences into the same lanes
            msg["lanes"] = np.asarray(lanes).tolist()
        if penalties is not None:
            gen, pres, freq, rep = penalties
            msg["penalties"] = {
                "gen": [[int(t) for t in g] for g in gen],
                "pres": np.asarray(pres).tolist(),
                "freq": np.asarray(freq).tolist(),
                "rep": np.asarray(rep).tolist(),
            }
        if logit_bias is not None:
            msg["logit_bias"] = {
                "ids": np.asarray(logit_bias[0], np.int32).tolist(),
                "vals": np.asarray(logit_bias[1], np.float32).tolist(),
            }
        if guided is not None:
            tok, init_states, lane_map, tc, cm, ct = guided
            # cache_token serials are process-local; serialize as a
            # list so every follower re-keys its device cache
            # consistently. The BIG tables ride the broadcast only when
            # the constraint set CHANGES — per-dispatch they are
            # device-cached on every host, so steady-state guided
            # decode adds just the (b,) init/lane vectors to the wire.
            wire_tok = list(map(int, tok[0])) + list(tok[1:])
            msg["guided"] = {
                "token": wire_tok,
                "init": np.asarray(init_states).tolist(),
                "lane": np.asarray(lane_map).tolist(),
            }
            if getattr(self, "_guided_sent_token", None) != tuple(
                wire_tok
            ):
                # raw int32/int8 bytes via base64, NOT a JSON int list:
                # tc is (m_pad, vocab) — with a 128k vocab a tolist()
                # payload is several MB of Python ints to serialize and
                # for every follower to parse. Pad rows (all-zero, above
                # n_real) are rebuilt follower-side, not shipped.
                n_real = len(tok[0]) + 1
                msg["guided"]["tc"] = _b64(np.asarray(tc)[:n_real])
                msg["guided"]["cm"] = _b64(
                    np.asarray(cm).astype(np.int8)
                )
                msg["guided"]["ct"] = _b64(np.asarray(ct))
                self._guided_sent_token = tuple(wire_tok)
        self._bc.publish(msg)
        return self._runner.decode_multi(
            token_ids, positions, block_tables, context_lens, steps,
            temps, top_ps, top_ks, keys, min_ps=min_ps,
            lora_slots=lora_slots, penalties=penalties,
            want_logprobs=want_logprobs, guided=guided,
            logit_bias=logit_bias, lanes=lanes,
        )

    def verify_batch(self, chunks, start_positions, block_tables,
                     total_lens, row_sampling, lora_slots=None):
        temps, top_ps, top_ks, min_ps, seeds, starts = row_sampling
        msg = {
            "kind": "verify_batch",
            "chunks": [[int(t) for t in c] for c in chunks],
            "start_positions": [int(p) for p in start_positions],
            "block_tables": [[int(b) for b in t] for t in block_tables],
            "total_lens": [int(t) for t in total_lens],
            "row_sampling": [
                np.asarray(temps, np.float32).tolist(),
                np.asarray(top_ps, np.float32).tolist(),
                np.asarray(top_ks, np.int32).tolist(),
                np.asarray(min_ps, np.float32).tolist(),
                np.asarray(seeds, np.uint32).tolist(),
                np.asarray(starts, np.int64).tolist(),
            ],
        }
        if lora_slots is not None:
            msg["lora_slots"] = [int(s) for s in lora_slots]
        self._bc.publish(msg)
        return self._runner.verify_batch(
            chunks, start_positions, block_tables, total_lens,
            row_sampling=row_sampling, lora_slots=lora_slots,
        )

    def embed(self, token_ids, lora_slot=0):
        self._bc.publish({
            "kind": "embed",
            "token_ids": [int(t) for t in token_ids],
            "lora_slot": int(lora_slot),
        })
        return self._runner.embed(token_ids, lora_slot=lora_slot)

    def precompile_prefill(self, singles=(), groups=()):
        # broadcast so FOLLOWERS compile ahead too — a follower that
        # first meets a program shape inside a live replayed step stalls
        # the whole collective for the compile
        self._bc.publish({
            "kind": "precompile_prefill",
            "singles": [[int(a), int(b)] for a, b in singles],
            "groups": [[int(s), int(a), int(b)] for s, a, b in groups],
        })
        return self._runner.precompile_prefill(singles, groups)

    def precompile_decode(self, context_lens, steps, chained=False,
                          stop=False):
        # stop is always False under multihost (_device_stop is gated
        # off — the broadcast wire ships host token lists, not stop
        # matrices), but precompile_serving passes the kwarg
        # unconditionally, so the proxy must accept and forward it
        self._bc.publish({
            "kind": "precompile_decode",
            "context_lens": [int(c) for c in context_lens],
            "steps": int(steps),
            "chained": bool(chained),
            "stop": bool(stop),
        })
        return self._runner.precompile_decode(
            context_lens, steps, chained=chained, stop=stop,
        )

    def shutdown_followers(self) -> None:
        self._bc.publish({"kind": "shutdown"})


def wrap_engine_for_multihost(engine) -> None:
    """Host 0: swap the engine's runner for the broadcasting proxy."""
    engine.runner = BroadcastingRunner(
        engine.runner, multihost.StepBroadcaster()
    )
    logger.info(
        "multihost host 0: broadcasting steps to %d follower hosts",
        multihost.process_count() - 1,
    )


def follower_loop(runner, timeout_s: float = 600.0) -> None:
    """Follower hosts: replay host 0's device steps until shutdown."""
    bc = multihost.StepBroadcaster()
    logger.info(
        "multihost follower %d: replaying host 0's steps",
        multihost.process_index(),
    )
    while True:
        msg = bc.next(timeout_s=timeout_s)
        kind = msg.pop("kind")
        if kind == "shutdown":
            logger.info("follower: shutdown received")
            return
        if kind == "prefill":
            runner.prefill(**msg)
        elif kind == "prefill_batch":
            runner.prefill_batch(**msg)
        elif kind == "decode":
            runner.decode(**msg)
        elif kind == "decode_multi":
            for arr in ("temps", "top_ps", "top_ks"):
                msg[arr] = np.asarray(msg[arr], np.float32
                                      if arr != "top_ks" else np.int32)
            if msg.get("min_ps") is not None:
                msg["min_ps"] = np.asarray(msg["min_ps"], np.float32)
            msg["keys"] = np.asarray(msg["keys"], np.uint32)
            if msg.get("lanes") is not None:
                msg["lanes"] = np.asarray(msg["lanes"], np.int32)
            lb = msg.pop("logit_bias", None)
            if lb is not None:
                msg["logit_bias"] = (
                    np.asarray(lb["ids"], np.int32),
                    np.asarray(lb["vals"], np.float32),
                )
            pen = msg.pop("penalties", None)
            if pen is not None:
                msg["penalties"] = (
                    pen["gen"],
                    np.asarray(pen["pres"], np.float32),
                    np.asarray(pen["freq"], np.float32),
                    np.asarray(pen["rep"], np.float32),
                )
            gd = msg.pop("guided", None)
            if gd is not None:
                tok = tuple(gd["token"])
                if "tc" in gd:
                    tc = _unb64(gd["tc"])
                    m_pad = tok[-1]  # cache_token layout: (..., m_pad)
                    if tc.shape[0] < m_pad:  # re-grow the all-zero pad
                        tc = np.concatenate([tc, np.zeros(
                            (m_pad - tc.shape[0], tc.shape[1]), np.int32
                        )])
                    tables = (
                        tc,
                        _unb64(gd["cm"]).astype(bool),
                        _unb64(gd["ct"]),
                    )
                    runner._guided_follower_tables = (tok, tables)
                else:
                    # host 0 sends the big tables only when the
                    # constraint set changes; in-order broadcast means
                    # they were seen before
                    cached = getattr(
                        runner, "_guided_follower_tables", None
                    )
                    if cached is None or cached[0] != tok:
                        raise RuntimeError(
                            "guided decode broadcast referenced tables "
                            "this follower never received"
                        )
                    tables = cached[1]
                msg["guided"] = (
                    tok,
                    np.asarray(gd["init"], np.int32),
                    np.asarray(gd["lane"], np.int32),
                    *tables,
                )
            runner.decode_multi(**msg)
        elif kind == "verify_batch":
            rs = msg.pop("row_sampling")
            msg["row_sampling"] = (
                np.asarray(rs[0], np.float32),
                np.asarray(rs[1], np.float32),
                np.asarray(rs[2], np.int32),
                np.asarray(rs[3], np.float32),
                np.asarray(rs[4], np.uint32),
                np.asarray(rs[5], np.int64),
            )
            runner.verify_batch(**msg)
        elif kind == "embed":
            runner.embed(**msg)
        elif kind == "precompile_prefill":
            runner.precompile_prefill(**msg)
        elif kind == "precompile_decode":
            runner.precompile_decode(**msg)
        else:  # future step kinds must fail loudly, not silently desync
            raise RuntimeError(f"unknown multihost step kind {kind!r}")
