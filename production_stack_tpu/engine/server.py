"""OpenAI-compatible HTTP server for the TPU engine (aiohttp.web).

API surface parity with the vLLM engine pods the reference deploys
(reference: helm/templates/deployment-vllm-multi.yaml:104-126 runs
`vllm serve`; the router proxies these endpoints, reference:
src/vllm_router/routers/main_router.py:45-231):

  POST /v1/completions            streaming + blocking
  POST /v1/chat/completions       streaming + blocking
  GET  /v1/models
  POST /tokenize /detokenize
  GET  /health /version /metrics
  POST /sleep /wake_up  GET /is_sleeping
  POST /v1/load_lora_adapter /v1/unload_lora_adapter

The Prometheus /metrics endpoint exports the exact vllm:* gauge names the
router's stats scraper parses (see engine/metrics.py).
"""

from __future__ import annotations

import asyncio
import json
import time

from aiohttp import web
from prometheus_client import CollectorRegistry, generate_latest

import production_stack_tpu
from production_stack_tpu.engine.async_engine import (
    AsyncLLMEngine,
    EngineSleepingError,
)
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.metrics import EngineMetrics
from production_stack_tpu.engine import protocol as proto
from production_stack_tpu.engine import tools
from production_stack_tpu.engine.sampling_params import SamplingParams
from production_stack_tpu.tracing import (
    REQUEST_ID_HEADER,
    TRACEPARENT_HEADER,
    log_otlp_payload,
    otlp_flush_loop,
    valid_request_id,
)
from production_stack_tpu.utils import init_logger
from production_stack_tpu.utils.tasks import spawn_watched

logger = init_logger(__name__)

STATS_UPDATE_INTERVAL_S = 1.0


class EngineServer:
    def __init__(self, config: EngineConfig, params: dict | None = None):
        self.config = config
        self.model_name = config.served_model_name or config.model
        self.engine = AsyncLLMEngine(config, params=params)
        if config.precompile_serving:
            t0 = time.time()
            n = self.engine.engine.precompile_serving()
            logger.info(
                "serving precompile: %d dispatches in %.1fs (every "
                "config-derivable program shape warm; only "
                "request-dependent sampling variants can still compile "
                "lazily)", n, time.time() - t0,
            )
        self.registry = CollectorRegistry()
        self.metrics = EngineMetrics(self.model_name, registry=self.registry)
        self.lora_adapters: dict[str, str] = {}  # name -> path
        self._stats_task: asyncio.Task | None = None
        self.app = self._build_app()

    # -- app wiring --------------------------------------------------------
    def _build_app(self) -> web.Application:
        middlewares = []
        if self.config.api_key:
            middlewares.append(self._auth_middleware)
        app = web.Application(
            client_max_size=64 * 2**20, middlewares=middlewares
        )
        r = app.router
        r.add_post("/v1/completions", self.handle_completions)
        r.add_post("/v1/chat/completions", self.handle_chat)
        r.add_get("/v1/models", self.handle_models)
        r.add_post("/v1/embeddings", self.handle_embeddings)
        r.add_post("/v1/rerank", self.handle_rerank)
        r.add_post("/rerank", self.handle_rerank)
        r.add_post("/v1/score", self.handle_score)
        r.add_post("/score", self.handle_score)
        r.add_post("/tokenize", self.handle_tokenize)
        r.add_post("/detokenize", self.handle_detokenize)
        r.add_get("/health", self.handle_health)
        r.add_get("/version", self.handle_version)
        r.add_get("/metrics", self.handle_metrics)
        r.add_get("/debug/requests", self.handle_debug_requests)
        r.add_post("/sleep", self.handle_sleep)
        r.add_post("/wake_up", self.handle_wake)
        r.add_get("/is_sleeping", self.handle_is_sleeping)
        r.add_post("/v1/load_lora_adapter", self.handle_load_lora)
        r.add_post("/v1/unload_lora_adapter", self.handle_unload_lora)
        app.on_startup.append(self._on_startup)
        app.on_cleanup.append(self._on_cleanup)
        return app

    @web.middleware
    async def _auth_middleware(self, request: web.Request, handler):
        """Bearer-token auth on the OpenAI surface (vLLM --api-key,
        reference tutorial 11-secure-vllm-serve). /health and /metrics
        stay open for probes and Prometheus."""
        if request.path.startswith("/v1/") or request.path in (
            "/tokenize", "/detokenize", "/sleep", "/wake_up",
            "/rerank", "/score",
        ):
            import hmac

            auth = request.headers.get("Authorization", "")
            # compare as bytes: compare_digest raises TypeError on
            # non-ASCII str input (reachable via latin-1 header bytes)
            if not hmac.compare_digest(
                auth.encode("utf-8", "surrogateescape"),
                f"Bearer {self.config.api_key}".encode(),
            ):
                return web.json_response(
                    proto.error_json("invalid API key",
                                     "authentication_error", 401),
                    status=401,
                )
        return await handler(request)

    async def _on_startup(self, app: web.Application) -> None:
        self.engine.start(asyncio.get_running_loop())
        self._stats_task = spawn_watched(self._stats_loop(), "engine-stats")
        if self.engine.tracer.exporter == "otlp":
            self._trace_flush_task = spawn_watched(
                otlp_flush_loop(self.engine.tracer), "engine-trace-flush"
            )
        # disaggregated prefill producer: serve KV block chains to
        # decode peers (reference: NIXL sender role,
        # LMCACHE_NIXL_ROLE=sender). prefill AND both roles serve —
        # a both-role engine can hand its chains to any peer.
        listen = (self.config.kv_transfer_config or {}).get("listen")
        if listen and self.config.pd_role() in ("prefill", "both"):
            from production_stack_tpu.kv import transfer
            from production_stack_tpu.kv.wire import parse_addr

            host, port = parse_addr(listen, transfer.DEFAULT_PORT)
            self._kv_transfer_server = transfer.KVTransferServer(self.engine)
            await self._kv_transfer_server.start(host or "0.0.0.0", port)

    async def _on_cleanup(self, app: web.Application) -> None:
        if self._stats_task:
            self._stats_task.cancel()
        if getattr(self, "_trace_flush_task", None) is not None:
            self._trace_flush_task.cancel()
            # final drain: up to a flush interval of spans is still
            # buffered — a graceful stop must not drop them
            log_otlp_payload(self.engine.tracer)
        if getattr(self, "_kv_transfer_server", None) is not None:
            await self._kv_transfer_server.stop()
        self.engine.shutdown()

    async def _stats_loop(self) -> None:
        while True:
            try:
                self.metrics.update_from_snapshot(self.engine.stats())
                self.metrics.observe_kv(
                    *self.engine.drain_kv_observations()
                )
                self.metrics.observe_ragged(
                    self.engine.drain_ragged_observations()
                )
            except Exception:  # pragma: no cover
                logger.exception("stats update failed")
            await asyncio.sleep(STATS_UPDATE_INTERVAL_S)

    # -- helpers -----------------------------------------------------------
    async def _json_body(self, request: web.Request):
        """-> (body, None) or (None, 400-response); body is a dict."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            body = None
        if not isinstance(body, dict):
            return None, web.json_response(
                proto.error_json("request body must be a JSON object"),
                status=400,
            )
        return body, None

    def _check_model(self, body: dict) -> web.Response | None:
        model = body.get("model")
        if model and model not in (self.model_name, self.config.model) and (
            model not in self.lora_adapters
        ):
            return web.json_response(
                proto.error_json(f"model {model!r} not found", code=404),
                status=404,
            )
        return None

    def _apply_truncation(self, ids: list[int], sp) -> list[int]:
        """vLLM truncate_prompt_tokens, applied BEFORE the context-length
        gate — the feature exists to make over-long prompts fit."""
        from production_stack_tpu.engine.sampling_params import (
            truncate_prompt,
        )

        return truncate_prompt(
            ids, sp, self.config.resolved_max_model_len()
        )

    @staticmethod
    def _parse_priority(body: dict):
        """-> (priority, None) or (0, 400-response)."""
        try:
            return int(body.get("priority", 0)), None
        except (TypeError, ValueError):
            return 0, web.json_response(
                proto.error_json("priority must be an integer"),
                status=400,
            )

    def _observe_content_chunk(
        self, request: web.Request, t_fetched: float
    ) -> None:
        """A content chunk was written. tpu:token_delivery_seconds,
        per chunk: the step thread's fetch of the chunk's round
        (`RequestOutput.t_fetched`; 0.0 = no round behind it) -> now.
        tpu:server_ttft_seconds, once per streamed request: handler
        entry -> now."""
        now = time.perf_counter()
        if t_fetched:
            self.engine.loop_phases.observe(
                "token_delivery", now - t_fetched)
        t_enter = request.pop("t_enter", None)
        if t_enter is not None:
            self.metrics.server_ttft.labels(self.model_name).observe(
                now - t_enter
            )

    def _observe_finish(self, out, arrival: float) -> None:
        m = out.metrics
        ttft = (
            m.first_token_time - arrival
            if m.first_token_time is not None
            else None
        )
        e2e = time.time() - arrival
        self.metrics.observe_request(
            out.finish_reason or "stop", ttft, e2e, len(out.token_ids),
            queue_s=(
                m.admitted_time - m.arrival_time
                if m.admitted_time is not None else None
            ),
            sched_delay_s=(
                m.first_scheduled_time - m.admitted_time
                if (m.first_scheduled_time is not None
                    and m.admitted_time is not None) else None
            ),
            preempt_stall_s=(
                m.preempt_stall_s if m.num_preemptions > 0 else None
            ),
        )

    # -- request identity + trace context ----------------------------------
    def _request_identity(
        self, request: web.Request, prefix: str
    ) -> tuple[str, str | None]:
        """(request_id, traceparent) for one inbound HTTP request.

        A router-supplied `x-request-id` becomes the ENGINE-side request
        id (and is echoed on the response) so logs, spans, and timelines
        join on one id end-to-end; ids failing the charset/length gate
        fall back to a fresh one. A supplied id that is still IN FLIGHT
        (client timeout-retry with a stable id, or two clients
        colliding) also falls back — correlation degrades for that
        retry, but the request is served instead of 400ing the way a
        hard duplicate would. The `traceparent` passes through verbatim
        — the timeline recorder validates it (malformed -> fresh
        trace)."""
        rid = request.headers.get(REQUEST_ID_HEADER)
        if (
            not valid_request_id(rid)
            or self.engine.has_request(rid)
            # multi-choice requests register per-choice `<rid>-c<i>`
            # sub-ids (any of which may still be running after others
            # finished), so a retried n>1 request collides on those
            or self.engine.has_request_prefix(rid)
        ):
            rid = proto.make_id(prefix)
        return rid, request.headers.get(TRACEPARENT_HEADER)

    # -- completions -------------------------------------------------------
    async def handle_completions(self, request: web.Request) -> web.StreamResponse:
        request["t_enter"] = time.perf_counter()
        body, err = await self._json_body(request)
        if err is not None:
            return err
        err = self._check_model(body)
        if err is not None:
            return err
        prompt = body.get("prompt")
        if prompt is None:
            return web.json_response(
                proto.error_json("missing 'prompt'"), status=400
            )
        # OpenAI batch semantics: prompt may be one string, one token-id
        # list, or a list of either (choices index prompt_idx*n+sample)
        if isinstance(prompt, str):
            raw_prompts: list = [prompt]
        elif isinstance(prompt, list) and prompt and all(
            isinstance(x, int) for x in prompt
        ):
            raw_prompts = [prompt]
        elif isinstance(prompt, list) and prompt and all(
            isinstance(x, str)
            or (isinstance(x, list) and x
                and all(isinstance(t, int) for t in x))
            for x in prompt
        ):
            raw_prompts = list(prompt)
        else:
            return web.json_response(
                proto.error_json(
                    "'prompt' must be a string, a token-id list, or a "
                    "non-empty list of either"
                ),
                status=400,
            )
        try:
            sp = proto.sampling_params_from_request(body)
        except proto.ProtocolError as e:
            return web.json_response(proto.error_json(str(e)), status=400)
        if body.get("suffix"):
            # vLLM-parity: fill-in-the-middle is a model capability the
            # decoder-only serving path does not provide
            return web.json_response(
                proto.error_json("suffix is not supported"), status=400
            )
        best_of = body.get("best_of")
        try:
            best_of = int(best_of) if best_of is not None else None
        except (TypeError, ValueError):
            return web.json_response(
                proto.error_json("best_of must be an integer"), status=400
            )
        if best_of is not None and best_of != sp.n:
            return web.json_response(
                proto.error_json(
                    "best_of != n is not supported (use n-way sampling)"
                ),
                status=400,
            )
        req_priority, perr = self._parse_priority(body)
        if perr is not None:
            return perr
        echo = bool(body.get("echo", False))
        if echo and sp.logprobs is not None:
            return web.json_response(
                proto.error_json(
                    "echo with logprobs is not supported; request "
                    "prompt_logprobs for per-prompt-token logprobs"
                ),
                status=400,
            )

        request_id, traceparent = self._request_identity(request, "cmpl")
        prompt_ids_list: list[list[int]] = []
        for p in raw_prompts:
            ids = (
                list(p) if isinstance(p, list)
                else self.engine.tokenizer.encode(p)
            )
            ids = self._apply_truncation(ids, sp)
            err = self._check_context_len(ids)
            if err is not None:
                return err
            prompt_ids_list.append(ids)
        lora_name = body.get("model") if (
            body.get("model") in self.lora_adapters) else None
        # OpenAI echo: the response text leads with the prompt the
        # engine ACTUALLY processed — after truncation (string prompts
        # echo verbatim only when untruncated)
        echo_prefixes = None
        if echo:
            echo_prefixes = [
                p if (isinstance(p, str)
                      and sp.truncate_prompt_tokens is None)
                else self.engine.tokenizer.decode(list(ids))
                for p, ids in zip(raw_prompts, prompt_ids_list)
            ]

        if len(prompt_ids_list) * sp.n > 1:
            return await self._multi_completion(
                request, request_id, sp, prompt_ids_list, lora_name,
                chat=False, model=body.get("model") or self.model_name,
                stream=bool(body.get("stream")),
                include_usage=self._wants_usage(body),
                echo_prefixes=echo_prefixes,
                priority=req_priority,
                traceparent=traceparent,
            )
        kwargs = {"prompt_token_ids": prompt_ids_list[0],
                  "priority": req_priority,
                  "traceparent": traceparent}
        if body.get("stream"):
            return await self._stream_completion(
                request, request_id, sp, kwargs, lora_name, chat=False,
                include_usage=self._wants_usage(body),
                echo_prefix=echo_prefixes[0] if echo_prefixes else None,
            )
        return await self._blocking_completion(
            request_id, sp, kwargs, lora_name, chat=False,
            model=body.get("model") or self.model_name,
            echo_prefix=echo_prefixes[0] if echo_prefixes else None,
        )

    # -- chat --------------------------------------------------------------
    async def handle_chat(self, request: web.Request) -> web.StreamResponse:
        request["t_enter"] = time.perf_counter()
        body, err = await self._json_body(request)
        if err is not None:
            return err
        err = self._check_model(body)
        if err is not None:
            return err
        messages = body.get("messages")
        if not messages:
            return web.json_response(
                proto.error_json("missing 'messages'"), status=400
            )
        req_tools = body.get("tools")
        tool_choice = body.get("tool_choice",
                               "auto" if req_tools else "none")
        use_tools = bool(req_tools) and tool_choice != "none"
        if use_tools and tool_choice == "auto" and not (
            self.config.enable_auto_tool_choice
        ):
            return web.json_response(
                proto.error_json(
                    "tools require --enable-auto-tool-choice (or a "
                    "named tool_choice)"
                ),
                status=400,
            )
        try:
            if use_tools:
                messages = tools.inject_tools(
                    messages, req_tools, tool_choice
                )
            prompt = self.engine.tokenizer.apply_chat_template(messages)
            sp = proto.sampling_params_from_request(body)
            if body.get("logprobs") is True:
                # chat form: logprobs: true + top_logprobs: N
                import dataclasses

                top_n = int(body.get("top_logprobs", 0) or 0)
                if not 0 <= top_n <= 20:
                    raise proto.ProtocolError(
                        "top_logprobs must be in [0, 20]"
                    )
                sp = dataclasses.replace(sp, logprobs=top_n)
        except (proto.ProtocolError, ValueError) as e:
            return web.json_response(proto.error_json(str(e)), status=400)
        except Exception as e:
            return web.json_response(
                proto.error_json(f"chat template error: {e}"), status=400
            )

        request_id, traceparent = self._request_identity(
            request, "chatcmpl"
        )
        prompt_ids = self.engine.tokenizer.encode(prompt)
        prompt_ids = self._apply_truncation(prompt_ids, sp)
        err = self._check_context_len(prompt_ids)
        if err is not None:
            return err
        req_priority, perr = self._parse_priority(body)
        if perr is not None:
            return perr
        lora_name = body.get("model") if (
            body.get("model") in self.lora_adapters) else None
        if sp.n > 1:
            return await self._multi_completion(
                request, request_id, sp, [prompt_ids], lora_name,
                chat=True, model=body.get("model") or self.model_name,
                stream=bool(body.get("stream")),
                include_usage=self._wants_usage(body),
                parse_tools=use_tools,
                priority=req_priority,
                traceparent=traceparent,
            )
        if body.get("stream"):
            # streamed responses pass tool-call text through verbatim
            # (parsing happens client-side); blocking mode parses
            return await self._stream_completion(
                request, request_id, sp,
                {"prompt_token_ids": prompt_ids,
                 "priority": req_priority,
                 "traceparent": traceparent},
                lora_name, chat=True,
                include_usage=self._wants_usage(body),
            )
        return await self._blocking_completion(
            request_id, sp,
            {"prompt_token_ids": prompt_ids, "priority": req_priority,
             "traceparent": traceparent},
            lora_name,
            chat=True,
            model=body.get("model") or self.model_name,
            parse_tools=use_tools,
        )

    # -- shared generation paths ------------------------------------------
    def _check_context_len(self, prompt_ids: list[int]) -> web.Response | None:
        """Reject prompts the KV layout cannot hold with a 400 up front
        (vLLM parity: 'maximum context length' errors) instead of
        admitting the request and streaming an abort."""
        limit = self.config.resolved_max_model_len()
        if len(prompt_ids) >= limit:
            return web.json_response(
                proto.error_json(
                    f"This model's maximum context length is {limit} "
                    f"tokens. However, your request has "
                    f"{len(prompt_ids)} prompt tokens; please reduce "
                    "the length of the messages or prompt.",
                    "context_length_exceeded",
                ),
                status=400,
            )
        return None

    @staticmethod
    def _wants_usage(body: dict) -> bool:
        opts = body.get("stream_options")
        return bool(isinstance(opts, dict) and opts.get("include_usage"))

    # -- logprobs formatting (OpenAI wire shapes) --------------------------
    def _tok_str(self, token_id: int) -> str:
        return self.engine.tokenizer.decode([token_id])

    def _fmt_completion_logprobs(
        self, entries: list[dict] | None, start: int = 0
    ) -> dict | None:
        """Completions shape: tokens / token_logprobs / top_logprobs.
        `start` seeds text_offset — streamed chunks pass the length of
        text already emitted so offsets index the full completion."""
        if entries is None:
            return None
        tokens, lps, tops, offsets = [], [], [], []
        pos = start
        for e in entries:
            s = self._tok_str(e["token_id"])
            tokens.append(s)
            lps.append(e["logprob"])
            top: dict = {}
            for t in e["top_logprobs"]:
                key = self._tok_str(t["token_id"])
                if key in top:
                    # distinct ids can decode to the same string (byte
                    # fallbacks, partial UTF-8): the OpenAI dict shape
                    # would silently drop one — disambiguate with
                    # vLLM's return_tokens_as_token_ids spelling
                    key = f"token_id:{t['token_id']}"
                top[key] = t["logprob"]
            tops.append(top)
            offsets.append(pos)
            pos += len(s)
        return {"tokens": tokens, "token_logprobs": lps,
                "top_logprobs": tops, "text_offset": offsets}

    def _fmt_chat_logprobs(
        self, entries: list[dict] | None
    ) -> dict | None:
        """Chat shape: {"content": [{token, logprob, bytes,
        top_logprobs: [...]}]}."""
        if entries is None:
            return None

        def one(token_id: int, lp: float) -> dict:
            s = self._tok_str(token_id)
            return {"token": s, "logprob": lp,
                    "bytes": list(s.encode("utf-8", "replace"))}

        return {"content": [
            {**one(e["token_id"], e["logprob"]),
             "top_logprobs": [one(t["token_id"], t["logprob"])
                              for t in e["top_logprobs"]]}
            for e in entries
        ]}

    def _stream_chunk(
        self, request_id: str, model: str, chat: bool, text: str,
        new_lps: list[dict] | None, index: int, lp_start: int,
    ) -> tuple[dict, int]:
        """One streamed content chunk (chat or completions) with its
        logprobs attached — the single copy of the chunk wire shape the
        single-choice and multi-choice streams share. Returns
        (chunk, next text_offset seed)."""
        chunk = (
            proto.chat_chunk(
                request_id, model, {"content": text}, None, index=index
            )
            if chat
            else proto.completion_chunk(
                request_id, model, text, None, index=index
            )
        )
        if new_lps:
            if chat:
                chunk["choices"][0]["logprobs"] = (
                    self._fmt_chat_logprobs(new_lps)
                )
            else:
                fmt = self._fmt_completion_logprobs(
                    new_lps, start=lp_start
                )
                chunk["choices"][0]["logprobs"] = fmt
                if fmt["tokens"]:
                    lp_start = (
                        fmt["text_offset"][-1] + len(fmt["tokens"][-1])
                    )
        return chunk, lp_start

    async def _blocking_completion(
        self, request_id: str, sp: SamplingParams, kwargs: dict,
        lora_name: str | None, chat: bool, model: str,
        parse_tools: bool = False, echo_prefix: str | None = None,
    ) -> web.Response:
        arrival = time.time()
        # correlation echo: the response carries the (possibly
        # router-supplied) engine request id so clients/routers join
        # logs, spans, and timelines on one id
        rid_hdr = {REQUEST_ID_HEADER: request_id}
        final = None
        try:
            async for out in self.engine.generate(
                request_id, sampling_params=sp, lora_name=lora_name, **kwargs
            ):
                final = out
        except EngineSleepingError:
            return web.json_response(
                proto.error_json("engine is sleeping", "service_unavailable",
                                 503),
                status=503, headers=rid_hdr,
            )
        except ValueError as e:
            return web.json_response(proto.error_json(str(e)), status=400,
                                     headers=rid_hdr)
        assert final is not None
        self._observe_finish(final, arrival)
        if chat:
            text, tool_calls = final.text, None
            if parse_tools:
                text, tool_calls = tools.parse_tool_calls(final.text)
            resp = proto.chat_response(
                request_id, model, text, final.finish_reason,
                len(final.prompt_token_ids), len(final.token_ids),
                tool_calls=tool_calls,
            )
            resp["choices"][0]["logprobs"] = self._fmt_chat_logprobs(
                final.logprobs
            )
            if final.prompt_logprobs is not None:
                resp["choices"][0]["prompt_logprobs"] = (
                    final.prompt_logprobs
                )
            return web.json_response(resp, headers=rid_hdr)
        resp = proto.completion_response(
            request_id, model,
            (echo_prefix or "") + final.text, final.finish_reason,
            len(final.prompt_token_ids), len(final.token_ids),
        )
        if final.prompt_logprobs is not None:
            # vLLM field: per-prompt-position entries, None first
            resp["choices"][0]["prompt_logprobs"] = final.prompt_logprobs
        resp["choices"][0]["logprobs"] = self._fmt_completion_logprobs(
            final.logprobs
        )
        return web.json_response(resp, headers=rid_hdr)

    async def _multi_completion(
        self, request: web.Request, request_id: str, sp: SamplingParams,
        prompt_ids_list: list[list[int]], lora_name: str | None,
        chat: bool, model: str, stream: bool,
        include_usage: bool = False, parse_tools: bool = False,
        echo_prefixes: list[str] | None = None,
        priority: int = 0,
        traceparent: str | None = None,
    ) -> web.StreamResponse:
        """Batch prompts and/or n>1 sampling: fan the choices out as
        engine sub-requests (continuous batching coalesces them on
        device) and assemble index-ordered choices. Choice index =
        prompt_idx * n + sample_idx (OpenAI/vLLM contract); an explicit
        seed derives per-sample seeds so samples differ but reproduce."""
        import dataclasses

        arrival = time.time()
        rid_hdr = {REQUEST_ID_HEADER: request_id}
        n = sp.n
        plan: list[tuple[int, SamplingParams, list[int]]] = []
        for pi, ids in enumerate(prompt_ids_list):
            for j in range(n):
                sp_j = sp
                if n > 1 and sp.seed is not None:
                    sp_j = dataclasses.replace(sp, seed=sp.seed + j)
                plan.append((pi * n + j, sp_j, ids))

        async def run_one(idx: int, sp_i: SamplingParams,
                          ids: list[int]):
            final = None
            async for out in self.engine.generate(
                f"{request_id}-c{idx}", sampling_params=sp_i,
                lora_name=lora_name, prompt_token_ids=ids,
                priority=priority, traceparent=traceparent,
            ):
                final = out
            return final

        if not stream:
            sub_tasks = [asyncio.ensure_future(run_one(i, s, ids))
                         for i, s, ids in plan]
            try:
                finals = await asyncio.gather(*sub_tasks)
            except BaseException as e:  # noqa: BLE001 — see below
                # ANY failure (or cancellation) must cancel the
                # siblings: their generate() finalizers abort the
                # engine-side requests, so no orphaned generation keeps
                # burning decode steps after the error response
                for t in sub_tasks:
                    if not t.done():
                        t.cancel()
                await asyncio.gather(*sub_tasks, return_exceptions=True)
                if isinstance(e, EngineSleepingError):
                    return web.json_response(
                        proto.error_json("engine is sleeping",
                                         "service_unavailable", 503),
                        status=503, headers=rid_hdr,
                    )
                if isinstance(e, ValueError):
                    return web.json_response(
                        proto.error_json(str(e)), status=400,
                        headers=rid_hdr,
                    )
                if isinstance(e, (asyncio.CancelledError, KeyboardInterrupt,
                                  SystemExit)):
                    raise
                logger.exception("multi-completion failed: %s", e)
                return web.json_response(
                    proto.error_json(f"internal error: {e}",
                                     "internal_error", 500),
                    status=500, headers=rid_hdr,
                )
            choices = []
            for (idx, _, _), final in zip(plan, finals):
                self._observe_finish(final, arrival)
                if chat:
                    text, tool_calls = final.text, None
                    if parse_tools:
                        text, tool_calls = tools.parse_tool_calls(
                            final.text
                        )
                    choice = proto.chat_message_choice(
                        idx, text, final.finish_reason, tool_calls
                    )
                    choice["logprobs"] = self._fmt_chat_logprobs(
                        final.logprobs
                    )
                    if final.prompt_logprobs is not None:
                        choice["prompt_logprobs"] = final.prompt_logprobs
                    choices.append(choice)
                else:
                    pfx = (
                        echo_prefixes[idx // n] if echo_prefixes else ""
                    )
                    choice = {
                        "index": idx, "text": pfx + final.text,
                        "logprobs": self._fmt_completion_logprobs(
                            final.logprobs
                        ),
                        "finish_reason": final.finish_reason,
                    }
                    if final.prompt_logprobs is not None:
                        choice["prompt_logprobs"] = final.prompt_logprobs
                    choices.append(choice)
            return web.json_response(proto.multi_choice_response(
                request_id, model, chat, choices,
                sum(len(ids) for ids in prompt_ids_list),
                sum(len(f.token_ids) for f in finals),
            ), headers=rid_hdr)

        # streamed: interleave per-choice chunks tagged with their index
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
                REQUEST_ID_HEADER: request_id,
            },
        )
        await resp.prepare(request)

        async def send(data: dict) -> None:
            await resp.write(
                b"data: " + json.dumps(data).encode() + b"\n\n"
            )

        if echo_prefixes and not chat:
            # OpenAI echo: each choice's stream leads with its prompt
            for idx, _, _ in plan:
                await send(proto.completion_chunk(
                    request_id, model, echo_prefixes[idx // n], None,
                    index=idx,
                ))

        queue: asyncio.Queue = asyncio.Queue()

        async def pump(idx: int, sp_i: SamplingParams, ids: list[int]):
            try:
                final = None
                async for out in self.engine.generate(
                    f"{request_id}-c{idx}", sampling_params=sp_i,
                    lora_name=lora_name, prompt_token_ids=ids,
                    priority=priority, traceparent=traceparent,
                ):
                    final = out
                    if out.delta_text or out.new_logprobs:
                        await queue.put((
                            "delta", idx,
                            (out.delta_text, out.new_logprobs,
                             out.t_fetched),
                        ))
                await queue.put(("finish", idx, final))
            except Exception as e:  # noqa: BLE001 — surfaced as a chunk
                await queue.put(("error", idx, e))

        tasks = [asyncio.ensure_future(pump(i, s, ids))
                 for i, s, ids in plan]
        completion_tokens = 0
        lp_pos: dict[int, int] = {}  # per-choice text_offset seeds

        async def send_finish(idx: int, reason: str,
                              prompt_lps=None) -> None:
            fin = (
                proto.chat_chunk(request_id, model, {}, reason, index=idx)
                if chat
                else proto.completion_chunk(
                    request_id, model, "", reason, index=idx
                )
            )
            if prompt_lps is not None:
                # same contract as the single-stream path: the field
                # rides the finishing chunk
                fin["choices"][0]["prompt_logprobs"] = prompt_lps
            await send(fin)
        try:
            if chat:
                for idx, _, _ in plan:
                    await send(proto.chat_chunk(
                        request_id, model, {"role": "assistant"}, None,
                        index=idx,
                    ))
            remaining = len(plan)
            while remaining:
                kind, idx, payload = await queue.get()
                if kind == "delta":
                    text, new_lps, t_fetched = payload
                    with self.engine.loop_phases.span("send"):
                        chunk, lp_pos[idx] = self._stream_chunk(
                            request_id, model, chat, text, new_lps, idx,
                            lp_pos.get(idx, 0),
                        )
                        await send(chunk)
                    self._observe_content_chunk(request, t_fetched)
                elif kind == "finish":
                    remaining -= 1
                    if payload is not None:
                        self._observe_finish(payload, arrival)
                        completion_tokens += len(payload.token_ids)
                        await send_finish(idx, payload.finish_reason,
                                          payload.prompt_logprobs)
                else:  # error
                    remaining -= 1
                    await send(proto.error_json(str(payload)))
                    # close the choice so clients waiting on a
                    # finish_reason for every index don't hang
                    await send_finish(idx, "stop")
            if include_usage:
                await send(proto.usage_tail_chunk(
                    request_id, model, chat,
                    sum(len(ids) for ids in prompt_ids_list),
                    completion_tokens,
                ))
            await resp.write(b"data: [DONE]\n\n")
        except (ConnectionResetError, asyncio.CancelledError):
            logger.info("client disconnected from %s", request_id)
            for t in tasks:
                t.cancel()
        await resp.write_eof()
        return resp

    async def _stream_completion(
        self, request: web.Request, request_id: str, sp: SamplingParams,
        kwargs: dict, lora_name: str | None, chat: bool,
        include_usage: bool = False, echo_prefix: str | None = None,
    ) -> web.StreamResponse:
        arrival = time.time()
        model = self.model_name
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
                REQUEST_ID_HEADER: request_id,
            },
        )
        await resp.prepare(request)

        async def send(data: dict) -> None:
            await resp.write(
                b"data: " + json.dumps(data).encode() + b"\n\n"
            )

        try:
            if echo_prefix and not chat:
                # OpenAI echo streams the prompt text as the first chunk
                await send(proto.completion_chunk(
                    request_id, model, echo_prefix, None
                ))
            if chat:
                await send(
                    proto.chat_chunk(
                        request_id, model, {"role": "assistant"}, None
                    )
                )
            final = None
            lp_pos = 0
            async for out in self.engine.generate(
                request_id, sampling_params=sp, lora_name=lora_name, **kwargs
            ):
                final = out
                if out.delta_text or out.new_logprobs:
                    with self.engine.loop_phases.span("send"):
                        chunk, lp_pos = self._stream_chunk(
                            request_id, model, chat, out.delta_text,
                            out.new_logprobs, 0, lp_pos,
                        )
                        await send(chunk)
                    self._observe_content_chunk(request, out.t_fetched)
            if final is not None:
                self._observe_finish(final, arrival)
                if chat:
                    fin = proto.chat_chunk(
                        request_id, model, {}, final.finish_reason
                    )
                    if final.prompt_logprobs is not None:
                        # same contract as completions: the field rides
                        # the finishing chunk
                        fin["choices"][0]["prompt_logprobs"] = (
                            final.prompt_logprobs
                        )
                    await send(fin)
                else:
                    fin = proto.completion_chunk(
                        request_id, model, "", final.finish_reason
                    )
                    if final.prompt_logprobs is not None:
                        # streamed requests get the field on the
                        # finishing chunk (blocking puts it on the
                        # choice) — the engine paid to compute it either
                        # way
                        fin["choices"][0]["prompt_logprobs"] = (
                            final.prompt_logprobs
                        )
                    await send(fin)
                if include_usage:
                    # OpenAI stream_options.include_usage contract: one
                    # final chunk with empty choices + the usage totals
                    await send(proto.usage_tail_chunk(
                        request_id, model, chat,
                        len(final.prompt_token_ids),
                        len(final.token_ids),
                    ))
            await resp.write(b"data: [DONE]\n\n")
        except EngineSleepingError:
            await resp.write(
                b"data: "
                + json.dumps(proto.error_json("engine is sleeping")).encode()
                + b"\n\n"
            )
        except ValueError as e:
            # e.g. duplicate router-supplied x-request-id: the stream is
            # already prepared, so the error rides an SSE chunk
            await resp.write(
                b"data: "
                + json.dumps(proto.error_json(str(e))).encode()
                + b"\n\n"
            )
        except (ConnectionResetError, asyncio.CancelledError):
            logger.info("client disconnected from %s", request_id)
        await resp.write_eof()
        return resp

    # -- embeddings (reference engines serve /v1/embeddings too) -----------
    async def handle_embeddings(self, request: web.Request) -> web.Response:
        body, err = await self._json_body(request)
        if err is not None:
            return err
        err = self._check_model(body)
        if err is not None:
            return err
        model = body.get("model", self.model_name)
        lora_name = model if model in self.lora_adapters else None
        inputs = body.get("input")
        if isinstance(inputs, str):
            inputs = [inputs]
        if (
            not isinstance(inputs, list)
            or not inputs
            or not all(isinstance(x, str) for x in inputs)
        ):
            return web.json_response(
                proto.error_json("'input' must be a non-empty string or "
                                 "list of strings"), status=400
            )

        loop = asyncio.get_running_loop()
        try:
            vecs, n_tokens = await loop.run_in_executor(
                None, self._embed_texts, inputs, lora_name
            )
        except ValueError as e:
            return web.json_response(proto.error_json(str(e)), status=400)
        data = [
            {"object": "embedding", "index": i, "embedding": v.tolist()}
            for i, v in enumerate(vecs)
        ]
        return web.json_response({
            "object": "list",
            "model": model,
            "data": data,
            "usage": {"prompt_tokens": n_tokens,
                      "total_tokens": n_tokens},
        })

    # -- rerank / score (router proxies these; reference engines serve
    # them for reranker/scorer models via cross-encoders. A decoder
    # engine scores by embedding-space cosine — the same decoder-as-
    # embedder pooling /v1/embeddings uses — which preserves the API
    # contract and ordering semantics; plug a cross-encoder family in
    # for calibrated absolute scores.) --------------------------------
    def _embed_texts(self, texts: list[str], lora_name):
        """One text per lock acquisition: an in-flight decode batch only
        ever waits for ONE embedding forward (or its first-bucket
        compile), never the whole list. Shared by /v1/embeddings,
        /v1/rerank, and /v1/score."""
        import numpy as np

        vecs = []
        n_tokens = 0
        for t in texts:
            with self.engine._lock:
                vec, count = self.engine.engine.embed_one(t, lora_name)
            vecs.append(np.asarray(vec))
            n_tokens += count
        return vecs, n_tokens

    async def handle_rerank(self, request: web.Request) -> web.Response:
        """Jina/Cohere-style rerank: query + documents -> sorted scores."""
        body, err = await self._json_body(request)
        if err is not None:
            return err
        err = self._check_model(body)
        if err is not None:
            return err
        query = body.get("query")
        docs = body.get("documents")
        if not isinstance(query, str) or not isinstance(docs, list) or (
            not docs
        ) or not all(isinstance(d, str) for d in docs):
            return web.json_response(
                proto.error_json("'query' must be a string and "
                                 "'documents' a non-empty list of "
                                 "strings"), status=400
            )
        model = body.get("model", self.model_name)
        lora_name = model if model in self.lora_adapters else None
        top_n = body.get("top_n", len(docs))
        if isinstance(top_n, bool) or not isinstance(top_n, int) \
                or top_n < 0:
            return web.json_response(
                proto.error_json("'top_n' must be a non-negative integer"),
                status=400,
            )

        loop = asyncio.get_running_loop()
        try:
            vecs, n_tokens = await loop.run_in_executor(
                None, self._embed_texts, [query] + docs, lora_name
            )
        except ValueError as e:
            return web.json_response(proto.error_json(str(e)), status=400)
        q = vecs[0]
        scored = sorted(
            (
                {"index": i, "relevance_score": float(q @ v),
                 "document": {"text": docs[i]}}
                for i, v in enumerate(vecs[1:])
            ),
            key=lambda r: -r["relevance_score"],
        )[:top_n]
        return web.json_response({
            "id": proto.make_id("rerank"),
            "model": model,
            "results": scored,
            "usage": {"total_tokens": n_tokens},
        })

    async def handle_score(self, request: web.Request) -> web.Response:
        """vLLM-style /v1/score: text_1 x text_2 similarity scores."""
        body, err = await self._json_body(request)
        if err is not None:
            return err
        err = self._check_model(body)
        if err is not None:
            return err
        t1 = body.get("text_1")
        t2 = body.get("text_2")
        if isinstance(t1, str):
            t1 = [t1]
        if isinstance(t2, str):
            t2 = [t2]
        ok = (
            isinstance(t1, list) and isinstance(t2, list) and t1 and t2
            and all(isinstance(x, str) for x in t1 + t2)
            and (len(t1) == 1 or len(t2) == 1 or len(t1) == len(t2))
        )
        if not ok:
            return web.json_response(
                proto.error_json(
                    "'text_1'/'text_2' must be strings or lists of "
                    "strings with broadcastable lengths (1xM, Nx1, NxN)"
                ),
                status=400,
            )
        if len(t1) == 1:
            pairs = [(t1[0], x) for x in t2]
        elif len(t2) == 1:
            pairs = [(x, t2[0]) for x in t1]
        else:
            pairs = list(zip(t1, t2))
        model = body.get("model", self.model_name)
        lora_name = model if model in self.lora_adapters else None
        loop = asyncio.get_running_loop()
        uniq = list(dict.fromkeys(t for p in pairs for t in p))
        try:
            vecs, n_tokens = await loop.run_in_executor(
                None, self._embed_texts, uniq, lora_name
            )
        except ValueError as e:
            return web.json_response(proto.error_json(str(e)), status=400)
        by_text = dict(zip(uniq, vecs))
        data = [
            {"object": "score", "index": i,
             "score": float(by_text[a] @ by_text[b])}
            for i, (a, b) in enumerate(pairs)
        ]
        return web.json_response({
            "id": proto.make_id("score"),
            "object": "list",
            "model": model,
            "data": data,
            "usage": {"total_tokens": n_tokens},
        })

    # -- misc endpoints ----------------------------------------------------
    async def handle_models(self, request: web.Request) -> web.Response:
        cards = [proto.model_card(
            self.model_name,
            kv_instance_id=self.config.kv_instance_id,
            kv_role=self.config.pd_role(),
            max_model_len=self.config.resolved_max_model_len(),
            sp_size=(
                self.config.context_parallel_size
                if getattr(self.engine, "long_prefill", None) is not None
                else None
            ),
        )]
        cards += [
            proto.model_card(name, root=path)
            for name, path in self.lora_adapters.items()
        ]
        return web.json_response({"object": "list", "data": cards})

    async def handle_tokenize(self, request: web.Request) -> web.Response:
        body = await request.json()
        if "prompt" in body:
            text = body["prompt"]
        elif "messages" in body:
            text = self.engine.tokenizer.apply_chat_template(body["messages"])
        else:
            return web.json_response(
                proto.error_json("missing 'prompt' or 'messages'"), status=400
            )
        ids = self.engine.tokenizer.encode(text)
        return web.json_response(
            {"tokens": ids, "count": len(ids),
             "max_model_len": self.config.resolved_max_model_len()}
        )

    async def handle_detokenize(self, request: web.Request) -> web.Response:
        body = await request.json()
        tokens = body.get("tokens")
        if tokens is None:
            return web.json_response(
                proto.error_json("missing 'tokens'"), status=400
            )
        return web.json_response(
            {"prompt": self.engine.tokenizer.decode(tokens)}
        )

    async def handle_health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "healthy"})

    async def handle_version(self, request: web.Request) -> web.Response:
        """Package version plus what the engine runs on: platform,
        device_kind, device_count, attention_impl, ragged_kernel and
        per-device bytes_in_use."""
        return web.json_response({
            "version": production_stack_tpu.__version__,
            **self.engine.engine.runner.device_report(),
        })

    async def handle_metrics(self, request: web.Request) -> web.Response:
        self.metrics.update_from_snapshot(self.engine.stats())
        self.metrics.observe_kv(*self.engine.drain_kv_observations())
        self.metrics.observe_ragged(
            self.engine.drain_ragged_observations()
        )
        return web.Response(
            body=generate_latest(self.registry),
            content_type="text/plain",
            charset="utf-8",
        )

    async def handle_debug_requests(
        self, request: web.Request
    ) -> web.Response:
        """Recent request lifecycle timelines (bounded ring) + in-flight
        ones: enqueue -> admit -> prefill chunks (staged/chained flags)
        -> first token -> sampled decode rounds -> preempt/resume ->
        finish. ?limit=N caps the finished-timeline count."""
        from production_stack_tpu.tracing import debug_requests_payload

        recorder = self.engine.timeline
        return web.json_response(debug_requests_payload(
            request.query.get("limit"),
            enabled=recorder.enabled,
            snapshot=lambda n: recorder.snapshot(limit=n),
            hint="start the engine with request_timeline=True (drop "
                 "--no-request-timeline) to record per-request "
                 "lifecycle timelines",
        ))

    # -- sleep/wake (reference: service_discovery.py:414-441 probes these) -
    async def handle_sleep(self, request: web.Request) -> web.Response:
        level = int(request.query.get("level", "1"))
        self.engine.sleep(level)
        return web.json_response({"status": "sleeping", "level": level})

    async def handle_wake(self, request: web.Request) -> web.Response:
        self.engine.wake_up()
        return web.json_response({"status": "awake"})

    async def handle_is_sleeping(self, request: web.Request) -> web.Response:
        return web.json_response({"is_sleeping": self.engine.is_sleeping()})

    # -- LoRA hot-load (reference: loraadapter_controller.go:582-598 POSTs) -
    async def handle_load_lora(self, request: web.Request) -> web.Response:
        body = await request.json()
        name = body.get("lora_name")
        path = body.get("lora_path")
        if not name or not path:
            return web.json_response(
                proto.error_json("need lora_name and lora_path"), status=400
            )
        try:
            with self.engine._lock:
                self.engine.engine.load_lora(name, path)
        except Exception as e:
            return web.json_response(
                proto.error_json(f"failed to load adapter: {e}", code=500),
                status=500,
            )
        self.lora_adapters[name] = path
        logger.info("loaded LoRA adapter %s from %s", name, path)
        return web.json_response({"status": "success"})

    async def handle_unload_lora(self, request: web.Request) -> web.Response:
        body = await request.json()
        name = body.get("lora_name")
        if name not in self.lora_adapters:
            return web.json_response(
                proto.error_json(f"adapter {name!r} not loaded", code=404),
                status=404,
            )
        with self.engine._lock:
            self.engine.engine.unload_lora(name)
        del self.lora_adapters[name]
        return web.json_response({"status": "success"})

    # -- run ---------------------------------------------------------------
    def run(self, host: str = "0.0.0.0", port: int = 8000) -> None:
        logger.info(
            "engine server for %s listening on %s:%d",
            self.model_name, host, port,
        )
        web.run_app(self.app, host=host, port=port, print=None)
