"""OpenAI-compatible HTTP frontend.

Ref: lib/llm/src/http/service/{openai.rs,service_v2.rs,metrics.rs,
disconnect.rs} — routes ``/v1/chat/completions`` (openai.rs:481),
``/v1/completions`` (:245), ``/v1/models``, SSE streaming with ``[DONE]``
sentinel, client-disconnect → context cancellation (disconnect.rs), per-route
metrics: TTFT/ITL histograms, inflight gauges (metrics.rs:1-700).

Built on aiohttp (the axum role). The service is engine-agnostic: it looks
up pipelines in the ModelManager, so aggregated single-process, routed
multi-worker, and disaggregated deployments all serve through this one
frontend.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import AsyncIterator, Optional

from aiohttp import web

from dynamo_tpu.llm.discovery import ModelManager
from dynamo_tpu.llm.protocols import openai as oai
from dynamo_tpu.llm.protocols.common import LLMEngineOutput, as_engine_output
from dynamo_tpu.runtime.engine import Annotated, Context, StreamDisconnect
from dynamo_tpu.runtime.logging import TraceParent, get_logger
from dynamo_tpu.runtime.push_router import NoInstancesError
from dynamo_tpu.runtime.tracing import NULL_SPAN, get_step_log, get_tracer
from dynamo_tpu.runtime.metrics import (
    DURATION_BUCKETS,
    FRONTEND_PREFIX,
    ITL_BUCKETS,
    TTFT_BUCKETS,
    MetricsRegistry,
)
from dynamo_tpu.runtime.telemetry import (
    DigestCollector,
    SloConfig,
    SloJudge,
    Telemetry,
)

logger = get_logger(__name__)

# Digest-exported frontend families (DigestCollector live mode): each stream
# renders as "<name>_seconds" (native histogram, cumulative) plus
# "<name>_seconds_quantile" (rolling-window p50/p90/p99 gauges). These are
# the frontend's OWN end-to-end measurements — client-observed TTFT/TPOT
# including routing and the serving plane, judged against the same SLO
# targets the engine judges its internal latencies with.
FRONTEND_DIGEST_FAMILIES = (
    "ttft_seconds", "ttft_seconds_quantile",
    "tpot_seconds", "tpot_seconds_quantile",
    "request_seconds", "request_seconds_quantile",
)


class HttpService:
    def __init__(
        self,
        manager: ModelManager,
        *,
        host: str = "0.0.0.0",
        port: int = 8000,
        metrics: Optional[MetricsRegistry] = None,
        tls_cert: Optional[str] = None,
        tls_key: Optional[str] = None,
        slo: Optional[SloConfig] = None,
        request_timeout_ms: Optional[float] = None,
    ):
        self.manager = manager
        self.host = host
        self.port = port
        # Default end-to-end request deadline (--request-timeout-ms). A
        # client ``timeout`` (seconds) overrides per request. The budget
        # rides the wire (stop_conditions.deadline_ms) so the scheduler
        # evicts past-deadline rows; the frontend's own watchdog is the
        # backstop for hung workers — either way the client gets a 504
        # with partial-usage accounting, never a silent hang.
        self.request_timeout_ms = request_timeout_ms
        # TLS termination (ref: frontend --tls-cert-path/--tls-key-path,
        # components/frontend/src/dynamo/frontend/main.py:81-286): both paths
        # or neither.
        if bool(tls_cert) != bool(tls_key):
            raise ValueError("TLS needs both tls_cert and tls_key")
        self._ssl = None
        if tls_cert:
            import ssl

            self._ssl = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            self._ssl.load_cert_chain(tls_cert, tls_key)
        self.metrics = metrics or MetricsRegistry(prefix=FRONTEND_PREFIX)
        self._runner: Optional[web.AppRunner] = None
        # Optional KServe gRPC twin sharing this manager; attached by the
        # entrypoint (start_frontend) and stopped with this service.
        self.grpc_service = None

        m = self.metrics
        self._m_requests = lambda model, status: m.counter(
            "requests_total", "HTTP requests", model=model, status=status
        )
        self._m_inflight = lambda model: m.gauge("inflight_requests", "in-flight requests", model=model)
        self._m_ttft = lambda model: m.histogram(
            "time_to_first_token_seconds", "TTFT", buckets=TTFT_BUCKETS, model=model
        )
        self._m_itl = lambda model: m.histogram(
            "inter_token_latency_seconds", "ITL", buckets=ITL_BUCKETS, model=model
        )
        self._m_duration = lambda model: m.histogram(
            "request_duration_seconds", "request duration", buckets=DURATION_BUCKETS, model=model
        )
        # Engine-admission queue time (ref: http_queue_guard / queue-time
        # histograms in http/service/metrics.rs) — the saturation signal the
        # SLA planner inverts for prefill replica math.
        self._m_queue = lambda model: m.histogram(
            "queue_time_seconds", "request queue time before engine admission",
            buckets=TTFT_BUCKETS, model=model,
        )
        self._m_output_tokens = lambda model: m.counter("output_tokens_total", "output tokens", model=model)
        # Failure lifecycle: deadline expiries (504s / timeout finishes),
        # migration replays (stream drops recovered on another worker),
        # exhausted migrations (502s), and no-instance rejections (503s).
        self._m_timeouts = lambda model: m.counter(
            "request_timeouts_total", "requests that exceeded their deadline", model=model
        )
        self._m_migrations = lambda model: m.counter(
            "migrations_total", "stream drops replayed on another worker", model=model
        )
        self._m_migration_exhausted = lambda model: m.counter(
            "migration_exhausted_total", "requests whose migration budget ran out (502)", model=model
        )
        self._m_no_instances = lambda model: m.counter(
            "no_instances_total", "requests rejected because no workers were live (503)", model=model
        )
        self._m_input_tokens = lambda model: m.counter("input_tokens_total", "input (prompt) tokens", model=model)
        # Engine-reported prefix-cache reuse: prompt tokens served from
        # resident KV (usage.prompt_tokens_details.cached_tokens).
        self._m_cached_tokens = lambda model: m.counter(
            "input_cached_tokens_total", "prompt tokens served from the prefix cache", model=model
        )
        # SLA telemetry: the frontend's own e2e digests (ttft/tpot/request
        # — FRONTEND_DIGEST_FAMILIES) and per-request SLO judgments against
        # --slo-ttft-ms/--slo-tpot-ms. Goodput = SLO-attained req/tok.
        self.slo = slo or SloConfig()
        self.telemetry = Telemetry()
        self._slo_judge = SloJudge(self.slo)
        self._digest_collector = DigestCollector(
            FRONTEND_PREFIX, registry=m.registry, telemetry=self.telemetry
        )
        self._m_slo = lambda model, phase, verdict: m.counter(
            "slo_attained_total" if verdict == "attained" else "slo_violated_total",
            "request phases meeting/missing the SLO target",
            model=model, phase=phase,
        )
        self._m_goodput_requests = lambda model: m.counter(
            "goodput_requests_total", "requests that attained every configured SLO", model=model
        )
        self._m_goodput_tokens = lambda model: m.counter(
            "goodput_tokens_total", "output tokens of SLO-attained requests", model=model
        )
        self._m_goodput_req_s = m.gauge(
            "goodput_requests_per_s", "SLO-attained requests/s over the rolling window"
        )
        self._m_goodput_tok_s = m.gauge(
            "goodput_tokens_per_s", "SLO-attained output tokens/s over the rolling window"
        )

    def _record_request_telemetry(
        self,
        model: str,
        start: float,
        first_at: Optional[float],
        last_at: Optional[float],
        n_tokens: int,
        ctx=None,
    ) -> None:
        """End-of-request e2e telemetry: digests + SLO judgment + goodput.
        Requests that never produced a token (errors, rejections) are not
        judged — they are failures, not latency violations."""
        if first_at is None:
            return
        now = time.monotonic()
        ttft_s = max(0.0, first_at - start)
        self.telemetry.observe("ttft", ttft_s)
        self.telemetry.observe("request", max(0.0, now - start))
        tpot_s = None
        if n_tokens > 1 and last_at is not None and last_at > first_at:
            tpot_s = (last_at - first_at) / (n_tokens - 1)
            self.telemetry.observe("tpot", tpot_s)
        if not self.slo.enabled:
            return
        good = self._slo_judge.judge(ttft_s, tpot_s, n_tokens)
        if not good and ctx is not None and get_tracer().tail:
            # Tail-based sampling: a request that violated its SLO keeps
            # its full span set regardless of the head-sampling rate. The
            # promotion itself is deferred to the request handler's finally
            # — the root http_request span has not ended yet here, and it
            # must be in the ring before the trace is promoted.
            ctx.metadata["_slo_promote"] = True
        if self.slo.ttft_ms is not None:
            verdict = "attained" if ttft_s * 1000.0 <= self.slo.ttft_ms else "violated"
            self._m_slo(model, "ttft", verdict).inc()
        if self.slo.tpot_ms is not None and tpot_s is not None:
            verdict = "attained" if tpot_s * 1000.0 <= self.slo.tpot_ms else "violated"
            self._m_slo(model, "tpot", verdict).inc()
        if good:
            self._m_goodput_requests(model).inc()
            self._m_goodput_tokens(model).inc(n_tokens)
        req_s, tok_s = self._slo_judge.goodput_rates()
        self._m_goodput_req_s.set(req_s)
        self._m_goodput_tok_s.set(tok_s)

    # --- lifecycle ----------------------------------------------------------
    def build_app(self) -> web.Application:
        app = web.Application()
        app.router.add_post("/v1/chat/completions", self.chat_completions)
        app.router.add_post("/v1/completions", self.completions)
        app.router.add_post("/v1/embeddings", self.embeddings)
        app.router.add_post("/v1/responses", self.responses)
        app.router.add_get("/v1/models", self.list_models)
        app.router.add_get("/health", self.health)
        app.router.add_get("/metrics", self.metrics_route)
        app.router.add_post("/clear_kv_blocks", self.clear_kv_blocks)
        return app

    async def start(self) -> None:
        import socket as _socket

        self._runner = web.AppRunner(self.build_app(), access_log=None)
        await self._runner.setup()
        # Bind the socket ourselves: aiohttp exposes no public API for the
        # OS-assigned port when port=0 (reaching into site._server.sockets is
        # a private-API trap across versions).
        # Bind off the loop: create_server resolves the host and binds
        # synchronously, which can stall an already-serving process loop
        # (multi-frontend startup, slow resolvers).
        sock = await asyncio.to_thread(
            _socket.create_server, (self.host, self.port), reuse_port=False
        )
        self.port = sock.getsockname()[1]
        site = web.SockSite(self._runner, sock, ssl_context=self._ssl)
        await site.start()
        logger.info(
            "OpenAI HTTP%s frontend on %s:%d", "S" if self._ssl else "", self.host, self.port
        )

    async def stop(self) -> None:
        try:
            if self.grpc_service is not None:
                await self.grpc_service.stop()
                self.grpc_service = None
        finally:
            if self._runner is not None:
                await self._runner.cleanup()
                self._runner = None

    # --- routes -------------------------------------------------------------
    async def health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "healthy", "models": self.manager.list_models()})

    async def metrics_route(self, request: web.Request) -> web.Response:
        return web.Response(body=self.metrics.render(), content_type="text/plain")

    async def list_models(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "object": "list",
                "data": [
                    {"id": name, "object": "model", "created": int(time.time()), "owned_by": "dynamo-tpu"}
                    for name in self.manager.list_models()
                ],
            }
        )

    async def clear_kv_blocks(self, request: web.Request) -> web.Response:
        # Ref: clear_kv_blocks.rs — forwarded to workers in the routed setup;
        # local engines expose a hook via the manager entry.
        results = {}
        for name in self.manager.list_models():
            engine = self.manager.get("chat", name) or self.manager.get("completions", name)
            hook = getattr(engine, "clear_kv_blocks", None)
            results[name] = "ok" if hook and await _maybe_await(hook()) is not None else "unsupported"
        return web.json_response(results)

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        return await self._serve(request, kind="chat")

    async def completions(self, request: web.Request) -> web.StreamResponse:
        return await self._serve(request, kind="completions")

    def _unary_envelope(self, model: str):
        """Shared request lifecycle for unary JSON endpoints: inflight gauge,
        duration histogram, status counters, structured 500 bodies."""

        service = self

        class _Scope:
            async def __aenter__(self):
                service._m_inflight(model).inc()
                self.start = time.monotonic()
                return self

            async def __aexit__(self, exc_type, exc, tb):
                service._m_inflight(model).dec()
                service._m_duration(model).observe(time.monotonic() - self.start)
                return False

            def run(self, coro):
                async def wrapped():
                    try:
                        resp = await coro()
                        service._m_requests(model, "200").inc()
                        return resp
                    except oai.RequestError as e:
                        service._m_requests(model, "400").inc()
                        return web.json_response(oai.error_body(str(e)), status=400)
                    except Exception as e:
                        logger.exception("request for %s failed", model)
                        service._m_requests(model, "500").inc()
                        return web.json_response(oai.error_body(str(e), "internal_error", 500), status=500)

                return wrapped()

        return _Scope()

    async def embeddings(self, request: web.Request) -> web.Response:
        """/v1/embeddings (ref: openai.rs:369) — routed to an engine
        registered under model_type 'embeddings'."""
        try:
            body = oai.validate_embedding_request(await request.json())
        except (json.JSONDecodeError, oai.RequestError) as e:
            return web.json_response(oai.error_body(str(e)), status=400)
        model = body["model"]
        engine = self.manager.get("embeddings", model)
        if engine is None:
            self._m_requests(model, "404").inc()
            return web.json_response(
                oai.error_body(f"no embeddings model {model!r}", "model_not_found", 404), status=404
            )

        async def handle():
            vectors, prompt_tokens = [], 0
            async for item in engine.generate(body, Context()):
                if isinstance(item, Annotated) and item.is_annotation():
                    continue
                wire = item.data if isinstance(item, Annotated) else item
                if isinstance(wire, dict) and "embeddings" in wire:
                    vectors = wire["embeddings"]
                    prompt_tokens = int(wire.get("prompt_tokens") or 0)
            self._m_input_tokens(model).inc(prompt_tokens)
            usage = oai.usage_dict(prompt_tokens=prompt_tokens, completion_tokens=0)
            return web.json_response(oai.embedding_response(oai.make_id("embd"), model, vectors, usage))

        async with self._unary_envelope(model) as scope:
            return await scope.run(handle)

    async def responses(self, request: web.Request) -> web.StreamResponse:
        """/v1/responses (ref: openai.rs:714) — mapped onto the chat
        pipeline; input items are converted to chat messages."""
        try:
            body = oai.validate_responses_request(await request.json())
        except (json.JSONDecodeError, oai.RequestError) as e:
            return web.json_response(oai.error_body(str(e)), status=400)
        model = body["model"]
        engine = self.manager.get("chat", model)
        if engine is None:
            self._m_requests(model, "404").inc()
            return web.json_response(oai.error_body(f"model {model!r} not found", "model_not_found", 404), status=404)
        rid = oai.make_id("resp")

        try:
            messages = oai.responses_input_to_messages(body)  # RequestError on bad items
        except oai.RequestError as e:
            self._m_requests(model, "400").inc()
            return web.json_response(oai.error_body(str(e)), status=400)
        chat_body = {
            "model": model,
            "messages": messages,
            "stream": False,
        }
        for key in ("temperature", "top_p", "max_output_tokens"):
            if body.get(key) is not None:
                chat_body["max_tokens" if key == "max_output_tokens" else key] = body[key]
        if body.get("tools"):
            chat_body["tools"] = oai.responses_tools_to_chat(body["tools"])
        if body.get("tool_choice") is not None:
            chat_body["tool_choice"] = oai.responses_tool_choice_to_chat(body["tool_choice"])
        rf = oai.responses_text_format_to_response_format(body)
        if rf is not None:
            chat_body["response_format"] = rf
        try:
            # Mirror the chat-side structural validation (response_format /
            # tools / tool_choice) so Responses clients get the same
            # structured 400s, not worker-side failures.
            oai.validate_chat_request(chat_body)
        except oai.RequestError as e:
            self._m_requests(model, "400").inc()
            return web.json_response(oai.error_body(str(e)), status=400)

        if body.get("stream"):
            return await self._responses_stream(request, engine, chat_body, rid, model)

        async def handle():
            text_parts, n_tokens, prompt_tokens = [], 0, 0
            cached_tokens = None
            tool_calls = None
            async for item in engine.generate(chat_body, Context()):
                if isinstance(item, Annotated) and item.is_annotation():
                    if item.event == "_metrics":
                        prompt_tokens = int(item.comment or 0)
                        self._m_input_tokens(model).inc(prompt_tokens)
                    elif item.event == "_queue":
                        self._m_queue(model).observe(float(item.comment or 0))
                    elif item.event == "_cached":
                        cached_tokens = int(item.comment or 0)
                        self._m_cached_tokens(model).inc(cached_tokens)
                    continue
                out = _as_output(item)
                if out is None:
                    continue
                if out.text:
                    text_parts.append(out.text)
                if out.tool_calls:
                    tool_calls = out.tool_calls
                n_tokens += len(out.token_ids)
            self._m_output_tokens(model).inc(n_tokens)
            usage = oai.usage_dict(
                prompt_tokens=prompt_tokens, completion_tokens=n_tokens,
                cached_tokens=cached_tokens,
            )
            return web.json_response(
                oai.responses_response(rid, model, "".join(text_parts), usage, tool_calls=tool_calls)
            )

        async with self._unary_envelope(model) as scope:
            return await scope.run(handle)

    async def _responses_stream(
        self, request: web.Request, engine, chat_body: dict, rid: str, model: str
    ) -> web.StreamResponse:
        """Responses-API semantic SSE stream (ref: openai.rs:714,
        protocols/openai/responses.rs): response.created →
        output_item.added → content_part.added → output_text.delta* →
        *.done → (function_call items) → response.completed."""
        ctx = Context(traceparent=TraceParent.from_headers(request.headers) or None)
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
                **_trace_headers(ctx),
            },
        )
        await resp.prepare(request)
        seq = [0]
        start = time.monotonic()

        async def emit(etype: str, payload: dict) -> None:
            payload = {"type": etype, "sequence_number": seq[0], **payload}
            seq[0] += 1
            await resp.write(
                b"event: " + etype.encode()
                + b"\ndata: " + json.dumps(payload, ensure_ascii=False).encode() + b"\n\n"
            )

        text_parts: list = []
        tool_calls = None
        n_tokens, prompt_tokens = 0, 0
        cached_tokens = None
        status = "200"
        msg_id = f"msg-{rid}"
        msg_started = False

        async def ensure_message_started() -> None:
            # The message output item opens lazily at the first text delta:
            # tool-call-only responses must match the unary shape (no empty
            # message item; function_call items start at output_index 0).
            nonlocal msg_started
            if msg_started:
                return
            msg_started = True
            await emit(
                "response.output_item.added",
                {"output_index": 0, "item": {"type": "message", "id": msg_id, "role": "assistant",
                                             "status": "in_progress", "content": []}},
            )
            await emit(
                "response.content_part.added",
                {"item_id": msg_id, "output_index": 0, "content_index": 0,
                 "part": {"type": "output_text", "text": "", "annotations": []}},
            )

        self._m_inflight(model).inc()
        try:
            await emit("response.created", {"response": oai.responses_envelope(rid, model, [], status="in_progress")})
            await emit("response.in_progress", {"response": oai.responses_envelope(rid, model, [], status="in_progress")})
            async for item in engine.generate(chat_body, ctx):
                if isinstance(item, Annotated) and item.is_annotation():
                    if item.event == "_metrics":
                        prompt_tokens = int(item.comment or 0)
                        self._m_input_tokens(model).inc(prompt_tokens)
                    elif item.event == "_queue":
                        self._m_queue(model).observe(float(item.comment or 0))
                    elif item.event == "_cached":
                        cached_tokens = int(item.comment or 0)
                        self._m_cached_tokens(model).inc(cached_tokens)
                    continue
                out = _as_output(item)
                if out is None:
                    continue
                n_tokens += len(out.token_ids)
                if out.text:
                    await ensure_message_started()
                    text_parts.append(out.text)
                    await emit(
                        "response.output_text.delta",
                        {"item_id": msg_id, "output_index": 0, "content_index": 0, "delta": out.text},
                    )
                if out.tool_calls:
                    tool_calls = out.tool_calls
            text = "".join(text_parts)
            output = []
            if msg_started or not tool_calls:
                await ensure_message_started()
                await emit(
                    "response.output_text.done",
                    {"item_id": msg_id, "output_index": 0, "content_index": 0, "text": text},
                )
                await emit(
                    "response.content_part.done",
                    {"item_id": msg_id, "output_index": 0, "content_index": 0,
                     "part": {"type": "output_text", "text": text, "annotations": []}},
                )
                output.append(oai.responses_message_item(rid, text))
                await emit("response.output_item.done", {"output_index": 0, "item": output[0]})
            for i, call in enumerate(tool_calls or []):
                idx = len(output)
                fc = oai.responses_function_call_item(rid, i, call)
                output.append(fc)
                await emit(
                    "response.output_item.added",
                    {"output_index": idx, "item": {**fc, "arguments": "", "status": "in_progress"}},
                )
                await emit(
                    "response.function_call_arguments.delta",
                    {"item_id": fc["id"], "output_index": idx, "delta": fc["arguments"]},
                )
                await emit(
                    "response.function_call_arguments.done",
                    {"item_id": fc["id"], "output_index": idx, "arguments": fc["arguments"]},
                )
                await emit("response.output_item.done", {"output_index": idx, "item": fc})
            usage = oai.usage_dict(
                prompt_tokens=prompt_tokens, completion_tokens=n_tokens,
                cached_tokens=cached_tokens,
            )
            await emit(
                "response.completed",
                {"response": oai.responses_envelope(rid, model, output, usage)},
            )
        except (ConnectionResetError, asyncio.CancelledError):
            ctx.stop_generating()
            status = "499"
            raise
        except Exception as e:  # noqa: BLE001 — stream errors become SSE error events
            logger.exception("responses stream %s failed", ctx.id)
            status = "500"
            await emit("error", {"message": str(e)})
        finally:
            self._m_inflight(model).dec()
            self._m_duration(model).observe(time.monotonic() - start)
            self._m_requests(model, status).inc()
            self._m_output_tokens(model).inc(n_tokens)
        await resp.write_eof()
        return resp

    # --- core serving path --------------------------------------------------
    async def _serve(self, request: web.Request, kind: str) -> web.StreamResponse:
        model = "unknown"
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(oai.error_body("invalid JSON body"), status=400)
        try:
            body = oai.validate_chat_request(body) if kind == "chat" else oai.validate_completion_request(body)
            model = body["model"]
            # Capacity-ledger attribution: resolve the tenant once, here,
            # so the preprocessor can put it on the wire and every usage
            # block echoes the id the request was billed under.
            body["_tenant"] = _resolve_tenant(body, request.headers)
        except oai.RequestError as e:
            self._m_requests(model, "400").inc()
            return web.json_response(oai.error_body(str(e)), status=400)

        engine = self.manager.get(kind, model) or self.manager.get(
            "chat" if kind == "completions" else "completions", model
        )
        if engine is None:
            self._m_requests(model, "404").inc()
            return web.json_response(oai.error_body(f"model {model!r} not found", "model_not_found", 404), status=404)

        # Pre-flight availability (routed pipelines expose the router's live
        # instance count): with zero workers the answer is an immediate,
        # retryable 503 — not a 500 after the router exhausts its budget,
        # and for SSE not an error event on an already-200 stream.
        probe = getattr(engine, "availability_probe", None)
        if probe is not None and probe() == 0:
            await asyncio.sleep(0.05)  # one watch delivery: absorb races
            if probe() == 0:
                self._m_no_instances(model).inc()
                self._m_requests(model, "503").inc()
                return web.json_response(
                    oai.error_body("no workers are live for this model; retry shortly",
                                   "service_unavailable", 503),
                    status=503, headers={"Retry-After": "1"},
                )

        # Request deadline: client ``timeout`` (seconds) or the frontend
        # default. Normalized into the body so the preprocessor puts the
        # budget on the wire (stop_conditions.deadline_ms).
        timeout_s = body.get("timeout")
        if timeout_s is None and self.request_timeout_ms:
            timeout_s = self.request_timeout_ms / 1000.0
            body["timeout"] = timeout_s
        deadline = (time.monotonic() + float(timeout_s)) if timeout_s else None

        stream = bool(body.get("stream", False))
        ctx = Context(traceparent=TraceParent.from_headers(request.headers) or None)
        # Root (or continuation) span for the request. When sampled, the
        # span becomes the parent of every downstream hop: ctx.traceparent
        # is re-rooted under it, and the same deterministic sampling
        # decision repeats in the worker and scheduler.
        span = get_tracer().span_from(
            "http_request", ctx.traceparent, service="frontend",
            model=model, kind=kind, stream=stream, tenant=body["_tenant"],
        )
        if span is not NULL_SPAN:
            ctx.traceparent = span.child_traceparent()
        rid = oai.make_id("chatcmpl" if kind == "chat" else "cmpl")
        start = time.monotonic()
        self._m_inflight(model).inc()
        try:
            if stream:
                return await self._serve_stream(request, engine, body, ctx, rid, kind, model, start, deadline)
            return await self._serve_unary(engine, body, ctx, rid, kind, model, start, deadline)
        except oai.RequestError as e:
            # Pipeline-stage rejection (e.g. image parts with no encode
            # path): a client/deployment-configuration 400, not a 500.
            self._m_requests(model, "400").inc()
            return web.json_response(
                oai.error_body(str(e)), status=400, headers=_trace_headers(ctx)
            )
        finally:
            self._m_inflight(model).dec()
            self._m_duration(model).observe(time.monotonic() - start)
            span.end()
            if ctx.metadata.pop("_slo_promote", False):
                tracer = get_tracer()
                tp = getattr(ctx, "traceparent", None)
                if tp is not None:
                    promoted = tracer.promote(tp.trace_id)
                    if promoted:
                        logger.info(
                            "slo violation: promoted %d buffered trace records for %s",
                            promoted, tp.trace_id,
                        )

    def _timeout_response(self, ctx, model, prompt_tokens, completion_tokens,
                          cached_tokens=None, tenant=None) -> web.Response:
        """504 with partial-usage accounting: the tokens that did stream are
        real work the client may be billed for, and the counts tell the
        operator how close the request got before the deadline."""
        self._m_timeouts(model).inc()
        self._m_requests(model, "504").inc()
        body = oai.error_body("request deadline exceeded", "timeout_error", 504)
        body["usage"] = oai.usage_dict(prompt_tokens, completion_tokens, cached_tokens,
                                       tenant=tenant)
        return web.json_response(body, status=504, headers=_trace_headers(ctx))

    def _failure_response(self, e, ctx, model, prompt_tokens, completion_tokens):
        """Map infrastructure failures to structured statuses: no live
        workers → retryable 503; migration budget exhausted mid-stream →
        502 carrying the partial token count. None = not ours (500 path)."""
        if isinstance(e, NoInstancesError):
            self._m_no_instances(model).inc()
            self._m_requests(model, "503").inc()
            return web.json_response(
                oai.error_body("no workers are live for this model; retry shortly",
                               "service_unavailable", 503),
                status=503, headers={"Retry-After": "1", **_trace_headers(ctx)},
            )
        if isinstance(e, StreamDisconnect):
            mig = ctx.metadata.get("migration") or {}
            self._m_migration_exhausted(model).inc()
            self._m_requests(model, "502").inc()
            body = oai.error_body(
                "upstream worker stream disconnected and the migration budget "
                "is exhausted", "bad_gateway", 502,
            )
            body["error"]["partial_tokens"] = int(
                mig.get("tokens_emitted", completion_tokens)
            )
            body["error"]["migrations"] = int(mig.get("attempts", 0))
            body["usage"] = oai.usage_dict(prompt_tokens, completion_tokens)
            return web.json_response(body, status=502, headers=_trace_headers(ctx))
        return None

    @staticmethod
    def _choice_bodies(body: dict) -> list:
        """Per-choice request bodies for n>1: each choice is an independent
        generation; seeded requests get seed+i so choices differ the way
        OpenAI's do (ref: protocols/openai n handling)."""
        n = int(body.get("n") or 1)
        if n == 1:
            return [body]
        out = []
        for i in range(n):
            b = dict(body)
            b["n"] = 1
            if body.get("seed") is not None:
                b["seed"] = int(body["seed"]) + i
            out.append(b)
        return out

    async def _serve_unary(self, engine, body, ctx, rid, kind, model, start, deadline=None) -> web.Response:
        bodies = self._choice_bodies(body)
        prompt_tokens_box = [0]
        cached_tokens_box = [None]
        first_box = [None]
        last_box = [None]
        # Per-choice live token counts: the 504/502 paths report honest
        # partial usage even for choices that never reached their final
        # frame.
        tokens_box = [0] * len(bodies)

        async def run_choice(i: int, b: dict, c: Context) -> dict:
            text_parts = []
            reasoning_parts = []
            tool_calls = None
            n_tokens = 0
            finish_reason = "stop"
            logprobs: list = []
            top_logprobs: list = []
            async for item in engine.generate(b, c):
                if isinstance(item, Annotated) and item.is_annotation():
                    if item.event == "_metrics" and i == 0:
                        prompt_tokens_box[0] = int(item.comment or 0)
                        self._m_input_tokens(model).inc(prompt_tokens_box[0])
                    elif item.event == "_queue" and i == 0:
                        self._m_queue(model).observe(float(item.comment or 0))
                    elif item.event == "_cached" and i == 0:
                        cached_tokens_box[0] = int(item.comment or 0)
                        self._m_cached_tokens(model).inc(cached_tokens_box[0])
                    continue
                out = _as_output(item)
                if out is None:
                    continue
                if out.token_ids:
                    last_box[0] = time.monotonic()
                if out.text:
                    if first_box[0] is None:
                        first_box[0] = time.monotonic()
                        self._m_ttft(model).observe(first_box[0] - start)
                    text_parts.append(out.text)
                if out.reasoning:
                    reasoning_parts.append(out.reasoning)
                if out.tool_calls:
                    tool_calls = out.tool_calls
                if out.logprobs:
                    logprobs.extend(out.logprobs)
                    # Keep alternatives index-aligned with the chosen-token
                    # list even if a frame carried logprobs without tops.
                    tops = out.top_logprobs or []
                    top_logprobs.extend(tops[: len(out.logprobs)])
                    while len(top_logprobs) < len(logprobs):
                        top_logprobs.append(None)
                n_tokens += len(out.token_ids)
                tokens_box[i] = n_tokens
                if out.finish_reason:
                    finish_reason = out.finish_reason
            return {
                "index": i,
                "text": "".join(text_parts),
                "reasoning": "".join(reasoning_parts) or None,
                "tool_calls": tool_calls,
                "finish_reason": finish_reason,
                "n_tokens": n_tokens,
                "logprobs": logprobs,
                "top_logprobs": top_logprobs if any(top_logprobs) else None,
            }

        # Children need UNIQUE ids: the engine keys sequences by context.id,
        # so sharing the parent's id would collide all n choices in the
        # scheduler (un-abortable orphans once one finishes).
        ctxs = [ctx] + [ctx.child(id=f"{ctx.id}-c{i}") for i in range(1, len(bodies))]
        tasks = [
            asyncio.create_task(run_choice(i, b, c))
            for i, (b, c) in enumerate(zip(bodies, ctxs))
        ]
        frontend_timed_out = False
        try:
            if deadline is None:
                results = await asyncio.gather(*tasks)
            else:
                # Frontend deadline backstop: the scheduler evicts
                # past-deadline rows itself, so the grace window only trips
                # when a worker is hung or unreachable — then we cancel into
                # the pipeline and answer 504 with whatever tokens landed.
                grace = max(0.5, 0.25 * max(deadline - start, 0.0))
                done, pending = await asyncio.wait(
                    set(tasks), timeout=max(0.0, deadline + grace - time.monotonic())
                )
                if pending:
                    frontend_timed_out = True
                    for c in ctxs:
                        c.stop_generating()
                    _, still = await asyncio.wait(pending, timeout=2.0)
                    for t in still:
                        t.cancel()
                    await asyncio.gather(*tasks, return_exceptions=True)
                for t in tasks:
                    if t.done() and not t.cancelled() and t.exception() is not None:
                        raise t.exception()
                results = [
                    t.result() for t in tasks if t.done() and not t.cancelled()
                ]
        except Exception as e:
            # Stop and reap the sibling choices — leaving them running wastes
            # engine work and leaks never-retrieved task exceptions.
            for c in ctxs:
                c.stop_generating()
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            if isinstance(e, oai.RequestError):
                # Pipeline-stage rejection (e.g. image parts with no encode
                # path): a client/configuration 400, not a server fault.
                self._m_requests(model, "400").inc()
                return web.json_response(
                    oai.error_body(str(e)), status=400, headers=_trace_headers(ctx)
                )
            mapped = self._failure_response(e, ctx, model, prompt_tokens_box[0], sum(tokens_box))
            if mapped is not None:
                return mapped
            logger.exception("request %s failed", ctx.id)
            self._m_requests(model, "500").inc()
            return web.json_response(
                oai.error_body(str(e), "internal_error", 500), status=500,
                headers=_trace_headers(ctx),
            )
        if frontend_timed_out or any(r["finish_reason"] == "timeout" for r in results):
            # Deadline expiry — engine-evicted (finish_reason "timeout") or
            # the frontend watchdog above. 504 with partial-usage accounting.
            return self._timeout_response(ctx, model, prompt_tokens_box[0],
                                          sum(tokens_box), cached_tokens_box[0],
                                          tenant=body.get("_tenant"))
        self._m_requests(model, "200").inc()
        total_tokens = sum(r["n_tokens"] for r in results)
        self._m_output_tokens(model).inc(total_tokens)
        self._record_request_telemetry(
            model, start, first_box[0], last_box[0], results[0]["n_tokens"], ctx=ctx
        )
        usage = oai.usage_dict(
            prompt_tokens=prompt_tokens_box[0], completion_tokens=total_tokens,
            cached_tokens=cached_tokens_box[0], tenant=body.get("_tenant"),
        )
        if kind == "chat":
            choices = [
                oai.chat_choice(
                    r["index"], r["text"], r["finish_reason"], r["tool_calls"], r["reasoning"],
                    logprobs=oai.chat_logprobs_content(None, r["logprobs"], r["top_logprobs"])
                    if r["logprobs"] else None,
                )
                for r in results
            ]
            return web.json_response(
                oai.chat_response_multi(rid, model, choices, usage), headers=_trace_headers(ctx)
            )
        choices = [
            oai.completion_choice(
                r["index"], r["text"], r["finish_reason"],
                logprobs=oai.completion_logprobs_block(
                    [""] * len(r["logprobs"]), r["logprobs"], r["top_logprobs"]
                )
                if r["logprobs"] else None,
            )
            for r in results
        ]
        return web.json_response(
            oai.completion_response_multi(rid, model, choices, usage), headers=_trace_headers(ctx)
        )

    @staticmethod
    async def _iter_with_deadline(stream, deadline: Optional[float], start: float):
        """Yield stream items, raising TimeoutError when the deadline (plus
        a hung-worker grace window — the engine's own eviction should fire
        first and arrives as a normal finish_reason='timeout' frame) lapses
        between items."""
        if deadline is None:
            async for item in stream:
                yield item
            return
        grace = max(0.5, 0.25 * max(deadline - start, 0.0))
        it = stream.__aiter__()
        while True:
            remaining = deadline + grace - time.monotonic()
            if remaining <= 0:
                raise asyncio.TimeoutError
            try:
                item = await asyncio.wait_for(it.__anext__(), remaining)
            except StopAsyncIteration:
                return
            yield item

    async def _serve_stream(self, request, engine, body, ctx, rid, kind, model, start, deadline=None) -> web.StreamResponse:
        if int(body.get("n") or 1) > 1:
            return await self._serve_stream_multi(request, engine, body, ctx, rid, kind, model, start, deadline)
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
                **_trace_headers(ctx),
            },
        )
        await resp.prepare(request)
        first = True
        first_at = None
        prev_tok_at = None
        n_tokens = 0
        prompt_tokens = 0
        cached_tokens = None
        status = "200"
        try:
            if kind == "chat":
                await _sse(resp, oai.chat_chunk(rid, model, {"role": "assistant", "content": ""}))
            async for item in self._iter_with_deadline(engine.generate(body, ctx), deadline, start):
                if isinstance(item, Annotated) and item.is_annotation():
                    if item.event.startswith("_"):
                        if item.event == "_metrics":
                            prompt_tokens = int(item.comment or 0)
                            self._m_input_tokens(model).inc(prompt_tokens)
                        elif item.event == "_queue":
                            self._m_queue(model).observe(float(item.comment or 0))
                        elif item.event == "_cached":
                            cached_tokens = int(item.comment or 0)
                            self._m_cached_tokens(model).inc(cached_tokens)
                        continue
                    await _sse_event(resp, item.event, item.comment)
                    continue
                out = _as_output(item)
                if out is None:
                    continue
                now = time.monotonic()
                if out.text or out.token_ids:
                    if first:
                        self._m_ttft(model).observe(now - start)
                        first = False
                        first_at = now
                    elif prev_tok_at is not None:
                        self._m_itl(model).observe(now - prev_tok_at)
                    prev_tok_at = now
                    n_tokens += len(out.token_ids)
                if out.reasoning and kind == "chat":
                    await _sse(resp, oai.chat_chunk(rid, model, {"reasoning_content": out.reasoning}))
                if out.text or out.logprobs:
                    # Tokens whose text is withheld (detok partials / stop
                    # jail) still stream their logprobs on an empty delta.
                    text = out.text or ""
                    lp = None
                    if out.logprobs:
                        lp = (
                            oai.chat_logprobs_content(text, out.logprobs, out.top_logprobs)
                            if kind == "chat"
                            else oai.completion_logprobs_block([text], out.logprobs, out.top_logprobs)
                        )
                    if kind == "chat":
                        await _sse(resp, oai.chat_chunk(rid, model, {"content": text}, logprobs=lp))
                    else:
                        await _sse(resp, oai.completion_chunk(rid, model, text, logprobs=lp))
                if out.tool_calls and kind == "chat":
                    delta_calls = [
                        {**tc, "index": i, "function": tc["function"]}
                        for i, tc in enumerate(out.tool_calls)
                    ]
                    await _sse(resp, oai.chat_chunk(rid, model, {"tool_calls": delta_calls}))
                if out.finish_reason:
                    if out.finish_reason == "timeout":
                        # Engine-side deadline eviction: headers are long
                        # gone, so the 504 lives in the finish_reason and
                        # the status counter.
                        status = "504"
                        self._m_timeouts(model).inc()
                    # Final frame carries the usage block (OpenAI
                    # stream_options include_usage shape) with the resolved
                    # tenant echoed — the client sees who it was billed as.
                    usage = oai.usage_dict(
                        prompt_tokens, n_tokens, cached_tokens,
                        tenant=body.get("_tenant"),
                    )
                    chunk = (
                        oai.chat_chunk(rid, model, {}, finish_reason=out.finish_reason,
                                       usage=usage)
                        if kind == "chat"
                        else oai.completion_chunk(rid, model, "", finish_reason=out.finish_reason)
                    )
                    if kind != "chat":
                        chunk["usage"] = usage
                    await _sse(resp, chunk)
        except (ConnectionResetError, asyncio.CancelledError):
            # Client went away: cancel into the pipeline (ref: disconnect.rs).
            ctx.stop_generating()
            status = "499"
            raise
        except asyncio.TimeoutError:
            # Frontend deadline backstop (hung/unreachable worker): cancel
            # into the pipeline and close the stream with a timeout finish.
            ctx.stop_generating()
            status = "504"
            self._m_timeouts(model).inc()
            chunk = (
                oai.chat_chunk(rid, model, {}, finish_reason="timeout")
                if kind == "chat"
                else oai.completion_chunk(rid, model, "", finish_reason="timeout")
            )
            await _sse(resp, chunk)
        except NoInstancesError:
            status = "503"
            self._m_no_instances(model).inc()
            await _sse(resp, oai.error_body(
                "no workers are live for this model; retry shortly",
                "service_unavailable", 503,
            ))
        except StreamDisconnect:
            mig = ctx.metadata.get("migration") or {}
            status = "502"
            self._m_migration_exhausted(model).inc()
            err = oai.error_body(
                "upstream worker stream disconnected and the migration budget "
                "is exhausted", "bad_gateway", 502,
            )
            err["error"]["partial_tokens"] = int(mig.get("tokens_emitted", n_tokens))
            err["error"]["migrations"] = int(mig.get("attempts", 0))
            await _sse(resp, err)
        except Exception as e:
            logger.exception("stream %s failed", ctx.id)
            status = "500"
            await _sse(resp, oai.error_body(str(e), "internal_error", 500))
        finally:
            self._m_requests(model, status).inc()
            self._m_output_tokens(model).inc(n_tokens)
            if status == "200":
                self._record_request_telemetry(model, start, first_at, prev_tok_at, n_tokens, ctx=ctx)
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    async def _serve_stream_multi(self, request, engine, body, ctx, rid, kind, model, start, deadline=None) -> web.StreamResponse:
        """n>1 streaming: one generation per choice, chunks multiplexed onto
        one SSE stream with their choice index (ref: OpenAI n semantics)."""
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
                **_trace_headers(ctx),
            },
        )
        await resp.prepare(request)
        bodies = self._choice_bodies(body)
        # Unique-id children of the request context: sequences key on the id
        # (collision = orphaned choices) and children inherit the traceparent.
        ctxs = [ctx] + [ctx.child(id=f"{ctx.id}-c{i}") for i in range(1, len(bodies))]
        queue: "asyncio.Queue" = asyncio.Queue()
        n_tokens = 0
        status = "200"

        async def pump(i: int, b: dict, c: Context):
            try:
                async for item in engine.generate(b, c):
                    if isinstance(item, Annotated) and item.is_annotation():
                        if item.event == "_metrics" and i == 0:
                            self._m_input_tokens(model).inc(int(item.comment or 0))
                        elif item.event == "_queue" and i == 0:
                            self._m_queue(model).observe(float(item.comment or 0))
                        elif item.event == "_cached" and i == 0:
                            self._m_cached_tokens(model).inc(int(item.comment or 0))
                        continue
                    out = _as_output(item)
                    if out is not None:
                        await queue.put((i, out, None))
            except Exception as e:  # noqa: BLE001 — surfaced on the stream
                await queue.put((i, None, e))
            finally:
                await queue.put((i, None, None))  # choice done

        tasks = [asyncio.create_task(pump(i, b, c)) for i, (b, c) in enumerate(zip(bodies, ctxs))]
        live = len(tasks)
        try:
            if kind == "chat":
                for i in range(len(bodies)):
                    await _sse(resp, oai.chat_chunk(rid, model, {"role": "assistant", "content": ""}, index=i))
            grace = max(0.5, 0.25 * max(deadline - start, 0.0)) if deadline else 0.0
            while live:
                if deadline is None:
                    i, out, err = await queue.get()
                else:
                    remaining = deadline + grace - time.monotonic()
                    if remaining <= 0:
                        raise asyncio.TimeoutError
                    i, out, err = await asyncio.wait_for(queue.get(), remaining)
                if err is not None:
                    raise err
                if out is None:
                    live -= 1
                    continue
                n_tokens += len(out.token_ids)
                if out.reasoning and kind == "chat":
                    await _sse(resp, oai.chat_chunk(rid, model, {"reasoning_content": out.reasoning}, index=i))
                if out.text or out.logprobs:
                    text = out.text or ""
                    lp = None
                    if out.logprobs:
                        lp = (
                            oai.chat_logprobs_content(text, out.logprobs, out.top_logprobs)
                            if kind == "chat"
                            else oai.completion_logprobs_block([text], out.logprobs, out.top_logprobs)
                        )
                    if kind == "chat":
                        await _sse(resp, oai.chat_chunk(rid, model, {"content": text}, index=i, logprobs=lp))
                    else:
                        await _sse(resp, oai.completion_chunk(rid, model, text, index=i, logprobs=lp))
                if out.tool_calls and kind == "chat":
                    delta_calls = [
                        {**tc, "index": j, "function": tc["function"]}
                        for j, tc in enumerate(out.tool_calls)
                    ]
                    await _sse(resp, oai.chat_chunk(rid, model, {"tool_calls": delta_calls}, index=i))
                if out.finish_reason:
                    chunk = (
                        oai.chat_chunk(rid, model, {}, finish_reason=out.finish_reason, index=i)
                        if kind == "chat"
                        else oai.completion_chunk(rid, model, "", finish_reason=out.finish_reason, index=i)
                    )
                    await _sse(resp, chunk)
        except (ConnectionResetError, asyncio.CancelledError):
            status = "499"
            raise
        except asyncio.TimeoutError:
            # Frontend deadline backstop: finish every live choice with a
            # timeout chunk (headers are long gone; the finally below
            # cancels into the pipeline).
            status = "504"
            self._m_timeouts(model).inc()
            for i in range(len(bodies)):
                chunk = (
                    oai.chat_chunk(rid, model, {}, finish_reason="timeout", index=i)
                    if kind == "chat"
                    else oai.completion_chunk(rid, model, "", finish_reason="timeout", index=i)
                )
                await _sse(resp, chunk)
        except Exception as e:
            logger.exception("stream %s failed", ctx.id)
            status = "500"
            await _sse(resp, oai.error_body(str(e), "internal_error", 500))
        finally:
            for c in ctxs:
                c.stop_generating()
            for t in tasks:
                t.cancel()
            self._m_requests(model, status).inc()
            self._m_output_tokens(model).inc(n_tokens)
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp


_as_output = as_engine_output

# The request's trace id is echoed on every response (SSE included) so a
# client report ("this request was slow") maps straight to the JSONL trace
# and ``tools/trace_view.py`` — even for unsampled requests, where it still
# correlates with the structured logs.
TRACE_ID_HEADER = "x-dynamo-trace-id"

# Capacity-ledger tenant attribution (runtime/ledger.py). Resolution order:
# the OpenAI ``user`` field, then this header, then a hash of the API key —
# "anon" only when the request carries nothing attributable.
TENANT_HEADER = "x-dynamo-tenant"


def _resolve_tenant(body: dict, headers) -> str:
    user = body.get("user")
    if user:
        return oai.validate_tenant(user, "user")
    hdr = headers.get(TENANT_HEADER)
    if hdr:
        return oai.validate_tenant(hdr, TENANT_HEADER)
    auth = headers.get("Authorization") or ""
    if auth:
        # Stable pseudonymous id per API key: attribution without storing
        # (or ever re-emitting) the credential itself.
        import hashlib

        token = auth.split(None, 1)[-1]
        return "key-" + hashlib.sha256(token.encode()).hexdigest()[:16]
    return "anon"


def _trace_headers(ctx: Context) -> dict:
    tp = getattr(ctx, "traceparent", None)
    return {TRACE_ID_HEADER: tp.trace_id} if tp is not None else {}


async def _sse(resp: web.StreamResponse, obj: dict) -> None:
    # One SSE frame encoded and written: an ``http.frame`` span (event loop).
    with get_step_log().span("http.frame"):
        await resp.write(b"data: " + json.dumps(obj, ensure_ascii=False).encode() + b"\n\n")


async def _sse_event(resp: web.StreamResponse, event: str, comment: Optional[str]) -> None:
    payload = json.dumps({"event": event, "comment": comment}, ensure_ascii=False).encode()
    await resp.write(b"event: " + event.encode() + b"\ndata: " + payload + b"\n\n")


async def _maybe_await(x):
    if asyncio.iscoroutine(x):
        return await x
    return x
