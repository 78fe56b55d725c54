"""OpenAI-compatible API server (aiohttp).

Role parity: reference `vllm/entrypoints/openai/api_server.py` (:48 app,
routes /health :134, /v1/models :140, /v1/completions :161,
/v1/chat/completions :146, /metrics :124, --api-key auth middleware).
aiohttp replaces FastAPI (not present in the TPU image); the wire format
is identical.
"""
from __future__ import annotations

import argparse
import asyncio
import json
from typing import Optional

from aiohttp import web

from intellillm_tpu.engine.arg_utils import AsyncEngineArgs
from intellillm_tpu.engine.async_llm_engine import AsyncLLMEngine
from intellillm_tpu.entrypoints.openai.protocol import (ChatCompletionRequest,
                                                        CompletionRequest,
                                                        ErrorResponse)
from intellillm_tpu.entrypoints.openai.serving_chat import OpenAIServingChat
from intellillm_tpu.entrypoints.debug_routes import add_debug_routes
from intellillm_tpu.entrypoints.openai.serving_completion import (
    OpenAIServingCompletion)
from intellillm_tpu.logger import init_logger

logger = init_logger(__name__)

openai_serving_chat: OpenAIServingChat = None
openai_serving_completion: OpenAIServingCompletion = None


def _error_to_response(error: ErrorResponse) -> web.Response:
    return web.json_response(data={"error": error.model_dump()},
                             status=error.code)


async def health(request: web.Request) -> web.Response:
    return web.Response(status=200)


async def show_available_models(request: web.Request) -> web.Response:
    models = await openai_serving_chat.show_available_models()
    return web.json_response(models.model_dump())


async def _streaming_response(request: web.Request, generator,
                              request_id: str = None) -> web.StreamResponse:
    headers = {"Content-Type": "text/event-stream",
               "Cache-Control": "no-cache"}
    if request_id is not None:
        headers["X-Request-Id"] = request_id
    response = web.StreamResponse(headers=headers)
    await response.prepare(request)
    try:
        async for chunk in generator:
            await response.write(chunk.encode())
        await response.write_eof()
    except ConnectionError:
        # The client has gone. Closing the generator below is what aborts
        # its request; without it the engine decodes to max_tokens for
        # nobody.
        logger.info("client of %s disconnected", request_id)
    finally:
        await generator.aclose()
    return response


def _request_id(request: web.Request, prefix: str) -> str:
    """The request id (= distributed trace id): a validated client
    X-Request-Id wins (client-side correlation, router propagation),
    else a server-minted `{prefix}-<uuid>`. Echoed on every response."""
    from intellillm_tpu.obs import sanitize_request_id
    from intellillm_tpu.utils import random_uuid
    return (sanitize_request_id(request.headers.get("X-Request-Id"))
            or f"{prefix}-{random_uuid()}")


async def create_chat_completion(request: web.Request) -> web.StreamResponse:
    request_id = _request_id(request, "chatcmpl")
    try:
        body = ChatCompletionRequest(**await request.json())
    except Exception as e:
        return _error_to_response(
            openai_serving_chat.create_error_response(str(e)))
    generator = await openai_serving_chat.create_chat_completion(
        body, request_id=request_id)
    if isinstance(generator, ErrorResponse):
        return _error_to_response(generator)
    if body.stream:
        return await _streaming_response(request, generator, request_id)
    return web.json_response(generator.model_dump(),
                             headers={"X-Request-Id": request_id})


async def create_completion(request: web.Request) -> web.StreamResponse:
    request_id = _request_id(request, "cmpl")
    try:
        body = CompletionRequest(**await request.json())
    except Exception as e:
        return _error_to_response(
            openai_serving_completion.create_error_response(str(e)))
    generator = await openai_serving_completion.create_completion(
        body, request_id=request_id)
    if isinstance(generator, ErrorResponse):
        return _error_to_response(generator)
    if body.stream and not body.use_beam_search:
        return await _streaming_response(request, generator, request_id)
    return web.json_response(generator.model_dump(),
                             headers={"X-Request-Id": request_id})


@web.middleware
async def auth_middleware(request: web.Request, handler):
    api_key = request.app.get("api_key")
    # Exact-match the unauthenticated health endpoints: a prefix check
    # would silently exempt any future route that happens to start with
    # /health.
    if api_key is not None and request.path not in ("/health",
                                                    "/health/detail"):
        auth = request.headers.get("Authorization", "")
        if auth != f"Bearer {api_key}":
            return web.json_response({"error": "Unauthorized"}, status=401)
    return await handler(request)


async def tenant_adapter(request: web.Request) -> web.Response:
    """Tenant registration + adapter hot load/unload
    (docs/multitenancy.md) — admin surface; sits behind --api-key like
    every non-health route. Same body contract as the demo server."""
    if openai_serving_completion is None:
        return web.json_response({"error": "engine not ready"}, status=503)
    engine = openai_serving_completion.engine
    tenant_id = request.match_info["tenant_id"]
    body = await request.json()
    try:
        if body.get("unload"):
            result = await engine.unload_lora_adapter(tenant_id)
        else:
            cap = body.get("token_share_cap")
            result = await engine.load_lora_adapter(
                tenant_id,
                lora_name=body.get("lora_name") or tenant_id,
                lora_int_id=int(body.get("lora_int_id") or 0),
                lora_local_path=body.get("lora_local_path") or "",
                weight=float(body.get("weight", 1.0)),
                token_share_cap=None if cap is None else float(cap))
    except (ValueError, TypeError) as e:
        return web.json_response({"error": str(e)}, status=400)
    except KeyError as e:
        return web.json_response({"error": str(e)}, status=404)
    except RuntimeError as e:
        return web.json_response({"error": str(e)}, status=409)
    return web.json_response(result)


async def tenants_list(request: web.Request) -> web.Response:
    from intellillm_tpu.tenancy import get_tenant_registry
    return web.json_response(get_tenant_registry().snapshot())


async def start_profile(request: web.Request) -> web.Response:
    """Begin a jax.profiler trace of the serving loop (view in
    TensorBoard/xprof) — admin endpoint; protect with --api-key."""
    trace_dir = request.query.get("dir", "/tmp/intellillm-trace")
    started = openai_serving_completion.engine.engine.start_profile(
        trace_dir)
    if started is None:
        return web.json_response(
            {"error": "a trace is already running"}, status=409)
    return web.json_response({"trace_dir": started})


async def stop_profile(request: web.Request) -> web.Response:
    # stop_trace serializes the whole trace to disk — keep it off the
    # event loop so in-flight requests/streams don't stall.
    loop = asyncio.get_event_loop()
    await loop.run_in_executor(
        None, openai_serving_completion.engine.engine.stop_profile)
    return web.json_response({"ok": True})


def build_app(api_key: Optional[str] = None,
              enable_profiling: bool = False) -> web.Application:
    app = web.Application(middlewares=[auth_middleware])
    app["api_key"] = api_key
    app.router.add_get("/health", health)
    # /metrics is registered by add_debug_routes (shared with the demo
    # server).
    app.router.add_get("/v1/models", show_available_models)
    app.router.add_post("/v1/chat/completions", create_chat_completion)
    app.router.add_post("/v1/completions", create_completion)
    app.router.add_get("/tenants", tenants_list)
    app.router.add_post("/tenants/{tenant_id}/adapter", tenant_adapter)
    if enable_profiling:
        # Admin endpoints: explicit opt-in (profiling degrades serving and
        # writes trace files to a caller-chosen directory).
        app.router.add_post("/start_profile", start_profile)
        app.router.add_post("/stop_profile", stop_profile)
    add_debug_routes(
        app, lambda: (openai_serving_completion.engine.engine
                      if openai_serving_completion is not None else None),
        enable_profiling=enable_profiling)
    return app


def make_arg_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description="intellillm-tpu OpenAI-compatible API server")
    parser.add_argument("--host", type=str, default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--served-model-name", type=str, default=None)
    parser.add_argument("--api-key", type=str, default=None)
    parser.add_argument("--chat-template", type=str, default=None)
    parser.add_argument("--response-role", type=str, default="assistant")
    parser.add_argument("--enable-profiling", action="store_true",
                        help="expose the jax.profiler admin endpoints "
                        "(/debug/profiler/start|stop and the legacy "
                        "/start_profile, /stop_profile)")
    parser = AsyncEngineArgs.add_cli_args(parser)
    return parser


async def init_serving(engine: AsyncLLMEngine, served_model: str,
                       response_role: str,
                       chat_template: Optional[str]) -> None:
    global openai_serving_chat, openai_serving_completion
    openai_serving_chat = OpenAIServingChat(engine, served_model,
                                            response_role, chat_template)
    openai_serving_completion = OpenAIServingCompletion(engine, served_model)
    await openai_serving_chat._post_init()
    await openai_serving_completion._post_init()


def main():
    # The interpreter's start and this module's imports, from the
    # process's start (obs/boot.py).
    from intellillm_tpu.obs import get_boot_timeline
    get_boot_timeline().mark("imports")
    args = make_arg_parser().parse_args()
    engine_args = AsyncEngineArgs.from_cli_args(args)
    served_model = args.served_model_name or args.model

    engine = AsyncLLMEngine.from_engine_args(engine_args)

    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)
    loop.run_until_complete(
        init_serving(engine, served_model, args.response_role,
                     args.chat_template))
    app = build_app(args.api_key, enable_profiling=args.enable_profiling)
    web.run_app(app, host=args.host, port=args.port, loop=loop)


if __name__ == "__main__":
    main()
