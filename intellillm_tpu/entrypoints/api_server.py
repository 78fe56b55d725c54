"""Minimal demo HTTP server (aiohttp).

Role parity: reference `vllm/entrypoints/api_server.py` (FastAPI /generate
+ /health with StreamingResponse). FastAPI isn't available in the TPU
image; aiohttp provides the same surface.

Endpoints:
    GET  /health       → 200
    POST /generate     → {"text": [...]} or newline-delimited JSON stream
plus the shared observability surface from entrypoints/debug_routes.py
(/metrics, /health/detail, /debug/*).

A client-supplied `X-Request-Id` header (validated: ≤128 chars from a
safe alphabet, else replaced) becomes the request id — the distributed
trace id the router propagates — and is echoed on every response, so
client-side correlation with /debug/trace works end to end.
"""
from __future__ import annotations

import argparse
import json
from typing import AsyncGenerator

from aiohttp import web

from intellillm_tpu.engine.arg_utils import AsyncEngineArgs
from intellillm_tpu.engine.async_llm_engine import AsyncLLMEngine
from intellillm_tpu.entrypoints.debug_routes import add_debug_routes
from intellillm_tpu.obs import request_context, sanitize_request_id
from intellillm_tpu.sampling_params import SamplingParams
from intellillm_tpu.utils import random_uuid

TIMEOUT_KEEP_ALIVE = 5
engine: AsyncLLMEngine = None


async def health(request: web.Request) -> web.Response:
    return web.Response(status=200)


def _resolve_lora(tenant, lora_int_id):
    """Map a /generate body's tenant / adapter naming to the registered
    LoRARequest (docs/multitenancy.md). Returns (lora_request, error):
    naming an unknown tenant or unregistered adapter is a client error —
    silently serving such traffic from the base model would misattribute
    it to the default tenant's fairness share and SLO metrics."""
    if tenant is None and not lora_int_id:
        return None, None
    from intellillm_tpu.tenancy import get_tenant_registry
    registry = get_tenant_registry()
    if tenant is not None:
        spec = registry.get(tenant)
        if spec is None:
            return None, f"unknown tenant {tenant!r}"
        if lora_int_id and spec.lora_int_id != int(lora_int_id):
            return None, (f"lora_int_id {lora_int_id} does not match "
                          f"tenant {tenant!r}'s adapter "
                          f"({spec.lora_int_id})")
        return spec.lora_request, None
    lora_int_id = int(lora_int_id)
    owner = registry.get(registry.tenant_for_adapter(lora_int_id))
    if owner is None or owner.lora_int_id != lora_int_id:
        return None, (f"adapter id {lora_int_id} is not registered "
                      "(POST /tenants/{id}/adapter first)")
    return owner.lora_request, None


async def generate(request: web.Request) -> web.StreamResponse:
    """Generate completion for the request.

    Body: {"prompt": str, "stream": bool, ...SamplingParams fields}
    """
    request_dict = await request.json()
    prompt = request_dict.pop("prompt")
    prefix_pos = request_dict.pop("prefix_pos", None)
    stream = request_dict.pop("stream", False)
    tenant = request_dict.pop("tenant", None)
    lora_int_id = request_dict.pop("lora_int_id", None)
    lora_request, lora_err = _resolve_lora(tenant, lora_int_id)
    if lora_err is not None:
        return web.json_response({"error": lora_err}, status=400)
    sampling_params = SamplingParams(**request_dict)
    # Honor a validated client X-Request-Id (this is how the router
    # propagates the distributed trace id — every flight-recorder event
    # then lands under the fleet-wide id); hostile or malformed values
    # are replaced with a server-minted one. Echoed on all responses.
    request_id = (sanitize_request_id(request.headers.get("X-Request-Id"))
                  or random_uuid())

    # Bind the request id to this handler's context for the whole
    # response lifetime (not just generator creation) so log lines
    # emitted from this handler while streaming carry %(request_id)s
    # (logger.py).
    with request_context(request_id):
        results_generator = engine.generate(prompt, sampling_params,
                                            request_id,
                                            lora_request=lora_request,
                                            prefix_pos=prefix_pos)

        if stream:
            response = web.StreamResponse(
                headers={"Content-Type": "application/x-ndjson",
                         "X-Request-Id": request_id})
            await response.prepare(request)
            async for request_output in results_generator:
                text_outputs = [
                    request_output.prompt + output.text
                    for output in request_output.outputs
                ]
                await response.write(
                    (json.dumps({"text": text_outputs}) + "\n").encode())
            await response.write_eof()
            return response

        final_output = None
        async for request_output in results_generator:
            if (request.transport is not None
                    and request.transport.is_closing()):
                await engine.abort(request_id)
                return web.Response(status=499,
                                    headers={"X-Request-Id": request_id})
            final_output = request_output

        assert final_output is not None
        text_outputs = [
            final_output.prompt + output.text
            for output in final_output.outputs
        ]
        return web.json_response({"text": text_outputs},
                                 headers={"X-Request-Id": request_id})


async def kv_export(request: web.Request) -> web.Response:
    """Export the KV prefix prefilled for a prompt (disaggregated
    serving; docs/routing.md "Disaggregated roles").

    Body: {"prompt": str} → opaque octet-stream payload
    (worker/kv_transfer.py wire format)."""
    body = await request.json()
    prompt = body.get("prompt")
    if not isinstance(prompt, str):
        return web.json_response({"error": "missing prompt"}, status=400)
    try:
        payload = await engine.export_kv(prompt)
    except KeyError as e:
        # Prefix not computed on this replica (yet): the router treats
        # this as a soft miss and falls back to local prefill.
        return web.json_response({"error": str(e)}, status=404)
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    return web.Response(body=payload,
                        content_type="application/octet-stream")


async def kv_import(request: web.Request) -> web.Response:
    """Install an exported KV payload as a computed prefix.

    Body: raw octet-stream payload → {"key", "imported", "num_blocks",
    "prefix_pos"}."""
    payload = await request.read()
    try:
        result = await engine.import_kv(payload)
    except ValueError as e:
        # Bad magic / geometry mismatch / key-token mismatch.
        return web.json_response({"error": str(e)}, status=400)
    except RuntimeError as e:
        # Would breach the allocation watermark — back-pressure, not a
        # client error.
        return web.json_response({"error": str(e)}, status=409)
    # JSON cannot carry the 64-bit key losslessly in all clients;
    # stringify it (the router treats it as opaque).
    result = dict(result)
    result["key"] = f"{result['key']:#018x}"
    return web.json_response(result)


async def tenant_adapter(request: web.Request) -> web.Response:
    """Tenant registration + adapter hot load/unload
    (docs/multitenancy.md).

    Body: {"lora_name", "lora_int_id", "lora_local_path",
           "weight"?, "token_share_cap"?}  — register/load
          {"unload": true}                 — unregister/unload"""
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


def build_app(enable_profiling: bool = False) -> web.Application:
    app = web.Application(client_max_size=1024**3)
    app.router.add_get("/health", health)
    app.router.add_post("/generate", generate)
    app.router.add_post("/kv/export", kv_export)
    app.router.add_post("/kv/import", kv_import)
    app.router.add_get("/tenants", tenants_list)
    app.router.add_post("/tenants/{tenant_id}/adapter", tenant_adapter)
    # This server has no auth middleware, so the profiler admin routes
    # (which degrade serving and write traces to a caller-chosen dir)
    # stay off unless explicitly opted in.
    add_debug_routes(app, lambda: engine.engine if engine else None,
                     enable_profiling=enable_profiling)
    return app


def main():
    global engine
    parser = argparse.ArgumentParser()
    parser.add_argument("--host", type=str, default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--enable-profiling", action="store_true",
                        help="expose the jax.profiler admin endpoints "
                        "(/debug/profiler/start|stop)")
    parser = AsyncEngineArgs.add_cli_args(parser)
    args = parser.parse_args()

    engine_args = AsyncEngineArgs.from_cli_args(args)
    engine = AsyncLLMEngine.from_engine_args(engine_args)

    web.run_app(build_app(enable_profiling=args.enable_profiling),
                host=args.host, port=args.port,
                keepalive_timeout=TIMEOUT_KEEP_ALIVE)


if __name__ == "__main__":
    main()
