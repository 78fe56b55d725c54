"""OpenAI-compatible server integration tests over real HTTP.

Reference: `tests/entrypoints/test_openai_server.py` (254 LoC — boots the
server and drives it with a client) and
`tests/async_engine/test_api_server.py`. The server runs as a subprocess
(inheriting the CPU-forcing env from conftest) against a tiny local
checkpoint; requests go through aiohttp.
"""
import asyncio
import json
import os
import signal
import subprocess
import sys
import time

import aiohttp
import pytest

PORT = 8731
BASE = f"http://127.0.0.1:{PORT}"


@pytest.fixture(scope="module")
def openai_server(tmp_path_factory):
    # Build the tiny checkpoint in-process (module-scoped tmp dir).
    import torch
    from transformers import OPTConfig, OPTForCausalLM
    from tests.conftest import _build_word_tokenizer

    d = str(tmp_path_factory.mktemp("srv-opt"))
    _, vocab_size = _build_word_tokenizer(d)
    torch.manual_seed(0)
    OPTForCausalLM(OPTConfig(
        vocab_size=vocab_size, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, ffn_dim=128, max_position_embeddings=128,
        do_layer_norm_before=True, pad_token_id=0, eos_token_id=1,
        bos_token_id=1, word_embed_proj_dim=64,
        torch_dtype=torch.float32)).eval().save_pretrained(
            d, safe_serialization=True)

    env = dict(os.environ)
    # Plain JAX_PLATFORMS is not honored when a site customization
    # pre-registers a TPU plugin; the server applies this override via
    # jax.config before backend init.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "intellillm_tpu.entrypoints.openai.api_server",
         "--model", d, "--dtype", "float32", "--max-model-len", "128",
         "--num-device-blocks-override", "128", "--port", str(PORT),
         "--served-model-name", "tiny-opt", "--enable-profiling",
         "--chat-template", "{% for m in messages %}{{ m['content'] }} "
         "{% endfor %}"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                out = proc.stdout.read().decode()
                raise RuntimeError(f"server died:\n{out[-3000:]}")
            try:
                import urllib.request
                urllib.request.urlopen(BASE + "/health", timeout=1)
                break
            except Exception:
                time.sleep(1.0)
        else:
            raise TimeoutError("server did not become healthy")
        yield d
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()


async def _post(path, payload):
    async with aiohttp.ClientSession() as s:
        async with s.post(BASE + path, json=payload) as resp:
            return resp.status, await resp.json()


async def _get(path):
    async with aiohttp.ClientSession() as s:
        async with s.get(BASE + path) as resp:
            return resp.status, await resp.json()


def test_models_endpoint(openai_server):
    status, body = asyncio.run(_get("/v1/models"))
    assert status == 200
    assert body["data"][0]["id"] == "tiny-opt"


def test_completion(openai_server):
    status, body = asyncio.run(_post("/v1/completions", {
        "model": "tiny-opt",
        "prompt": "hello my name is",
        "max_tokens": 8,
        "temperature": 0.0,
    }))
    assert status == 200
    assert body["object"] == "text_completion"
    assert len(body["choices"]) == 1
    assert body["choices"][0]["finish_reason"] in ("length", "stop")
    assert body["usage"]["completion_tokens"] >= 1


def test_completion_streaming(openai_server):
    async def run():
        chunks = []
        async with aiohttp.ClientSession() as s:
            async with s.post(BASE + "/v1/completions", json={
                "model": "tiny-opt",
                "prompt": "the capital of france is",
                "max_tokens": 8,
                "temperature": 0.0,
                "stream": True,
            }) as resp:
                assert resp.status == 200
                async for raw in resp.content:
                    line = raw.decode().strip()
                    if not line.startswith("data:"):
                        continue
                    data = line[len("data:"):].strip()
                    if data == "[DONE]":
                        break
                    chunks.append(json.loads(data))
        return chunks

    chunks = asyncio.run(run())
    assert chunks, "no SSE chunks received"
    text = "".join(c["choices"][0]["text"] for c in chunks)
    assert isinstance(text, str)
    assert chunks[-1]["choices"][0]["finish_reason"] in ("length", "stop")


def test_streaming_matches_nonstreaming(openai_server):
    payload = {"model": "tiny-opt", "prompt": "the cat runs fast",
               "max_tokens": 8, "temperature": 0.0}
    _, body = asyncio.run(_post("/v1/completions", payload))
    full = body["choices"][0]["text"]

    async def run():
        parts = []
        async with aiohttp.ClientSession() as s:
            async with s.post(BASE + "/v1/completions",
                              json={**payload, "stream": True}) as resp:
                async for raw in resp.content:
                    line = raw.decode().strip()
                    if not line.startswith("data:"):
                        continue
                    data = line[len("data:"):].strip()
                    if data == "[DONE]":
                        break
                    parts.append(
                        json.loads(data)["choices"][0]["text"])
        return "".join(parts)

    assert asyncio.run(run()) == full


def test_chat_completion(openai_server):
    status, body = asyncio.run(_post("/v1/chat/completions", {
        "model": "tiny-opt",
        "messages": [{"role": "user", "content": "hello my name is"}],
        "max_tokens": 8,
        "temperature": 0.0,
    }))
    assert status == 200
    assert body["object"] == "chat.completion"
    assert body["choices"][0]["message"]["role"] == "assistant"


def test_x_request_id_honored_and_echoed(openai_server):
    """A valid X-Request-Id becomes the completion id (the distributed
    trace id), is echoed on the response, and the engine's flight
    recorder holds the trace under the derived per-prompt id."""
    rid = "trace-openai-7"

    async def run():
        async with aiohttp.ClientSession() as s:
            async with s.post(BASE + "/v1/completions", json={
                "model": "tiny-opt", "prompt": "hello my name is",
                "max_tokens": 4, "temperature": 0.0,
            }, headers={"X-Request-Id": rid}) as resp:
                assert resp.status == 200
                assert resp.headers["X-Request-Id"] == rid
                body = await resp.json()
            assert body["id"] == rid
            # Completions fan out per prompt as `<id>-<i>`.
            async with s.get(BASE + "/debug/trace",
                             params={"request_id": f"{rid}-0"}) as resp:
                assert resp.status == 200
                trace = await resp.json()
            assert [e["event"] for e in trace["events"]][-1] == "finished"
            # An invalid id is replaced by a minted cmpl- uuid.
            async with s.post(BASE + "/v1/completions", json={
                "model": "tiny-opt", "prompt": "hello",
                "max_tokens": 2, "temperature": 0.0,
            }, headers={"X-Request-Id": "bad id{}"}) as resp:
                assert resp.status == 200
                assert resp.headers["X-Request-Id"].startswith("cmpl-")
            # Chat echoes too.
            async with s.post(BASE + "/v1/chat/completions", json={
                "model": "tiny-opt",
                "messages": [{"role": "user", "content": "hello"}],
                "max_tokens": 2, "temperature": 0.0,
            }, headers={"X-Request-Id": "chat-trace-1"}) as resp:
                assert resp.status == 200
                assert resp.headers["X-Request-Id"] == "chat-trace-1"
                assert (await resp.json())["id"] == "chat-trace-1"

    asyncio.run(run())


def test_bad_request_returns_error(openai_server):
    status, body = asyncio.run(_post("/v1/completions", {
        "model": "tiny-opt",
        "prompt": "hello",
        "max_tokens": 8,
        "temperature": -1.0,       # invalid
    }))
    assert status >= 400
    assert "error" in body or body.get("object") == "error"


def test_profile_endpoints(openai_server, tmp_path):
    """/start_profile + /stop_profile wrap the serving loop in a
    jax.profiler trace (SURVEY §5 tracing hook)."""
    trace_dir = str(tmp_path / "trace")

    async def run():
        async with aiohttp.ClientSession() as s:
            async with s.post(BASE + f"/start_profile?dir={trace_dir}") as r:
                assert r.status == 200
            async with s.post(BASE + "/v1/completions", json={
                "model": "tiny-opt", "prompt": "hello",
                "max_tokens": 4, "temperature": 0.0}) as r:
                assert r.status == 200
            async with s.post(BASE + "/stop_profile") as r:
                assert r.status == 200

    asyncio.run(run())
    # A real trace was produced (server shares the test filesystem).
    import glob
    assert glob.glob(trace_dir + "/**/*", recursive=True), (
        "no trace files written")


def test_client_disconnect_aborts_request(openai_server):
    """Dropping a streaming connection must abort the request server-side
    (reference async_llm_engine abort-on-disconnect); the server must keep
    serving afterwards."""
    async def run():
        async with aiohttp.ClientSession() as s:
            resp = await s.post(BASE + "/v1/completions", json={
                "model": "tiny-opt", "prompt": "hello my name is",
                "max_tokens": 100, "temperature": 1.0,
                "ignore_eos": True, "stream": True})
            assert resp.status == 200
            # Read one chunk then hard-drop the connection.
            await resp.content.readany()
            resp.close()
        await asyncio.sleep(1.0)
        # Server still alive and serving.
        async with aiohttp.ClientSession() as s:
            async with s.post(BASE + "/v1/completions", json={
                "model": "tiny-opt", "prompt": "hello",
                "max_tokens": 4, "temperature": 0.0}) as resp:
                assert resp.status == 200
                return await resp.json()

    body = asyncio.run(run())
    assert body["choices"][0]["text"] is not None


def test_dropped_stream_closes_its_generator():
    """`_streaming_response` alone, no engine, behind a runner configured
    as `web.run_app` configures the real one (a handler is NOT cancelled
    when its connection drops; aiohttp's test server would cancel it): the
    next write fails, and the handler closes the generator it was fed from
    (that close is what aborts the request in the engine) and ends quietly.
    A stream nobody reads must not run to its end."""
    from aiohttp import web
    from intellillm_tpu.entrypoints.openai.api_server import (
        _streaming_response)

    seen = {"chunks": 0, "closed": False}

    async def endless():
        try:
            while True:
                seen["chunks"] += 1
                yield "data: " + "x" * 4096 + "\n\n"
                await asyncio.sleep(0.005)
        finally:
            seen["closed"] = True

    async def handler(request):
        # Held here as the engine's producer tasks hold the real one: no
        # garbage collection closes it for the handler.
        seen["generator"] = endless()
        return await _streaming_response(request, seen["generator"],
                                         "cmpl-test")

    async def run():
        app = web.Application()
        app.router.add_post("/v1/completions", handler)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        try:
            async with aiohttp.ClientSession() as s:
                resp = await s.post(
                    f"http://127.0.0.1:{port}/v1/completions")
                assert resp.headers["X-Request-Id"] == "cmpl-test"
                await resp.content.readany()
                resp.close()
            for _ in range(200):
                if seen["closed"]:
                    break
                await asyncio.sleep(0.025)
            # Read here: asyncio.run() closes what is left open as it ends.
            return seen["chunks"], seen["closed"]
        finally:
            await runner.cleanup()

    chunks, closed = asyncio.run(run())
    assert closed, f"still streaming after {chunks} chunks"
