"""Simple /generate server integration test over real HTTP.

Role parity: reference `tests/async_engine/test_api_server.py` — boot
the plain API server as a subprocess and drive /generate (sync and
streaming) plus abort-on-disconnect behavior at the HTTP level.
"""
import json
import os
import signal
import subprocess
import sys
import time

import pytest
import requests

PORT = 8733
BASE = f"http://127.0.0.1:{PORT}"


@pytest.fixture(scope="module")
def api_server(tmp_path_factory):
    import torch
    from tests.conftest import _build_word_tokenizer
    from transformers import OPTConfig, OPTForCausalLM

    d = str(tmp_path_factory.mktemp("srv-opt-simple"))
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
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "intellillm_tpu.entrypoints.api_server",
         "--model", d, "--dtype", "float32", "--max-model-len", "128",
         "--num-device-blocks-override", "128", "--port", str(PORT)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                out = proc.stdout.read().decode()
                raise RuntimeError(f"server died:\n{out[-3000:]}")
            try:
                requests.post(BASE + "/generate",
                              json={"prompt": "hello", "max_tokens": 1},
                              timeout=2)
                break
            except requests.exceptions.RequestException:
                time.sleep(1.0)
        else:
            raise TimeoutError("server did not come up")
        yield d
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()


def test_generate(api_server):
    r = requests.post(BASE + "/generate",
                      json={"prompt": "hello my name is",
                            "max_tokens": 8, "temperature": 0.0})
    assert r.status_code == 200
    body = r.json()
    assert len(body["text"]) == 1
    assert body["text"][0].startswith("hello my name is")


def test_generate_n(api_server):
    r = requests.post(BASE + "/generate",
                      json={"prompt": "the capital of france is",
                            "n": 2, "max_tokens": 8,
                            "temperature": 0.8, "top_p": 0.9})
    assert r.status_code == 200
    assert len(r.json()["text"]) == 2


def test_generate_stream(api_server):
    r = requests.post(BASE + "/generate",
                      json={"prompt": "hello my name is",
                            "max_tokens": 8, "temperature": 0.0,
                            "stream": True}, stream=True)
    assert r.status_code == 200
    chunks = [json.loads(line) for line in
              r.iter_lines(decode_unicode=True) if line]
    assert len(chunks) >= 2                     # streamed incrementally
    # Each chunk carries the text so far; it only grows.
    texts = [c["text"][0] for c in chunks]
    for a, b in zip(texts, texts[1:]):
        assert b.startswith(a[:len(a) - 8] if len(a) > 8 else a[:1])


def test_demo_server_serves_metrics(api_server):
    """The demo server gets /metrics via the shared debug_routes handler
    (it used to have no scrape endpoint at all) — including the device
    telemetry series."""
    r = requests.get(BASE + "/metrics")
    assert r.status_code == 200
    body = r.text
    assert "intellillm_" in body
    assert "intellillm_device_hbm_bytes_in_use" in body
    assert "intellillm_device_hbm_bytes_limit" in body
    assert "intellillm_device_hbm_peak_bytes" in body
    assert "intellillm_hbm_ledger_bytes" in body
    # The direction children are pre-created, so the series exist at 0
    # before any swap happens.
    assert 'intellillm_swap_bytes_total{direction="in"}' in body
    assert 'intellillm_swap_bytes_total{direction="out"}' in body


def test_health_detail_device_telemetry_block(api_server):
    """On the CPU backend /health/detail must still carry a
    device_telemetry block: per-device entries (null byte fields) and a
    non-empty ledger with params + kv components."""
    r = requests.get(BASE + "/health/detail")
    assert r.status_code == 200
    dt = r.json()["device_telemetry"]
    assert dt["enabled"] is True
    assert dt["devices"], dt
    for entry in dt["devices"].values():
        assert set(entry) == {"bytes_in_use", "bytes_limit", "peak_bytes"}
    ledger = dt["ledger_bytes"]
    assert ledger["params"] > 0
    assert ledger["kv_pool"] > 0
    assert "cpu_swap_pool" in ledger
    assert set(dt["swap_bytes_total"]) == {"in", "out", "copy"}


def test_top_renders_one_frame(api_server):
    """`python -m intellillm_tpu.tools.top --once` against the live
    server must render a frame without error (acceptance criterion)."""
    from intellillm_tpu.tools import top

    frame = top.run_once(BASE)
    assert "intellillm-top" in frame
    assert "Devices (HBM):" in frame
    assert "Memory ledger" in frame
    assert "params" in frame and "kv_pool" in frame
    assert "UNREACHABLE" not in frame
    # The ALERTS panel renders from /debug/alerts ("all clear" when no
    # rule is pending/firing; the rule table when one is).
    assert "Alerts:" in frame

    # The module entry point end-to-end (imports the heavy package, so
    # give it a generous timeout on cold CPU).
    out = subprocess.run(
        [sys.executable, "-m", "intellillm_tpu.tools.top", "--once",
         "--url", BASE],
        capture_output=True, timeout=180, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "intellillm-top" in out.stdout
    assert "Queues:" in out.stdout


def test_x_request_id_honored_and_echoed(api_server):
    """A valid client X-Request-Id becomes the engine request id (the
    distributed trace id): echoed on the response and queryable in the
    flight recorder under the SAME id."""
    rid = "trace-e2e-0042"
    r = requests.post(BASE + "/generate",
                      json={"prompt": "hello my name is",
                            "max_tokens": 4, "temperature": 0.0},
                      headers={"X-Request-Id": rid})
    assert r.status_code == 200
    assert r.headers["X-Request-Id"] == rid
    tr = requests.get(BASE + "/debug/trace", params={"request_id": rid})
    assert tr.status_code == 200
    events = tr.json()["events"]
    assert [e["event"] for e in events][-1] == "finished"
    assert all(e["hop"] == "engine" for e in events)


def test_x_request_id_echoed_on_stream(api_server):
    rid = "trace-stream-1"
    r = requests.post(BASE + "/generate",
                      json={"prompt": "hello", "max_tokens": 2,
                            "temperature": 0.0, "stream": True},
                      headers={"X-Request-Id": rid}, stream=True)
    assert r.status_code == 200
    assert r.headers["X-Request-Id"] == rid
    for _ in r.iter_lines():
        pass


def test_invalid_x_request_id_replaced(api_server):
    """Hostile/invalid ids (bad charset) are rejected and replaced with
    a minted uuid — still echoed so the client learns the real id."""
    r = requests.post(BASE + "/generate",
                      json={"prompt": "hello", "max_tokens": 2,
                            "temperature": 0.0},
                      headers={"X-Request-Id": "bad id/../{}"})
    assert r.status_code == 200
    echoed = r.headers["X-Request-Id"]
    assert echoed and echoed != "bad id/../{}"


def test_client_disconnect_aborts(api_server):
    """Closing the HTTP connection mid-stream must abort the request
    server-side (failure-detection parity: abort-on-disconnect), leaving
    the server healthy for subsequent requests."""
    r = requests.post(BASE + "/generate",
                      json={"prompt": "the cat runs fast and the dog",
                            "max_tokens": 64, "temperature": 0.0,
                            "stream": True}, stream=True)
    it = r.iter_lines(decode_unicode=True)
    next(it)                                   # first chunk arrived
    r.close()                                  # drop the connection
    time.sleep(1.0)
    r2 = requests.post(BASE + "/generate",
                       json={"prompt": "hello my name is",
                             "max_tokens": 4, "temperature": 0.0})
    assert r2.status_code == 200


def test_tenant_registration_and_attribution(api_server):
    """Tenancy HTTP surface on a base-model engine
    (docs/multitenancy.md): register a base-model tenant (no adapter),
    serve under its name, read its per-tenant stats from
    /health/detail, and unregister."""
    r = requests.post(BASE + "/tenants/acme/adapter",
                      json={"weight": 2.0, "token_share_cap": 0.5})
    assert r.status_code == 200, r.text
    body = r.json()
    assert body["tenant"] == "acme"
    assert body["lora_int_id"] == 0 and body["active"] is False
    try:
        listed = requests.get(BASE + "/tenants").json()["tenants"]
        assert [t["tenant_id"] for t in listed] == ["acme"]
        assert listed[0]["weight"] == 2.0

        r = requests.post(BASE + "/generate",
                          json={"prompt": "hello my name is",
                                "max_tokens": 4, "temperature": 0.0,
                                "tenant": "acme"})
        assert r.status_code == 200

        tenants = requests.get(
            BASE + "/health/detail").json().get("tenants")
        assert tenants is not None
        assert [t["tenant_id"] for t in tenants["tenants"]] == ["acme"]
        assert tenants["active_adapters"] == []
        # The engine finish hook attributed the request: base-model
        # tenants resolve through adapter id 0 → `default` (the tenant
        # field names the SLO owner for admission, attribution is by
        # adapter), so the stats block exists and counted one finish.
        stats = tenants["stats"]
        assert sum(v["finished"] for v in stats.values()) >= 1
    finally:
        r = requests.post(BASE + "/tenants/acme/adapter",
                          json={"unload": True})
        assert r.status_code == 200, r.text
        assert r.json()["unloaded"] is True
    assert requests.get(BASE + "/tenants").json()["tenants"] == []


def test_tenant_error_mapping(api_server):
    """Client errors map to conventional statuses: unknown tenant in
    /generate → 400, adapter load on a LoRA-disabled engine → 409,
    unloading an unknown tenant → 404, bad fairness knobs → 400."""
    r = requests.post(BASE + "/generate",
                      json={"prompt": "hello", "max_tokens": 2,
                            "tenant": "ghost"})
    assert r.status_code == 400
    assert "unknown tenant" in r.json()["error"]

    r = requests.post(BASE + "/generate",
                      json={"prompt": "hello", "max_tokens": 2,
                            "lora_int_id": 9})
    assert r.status_code == 400
    assert "not registered" in r.json()["error"]

    r = requests.post(BASE + "/tenants/acme/adapter",
                      json={"lora_name": "x", "lora_int_id": 1,
                            "lora_local_path": "/nonexistent"})
    assert r.status_code == 409
    assert "LoRA" in r.json()["error"]

    r = requests.post(BASE + "/tenants/ghost/adapter",
                      json={"unload": True})
    assert r.status_code == 404

    r = requests.post(BASE + "/tenants/acme/adapter",
                      json={"token_share_cap": 1.5})
    assert r.status_code == 400
