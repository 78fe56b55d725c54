"""Unit tests for obs/device_telemetry.py: the poller's degradation
contract (None/raising memory_stats — the CPU tier-1 backend), headroom
derivation and the one-shot low-HBM warning episode, the memory-ledger
math against a fake sharded param tree + CacheEngine sizing, and the
swap-byte accounting."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import intellillm_tpu.obs.device_telemetry as dt_mod
from intellillm_tpu.obs.device_telemetry import DeviceTelemetry


class _FakeDev:
    def __init__(self, platform, dev_id, stats):
        self.platform = platform
        self.id = dev_id
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def _telemetry(**kw):
    kw.setdefault("enabled", True)
    kw.setdefault("poll_s", 60.0)
    kw.setdefault("headroom_warn", 0.05)
    return DeviceTelemetry(**kw)


def test_poll_samples_every_device_and_derives_min_headroom(monkeypatch):
    devs = [
        _FakeDev("tpu", 0, {"bytes_in_use": 600, "bytes_limit": 1000,
                            "peak_bytes_in_use": 800}),
        _FakeDev("tpu", 1, {"bytes_in_use": 900, "bytes_limit": 1000,
                            "peak_bytes_in_use": 950}),
    ]
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    t = _telemetry()
    sample = t.poll_once()
    assert sample["tpu:0"] == {"bytes_in_use": 600, "bytes_limit": 1000,
                               "peak_bytes": 800}
    assert sample["tpu:1"]["peak_bytes"] == 950
    # min over devices: tpu:1 is the constrained one.
    assert t.headroom_ratio() == pytest.approx(0.1)
    snap = t.snapshot()
    assert snap["headroom_ratio"] == pytest.approx(0.1)
    assert snap["last_poll_age_s"] is not None


def test_poll_degrades_on_none_and_raising_memory_stats(monkeypatch):
    devs = [_FakeDev("cpu", 0, None),
            _FakeDev("cpu", 1, RuntimeError("not supported"))]
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    t = _telemetry()
    sample = t.poll_once()
    assert set(sample) == {"cpu:0", "cpu:1"}
    for entry in sample.values():
        assert entry == {"bytes_in_use": None, "bytes_limit": None,
                         "peak_bytes": None}
    assert t.headroom_ratio() is None
    assert t.snapshot()["low_hbm"] is False
    if t._metrics is not None:
        # The exported gauge must be NaN, not a 0.0 that would read as
        # "out of HBM" and trip low-headroom alert rules.
        import math
        assert math.isnan(t._metrics.gauge_headroom._value.get())


def test_poll_survives_real_cpu_backend():
    """On the tier-1 CPU backend memory_stats() returns None — the poller
    must still emit one entry per device and never raise."""
    t = _telemetry()
    sample = t.poll_once()
    assert len(sample) == len(jax.local_devices())
    for label, entry in sample.items():
        assert label.startswith("cpu:")
        assert set(entry) == {"bytes_in_use", "bytes_limit", "peak_bytes"}


def test_low_hbm_warning_is_one_shot_per_episode(monkeypatch):
    low = {"bytes_in_use": 990, "bytes_limit": 1000,
           "peak_bytes_in_use": 990}
    high = {"bytes_in_use": 100, "bytes_limit": 1000,
            "peak_bytes_in_use": 990}
    dev = _FakeDev("tpu", 0, low)
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    warnings = []
    monkeypatch.setattr(
        dt_mod.logger, "warning",
        lambda msg, *args: warnings.append(msg % args))
    t = _telemetry(headroom_warn=0.05)
    t.set_ledger({"params": 500, "kv_pool": 400}, log_table=False)

    t.poll_once()
    t.poll_once()  # still low: must NOT fire again
    assert len(warnings) == 1
    assert "LOW HBM HEADROOM" in warnings[0]
    assert t.snapshot()["low_hbm"] is True
    assert t.snapshot()["low_hbm_warnings"] == 1

    dev._stats = high
    t.poll_once()  # recovery clears the episode
    assert t.snapshot()["low_hbm"] is False

    dev._stats = low
    t.poll_once()  # new episode: fires once more
    assert len(warnings) == 2
    assert t.snapshot()["low_hbm_warnings"] == 2


def test_residual_other_component_from_live_sample(monkeypatch):
    dev = _FakeDev("tpu", 0, {"bytes_in_use": 1000, "bytes_limit": 4000,
                              "peak_bytes_in_use": 1000})
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    t = _telemetry()
    t.set_ledger({"params": 500, "kv_pool": 300, "cpu_swap_pool": 999},
                 log_table=False)
    t.poll_once()
    # other = in_use - (params + kv_pool); the host pool is not on-device.
    assert t.ledger()["other"] == 200

    dev._stats = {"bytes_in_use": 100, "bytes_limit": 4000,
                  "peak_bytes_in_use": 1000}
    t.poll_once()
    assert t.ledger()["other"] == 0  # clamped, never negative


def test_ledger_math_against_fake_param_tree_and_cache_sizing():
    """worker.memory_ledger(): params from the (shard-aware) param tree,
    kv_pool from CacheEngine physical block bytes x block count, swap
    pool from logical bytes x cpu block count."""
    from intellillm_tpu.parallel.mesh import param_shard_bytes
    from intellillm_tpu.worker.cache_engine import CacheEngine
    from intellillm_tpu.worker.worker import Worker

    params = {"wte": jnp.zeros((64, 32), jnp.float32),
              "layers": [{"w": jnp.zeros((32, 32), jnp.float32)},
                         {"w": jnp.zeros((32, 32), jnp.float32)}]}
    expected_params = (64 * 32 + 2 * 32 * 32) * 4
    assert param_shard_bytes(params) == expected_params

    model_config = SimpleNamespace(
        dtype="float32",
        get_cache_row_shape=lambda: (2, 4, 16),
        get_num_layers=lambda: 2,
        get_num_kv_layers=lambda: 2,
        get_num_window_layers=lambda: 0,
        get_window_group_share=lambda ahead: 0.0,
        get_state_arrays=lambda: ())
    w = Worker.__new__(Worker)
    w.params = params
    w.model_config = model_config
    w.parallel_config = SimpleNamespace(tensor_parallel_size=1)
    w.cache_config = SimpleNamespace(block_size=8, cache_dtype="auto",
                                     num_device_blocks=10, num_cpu_blocks=3)
    w.cache_engine = object()  # ledger only checks it exists

    ledger = w.memory_ledger()
    physical = CacheEngine.get_cache_block_size(
        8, "auto", model_config, w.parallel_config)
    logical = CacheEngine.get_logical_cache_block_size(
        8, "auto", model_config)
    assert ledger["params"] == expected_params
    assert ledger["kv_pool"] == physical * 10
    assert ledger["cpu_swap_pool"] == logical * 3
    # head_size 16 pads to the 128-lane tile on device: physical > logical.
    assert physical > logical


def test_swap_accounting_totals():
    t = _telemetry()
    t.record_swap("out", 4, 100)
    t.record_swap("out", 1, 100)
    t.record_swap("in", 2, 100)
    t.record_swap("copy", 3, 700)
    t.record_swap("in", 0, 100)  # zero blocks: no-op
    assert t.swap_bytes_total() == {"in": 200, "out": 500, "copy": 2100}
    assert t.snapshot()["swap_bytes_total"]["copy"] == 2100


def test_disabled_telemetry_is_inert(monkeypatch):
    monkeypatch.setenv("INTELLILLM_DEVICE_TELEMETRY", "0")
    t = DeviceTelemetry()  # enabled resolved from env
    assert t.enabled is False
    assert t.poll_once() == {}
    t.record_swap("in", 5, 100)
    assert t.swap_bytes_total() == {"in": 0, "out": 0, "copy": 0}
    t.set_ledger({"params": 1})
    assert t.ledger() == {}
    t.attach()  # must not start a poller thread
    assert t._poller is None
    assert t.snapshot()["enabled"] is False


def test_configure_and_env_defaults(monkeypatch):
    monkeypatch.setenv("INTELLILLM_DEVICE_POLL_S", "2.5")
    monkeypatch.setenv("INTELLILLM_HBM_HEADROOM_WARN", "0.2")
    t = DeviceTelemetry(enabled=True)
    assert t.poll_s == 2.5
    assert t.headroom_warn == 0.2
    t.configure(poll_s=7.0, headroom_warn=0.1)
    assert t.poll_s == 7.0
    assert t.headroom_warn == 0.1
    monkeypatch.setenv("INTELLILLM_DEVICE_POLL_S", "bogus")
    assert DeviceTelemetry(enabled=True).poll_s == 10.0  # fallback


def test_global_accessor_and_reset():
    t = dt_mod.get_device_telemetry()
    assert dt_mod.get_device_telemetry() is t
    t.record_swap("in", 1, 8)
    t.reset_for_testing()
    assert t.swap_bytes_total() == {"in": 0, "out": 0, "copy": 0}
    assert t._poller is None
