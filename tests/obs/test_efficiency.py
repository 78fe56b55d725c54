"""Unit coverage for obs/efficiency.py: the analytic FLOPs model, the
peak-FLOPs resolution order (explicit > env > per-chip table > None),
warm-up exclusion, and the rolling-MFU NaN/finite transitions."""
import math

import pytest

from intellillm_tpu.obs import efficiency as eff_mod
from intellillm_tpu.obs.efficiency import (EfficiencyTracker,
                                           analytic_flops_per_token,
                                           resolve_peak_flops)


class _FakeHF:
    def __init__(self, **kwargs):
        self.__dict__.update(kwargs)


class _FakeModelConfig:
    """Just the ModelConfig surface analytic_flops_per_token touches."""

    def __init__(self, hidden=64, layers=2, heads=4, kv_heads=4,
                 head_size=16, vocab=100, **hf_kwargs):
        self._h, self._l, self._heads = hidden, layers, heads
        self._kv, self._hs, self._v = kv_heads, head_size, vocab
        self.hf_config = _FakeHF(**hf_kwargs)

    def get_hidden_size(self):
        return self._h

    def get_num_layers(self):
        return self._l

    def get_num_attention_heads(self):
        return self._heads

    def get_total_num_kv_heads(self):
        return self._kv

    def get_head_size(self):
        return self._hs

    def get_vocab_size(self):
        return self._v


@pytest.fixture
def tracker(monkeypatch):
    monkeypatch.delenv("INTELLILLM_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("INTELLILLM_MFU_WINDOW", raising=False)
    monkeypatch.delenv("INTELLILLM_EFFICIENCY", raising=False)
    return EfficiencyTracker(enabled=True)


def test_analytic_flops_per_token_ungated():
    # h=64, layers=2, kv_dim=64, ffn_dim=128, vocab=100, relu MLP (2
    # mats): 2 * (2*(2*64*64 + 2*64*64 + 2*64*128) + 64*100) = 143872.
    cfg = _FakeModelConfig(ffn_dim=128, activation_function="relu")
    assert analytic_flops_per_token(cfg) == pytest.approx(143872.0)


def test_analytic_flops_per_token_latent_attention_by_its_mechanism():
    """A config with `kv_lora_rank` + `qk_rope_head_dim` is reckoned from
    its low-rank projections (absorbed form), not from kv heads x head
    size, which say nothing about what such a layer multiplies."""
    cfg = _FakeModelConfig(hidden=64, layers=1, heads=4, vocab=100,
                           intermediate_size=128, hidden_act="silu",
                           q_lora_rank=24, kv_lora_rank=32,
                           qk_nope_head_dim=16, qk_rope_head_dim=8,
                           v_head_dim=16)
    attn = (64 * 24 + 24 * 4 * (16 + 8)      # x -> c_q -> q
            + 64 * (32 + 8)                  # x -> [c_kv | k_rope]
            + 4 * 32 * (16 + 16)             # q_nope W_UK, attended W_UV
            + 4 * 16 * 64)                   # o
    assert analytic_flops_per_token(cfg) == pytest.approx(
        2.0 * (attn + 3 * 64 * 128 + 64 * 100))


def test_analytic_flops_per_token_gated_mlp_counts_third_matrix():
    base = _FakeModelConfig(intermediate_size=128, hidden_act="gelu")
    gated = _FakeModelConfig(intermediate_size=128, hidden_act="silu")
    # SwiGLU carries one extra h x inter matmul per layer:
    # delta = 2 * layers * h * inter = 2 * 2 * 64 * 128 = 32768.
    assert (analytic_flops_per_token(gated)
            - analytic_flops_per_token(base)) == pytest.approx(32768.0)


def test_analytic_flops_defaults_inter_to_4h():
    cfg = _FakeModelConfig()  # no intermediate_size/ffn_dim on hf_config
    # inter = 4 * 64 = 256, relu-style: 2*(2*(16384 + 2*64*256) + 6400)
    assert analytic_flops_per_token(cfg) == pytest.approx(209408.0)


def test_analytic_flops_none_on_broken_config():
    class Broken:
        hf_config = None

        def get_hidden_size(self):
            raise RuntimeError("no dims")

    assert analytic_flops_per_token(Broken()) is None


def test_resolve_peak_flops_table_and_env(monkeypatch):
    monkeypatch.delenv("INTELLILLM_PEAK_FLOPS", raising=False)
    # Substring match over lowercase device_kind.
    assert resolve_peak_flops("TPU v6e") == pytest.approx(918e12)
    assert resolve_peak_flops("TPU v5p") == pytest.approx(459e12)
    assert resolve_peak_flops("TPU v5 lite") == pytest.approx(197e12)
    assert resolve_peak_flops("cpu") is None
    assert resolve_peak_flops(None) is None
    # Env override beats the table (int8 serving / future chips).
    monkeypatch.setenv("INTELLILLM_PEAK_FLOPS", "2e15")
    assert resolve_peak_flops("TPU v6e") == pytest.approx(2e15)
    # Garbage env is ignored, not fatal.
    monkeypatch.setenv("INTELLILLM_PEAK_FLOPS", "fast")
    assert resolve_peak_flops("TPU v4") == pytest.approx(275e12)


def test_warmup_excludes_dispatches_from_ledger(tracker):
    """Acceptance: warm-up dispatches must not pollute the ledger —
    suppressed entirely, but counted as excluded."""
    with tracker.warmup():
        tracker.record_dispatch("decode", 1, 64, real_tokens=1,
                                padded_tokens=64, width_real=1,
                                width_padded=16)
        with tracker.warmup():  # nesting must not unsuppress early
            tracker.record_dispatch("decode", 1, 32, real_tokens=1,
                                    padded_tokens=32)
        tracker.record_dispatch("prefill", 1, 8, real_tokens=16,
                                padded_tokens=128, len_real=16,
                                len_padded=16)
    snap = tracker.snapshot()
    assert snap["tokens_total"]["decode"] == {"real": 0, "pad": 0}
    assert snap["tokens_total"]["prefill"] == {"real": 0, "pad": 0}
    assert snap["dispatches"] == {"prefill": 0, "decode": 0}
    assert snap["fill_ratio_avg"]["decode"]["block_width"] is None
    assert snap["top_waste"] == []
    assert snap["warmup_excluded_dispatches"] == 3
    assert tracker.warmup_excluded() == 3
    # After the context exits, recording resumes.
    tracker.record_dispatch("decode", 2, 4, real_tokens=2, padded_tokens=4)
    assert tracker.tokens_total()["decode"] == {"real": 2, "pad": 2}


def test_mfu_nan_without_peak_then_finite_with_override(tracker):
    cfg = _FakeModelConfig(ffn_dim=128)
    tracker.configure_model(cfg)  # CPU: no table entry -> peak None
    tracker.record_dispatch("decode", 4, 4, real_tokens=4, padded_tokens=4)
    assert tracker.record_step(0.01) is None
    assert tracker.rolling_mfu() is None
    snap = tracker.snapshot()
    assert snap["peak_flops"] is None
    assert snap["mfu"] is None          # JSON-safe: None, never NaN
    assert snap["flops_per_token"] == pytest.approx(143872.0)
    if tracker._metrics is not None:    # the gauge itself carries NaN
        assert math.isnan(tracker._metrics.gauge_mfu._value.get())

    tracker.configure(peak_flops=1e9)
    tracker.record_dispatch("decode", 4, 4, real_tokens=4, padded_tokens=4)
    mfu = tracker.record_step(0.01)
    # Window holds two steps: 8 real tokens over 0.02 s against 1e9
    # peak -> 8 * 143872 / (0.02 * 1e9).
    assert mfu == pytest.approx(8 * 143872.0 / (0.02 * 1e9))
    assert tracker.snapshot()["mfu"] == pytest.approx(mfu, abs=1e-6)


def test_explicit_peak_survives_attach_device(tracker):
    tracker.configure(peak_flops=5e12)
    tracker.attach_device()  # CPU would otherwise reset peak to None
    assert tracker.snapshot()["peak_flops"] == pytest.approx(5e12)
    # reset_for_testing drops the override (fresh resolution order).
    tracker.reset_for_testing()
    assert not hasattr(tracker, "_peak_override")


def test_mfu_window_is_rolling(monkeypatch):
    monkeypatch.delenv("INTELLILLM_PEAK_FLOPS", raising=False)
    monkeypatch.setenv("INTELLILLM_MFU_WINDOW", "2")
    t = EfficiencyTracker(enabled=True)
    t.configure(peak_flops=1e6)
    t._flops_per_token = 100.0
    t.record_dispatch("decode", 10, 10, real_tokens=10, padded_tokens=10)
    t.record_step(1.0)
    t.record_dispatch("decode", 10, 10, real_tokens=10, padded_tokens=10)
    t.record_step(1.0)
    # A third step evicts the first: only the last 2 steps count.
    t.record_dispatch("decode", 40, 40, real_tokens=40, padded_tokens=40)
    mfu = t.record_step(1.0)
    assert mfu == pytest.approx((10 + 40) * 100.0 / (2.0 * 1e6))


def test_disabled_tracker_is_a_noop(monkeypatch):
    monkeypatch.setenv("INTELLILLM_EFFICIENCY", "0")
    t = EfficiencyTracker()          # enabled resolved from env
    assert t.enabled is False
    t.record_dispatch("prefill", 4, 8, real_tokens=40, padded_tokens=128)
    assert t.record_step(0.01) is None
    snap = t.snapshot()
    assert snap["enabled"] is False
    assert snap["tokens_total"]["prefill"] == {"real": 0, "pad": 0}
    assert snap["steps"] == 0


def test_record_dispatch_clamps_and_attributes_buckets(tracker):
    # real > padded (defensive): pad clamps to 0, fill to 1.0.
    tracker.record_dispatch("prefill", 9, 8, real_tokens=130,
                            padded_tokens=128, len_real=20, len_padded=16)
    tracker.record_dispatch("prefill", 2, 8, real_tokens=20,
                            padded_tokens=128, len_real=10, len_padded=16)
    snap = tracker.snapshot()
    assert snap["tokens_total"]["prefill"] == {"real": 150, "pad": 108}
    assert snap["pad_fraction"] == pytest.approx(108 / 258, abs=1e-4)
    # Both dispatches share the (batch=8, len=16) bucket pair.
    assert len(snap["per_bucket"]) == 1
    worst = snap["top_waste"][0]
    assert (worst["phase"], worst["batch_bucket"],
            worst["inner_bucket"]) == ("prefill", 8, 16)
    assert worst["axis"] == "len"
    assert worst["dispatches"] == 2
    assert worst["pad_tokens"] == 108


def test_record_dispatch_counts_programs_device_state_and_context(tracker):
    """The dispatch boundary's three more facts: which program, whether
    the device had run dry, and the rows x substeps / context tokens the
    attention kernel walks (the counts its bytes are computed from),
    with the tokens its page walk moves for them."""
    tracker.record_dispatch("decode", 3, 4, real_tokens=24, padded_tokens=32,
                            program="decode_fused", device_idle=True,
                            row_steps=24, context_tokens=24 * 100 + 84,
                            walked_tokens=24 * 112)
    tracker.record_dispatch("decode", 3, 4, real_tokens=24, padded_tokens=32,
                            program="decode_cont", device_idle=False,
                            row_steps=24, context_tokens=24 * 108 + 84,
                            walked_tokens=24 * 112)
    # a mixed step reports its two sides separately; the dispatch is
    # named, and so counted, on one of the calls only
    tracker.record_dispatch("prefill", 1, 1, real_tokens=8, padded_tokens=8,
                            program="mixed", device_idle=False,
                            row_steps=10, context_tokens=300,
                            walked_tokens=320)
    tracker.record_dispatch("decode", 2, 8, real_tokens=2, padded_tokens=8)
    programs = tracker.snapshot()["programs"]
    assert programs["decode_fused"] == {"idle": 1, "busy": 0,
                                        "row_steps": 24,
                                        "context_tokens": 2484,
                                        "walked_tokens": 2688}
    assert programs["decode_cont"] == {"idle": 0, "busy": 1,
                                       "row_steps": 24,
                                       "context_tokens": 2676,
                                       "walked_tokens": 2688}
    assert programs["mixed"] == {"idle": 0, "busy": 1, "row_steps": 10,
                                 "context_tokens": 300,
                                 "walked_tokens": 320}
    assert tracker.snapshot()["dispatches"] == {"prefill": 1, "decode": 3}


@pytest.mark.parametrize("via,rows", [("device", 4), ("fresh", 5)])
def test_decode_join_rows_count_by_how_the_row_entered(tracker, via, rows):
    """intellillm_decode_join_rows_total{via}: rows that took a slot of
    the running batch at a continuation, their first token gathered on
    the device, or were merged by a fresh schedule; the other child
    stays where it was."""
    from prometheus_client import REGISTRY
    from intellillm_tpu.obs.efficiency import JOIN_VIAS
    assert JOIN_VIAS == ("device", "fresh")

    def sample(v):
        return REGISTRY.get_sample_value(
            "intellillm_decode_join_rows_total", {"via": v})

    before = {v: sample(v) for v in JOIN_VIAS}
    assert None not in before.values()      # every child from scrape 1
    tracker.record_decode_join(via, rows)
    tracker.record_decode_join(via, 0)
    assert tracker.snapshot()["decode_join_rows"] == {
        v: rows if v == via else 0 for v in JOIN_VIAS}
    for v in JOIN_VIAS:
        assert sample(v) - before[v] == (rows if v == via else 0)


def test_decode_join_device_child_is_exported_from_the_start():
    """`via="device"` stands at 0 in `/metrics` and in
    `/debug/efficiency` before any sequence has joined."""
    from prometheus_client import generate_latest

    from intellillm_tpu.obs.efficiency import EfficiencyTracker
    fresh = EfficiencyTracker()
    assert fresh.snapshot()["decode_join_rows"]["device"] == 0
    assert (b'intellillm_decode_join_rows_total{via="device"}'
            in generate_latest())


@pytest.mark.parametrize("cause", ["memory", "params", "chunked_prefill",
                                   "bucket", "idle"])
def test_pipeline_drains_count_by_cause(tracker, cause):
    """intellillm_pipeline_drains_total{cause}: every cause is exported
    at 0 before the first drain, and a drain counts under its own."""
    from prometheus_client import REGISTRY
    from intellillm_tpu.obs.efficiency import DRAIN_CAUSES

    def sample(c):
        return REGISTRY.get_sample_value(
            "intellillm_pipeline_drains_total", {"cause": c})

    before = {c: sample(c) for c in DRAIN_CAUSES}
    assert None not in before.values()
    assert tracker.snapshot()["pipeline_drains"] == dict.fromkeys(
        DRAIN_CAUSES, 0)
    tracker.record_pipeline_drain(cause)
    drains = tracker.snapshot()["pipeline_drains"]
    assert drains.pop(cause) == 1 and not any(drains.values())
    assert {c: sample(c) - before[c] for c in DRAIN_CAUSES} == {
        c: int(c == cause) for c in DRAIN_CAUSES}


@pytest.mark.parametrize("rows,substeps,top_k,width,path", [
    (128, 8, 6, 64, "dense"), (256, 1, 6, 64, "dense"),
    (257, 1, 6, 64, "grouped"), (512, 1, 6, 64, "grouped"),
    # So few rows that experts go unchosen: 16 decode rows of top-6 of 64
    # and a lone request's row are grouped, 32 rows of top-4 of 32 and of
    # top-6 of 64 are not; a share's router is wider than its stack.
    (16, 8, 6, 64, "grouped"), (1, 8, 6, 64, "grouped"),
    (32, 8, 4, 32, "dense"), (32, 1, 6, 64, "dense"),
    (32, 1, 8, 192, "grouped"), (64, 1, 8, 192, "dense")])
def test_moe_ffn_rows_count_by_the_form_the_rows_take(tracker, rows,
                                                      substeps, top_k,
                                                      width, path):
    """intellillm_moe_ffn_rows_total{path}: a dispatched program's rows x
    substeps, under the form `layers/moe.py` `moe_ffn` takes at those
    shapes (the rule is asked, not copied); both children from the first
    scrape; warm-up dispatches are not counted."""
    from prometheus_client import REGISTRY
    from intellillm_tpu.layers.moe import grouped_rows
    from intellillm_tpu.obs.efficiency import MOE_FFN_PATHS
    assert grouped_rows(rows, top_k, width) == (path == "grouped")

    def sample(p):
        return REGISTRY.get_sample_value(
            "intellillm_moe_ffn_rows_total", {"path": p})

    before = {p: sample(p) for p in MOE_FFN_PATHS}
    assert None not in before.values()
    with tracker.warmup():
        tracker.record_moe_ffn_rows(rows, substeps, top_k, width)
    assert tracker.snapshot()["moe_ffn_rows"] == dict.fromkeys(
        MOE_FFN_PATHS, 0)
    tracker.record_moe_ffn_rows(rows, substeps, top_k, width)
    counted = tracker.snapshot()["moe_ffn_rows"]
    assert counted.pop(path) == rows * substeps and not any(counted.values())
    assert {p: sample(p) - before[p] for p in MOE_FFN_PATHS} == {
        p: rows * substeps * (p == path) for p in MOE_FFN_PATHS}


@pytest.mark.parametrize("program,visited,held", [
    ("decode_cont", 3104, 4096), ("decode_fused", 48, 512),
    ("mixed", 512, 512)])
def test_moe_expert_visits_count_what_a_step_read(tracker, program, visited,
                                                  held):
    """intellillm_moe_expert_visits_total{program,visited}: of the held
    experts x layers x substeps of a finished step, those whose weights
    were read and those no row chose; every child from the first scrape;
    warm-up steps and programs that are not counted by name are left
    out."""
    from prometheus_client import REGISTRY
    from intellillm_tpu.obs.efficiency import EXPERT_VISITED, PROGRAMS

    def sample(prog, v):
        return REGISTRY.get_sample_value(
            "intellillm_moe_expert_visits_total",
            {"program": prog, "visited": v})

    before = {(p, v): sample(p, v) for p in PROGRAMS for v in EXPERT_VISITED}
    assert None not in before.values()
    zero = {p: dict.fromkeys(EXPERT_VISITED, 0) for p in PROGRAMS}
    with tracker.warmup():
        tracker.record_moe_expert_visits(program, visited, held)
    tracker.record_moe_expert_visits("decode_teacher", visited, held)
    assert tracker.snapshot()["moe_expert_visits"] == zero
    tracker.record_moe_expert_visits(program, visited, held)
    want = dict(zero, **{program: {"yes": visited, "no": held - visited}})
    assert tracker.snapshot()["moe_expert_visits"] == want
    assert {k: sample(*k) - before[k] for k in before} == {
        (p, v): want[p][v] for p in PROGRAMS for v in EXPERT_VISITED}


@pytest.mark.parametrize("tiles", [{"one": 128}, {"one": 3, "many": 509},
                                   {"many": 512}])
def test_mixed_tile_rows_count_by_the_tile_a_row_ran_in(tracker, tiles):
    """intellillm_mixed_tile_rows_total{tile}: a `mixed` step's live rows
    by whether the ragged kernel walked their context alone or a tile of
    a chunk's rows together; both children from the first scrape; warm-up
    dispatches are not counted."""
    from prometheus_client import REGISTRY
    from intellillm_tpu.obs.efficiency import RAGGED_TILES

    def sample(t):
        return REGISTRY.get_sample_value(
            "intellillm_mixed_tile_rows_total", {"tile": t})

    def dispatch():
        n = sum(tiles.values())
        tracker.record_dispatch("prefill", 1, 1, real_tokens=n,
                                padded_tokens=n, program="mixed",
                                row_steps=n, tile_rows=tiles)

    before = {t: sample(t) for t in RAGGED_TILES}
    assert None not in before.values()
    with tracker.warmup():
        dispatch()
    assert tracker.snapshot()["mixed_tile_rows"] == dict.fromkeys(
        RAGGED_TILES, 0)
    dispatch()
    want = {t: tiles.get(t, 0) for t in RAGGED_TILES}
    assert tracker.snapshot()["mixed_tile_rows"] == want
    assert {t: sample(t) - before[t] for t in RAGGED_TILES} == want


def test_dispatch_program_counters_skip_warmup_and_exist_at_zero(tracker):
    from prometheus_client import REGISTRY
    zero = {"idle": 0, "busy": 0, "row_steps": 0, "context_tokens": 0,
            "walked_tokens": 0}
    with tracker.warmup():
        tracker.record_dispatch("decode", 1, 16, real_tokens=8,
                                padded_tokens=128, program="decode_fused",
                                device_idle=True, row_steps=8,
                                context_tokens=64, walked_tokens=64)
    assert tracker.snapshot()["programs"] == {
        "mixed": zero, "decode_fused": zero, "decode_cont": zero}
    # Both `device` children of every program are exported from the first
    # scrape: a ratio over a series that never appeared has no answer.
    for program in ("mixed", "decode_fused", "decode_cont"):
        for device in ("idle", "busy"):
            assert REGISTRY.get_sample_value(
                "intellillm_dispatch_total",
                {"program": program, "device": device}) is not None
        for name in ("intellillm_dispatch_row_steps_total",
                     "intellillm_dispatch_context_tokens_total",
                     "intellillm_dispatch_walked_tokens_total"):
            assert REGISTRY.get_sample_value(
                name, {"program": program}) is not None
