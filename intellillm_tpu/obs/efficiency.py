"""Compute-efficiency telemetry: where the FLOPs went.

Every dispatch is padded to (batch, seq-len, block-table-width) buckets
(`worker/model_runner.py`), so a slice of every step's FLOPs is spent on
pad rows and pad tokens. PRs 1-3 instrumented *time* (step phases, SLO
latencies, stalls) and *memory* (HBM, swap bytes); this module closes
the *compute* axis with three pieces:

**Padding-waste accounting.** The model runner reports every dispatch's
real vs padded extent along all three bucket axes, split by
prefill/decode. Exported as `intellillm_tokens_total{kind=real|pad,
phase=prefill|decode}` plus per-axis fill-ratio histograms
(`intellillm_fill_ratio{phase, axis}`), and kept as a plain cumulative
ledger — waste attributed per (batch bucket, len/width bucket) pair —
served at `GET /debug/efficiency` so operators can see which buckets
burn the most pad FLOPs. Warm-up dispatches are excluded: the worker
wraps `warm_up_model()` in `warmup()`, which suppresses recording and
counts the suppressed dispatches instead.

**MFU gauge.** `intellillm_mfu` = achieved model FLOPs / hardware peak,
rolling over the last `INTELLILLM_MFU_WINDOW` (default 64) engine steps.
Achieved FLOPs use an analytic per-token model derived from ModelConfig
dims (layers, hidden, kv heads, ffn, vocab): matmul FLOPs only, i.e.
2 x (attention projections + MLP + LM head) per token. Known error
bars: attention score/AV FLOPs (context-length dependent), embeddings,
and norms are ignored, so the model UNDERcounts at long context —
treat MFU as a lower-bound trend line, not an absolute. Peak FLOPs come
from a per-chip table keyed on the jax device kind, overridable with
`INTELLILLM_PEAK_FLOPS`; on backends with no table entry (the CPU
tier-1 backend) the gauge degrades to NaN — not 0, which would read as
"completely stalled" — the same convention as
`intellillm_hbm_headroom_ratio` in device telemetry.

**Read side.** The StatLogger periodic line gains `MFU`/`pad`,
`/health/detail` gains an `efficiency` block, `/debug/efficiency`
serves the full ledger on both servers, `tools/top.py` renders an
efficiency panel, and `benchmarks/serve_bench.py` embeds the summary.

INTELLILLM_EFFICIENCY=0 disables everything (recorders become no-ops).
"""
from __future__ import annotations

import contextlib
import math
import os
import threading
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from intellillm_tpu.logger import init_logger

logger = init_logger(__name__)

try:
    from prometheus_client import Counter, Gauge, Histogram
    _PROMETHEUS = True
except ImportError:  # pragma: no cover
    _PROMETHEUS = False

PHASES = ("prefill", "decode")
# The device programs whose dispatches are counted by name, and what the
# device was doing when the host dispatched one.
PROGRAMS = ("mixed", "decode_fused", "decode_cont")
DEVICE_STATES = ("idle", "busy")
# How a row entered a decode batch, and why the step pipeline drained to
# a fresh schedule (engine/llm_engine.py step_pipelined).
JOIN_VIAS = ("device", "fresh")
# The two forms of the expert FFN (layers/moe.py moe_ffn).
MOE_FFN_PATHS = ("dense", "grouped")
# Whether a held expert's weights were read by a layer-substep.
EXPERT_VISITED = ("yes", "no")
DRAIN_CAUSES = ("memory", "params", "chunked_prefill", "bucket", "idle")
TOKEN_KINDS = ("real", "pad")
# What happens to a per-sequence state slot (core/block_manager.py), and
# the device cache pools by kind.
STATE_SLOT_EVENTS = ("alloc", "free", "swap_out", "swap_in", "fork")
CACHE_KINDS = ("kv", "kv_window", "state")
# Layers by how far their attention sees (the attended-tokens counter).
ATTENTION_REACHES = ("full", "window")
WINDOW_BLOCK_EVENTS = ("recycled", )
# A `mixed` row by the tile of rows that walked its context with it.
RAGGED_TILES = ("one", "many")
# The form of a linear-attention layer's delta rule a row went through.
LINEAR_ATTN_FORMS = ("chunk", "step")
# Rows x substeps of a dispatched program by what the head did with them.
HEAD_ROW_KINDS = ("batch", "computed", "emitting")
AXES = ("batch", "len", "block_width")
_DEFAULT_MFU_WINDOW = 64
_FILL_BUCKETS = (0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9,
                 0.95, 1.0)

# Dense bf16 matmul peak per chip, matched as a lowercase substring of
# jax's Device.device_kind. Override with INTELLILLM_PEAK_FLOPS (e.g.
# for int8 serving or future chips).
_PEAK_FLOPS_BY_KIND = (
    ("v6e", 918e12),
    ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


class _EfficiencyMetrics:
    """Prometheus collectors for compute efficiency (process-global,
    built once — same singleton pattern as engine/metrics._Metrics)."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
            cls._instance._init()
        return cls._instance

    def _init(self) -> None:
        self.counter_tokens = Counter(
            "intellillm_tokens_total",
            "Tokens dispatched to the device, split into real work vs "
            "bucket padding (kind: real | pad; phase: prefill | decode).",
            ["kind", "phase"])
        self.hist_fill_ratio = Histogram(
            "intellillm_fill_ratio",
            "Per-dispatch fill ratio (real/padded extent) along each "
            "bucket axis (axis: batch | len | block_width).",
            ["phase", "axis"],
            buckets=_FILL_BUCKETS)
        self.gauge_mfu = Gauge(
            "intellillm_mfu",
            "Rolling model FLOPs utilization: analytic per-token FLOPs x "
            "real tokens / (step wall-time x per-chip peak FLOPs). NaN "
            "when the chip's peak is unknown (e.g. CPU backend).")
        self.counter_dispatch = Counter(
            "intellillm_dispatch_total",
            "Device dispatches by program (mixed | decode_fused | "
            "decode_cont) and by whether anything dispatched earlier was "
            "still running on the device at that moment (device: idle | "
            "busy). device=idle is the profiler-free twin of a trace's "
            "'idle gap before program X'.", ["program", "device"])
        self.counter_dispatch_row_steps = Counter(
            "intellillm_dispatch_row_steps_total",
            "Live rows x substeps of the dispatched programs: attention "
            "calls per layer, one per row and substep.", ["program"])
        self.counter_dispatch_context_tokens = Counter(
            "intellillm_dispatch_context_tokens_total",
            "Context lengths the attention kernel walks, summed over the "
            "live rows and substeps of the dispatched programs.",
            ["program"])
        self.counter_dispatch_walked_tokens = Counter(
            "intellillm_dispatch_walked_tokens_total",
            "Tokens of paged context the attention kernel's walk moves "
            "for the same rows and substeps (whole pages, by the "
            "kernel's own rule): over the context tokens, what the walk "
            "fetches beyond what the rows hold.", ["program"])
        self.counter_dispatch_kv_walk_bytes = Counter(
            "intellillm_dispatch_kv_walk_bytes_total",
            "Bytes of paged K/V (or latent) cache the attention kernels' "
            "walks moved: intellillm_dispatch_walked_tokens_total x the "
            "bytes one token holds over every layer with a paged pool. "
            "Beside intellillm_dispatch_state_bytes_total: which of a "
            "hybrid's two caches a decode step's bytes go to.",
            ["program"])
        self.counter_dispatch_shared_kv_walk_bytes = Counter(
            "intellillm_dispatch_shared_kv_walk_bytes_total",
            "The part of intellillm_dispatch_kv_walk_bytes_total moved by "
            "layers that hold no pool of their own and walk another "
            "layer's (cross layers over one full-attention layer's keys "
            "and values). Stays 0 for a model every walker of which "
            "holds what it walks.", ["program"])
        self.counter_dispatch_attended_tokens = Counter(
            "intellillm_dispatch_attended_tokens_total",
            "Tokens a row attends to in ONE layer of a kind (layers: full "
            "= its whole context; window = what a sliding window leaves "
            "of it), summed over the same live rows and substeps as the "
            "context tokens. A kind the model has no layer of stays 0.",
            ["program", "layers"])
        self.counter_mixed_tile_rows = Counter(
            "intellillm_mixed_tile_rows_total",
            "Live rows of the `mixed` programs by the tile the ragged "
            "attention kernel ran them in (tile: one = the row walked "
            "its paged context alone, as a decode row does; many = rows "
            "of one prompt chunk that walked it once together). "
            "many / (one + many) is how often the tiling engages.",
            ["tile"])
        self.counter_head_rows = Counter(
            "intellillm_head_rows_total",
            "Rows x substeps of the dispatched programs by what the head "
            "(final projection onto the vocabulary) and the sampler did "
            "with them (kind: batch = rows the program held, pad rows "
            "included; computed = rows whose logits were computed: all "
            "of them, or the rows that emit made up to a bucket, or none "
            "in a step no row of which emits; emitting = rows whose "
            "token is a result). On program=\"mixed\", 1 - computed / "
            "batch is the share of the head's rows a prompt step did "
            "not compute.", ["program", "kind"])
        self.counter_linear_attn_rows = Counter(
            "intellillm_linear_attn_rows_total",
            "Live rows x substeps of the dispatched programs that went "
            "through a matrix-state layer's recurrence (a delta rule, a "
            "state-space scan), by its form (form: chunk = the `mixed` "
            "programs' chunk form over runs of rows; step = a decode "
            "substep on the state pool by slot). Stays 0 for a model "
            "whose state, if any, is not updated in its pool.",
            ["program", "form"])
        self.counter_dispatch_state_bytes = Counter(
            "intellillm_dispatch_state_bytes_total",
            "Bytes of per-sequence matrix state the dispatched programs "
            "read and wrote: live rows x substeps x state layers x a "
            "slot's matrix bytes x 2. Over "
            "intellillm_dispatch_row_steps_total: the state traffic of "
            "one row-substep, what a roofline of the step form divides "
            "by.", ["program"])
        self.counter_dispatch_stream_mix_bytes = Counter(
            "intellillm_dispatch_stream_mix_bytes_total",
            "Bytes of residual streams the dispatched programs of a model "
            "with a multi-stream residual (hyper-connections) moved "
            "through its mixes: live rows x substeps x sublayers x the "
            "streams' bytes a row x 3 (a pre-mix reads the streams once, "
            "a post-mix reads them and writes them). Over "
            "intellillm_dispatch_row_steps_total: the mixes' traffic of "
            "one row-substep. Stays 0 for every other model.", ["program"])
        self.gauge_mhc_col_error_max = Gauge(
            "intellillm_mhc_col_error_max",
            "The largest |column sum - 1| of any stream-mixing matrix of "
            "a live row in the finished steps since boot (warm-up "
            "excluded): how far the Sinkhorn sweeps, the clamp and the "
            "epsilon leave the matrices from doubly stochastic (rows are "
            "normalised last and sum to 1 to rounding). It rides the "
            "step's packed device-to-host fetch. 0 for a model without a "
            "multi-stream residual.")
        self.counter_window_blocks = Counter(
            "intellillm_window_blocks_total",
            "What the block manager did with blocks of a sliding-window "
            "cache group (event: recycled = given back to the group's "
            "free list because every token in it fell out of the "
            "window).", ["event"])
        self.counter_moe_assignments = Counter(
            "intellillm_moe_assignments_total",
            "Token x expert choices of an expert-parallel share's router, "
            "by whether this chip holds the chosen expert (held: here | "
            "absent). Only a model whose expert stacks hold fewer experts "
            "than its router has outputs counts here; here / (here + "
            "absent) is held experts / router width in expectation.",
            ["held"])
        self.counter_moe_ffn_rows = Counter(
            "intellillm_moe_ffn_rows_total",
            "Token-rows (pad rows included) x substeps that the dispatched "
            "programs of a model with expert layers sent through each "
            "form of the expert FFN (path: dense = every held expert over "
            "every row; grouped = the routed rows only: a step of more "
            "than 256 rows, or of so few that a good share of the experts "
            "is chosen by none), by layers/moe.py's own rule.",
            ["path"])
        self.counter_moe_expert_visits = Counter(
            "intellillm_moe_expert_visits_total",
            "Held experts x expert layers x substeps of the finished steps "
            "of a model that reports them, by whether the layer-substep "
            "read the expert's weights (visited: yes = some row chose it, "
            "or the form was the dense one, which reads every stack; no = "
            "the grouped form and no row chose it). yes / (yes + no) is "
            "the share of the held experts' bytes a step read.",
            ["program", "visited"])
        self.counter_decode_join_rows = Counter(
            "intellillm_decode_join_rows_total",
            "Rows that entered a decode batch, by how (via: device = took "
            "a free slot of the running batch at a continuation, its "
            "first token read on the device from its prompt step still "
            "in flight; fresh = merged by a full schedule after the "
            "pipeline drained).",
            ["via"])
        self.counter_pipeline_drains = Counter(
            "intellillm_pipeline_drains_total",
            "Full scheduling passes that dispatched decode rows with the "
            "step pipeline empty, by what emptied it (cause: memory | "
            "params | chunked_prefill | bucket | idle = no decode step was "
            "in flight to continue).", ["cause"])
        self.gauge_state_slots = Gauge(
            "intellillm_state_slots",
            "Per-sequence state slots of a model with state layers (a "
            "short convolution's taps), on the device (state: used | "
            "free). One slot a running sequence; 0 / 0 for every other "
            "model.", ["state"])
        self.counter_state_slot_events = Counter(
            "intellillm_state_slot_events_total",
            "What the block manager did with state slots (event: alloc = "
            "an admission took one; free = a sequence ended, was aborted "
            "or was preempted by recompute; swap_out / swap_in = moved to "
            "or from a host slot with the sequence's blocks; fork = a "
            "child sequence took one and the parent's state was copied).",
            ["event"])
        self.gauge_cache_bytes = Gauge(
            "intellillm_cache_bytes",
            "Device bytes of the cache pools (kind: kv = the paged K/V or "
            "latent pools of the layers that keep the whole context; "
            "kv_window = those of the sliding-window layers, where they "
            "are a group of their own; state = the per-sequence state "
            "pools).", ["kind"])
        for state in ("used", "free"):
            self.gauge_state_slots.labels(state).set(0)
        for path in MOE_FFN_PATHS:
            self.counter_moe_ffn_rows.labels(path)
        for program in PROGRAMS:
            for visited in EXPERT_VISITED:
                self.counter_moe_expert_visits.labels(program, visited)
        for event in STATE_SLOT_EVENTS:
            self.counter_state_slot_events.labels(event)
        for kind in CACHE_KINDS:
            self.gauge_cache_bytes.labels(kind).set(0)
        # Pre-create the label children so the series exist (at 0) from
        # the first scrape, before any dispatch happens: a ratio over a
        # series that never appeared reads as "no answer", not as 0.
        for kind in TOKEN_KINDS:
            for phase in PHASES:
                self.counter_tokens.labels(kind, phase)
        for program in PROGRAMS:
            for device in DEVICE_STATES:
                self.counter_dispatch.labels(program, device)
            self.counter_dispatch_row_steps.labels(program)
            self.counter_dispatch_context_tokens.labels(program)
            self.counter_dispatch_walked_tokens.labels(program)
            self.counter_dispatch_kv_walk_bytes.labels(program)
            self.counter_dispatch_shared_kv_walk_bytes.labels(program)
            for reach in ATTENTION_REACHES:
                self.counter_dispatch_attended_tokens.labels(program, reach)
            for form in LINEAR_ATTN_FORMS:
                self.counter_linear_attn_rows.labels(program, form)
            for kind in HEAD_ROW_KINDS:
                self.counter_head_rows.labels(program, kind)
            self.counter_dispatch_state_bytes.labels(program)
            self.counter_dispatch_stream_mix_bytes.labels(program)
        self.gauge_mhc_col_error_max.set(0)
        for tile in RAGGED_TILES:
            self.counter_mixed_tile_rows.labels(tile)
        for event in WINDOW_BLOCK_EVENTS:
            self.counter_window_blocks.labels(event)
        for via in JOIN_VIAS:
            self.counter_decode_join_rows.labels(via)
        for cause in DRAIN_CAUSES:
            self.counter_pipeline_drains.labels(cause)

    @classmethod
    def reset_for_testing(cls) -> None:
        inst = cls._instance
        if inst is not None and _PROMETHEUS:
            from prometheus_client import REGISTRY
            for collector in vars(inst).values():
                try:
                    REGISTRY.unregister(collector)
                except Exception:
                    pass
        cls._instance = None


def _enabled_from_env() -> bool:
    from intellillm_tpu.utils import parse_env_flag
    flag = parse_env_flag(os.environ.get("INTELLILLM_EFFICIENCY"))
    return True if flag is None else flag


def _env_f(name: str) -> Optional[float]:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return None
    try:
        return float(raw)
    except ValueError:
        logger.warning("Ignoring invalid %s=%r (want a float).", name, raw)
        return None


def analytic_flops_per_token(model_config) -> Optional[float]:
    """Matmul FLOPs per token: 2 x (attention projections + MLP + LM
    head) weights touched. Ignores attention score/AV FLOPs (context
    dependent), embeddings, and norms — see module docstring for the
    error bars this implies."""
    try:
        h = int(model_config.get_hidden_size())
        layers = int(model_config.get_num_layers())
        vocab = int(model_config.get_vocab_size())
        hf = model_config.hf_config
        inter = getattr(hf, "intermediate_size", None) \
            or getattr(hf, "ffn_dim", None) or 4 * h
        act = str(getattr(hf, "hidden_act", "")
                  or getattr(hf, "activation_function", "")).lower()
        # Gated MLPs (SwiGLU-family) carry a third h x inter matrix.
        mlp_mats = 3 if ("silu" in act or "swish" in act
                         or "glu" in act) else 2
        rank = getattr(hf, "kv_lora_rank", None)
        rope = getattr(hf, "qk_rope_head_dim", None)
        if rank and rope:
            # Latent attention as every step runs it (absorbed): x -> c_q
            # -> q, x -> [c_kv | k_rope], q_nope W_UK and the attended
            # latent W_UV per head, o.
            heads = int(model_config.get_num_attention_heads())
            qk = int(hf.qk_nope_head_dim) + rope
            nope_v = int(hf.qk_nope_head_dim) + int(hf.v_head_dim)
            q_rank = getattr(hf, "q_lora_rank", None)
            q = (h * q_rank + q_rank * heads * qk if q_rank
                 else h * heads * qk)
            attn = (q + h * (rank + rope) + heads * rank * nope_v
                    + heads * int(hf.v_head_dim) * h)
        else:
            kv_dim = (int(model_config.get_total_num_kv_heads())
                      * int(model_config.get_head_size()))
            attn = 2 * h * h + 2 * h * kv_dim  # q,o + k,v projections
        mlp = mlp_mats * h * int(inter)
        return float(2 * (layers * (attn + mlp) + h * vocab))
    except Exception as e:
        logger.warning("Efficiency: cannot derive a FLOPs model from the "
                       "HF config (%s); MFU will read NaN.", e)
        return None


def resolve_peak_flops(device_kind: Optional[str]) -> Optional[float]:
    """Env override first, then the per-chip table; None (-> NaN MFU)
    when neither matches — same degradation as device telemetry."""
    env = _env_f("INTELLILLM_PEAK_FLOPS")
    if env is not None:
        return env
    if device_kind:
        kind = device_kind.lower()
        for marker, peak in _PEAK_FLOPS_BY_KIND:
            if marker in kind:
                return peak
    return None


class EfficiencyTracker:
    """Process-global compute-efficiency ledger (one engine per
    process). All recorders are cheap dict/deque updates guarded by one
    lock; everything works without prometheus_client."""

    def __init__(self, enabled: Optional[bool] = None) -> None:
        self.enabled = (_enabled_from_env() if enabled is None else enabled)
        self._lock = threading.Lock()
        self._warmup_depth = 0
        self._warmup_excluded = 0
        self._co_batched_steps = 0
        self._tokens: Dict[str, Dict[str, int]] = {
            phase: {kind: 0 for kind in TOKEN_KINDS} for phase in PHASES}
        self._dispatches: Dict[str, int] = {phase: 0 for phase in PHASES}
        # program -> device dispatches by device state, row-steps,
        # context tokens and walked tokens (the counts a kernel's bytes
        # are computed from).
        self._programs: Dict[str, Dict[str, int]] = {
            program: dict.fromkeys(
                DEVICE_STATES + ("row_steps", "context_tokens",
                                 "walked_tokens"), 0)
            for program in PROGRAMS}
        self._decode_joins: Dict[str, int] = dict.fromkeys(JOIN_VIAS, 0)
        self._moe_ffn_rows: Dict[str, int] = dict.fromkeys(MOE_FFN_PATHS, 0)
        self._moe_expert_visits: Dict[str, Dict[str, int]] = {
            program: dict.fromkeys(EXPERT_VISITED, 0)
            for program in PROGRAMS}
        self._mixed_tile_rows: Dict[str, int] = dict.fromkeys(
            RAGGED_TILES, 0)
        self._head_rows: Dict[str, Dict[str, int]] = {
            program: dict.fromkeys(HEAD_ROW_KINDS, 0)
            for program in PROGRAMS}
        # A multi-stream residual's bytes (record_dispatch) and the
        # largest column error of its matrices (record_mhc_col_error).
        self._mhc: Dict[str, float] = {"stream_mix_bytes": 0,
                                       "col_error_max": 0.0}
        self._pipeline_drains: Dict[str, int] = dict.fromkeys(
            DRAIN_CAUSES, 0)
        # (phase, axis) -> [sum of fill ratios, observations]
        self._fill: Dict[Tuple[str, str], List[float]] = {}
        # (phase, batch_bucket, inner_bucket) -> cumulative waste row;
        # inner bucket is the padded seq-len for prefill, the padded
        # block-table width for decode.
        self._buckets: Dict[Tuple[str, int, int], Dict[str, int]] = {}
        self._flops_per_token: Optional[float] = None
        self._model_dims: Dict[str, int] = {}
        self._peak_flops: Optional[float] = None
        self._device_kind: Optional[str] = None
        window = _env_f("INTELLILLM_MFU_WINDOW")
        self._mfu_window = int(window) if window else _DEFAULT_MFU_WINDOW
        # (real tokens, step seconds) per engine step, rolling.
        self._steps: deque = deque(maxlen=max(self._mfu_window, 1))
        self._num_steps = 0
        self._pending_tokens = 0
        self._mfu: Optional[float] = None
        self._metrics = _EfficiencyMetrics() if _PROMETHEUS else None
        if self._metrics is not None:
            self._metrics.gauge_mfu.set(float("nan"))

    # --- configuration ----------------------------------------------------

    def configure_model(self, model_config) -> None:
        """Engine init: derive the analytic FLOPs model from the model's
        dims and resolve this chip's peak FLOPs."""
        if not self.enabled:
            return
        with self._lock:
            self._flops_per_token = analytic_flops_per_token(model_config)
            try:
                self._model_dims = {
                    "layers": int(model_config.get_num_layers()),
                    "hidden": int(model_config.get_hidden_size()),
                    "heads": int(model_config.get_num_attention_heads()),
                    "vocab": int(model_config.get_vocab_size()),
                }
            except Exception:
                self._model_dims = {}
        self.attach_device()

    def attach_device(self) -> None:
        """Resolve peak FLOPs for the local chip (env override wins;
        unknown chip -> None -> NaN MFU)."""
        kind = None
        try:
            import jax
            devices = jax.local_devices()
            if devices:
                kind = getattr(devices[0], "device_kind", None) \
                    or getattr(devices[0], "platform", None)
        except Exception:
            kind = None
        with self._lock:
            self._device_kind = kind
            if self._explicit_peak() is None:
                self._peak_flops = resolve_peak_flops(kind)

    def _explicit_peak(self) -> Optional[float]:
        return getattr(self, "_peak_override", None)

    def configure(self, peak_flops: Optional[float] = None,
                  mfu_window: Optional[int] = None) -> None:
        """Operator overrides (--peak-flops CLI flag / tests)."""
        with self._lock:
            if peak_flops is not None:
                self._peak_override = float(peak_flops)
                self._peak_flops = float(peak_flops)
            if mfu_window is not None and mfu_window > 0:
                self._mfu_window = int(mfu_window)
                self._steps = deque(self._steps, maxlen=self._mfu_window)

    # --- warm-up exclusion ------------------------------------------------

    @contextlib.contextmanager
    def warmup(self):
        """Suppress recording for the duration (worker warm-up sweeps
        dispatch every decode bucket; counting them would charge steady
        -state series with synthetic all-pad batches). Suppressed
        dispatches are counted so the ledger shows they were excluded,
        not lost."""
        with self._lock:
            self._warmup_depth += 1
        try:
            yield self
        finally:
            with self._lock:
                self._warmup_depth -= 1

    # --- record side (model runner / engine hot path) ---------------------

    def record_co_batched_step(self) -> None:
        """One mixed step carried prefill-chunk rows AND decode rows."""
        with self._lock:
            if self._warmup_depth == 0:
                self._co_batched_steps += 1

    def record_dispatch(self, phase: str, real_rows: int, padded_rows: int,
                        *, real_tokens: int, padded_tokens: int,
                        len_real: Optional[int] = None,
                        len_padded: Optional[int] = None,
                        width_real: Optional[int] = None,
                        width_padded: Optional[int] = None,
                        program: Optional[str] = None,
                        device_idle: bool = False,
                        row_steps: int = 0,
                        context_tokens: int = 0,
                        walked_tokens: int = 0,
                        kv_walk_bytes: int = 0,
                        shared_kv_walk_bytes: int = 0,
                        attended_tokens: Optional[Dict[str, int]] = None,
                        tile_rows: Optional[Dict[str, int]] = None,
                        head_rows: Optional[Dict[str, int]] = None,
                        linear_form: Optional[str] = None,
                        state_bytes: int = 0,
                        stream_mix_bytes: int = 0) -> None:
        """Account one device dispatch. Extents are pre-padding vs
        post-padding; token counts are what the device actually
        computes (prefill: rows x padded len; decode: rows x substeps).

        `program` counts the dispatch itself, once: a mixed step that
        reports its prefill and its decode side separately names the
        program on one of the two calls only. `device_idle`: nothing
        dispatched earlier was still running. `row_steps` / `context_tokens`:
        live rows x substeps, and the context lengths attention walks
        summed over them. `walked_tokens`: what the kernel's page walk
        moves for those same row-substeps (`walk_extent` of the kernel
        module: whole pages; where the model has two cache groups, the
        mean over its layers), and `kv_walk_bytes` the cache bytes that
        is over all the layers that walk a paged pool, of which
        `shared_kv_walk_bytes` by layers that hold none and walk another
        layer's. `attended_tokens`: by
        ATTENTION_REACHES,
        what those row-substeps attend to in one layer of that kind.
        `tile_rows`: by RAGGED_TILES, a `mixed` step's live rows by the
        tile the ragged kernel ran them in. `head_rows`: by
        HEAD_ROW_KINDS, the program's rows x substeps (pad rows
        included), those the head computed logits of, and those whose
        token is a result. `linear_form` (of
        LINEAR_ATTN_FORMS; None for a model without a matrix state
        updated in its pool): the form of the recurrence the
        row-substeps went through, and `state_bytes` the matrix state
        they read and wrote. `stream_mix_bytes` (0 for a model without a
        multi-stream residual): the streams' bytes those row-substeps
        moved through its mixes."""
        if not self.enabled:
            return
        real_tokens = int(real_tokens)
        pad_tokens = max(int(padded_tokens) - real_tokens, 0)
        fills: List[Tuple[str, float]] = []
        if padded_rows > 0:
            fills.append(("batch", min(real_rows / padded_rows, 1.0)))
        if len_padded and len_real is not None:
            fills.append(("len", min(len_real / len_padded, 1.0)))
        if width_padded and width_real is not None:
            fills.append(("block_width",
                          min(width_real / width_padded, 1.0)))
        inner = (len_padded if phase == "prefill" else width_padded) or 0
        with self._lock:
            if self._warmup_depth > 0:
                self._warmup_excluded += 1
                return
            tok = self._tokens.setdefault(
                phase, {kind: 0 for kind in TOKEN_KINDS})
            tok["real"] += real_tokens
            tok["pad"] += pad_tokens
            self._dispatches[phase] = self._dispatches.get(phase, 0) + 1
            self._pending_tokens += real_tokens
            for axis, ratio in fills:
                cell = self._fill.setdefault((phase, axis), [0.0, 0])
                cell[0] += ratio
                cell[1] += 1
            row = self._buckets.setdefault(
                (phase, int(padded_rows), int(inner)),
                {"dispatches": 0, "real_tokens": 0, "pad_tokens": 0})
            row["dispatches"] += 1
            row["real_tokens"] += real_tokens
            row["pad_tokens"] += pad_tokens
            device = "idle" if device_idle else "busy"
            if program is not None:
                counts = self._programs[program]
                counts[device] += 1
                counts["row_steps"] += int(row_steps)
                counts["context_tokens"] += int(context_tokens)
                counts["walked_tokens"] += int(walked_tokens)
                for tile, n in (tile_rows or {}).items():
                    self._mixed_tile_rows[tile] += int(n)
                for kind, n in (head_rows or {}).items():
                    self._head_rows[program][kind] += int(n)
                self._mhc["stream_mix_bytes"] += int(stream_mix_bytes)
        if self._metrics is not None:
            m = self._metrics
            if program is not None:
                m.counter_dispatch.labels(program, device).inc()
                m.counter_dispatch_row_steps.labels(program).inc(row_steps)
                m.counter_dispatch_context_tokens.labels(program).inc(
                    context_tokens)
                m.counter_dispatch_walked_tokens.labels(program).inc(
                    walked_tokens)
                m.counter_dispatch_kv_walk_bytes.labels(program).inc(
                    kv_walk_bytes)
                if shared_kv_walk_bytes:
                    m.counter_dispatch_shared_kv_walk_bytes.labels(
                        program).inc(shared_kv_walk_bytes)
                for reach, n in (attended_tokens or {}).items():
                    m.counter_dispatch_attended_tokens.labels(
                        program, reach).inc(n)
                for tile, n in (tile_rows or {}).items():
                    m.counter_mixed_tile_rows.labels(tile).inc(n)
                for kind, n in (head_rows or {}).items():
                    m.counter_head_rows.labels(program, kind).inc(n)
                if linear_form is not None:
                    m.counter_linear_attn_rows.labels(
                        program, linear_form).inc(row_steps)
                    m.counter_dispatch_state_bytes.labels(program).inc(
                        state_bytes)
                if stream_mix_bytes:
                    m.counter_dispatch_stream_mix_bytes.labels(
                        program).inc(stream_mix_bytes)
            m.counter_tokens.labels("real", phase).inc(real_tokens)
            m.counter_tokens.labels("pad", phase).inc(pad_tokens)
            for axis, ratio in fills:
                m.hist_fill_ratio.labels(phase, axis).observe(ratio)

    def record_moe_ffn_rows(self, rows: int, substeps: int, top_k: int,
                            router_width: int) -> None:
        """A dispatched program of `rows` token-rows (as padded: what the
        device multiplies) ran its expert layers `substeps` times; the
        form is the one `layers/moe.py` `moe_ffn` takes at those shapes
        (rows, and the model's `top_k` of `router_width`)."""
        if not self.enabled:
            return
        from intellillm_tpu.layers.moe import grouped_rows
        path = ("grouped" if grouped_rows(rows, top_k, router_width)
                else "dense")
        with self._lock:
            if self._warmup_depth > 0:
                return
            self._moe_ffn_rows[path] += int(rows) * int(substeps)
        if self._metrics is not None:
            self._metrics.counter_moe_ffn_rows.labels(path).inc(
                int(rows) * int(substeps))

    def record_decode_join(self, via: str, rows: int) -> None:
        """`rows` sequences were given a row of a decode batch for the
        first time: by a continuation's re-snapshot (`device`: their
        first token gathered on the device from their prompt step in
        flight) or by a full schedule after a drain (`fresh`)."""
        if not self.enabled or not rows:
            return
        with self._lock:
            self._decode_joins[via] += int(rows)
        if self._metrics is not None:
            self._metrics.counter_decode_join_rows.labels(via).inc(rows)

    def record_pipeline_drain(self, cause: str) -> None:
        """A full scheduling pass dispatched decode rows: the pipeline
        had drained for `cause`."""
        if not self.enabled:
            return
        with self._lock:
            self._pipeline_drains[cause] += 1
        if self._metrics is not None:
            self._metrics.counter_pipeline_drains.labels(cause).inc()

    def record_state_slots(self, used: int, free: int,
                           event: Optional[str] = None) -> None:
        """The block manager's state slots after a change, and the change
        (one of STATE_SLOT_EVENTS) if there was one."""
        if not self.enabled or self._metrics is None:
            return
        m = self._metrics
        m.gauge_state_slots.labels("used").set(used)
        m.gauge_state_slots.labels("free").set(free)
        if event is not None:
            m.counter_state_slot_events.labels(event).inc()

    def record_window_blocks(self, event: str, blocks: int) -> None:
        """Blocks of a sliding-window cache group, by what happened to
        them (one of WINDOW_BLOCK_EVENTS)."""
        if self.enabled and self._metrics is not None:
            self._metrics.counter_window_blocks.labels(event).inc(blocks)

    def record_cache_bytes(self, kv: int, state: int,
                           kv_window: int = 0) -> Dict[str, int]:
        """Sizes of the device cache pools, once at engine init; returned
        for `/health/detail`."""
        sizes = {"kv": int(kv), "kv_window": int(kv_window),
                 "state": int(state)}
        if self.enabled and self._metrics is not None:
            for kind, n in sizes.items():
                self._metrics.gauge_cache_bytes.labels(kind).set(n)
        return sizes

    def record_moe_assignments(self, here: int, total: int) -> None:
        """Token x expert choices of a finished step: `here` went to
        experts this chip holds, `total - here` to experts held
        elsewhere. The numbers ride the step's packed device->host fetch
        (worker/model_runner.py `_pack`)."""
        if not self.enabled or self._metrics is None:
            return
        with self._lock:
            if self._warmup_depth > 0:
                return
        m = self._metrics
        m.counter_moe_assignments.labels("here").inc(int(here))
        m.counter_moe_assignments.labels("absent").inc(
            max(int(total) - int(here), 0))

    def record_moe_expert_visits(self, program: str, visited: int,
                                 held: int) -> None:
        """A finished step of `program` ran `held` (expert, layer,
        substep) triples over the experts this chip holds and read the
        weights of `visited` of them. The numbers ride the step's packed
        device->host fetch (worker/model_runner.py `_pack`)."""
        if not self.enabled or program not in self._moe_expert_visits:
            return
        counts = {"yes": int(visited), "no": max(int(held - visited), 0)}
        with self._lock:
            if self._warmup_depth > 0:
                return
            for key, n in counts.items():
                self._moe_expert_visits[program][key] += n
        if self._metrics is not None:
            for key, n in counts.items():
                self._metrics.counter_moe_expert_visits.labels(
                    program, key).inc(n)

    def record_mhc_col_error(self, worst: float) -> None:
        """A finished step's largest |column sum - 1| over the
        stream-mixing matrices of its live rows (a multi-stream residual;
        the number rides the step's packed fetch: worker/model_runner.py
        `_pack`). A NaN is kept: it is what went wrong."""
        if not self.enabled:
            return
        with self._lock:
            if self._warmup_depth > 0:
                return
            if not worst <= self._mhc["col_error_max"]:
                self._mhc["col_error_max"] = worst
            worst = self._mhc["col_error_max"]
        if self._metrics is not None:
            self._metrics.gauge_mhc_col_error_max.set(worst)

    def record_step(self, step_time: float) -> Optional[float]:
        """Engine step boundary: fold the real tokens dispatched since
        the previous boundary with this step's wall time into the
        rolling MFU. Returns the rolling value (None when peak or FLOPs
        model is unknown)."""
        if not self.enabled:
            return None
        with self._lock:
            tokens = self._pending_tokens
            self._pending_tokens = 0
            if step_time is None or step_time <= 0:
                return self._mfu
            self._steps.append((tokens, float(step_time)))
            self._num_steps += 1
            mfu = self._rolling_mfu_locked()
            self._mfu = mfu
        if self._metrics is not None:
            self._metrics.gauge_mfu.set(
                mfu if mfu is not None else float("nan"))
        return mfu

    def _rolling_mfu_locked(self) -> Optional[float]:
        if (self._flops_per_token is None or self._peak_flops is None
                or not self._steps):
            return None
        total_s = sum(dt for _, dt in self._steps)
        if total_s <= 0:
            return None
        total_tokens = sum(t for t, _ in self._steps)
        return (total_tokens * self._flops_per_token
                / (total_s * self._peak_flops))

    # --- read side (endpoints / StatLogger / serve_bench / top) -----------

    def rolling_mfu(self) -> Optional[float]:
        with self._lock:
            return self._mfu

    def tokens_total(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {phase: dict(kinds)
                    for phase, kinds in self._tokens.items()}

    def warmup_excluded(self) -> int:
        with self._lock:
            return self._warmup_excluded

    def _bucket_rows_locked(self) -> List[Dict[str, Any]]:
        fpt = self._flops_per_token
        rows = []
        for (phase, batch_bucket, inner), row in self._buckets.items():
            rows.append({
                "phase": phase,
                "batch_bucket": batch_bucket,
                "axis": "len" if phase == "prefill" else "block_width",
                "inner_bucket": inner,
                "dispatches": row["dispatches"],
                "real_tokens": row["real_tokens"],
                "pad_tokens": row["pad_tokens"],
                "pad_flops": (row["pad_tokens"] * fpt
                              if fpt is not None else None),
            })
        rows.sort(key=lambda r: r["pad_tokens"], reverse=True)
        return rows

    def snapshot(self, top_n: int = 8,
                 include_buckets: bool = True) -> Dict[str, Any]:
        """JSON-safe ledger for /debug/efficiency, /health/detail and
        serve_bench (mfu is None — never NaN — when unknown)."""
        with self._lock:
            real = sum(k["real"] for k in self._tokens.values())
            pad = sum(k["pad"] for k in self._tokens.values())
            fill_avg: Dict[str, Dict[str, Optional[float]]] = {
                phase: {axis: None for axis in AXES} for phase in PHASES}
            for (phase, axis), (total, count) in self._fill.items():
                if count:
                    fill_avg.setdefault(phase, {})[axis] = round(
                        total / count, 4)
            buckets = self._bucket_rows_locked()
            mfu = self._mfu
            body = {
                "enabled": self.enabled,
                "device_kind": self._device_kind,
                "peak_flops": self._peak_flops,
                "flops_per_token": self._flops_per_token,
                "model_dims": dict(self._model_dims),
                "mfu": (round(mfu, 6)
                        if mfu is not None and math.isfinite(mfu)
                        else None),
                "mfu_window_steps": self._mfu_window,
                "steps": self._num_steps,
                "tokens_total": {phase: dict(kinds)
                                 for phase, kinds in self._tokens.items()},
                "pad_fraction": (round(pad / (real + pad), 4)
                                 if real + pad else None),
                "fill_ratio_avg": fill_avg,
                "dispatches": dict(self._dispatches),
                "programs": {program: dict(counts) for program, counts
                             in self._programs.items()},
                "decode_join_rows": dict(self._decode_joins),
                "moe_ffn_rows": dict(self._moe_ffn_rows),
                "moe_expert_visits": {
                    program: dict(counts) for program, counts
                    in self._moe_expert_visits.items()},
                "mixed_tile_rows": dict(self._mixed_tile_rows),
                "head_rows": {program: dict(counts) for program, counts
                              in self._head_rows.items()},
                "mhc": dict(self._mhc),
                "pipeline_drains": dict(self._pipeline_drains),
                "co_batched_steps": self._co_batched_steps,
                "warmup_excluded_dispatches": self._warmup_excluded,
                "top_waste": buckets[:top_n],
            }
            if include_buckets:
                body["per_bucket"] = buckets
            return body

    def reset_for_testing(self) -> None:
        if hasattr(self, "_peak_override"):
            del self._peak_override
        self.__init__()


_TRACKER: Optional[EfficiencyTracker] = None
_TRACKER_LOCK = threading.Lock()


def get_efficiency_tracker() -> EfficiencyTracker:
    global _TRACKER
    if _TRACKER is None:
        with _TRACKER_LOCK:
            if _TRACKER is None:
                _TRACKER = EfficiencyTracker()
    return _TRACKER
