"""Batch preparation + the jitted model step.

Role parity: reference `vllm/worker/model_runner.py` (ModelRunner :45:
_prepare_prompt :95, _prepare_decode :234, _prepare_sample :360,
execute_model :516, CUDAGraphRunner :701). TPU redesign:

- CUDA graphs → XLA compilation with *shape bucketing*: decode rows and
  prefill-chunk rows flatten into ONE (token_budget,)-bucketed batch of
  the single-step program (the "mixed" dispatch), so jit caches one
  small executable family (the analogue of `_BATCH_SIZES_TO_CAPTURE`,
  model_runner.py:26-28) regardless of the prompt-length mix. Prompt
  rows are chunk tokens: each is one token with its own absolute
  position / block table / context; KV is written to the pool before
  attention reads, so a chunk token attends to the prompt's earlier
  chunks plus the in-flight chunk's earlier rows — exact causal
  attention with no whole-prompt prefill program.
- The per-step driver→worker tensor broadcast (:432-514) disappears:
  single-controller JAX passes batch arrays straight into the jitted,
  mesh-sharded step function; XLA moves what each chip needs over ICI.
- Sampling runs inside the same jitted step (see layers/sampler.py) —
  logits never leave the device.
- **Multi-step decode**: K decode iterations are fused into one device
  call (`lax.scan` over the model+sampler), with the per-token KV slots
  computed on device from the block tables. The host pays one dispatch +
  one fetch per K tokens — this is what hides host/interconnect latency
  the way the reference hides CPU batch-prep behind CUDA graphs.
- All sampler outputs pack into a single f32 array (ids bitcast) so the
  device→host path is ONE transfer per step.
- KV caches are donated: XLA updates the pool in place.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from intellillm_tpu.config import (CacheConfig, ModelConfig, ParallelConfig,
                                   SchedulerConfig)
from intellillm_tpu.layers.attention import AttentionMetadata
from intellillm_tpu.layers.sampler import (LOGPROB_K_BUCKETS,
                                           _SAMPLING_EPS, SamplingTensors,
                                           apply_penalties,
                                           apply_penalties_host,
                                           penalty_tensors_from_tokens,
                                           sample, sample_row_host)
from intellillm_tpu.logger import init_logger
from intellillm_tpu.native import build_decode_batch
from intellillm_tpu.obs import (get_compile_tracker,
                                get_efficiency_tracker, get_kernel_ledger,
                                get_step_tracer)
from intellillm_tpu.ops.ragged_attention import walk_extent
from intellillm_tpu.ops.state_rows import gather_rows, scatter_rows
from intellillm_tpu.sampling_params import SamplingParams, SamplingType
from intellillm_tpu.sequence import (SamplerOutput, SequenceGroupMetadata,
                                     SequenceGroupOutput, SequenceOutput)
from intellillm_tpu.utils import (STR_DTYPE_TO_JNP, default_len_buckets,
                                  pad_to_bucket)

logger = init_logger(__name__)

# Min padded block-table width: large enough that short contexts share one
# executable (each width bucket is a separate XLA compile of the model).
_MIN_BLOCK_TABLE_WIDTH = 16
_SAMPLE_BUCKETS = (1, 2, 4, 8, 16)
_SEED_STRIDE = np.uint32(0x9E3779B9)  # per-substep seed fold


class DecodeContState:
    """Row snapshot of ONE dispatched fused decode step, from which the
    engine derives the next continuation's snapshot (pipelined decode:
    dispatch step N+1 before fetching step N, hiding the device→host
    fetch latency behind device compute).

    Host sequence state lags the device by the un-fetched steps, so the
    snapshot carries everything the next step needs numerically: context
    and output lengths at THIS step's start, the slot each row holds in
    the padded batch, params. The engine re-snapshots per continuation
    (engine/llm_engine.py `_dispatch_cont`): rows the host knows to be
    done give up their slot, admitted sequences take free slots, and a
    row that may have stopped on a token the host has not seen stays as
    a zombie (its outputs are overshoot, discarded by the engine; its KV
    pages are free-guarded by the scheduler)."""

    def __init__(self, metas, rows, slots, ctx0, out_lens0, row_params,
                 row_loras, num_steps):
        self.metas = metas              # one per group holding a live row
        self.rows = rows                # [(request_id, seq_id)] by slot
        self.slots = slots              # np [len(rows)]: each row's slot
        self.ctx0 = ctx0                # np [B] ctx at this step's start
        self.out_lens0 = out_lens0      # np [B] output len at its start
        self.row_params = row_params    # per slot (PAD_PARAMS when empty)
        self.row_loras = row_loras      # per slot, or None (no adapters)
        self.num_steps = num_steps      # K of the fused program
        self.owners = None              # engine fills, per row:
        #                                 (Sequence, its meta, its group)
        self.state_slots = None         # per row (`rows` order): the
        #                                 sequence's state slot, for a
        #                                 model with state layers
        self.window_tables = None       # per row (`rows` order): the
        #                                 window group's block table, for
        #                                 a model with two cache groups


# What an empty slot of a continuation samples with: greedy, so that an
# all-greedy batch keeps the no-Gumbel program (SamplingTensors.build).
PAD_PARAMS = SamplingParams(temperature=0.0)


class InflightStep:
    """A dispatched-but-unfetched device step. `finalize()` performs the
    single packed device→host fetch and builds the per-substep sampler
    outputs — identical post-processing to the eager path, just split so
    the engine can overlap it with the next dispatched step."""

    def __init__(self, runner, packed, metas, rows, t1, t2, logprob_k,
                 is_prompt, num_steps, proc=None, mixed_plp=None, emit=None,
                 numerics=None, program=""):
        self.runner = runner
        self.program = program          # the dispatched program's name
        self.packed = packed            # device array (also the cont input)
        self.metas = metas
        self.rows = rows
        self.t1 = t1
        self.t2 = t2
        self.logprob_k = logprob_k
        self.is_prompt = is_prompt
        self.num_steps = num_steps
        self.proc = proc                # (proc_rows, fetched_dev, params, tokens, seeds)
        # (plp_device_array [B,1+2K], K, jobs, finals) — per-chunk prompt
        # logprob rows accumulated host-side (see _attach_prompt_logprobs).
        self.mixed_plp = mixed_plp
        # (emit_idx, emit_rows): the flat-row subset that emits samples in
        # a mixed step (decode rows + final chunks' last rows).
        self.emit = emit
        # [B, 5] device panel of per-row logit statistics (numerics
        # sentinels, obs/numerics.py) — only when --enable-numerics.
        self.numerics = numerics
        self.cont_state: Optional[DecodeContState] = None
        # A prompt step dispatched ahead of its fetch: its rows' first
        # samples on the device (`ModelRunner._first_tokens`), for the
        # continuations that take its sequences before the fetch.
        self.first_tokens = None

    def finalize(self) -> List[SamplerOutput]:
        with self.runner._tracer.span("sample"):
            return self._finalize()

    def _finalize(self) -> List[SamplerOutput]:
        r = self.runner
        if self.mixed_plp is not None:
            plp_dev, plp_k, jobs, finals = self.mixed_plp
            # plp_dev is None when the step carried no panel rows (e.g.
            # a 1-token prompt's final chunk) — only finals to assemble.
            host_plp = None
            if plp_dev is not None:
                with r._tracer.span("fetch_wait", waits_on=self.packed):
                    # lint: allow(host-sync) reason=the designed single D2H point for prompt logprobs: the panel must reach the host to be attached to request output
                    host_plp = np.asarray(plp_dev)
            r._attach_prompt_logprobs(host_plp, plp_k, jobs, finals)
        # The host waits for the device here and nowhere else in the step:
        # its own phase, so that `sample` is host work only.
        with r._tracer.span("fetch_wait", waits_on=self.packed):
            # lint: allow(host-sync) reason=the one intentional fetch per step: sampled ids must cross to the host here so the engine can emit tokens; everything upstream stays async
            packed = np.array(self.packed) if self.proc else np.asarray(
                self.packed)
        sampled, sampled_lp, topk_ids, topk_lp = r._unpack(
            packed, self.t1, self.t2, self.logprob_k)
        # The counter columns `_pack` appended, from the end: the mixing
        # matrices' column error of a model with a multi-stream residual,
        # the expert visits of a model that counts them, before them the
        # two of an expert-parallel share.
        end = packed.shape[1]
        if r._mhc_bytes_per_row:
            end -= 1
            r._efficiency.record_mhc_col_error(
                float(packed[0, end:end + 1].view(np.float32)[0]))
        if getattr(r.model, "counts_expert_visits", False):
            end -= 2
            r._efficiency.record_moe_expert_visits(
                self.program, int(packed[0, end]), int(packed[0, end + 1]))
        choices = getattr(r.model, "moe_choices_per_token", 0)
        if choices:
            r._efficiency.record_moe_assignments(
                int(packed[:, end - 2].sum()),
                int(packed[:, end - 1].sum()) * choices)
        if self.proc:
            proc_rows, fetched, row_params, row_tokens, row_seeds = self.proc
            r._resample_processor_rows(
                # lint: allow(host-sync) reason=processor rows resample on the host by design; fetched was produced by the same dispatch the packed fetch above already waited on
                proc_rows, np.asarray(fetched), row_params, row_tokens,
                row_seeds, sampled, sampled_lp, topk_ids, topk_lp, self.t1)
        if self.numerics is not None:
            # lint: allow(host-sync) reason=the sentinel panel rides the same dispatch the packed fetch above already waited on; this asarray is a ready-result copy
            stats = np.asarray(self.numerics)
            if self.emit is not None:
                pairs = list(zip(self.emit[0], self.emit[1]))
            else:
                pairs = list(enumerate(self.rows))
            r._numerics.observe_step(stats, pairs)
        rows = self.rows
        if self.emit is not None:
            emit_idx, emit_rows = self.emit
            # lint: allow(host-sync) reason=emit_idx is host-resident numpy built during batch prep; asarray here is a dtype cast, not a device fetch
            idx = np.asarray(emit_idx, np.int64)
            sampled = sampled[idx]
            sampled_lp = sampled_lp[idx]
            topk_ids = topk_ids[idx]
            topk_lp = topk_lp[idx]
            rows = emit_rows
        return r._process_sampling(self.metas, rows, sampled,
                                   sampled_lp, topk_ids, topk_lp,
                                   self.is_prompt, self.num_steps)


def _padded_run_starts(run_starts, padded_n: int) -> np.ndarray:
    """`run_starts` of a step's live rows made up to the bucket: a pad
    row continues nothing."""
    starts = np.ones(padded_n, np.int32)
    starts[:len(run_starts)] = run_starts
    return starts


class ModelRunner:

    def __init__(
        self,
        model,
        params,  # device param pytree
        model_config: ModelConfig,
        scheduler_config: SchedulerConfig,
        cache_config: CacheConfig,
        parallel_config: ParallelConfig,
        mesh=None,
        lora_manager=None,
    ) -> None:
        self.model = model
        self.params = params
        self.lora_manager = lora_manager
        self.model_config = model_config
        self.scheduler_config = scheduler_config
        self.cache_config = cache_config
        self.parallel_config = parallel_config
        self.mesh = mesh
        self._dp = (mesh.shape.get("data", 1) if mesh is not None else 1)
        # The router's width where a program runs expert layers
        # (layers/moe.py moe_ffn), else 0: its rows then count in
        # intellillm_moe_ffn_rows_total{path}, by the form that rows,
        # the model's `top_k` and this width give.
        layers = params.get("layers", ()) if isinstance(params, dict) else ()
        self._router_width = next(
            (lp["gate_router"].shape[1] for lp in layers
             if isinstance(lp, dict) and "gate_router" in lp), 0)
        self._tracer = get_step_tracer()
        self._compile_tracker = get_compile_tracker()
        self._efficiency = get_efficiency_tracker()
        self._kernel_ledger = get_kernel_ledger()
        from intellillm_tpu.obs import get_numerics_tracker
        self._numerics = get_numerics_tracker()

        self.block_size = cache_config.block_size
        self.sliding_window = model_config.get_sliding_window()
        # Query heads a KV head: with the table width, what the ragged
        # kernel sizes its tiles of rows by (`_mixed_walkers`). None: the
        # latent kernels walk a row at a time.
        self._queries_per_kv = (
            None if model_config.get_latent_cache_width()
            else model_config.get_num_attention_heads()
            // model_config.get_cache_row_shape()[1])
        # What each layer caches (`ModelConfig.get_layer_cache_kinds`).
        # A model with "state" layers gives every row of a step the state
        # slot of its sequence (`AttentionMetadata.state_slots`); the
        # programs of every other model take no such argument.
        self.layer_kinds = model_config.get_layer_cache_kinds()
        self.stateful = "state" in self.layer_kinds
        self._kv0 = self.layer_kinds.index("kv")
        # A model with "kv_window" layers beside "kv" ones hands every
        # step program the window group's block tables too
        # (`window_tables`); the programs of every other model take no
        # such argument. `_window` is the window of whichever layers have
        # one (every layer's, or the window group's).
        self.window_group = model_config.get_window_group_size()
        self._window = self.sliding_window or self.window_group
        # The layers that WALK a pool, by reach: those that hold one and
        # those that read another layer's (`get_cache_walkers`; of the
        # latter, `_shared_walkers`, every one reads a full pool).
        full_walkers, window_walkers, self._shared_walkers = \
            model_config.get_cache_walkers()
        self._layers_by_reach = (
            (full_walkers, window_walkers) if self.window_group
            else (0, 1) if self.sliding_window
            else (1, 0))
        self.num_state_slots = cache_config.num_state_slots
        state_arrays = model_config.get_state_arrays()
        self._state_in_place = [ModelConfig.state_array_in_place(shape)
                                for shape, _ in state_arrays]
        # Bytes of matrix state (a delta-rule or state-space layer's,
        # updated in its pool) one row-substep reads and writes, all
        # layers.
        self._matrix_state_bytes = (
            2 * model_config.get_num_state_layers() * sum(
                int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                for (shape, dtype), in_place in zip(state_arrays,
                                                    self._state_in_place)
                if in_place))
        # Bytes of K/V (or latent) one token moves over every layer that
        # walks a paged pool, either group (`_kv_layer_token_bytes`: in
        # one of them).
        pools, kv_heads, kv_width = model_config.get_cache_row_shape()
        cache_dtype = (model_config.dtype
                       if cache_config.cache_dtype == "auto"
                       else cache_config.cache_dtype)
        self._kv_layer_token_bytes = (
            pools * kv_heads * kv_width
            * jnp.dtype(STR_DTYPE_TO_JNP[cache_dtype]).itemsize)
        self._kv_token_bytes = self._kv_layer_token_bytes * (
            full_walkers + window_walkers)
        # A model with a multi-stream residual (layers/
        # hyper_connections.py): the streams' bytes a row moves through
        # all its sublayers; 0 for every other model.
        self._mhc_bytes_per_row = getattr(model, "mhc_bytes_per_row", 0)
        from intellillm_tpu.layers.attention import model_uses_alibi
        self._uses_alibi = model_uses_alibi(model)
        self.vocab_size = model_config.get_vocab_size()
        self.engine_seed = model_config.seed
        self.max_model_len = model_config.max_model_len

        # Fused-decode staging chunk size (see _decode_fn): parsed once so
        # every trace of the decode program chunks consistently.
        import os as _os
        raw_chunk = _os.environ.get("INTELLILLM_DECODE_CHUNK", "").strip()
        try:
            self.decode_chunk = int(raw_chunk) if raw_chunk else 16
        except ValueError:
            logger.warning("INTELLILLM_DECODE_CHUNK=%r is not an integer; "
                           "using the default (16)", raw_chunk)
            self.decode_chunk = 16
        if self._window and 0 < self._window < max(self.decode_chunk, 1):
            raise ValueError(
                f"a sliding window of {self._window} tokens is narrower "
                f"than the K-step decode's staging chunk "
                f"({self.decode_chunk}): the staged tokens would not all "
                f"be inside it")

        # ONE bucket family: decode rows + prefill-chunk rows flatten into
        # a single (token_budget,)-bucketed batch, and block-table widths
        # pad onto the SAME list — no batch×len×width shape zoo. The list
        # covers up to max(budget, max table width) so every dimension the
        # step programs see comes from this family.
        max_blocks = (scheduler_config.max_model_len + self.block_size -
                      1) // self.block_size
        self.mixed_token_buckets = default_len_buckets(
            max(scheduler_config.max_num_batched_tokens,
                scheduler_config.max_num_seqs, max_blocks,
                _MIN_BLOCK_TABLE_WIDTH),
            start=_MIN_BLOCK_TABLE_WIDTH)

        # The widest block table a step can carry (a max_model_len
        # context), on the bucket list like every other dimension.
        self.max_table_width = pad_to_bucket(
            max(max_blocks, _MIN_BLOCK_TABLE_WIDTH),
            self.mixed_token_buckets)
        # The narrowest: a deployment whose contexts are all long asks for
        # a wider one (--min-block-table-width) and gets fewer executables.
        self.min_table_width = min(
            max(scheduler_config.min_block_table_width,
                _MIN_BLOCK_TABLE_WIDTH), self.max_table_width)

        self._jit_decode = jax.jit(
            self._decode_fn,
            static_argnames=("num_steps", "logprob_k", "do_topk", "do_topp",
                             "do_minp", "do_penalties", "do_random"),
            donate_argnames=("kv_caches", ),
        )
        self._jit_decode_single = jax.jit(
            self._decode_fn_single,
            static_argnames=("num_samples", "plp_k", "logprob_k", "do_topk",
                             "do_topp", "do_minp", "do_penalties",
                             "do_random", "do_numerics"),
            donate_argnames=("kv_caches", ),
        )
        self._jit_decode_teacher = jax.jit(
            self._decode_teacher_fn,
            static_argnames=("num_steps", "logprob_k", "do_topk", "do_topp",
                             "do_minp", "do_penalties", "do_random"),
            donate_argnames=("kv_caches", ),
        )
        # Pipelined continuation: same fused program, but the input tokens
        # are sliced on device from the PREVIOUS step's packed output —
        # prev_packed is NOT donated (the host still fetches it later).
        self._jit_decode_cont = jax.jit(
            self._decode_cont_fn,
            static_argnames=("prev_t1", "num_steps", "logprob_k", "do_topk",
                             "do_topp", "do_minp", "do_penalties",
                             "do_random"),
            donate_argnames=("kv_caches", ),
        )
        # A joiner's first token goes from its prompt step to the
        # continuation on the device, in two small programs: a prompt
        # step's first samples at one length (an executable a packed
        # shape, called with every prompt step dispatched ahead of its
        # fetch), and the gather of a continuation's `join_tokens` from
        # them (an executable a decode batch). On a mesh of several
        # devices their outputs are whole on each, as the zeros a
        # continuation without joiners passes are: `decode_cont` sees
        # one placement (on one device nothing is committed to it).
        self._whole = None
        if mesh is not None and mesh.size > 1:
            from jax.sharding import NamedSharding, PartitionSpec
            self._whole = NamedSharding(mesh, PartitionSpec())
        self._jit_first_tokens = jax.jit(
            self._first_tokens_fn, static_argnames=("length", ),
            out_shardings=self._whole)
        self._jit_join_gather = jax.jit(self._join_gather_fn,
                                        out_shardings=self._whole)
        self._join_batches: Set[int] = set()

        # Pin the trace-time kernel selection at construction: every
        # executable this runner compiles bakes these paths in, and a
        # mid-flight env flip would otherwise be invisible in the logs
        # (the flags are only consulted while tracing).
        from intellillm_tpu.ops.dispatch import kernel_selection
        self.kernel_selection = kernel_selection()
        logger.info("Kernel selection for this runner's programs: %s",
                    self.kernel_selection)

    def lower_mixed_step(self, kv_struct, b: int, w: int, *,
                         logprob_k: int = 1, do_random: bool = True,
                         emit_slots: Optional[int] = None):
        """The `mixed` program lowered from shapes alone — b rows, w-block
        tables, `kv_struct` a pytree of cache ShapeDtypeStructs — for
        what only a compile can say (temp bytes, compiled text). The
        head over all b rows, which is what a step may need (every row
        a decode row, a `prompt_logprobs` panel) and so what the memory
        profile reserves; `emit_slots`: the form that runs it on so
        many emitting rows (`_decode_fn_single` `emit_rows`)."""
        def sds(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype)

        i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
        return self._jit_decode_single.lower(
            self.params, kv_struct, sds(i32, b, 1), sds(i32, b, 1),
            sds(i32, b, w), sds(i32, b), sds(f32, b), sds(i32, b),
            sds(f32, b), sds(f32, b), sds(u32, b), sds(f32, b), sds(f32, b),
            sds(f32, b), None, None, logprob_k=logprob_k, do_topk=False,
            do_topp=False, do_minp=False, do_penalties=False,
            do_random=do_random, run_starts=sds(i32, b),
            **({"state_slots": sds(i32, b)} if self.stateful else {}),
            **({"window_tables": sds(i32, b, w)} if self.window_group
               else {}),
            **({"emit_rows": sds(i32, emit_slots)} if emit_slots else {}))

    def window_table_kwargs(self, tables, rows, padded_n: int,
                            w: int) -> Dict[str, Any]:
        """The `window_tables` argument of a step program: the window
        group's block table of each live row (`tables`, at batch rows
        `rows`), padded like the primary tables. Empty for a model
        without a window group, whose programs are then called exactly
        as before."""
        if not self.window_group:
            return {}
        arr = np.zeros((padded_n, w), np.int32)
        # A chunk's rows all carry their sequence's one table (the same
        # list object): write it once for all of them.
        rows_of: Dict[int, Tuple[Any, List[int]]] = {}
        for row, t in zip(rows, tables):
            rows_of.setdefault(id(t), (t, []))[1].append(row)
        for t, its_rows in rows_of.values():
            arr[its_rows, :len(t)] = np.asarray(t, np.int32)
        return {"window_tables": self._place_batch_array(arr)}

    def state_slot_kwargs(self, row_slots, padded_n: int) -> Dict[str, Any]:
        """The `state_slots` argument of a step program: `row_slots` (one
        a live row, in row order) padded with a slot past the pool, as
        `slot_mapping` pads. Empty for a model without state layers, whose
        programs are then called exactly as before."""
        if not self.stateful:
            return {}
        slots = np.full(padded_n, self.num_state_slots, np.int32)
        slots[:len(row_slots)] = row_slots
        return {"state_slots": self._place_batch_array(slots)}

    def run_start_kwargs(self, run_starts, padded_n: int) -> Dict[str, Any]:
        """The `run_starts` argument of the `mixed` program: 0 for a row
        that continues the row before it (a prompt chunk's rows after its
        first), 1 for every other row, pad rows included. `run_starts`
        says it of the live rows, in row order; what it leaves out is 1
        (a step of decode rows hands in none)."""
        return {"run_starts": self._place_batch_array(
            _padded_run_starts(run_starts, padded_n))}

    def _row_step_counts(self, staged: bool, row_steps: int, walks):
        """`record_dispatch`'s account of what `row_steps` live
        row-substeps moved: the K/V pages their attention walked
        (`walks` as `_walks` gives them: the walked tokens x a token's
        bytes over the layers that walk a paged pool, and of those bytes
        the ones moved by layers that hold no pool of their own) and,
        besides the cache, a matrix state's recurrence
        (a delta rule, a state-space scan; nothing for a model none of
        whose state arrays is updated in its pool:
        `ModelConfig.state_array_in_place`) and a multi-stream residual's
        mixes (nothing for a model without one)."""
        walked_tokens, shared_walks = (int(n) for n in walks)
        counts = dict(walked_tokens=walked_tokens,
                      kv_walk_bytes=walked_tokens * self._kv_token_bytes)
        if self._shared_walkers:
            counts.update(shared_kv_walk_bytes=(
                shared_walks * self._kv_layer_token_bytes))
        if self._matrix_state_bytes:
            counts.update(
                linear_form="step" if staged else "chunk",
                state_bytes=row_steps * self._matrix_state_bytes)
        if self._mhc_bytes_per_row:
            counts.update(
                stream_mix_bytes=row_steps * self._mhc_bytes_per_row)
        return counts

    def _record_moe_ffn_rows(self, rows: int, substeps: int = 1) -> None:
        """`rows` x `substeps` of a dispatched program went through the
        expert layers, if the model has any."""
        if self._router_width:
            self._efficiency.record_moe_ffn_rows(
                rows, substeps, self.model.top_k, self._router_width)

    def _guarded_call(self, program, key, fn, /, *args, **kwargs):
        """Every jitted dispatch goes through here: compile tracking
        (obs/compile_tracker.py), the kernel cost ledger
        (obs/kernels.py — a new bucket's executable is introspected via
        cost_analysis()/memory_analysis() after its first successful
        dispatch; the abstract signature is captured BEFORE the call
        because kv_caches are donated), plus the watchdog dispatch
        guard — a dispatch blocked past INTELLILLM_WATCHDOG_DISPATCH_S
        fires the stall report (obs/watchdog.py)."""
        import time as _time
        from intellillm_tpu.obs import get_watchdog
        pending = self._kernel_ledger.prepare(program, key, fn, args,
                                              kwargs)
        t0 = _time.monotonic() if pending is not None else 0.0
        with get_watchdog().dispatch(program):
            try:
                out = self._compile_tracker.call(program, key, fn,
                                                 *args, **kwargs)
            except BaseException:
                self._kernel_ledger.abandon(pending)
                raise
        if pending is not None:
            self._kernel_ledger.commit(pending, _time.monotonic() - t0)
        return out

    # --- packing helpers --------------------------------------------------

    @staticmethod
    def _pack_samples(sampled, sampled_lp, topk_ids, topk_lp):
        """[B,T1] i32, [B,T1] f32, [B,T2,Kt] i32, [B,T2,Kt] f32 →
        single [B, 2*T1 + 2*T2*Kt] int32 for a 1-fetch D2H.

        Packed as INT (floats bitcast to their bit patterns): small ints
        bitcast to f32 are denormals, which TPU ops flush to zero — the
        reverse direction is safe."""
        b = sampled.shape[0]
        return jnp.concatenate([
            sampled,
            jax.lax.bitcast_convert_type(sampled_lp, jnp.int32),
            topk_ids.reshape(b, -1),
            jax.lax.bitcast_convert_type(topk_lp, jnp.int32).reshape(b, -1),
        ], axis=-1)

    @staticmethod
    def _pack_counters(samples, counters=None, live=None, substeps=1):
        """`samples` (`_pack_samples`, a row a batch row) with a model's
        `counters` (`_call_model`), summed over layers and substeps, as
        columns after everything `_unpack` reads, so that they ride the
        same fetch. "held" [B] (an expert-parallel share:
        a row's expert choices held on this chip): the held choices of
        live rows and the substeps of live rows (0 for pad rows), for
        intellillm_moe_assignments_total. Then "expert_visits" [2] (held
        experts whose weights were read, held experts, both x layers x
        substeps), in row 0 of two columns of their own, for
        intellillm_moe_expert_visits_total. Last "mhc_col_error" [B] f32
        (a multi-stream residual: a row's largest |column sum - 1| over
        its mixing matrices, the largest over the substeps): the live
        rows' largest, bitcast into row 0 of one column, for
        intellillm_mhc_col_error_max.
        """
        b = samples.shape[0]
        parts = [samples]
        counters = counters or {}
        if "held" in counters:
            parts += [jnp.where(live, counters["held"], 0)[:, None],
                      jnp.where(live, substeps, 0).astype(jnp.int32)[:, None]]
        if "expert_visits" in counters:
            parts.append(jnp.zeros((b, 2), jnp.int32).at[0].set(
                counters["expert_visits"]))
        if "mhc_col_error" in counters:
            worst = jnp.max(jnp.where(live, counters["mhc_col_error"], 0.0))
            parts.append(jnp.zeros((b, 1), jnp.int32).at[0, 0].set(
                jax.lax.bitcast_convert_type(worst, jnp.int32)))
        return jnp.concatenate(parts, axis=-1)

    @classmethod
    def _pack(cls, sampled, sampled_lp, topk_ids, topk_lp, counters=None,
              live=None, substeps=1):
        """`_pack_samples` of a step's results, then `_pack_counters`."""
        return cls._pack_counters(
            cls._pack_samples(sampled, sampled_lp, topk_ids, topk_lp),
            counters, live, substeps)

    @staticmethod
    def _unpack(packed: np.ndarray, t1: int, t2: int, kt: int):
        """Inverse of _pack, on host numpy."""
        o = 0
        sampled = packed[:, o:o + t1]; o += t1
        sampled_lp = packed[:, o:o + t1].view(np.float32); o += t1
        topk_ids = packed[:, o:o + t2 * kt].reshape(-1, t2, kt); o += t2 * kt
        topk_lp = packed[:, o:o + t2 * kt].view(np.float32).reshape(
            -1, t2, kt)
        return sampled, sampled_lp, topk_ids, topk_lp

    def _call_model(self, params, token_ids, positions, kv_caches,
                    attn_metadata, lora):
        """Models outside the llama family don't take a `lora` kwarg; only
        pass it when a batch actually uses adapters."""
        if lora is None:
            out = self.model(params, token_ids, positions, kv_caches,
                             attn_metadata)
        else:
            out = self.model(params, token_ids, positions, kv_caches,
                             attn_metadata, lora=lora)
        # A model may return counters of the call beside its result, each
        # summed over its layers: "held" ([B, L] i32, an expert-parallel
        # share: how many of a token's expert choices this chip holds; L
        # is 1 in every program), "expert_visits" ([2] i32: held
        # experts whose weights a layer read, held experts) and
        # "mhc_col_error" ([B, L] f32, a multi-stream residual: a token's
        # largest |column sum - 1| over the call's mixing matrices).
        hidden, caches, *rest = out
        counters = dict(rest[0]) if rest else {}
        for key in ("held", "mhc_col_error"):
            if key in counters:
                counters[key] = counters[key][:, 0]
        return hidden, caches, counters

    # --- jitted step functions -------------------------------------------

    def _compute_logits_and_sample(self, params, hidden_rows, temperatures,
                                   top_ks, top_ps, min_ps, seeds, pres_pen,
                                   freq_pen, rep_pen, prompt_tokens,
                                   output_tokens, lora=None, *, num_samples,
                                   logprob_k, do_topk, do_topp, do_minp,
                                   do_penalties, do_random=True,
                                   fetch_indices=None, plp_targets=None,
                                   plp_k=0, do_numerics=False,
                                   numerics_inject=None):
        """fetch_indices: optional [M] row indices whose RAW (pre-penalty)
        logits are additionally returned for the host logits_processors
        escape path (reference sampler.py `_apply_logits_processors` runs
        arbitrary Python callables on the driver; here such rows are
        re-sampled on host — see execute_model).

        plp_targets/plp_k: prompt-logprob panel for chunk-token rows —
        RAW (pre-penalty, vocab-pad-masked) log_softmax of each row's
        logits, packed [B, 1 + 2*plp_k] (target logprob bitcast, top ids,
        top logprobs bitcast). Position p's row predicts prompt token
        p+1; the host accumulates rows across chunks into the reference
        prompt-logprob panel (see _attach_prompt_logprobs).

        do_numerics/numerics_inject: the in-graph sentinels
        (obs/numerics.py). When enabled the call additionally returns a
        [B, 5] float32 panel (NaN count, +Inf count, finite max-abs,
        top-1 prob, entropy) of the FINAL sampling logits, so of the
        rows this call was handed: a `mixed` step that runs the head on
        its emitting rows (`_head_on_rows`) watches those, the rows
        `InflightStep` reads the panel at;
        numerics_inject is the forced-corruption testing hook — an
        additive [B] row vector (zeros, or NaN on a poisoned row)."""
        lora_vocab = lora is not None and "vocab" in lora
        # named_scope: metadata only, so that the trace's device
        # operations carry their layer.
        with jax.named_scope("lm_head"):
            if lora_vocab:
                # Extra-vocab LoRA: the model returns EXACTLY vocab+extra
                # columns with invalid extras already -inf (lora/layers.py
                # lora_logits) — no padding mask needed.
                logits = self.model.compute_logits(params, hidden_rows,
                                                   lora)
            else:
                logits = self.model.compute_logits(params, hidden_rows)
        logits = logits.astype(jnp.float32)
        if not lora_vocab and logits.shape[-1] > self.vocab_size:
            # TP vocab padding (parallel/mesh.py): the padded columns hold
            # zeros from the padded weights — mask them so they can never
            # win greedy argmax or receive sampling mass.
            pad = jnp.arange(logits.shape[-1]) >= self.vocab_size
            logits = jnp.where(pad[None, :], -1e30, logits)
        if do_numerics and numerics_inject is not None:
            # Forced-corruption hook: NaN rows poison everything
            # downstream (panel, penalties, sample) exactly like a real
            # in-graph numerics fault would.
            logits = logits + numerics_inject[:, None]
        fetched = (logits[fetch_indices]
                   if fetch_indices is not None else None)
        plp_out = None
        if plp_k:
            # Pre-penalty, like the legacy whole-prompt panel: penalties
            # condition SAMPLING on the generation so far; the prompt's
            # own per-position distribution is reported raw.
            lp = jax.nn.log_softmax(logits, axis=-1)
            tgt_lp = jnp.take_along_axis(lp, plp_targets[:, None], axis=-1)
            top_lp, top_ids = jax.lax.top_k(lp, plp_k)
            plp_out = jnp.concatenate([
                jax.lax.bitcast_convert_type(tgt_lp, jnp.int32),
                top_ids.astype(jnp.int32),
                jax.lax.bitcast_convert_type(top_lp, jnp.int32),
            ], axis=-1)                                  # [B, 1 + 2K]
        if do_penalties:
            # Token histories scatter into [N, V] mask/counts ON DEVICE —
            # the host ships only the padded id lists.
            prompt_mask, output_counts = penalty_tensors_from_tokens(
                prompt_tokens, output_tokens, logits.shape[-1])
            logits = apply_penalties(logits, prompt_mask, output_counts,
                                     pres_pen, freq_pen, rep_pen)
        num_stats = None
        if do_numerics:
            # Sentinel panel over the FINAL sampling logits (post
            # penalties): pad columns sit at -1e30 and excluded tokens
            # at -inf — both are masking semantics, not anomalies, so
            # max-abs skips them and only +inf counts as inf.
            p = jax.nn.softmax(logits, axis=-1)
            finite = jnp.isfinite(logits)
            nan_c = jnp.sum(jnp.isnan(logits), axis=-1)
            inf_c = jnp.sum(jnp.isposinf(logits), axis=-1)
            mag = jnp.where(finite & (logits > -1e29),
                            jnp.abs(logits), 0.0)
            max_abs = jnp.max(mag, axis=-1)
            top1 = jnp.max(p, axis=-1)
            entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0),
                               axis=-1)
            num_stats = jnp.stack(
                [nan_c.astype(jnp.float32), inf_c.astype(jnp.float32),
                 max_abs, top1, entropy], axis=-1)
        with jax.named_scope("sampler"):
            out = sample(logits, temperatures, top_ks, top_ps, min_ps,
                         seeds, logprob_k=logprob_k,
                         num_samples=num_samples, do_topk=do_topk,
                         do_topp=do_topp, do_minp=do_minp,
                         do_random=do_random)
        return out + (fetched, plp_out, num_stats)

    def _decode_cont_fn(self, params, kv_caches, prev_packed, join_tokens,
                        join_mask, positions, block_tables, context_lens,
                        temperatures, top_ks, top_ps, min_ps, seeds,
                        pres_pen, freq_pen, rep_pen, prompt_tokens,
                        output_tokens, lora=None, state_slots=None,
                        window_tables=None, *,
                        prev_t1, num_steps, logprob_k, do_topk, do_topp,
                        do_minp, do_penalties, do_random=True):
        """Continuation of a fused decode: a row's input token is the last
        substep's sample from the previous step's packed output (column
        prev_t1-1 of the _pack layout), so the host never needs the
        previous step's results to keep the device busy. A row that joins
        the batch at this step (`join_mask` [B, 1]) reads its prefill's
        token from `join_tokens` [B, 1] instead; a plain continuation is
        the all-false mask of the same program."""
        token_ids = jnp.where(join_mask, join_tokens,
                              prev_packed[:, prev_t1 - 1:prev_t1])
        return self._decode_fn(
            params, kv_caches, token_ids, positions, block_tables,
            context_lens, temperatures, top_ks, top_ps, min_ps, seeds,
            pres_pen, freq_pen, rep_pen, prompt_tokens, output_tokens,
            lora, num_steps=num_steps, logprob_k=logprob_k,
            do_topk=do_topk, do_topp=do_topp, do_minp=do_minp,
            do_penalties=do_penalties, do_random=do_random,
            state_slots=state_slots, window_tables=window_tables)

    @staticmethod
    def _first_tokens_fn(packed, *, length):
        """A step's first sample of every flat row (column 0 of the
        _pack layout), padded to `length` so that the gather below is
        keyed by the decode batch alone."""
        first = packed[:, 0]
        return jnp.pad(first, (0, max(0, length - first.shape[0])))

    @staticmethod
    def _join_gather_fn(tokens, first_tokens, rows):
        """The continuation's `join_tokens` [B, 1] with the first tokens
        a prompt step sampled: slot i takes `first_tokens[rows[i]]`, and
        keeps `tokens[i]` where `rows[i]` is -1. The step need not have
        been fetched, nor have run: device order makes it ready."""
        picked = first_tokens[jnp.maximum(rows, 0)]
        return jnp.where(rows >= 0, picked, tokens[:, 0])[:, None]

    def _decode_teacher_fn(self, params, kv_caches, teacher_tokens,
                           positions, block_tables, context_lens,
                           temperatures, top_ks, top_ps, min_ps, seeds,
                           pres_pen, freq_pen, rep_pen, prompt_tokens,
                           output_tokens, lora=None, *, num_steps,
                           logprob_k, do_topk, do_topp, do_minp,
                           do_penalties, do_random=True):
        """Teacher-forced fused decode (speculative verification): substep
        k's input is teacher_tokens[:, k] — the draft's proposal — not the
        previous substep's sample, so one device call scores every draft
        position with the TARGET model while committing their KV (rejected
        positions are simply overwritten on the next step; context length
        governs what attention ever reads). Outputs are the target's own
        choices per position, which the host compares against the drafts
        (reference rejection-sampler role for greedy acceptance)."""
        return self._decode_fn(
            params, kv_caches, teacher_tokens[:, :1], positions,
            block_tables, context_lens, temperatures, top_ks, top_ps,
            min_ps, seeds, pres_pen, freq_pen, rep_pen, prompt_tokens,
            output_tokens, lora, num_steps=num_steps, logprob_k=logprob_k,
            do_topk=do_topk, do_topp=do_topp, do_minp=do_minp,
            do_penalties=do_penalties, do_random=do_random,
            teacher_tokens=teacher_tokens)

    def _decode_fn(self, params, kv_caches, token_ids, positions,
                   block_tables, context_lens, temperatures, top_ks, top_ps,
                   min_ps, seeds, pres_pen, freq_pen, rep_pen, prompt_tokens,
                   output_tokens, lora=None, *, num_steps, logprob_k,
                   do_topk, do_topp, do_minp, do_penalties,
                   do_random=True, teacher_tokens=None, state_slots=None,
                   window_tables=None):
        """K fused decode iterations (staged, chunked).

        The paged pool stays loop-invariant (read-only) through each scan —
        carrying it would make XLA double-buffer gigabytes. Each substep's
        K/V land in small per-layer staging buffers [B, C, Hkv, D]; the
        attention layer merges pool-part and stage-part by logsumexp.

        Chunking: every substep reads the FULL staging buffer (masked), so
        a single K-wide scan pays O(K²·B·Hkv·D) HBM traffic — at K=128 the
        stage-side reads cost as much as the pool kernel itself (measured
        ~36% of the fused step on v5e). Instead the K steps run as
        ceil(K/C) statically-unrolled chunks of C=INTELLILLM_DECODE_CHUNK
        (default 16) substeps: scan over a C-wide stage, scatter the chunk
        into the pool (the buffers are dead between chunks, so XLA reuses
        them in place — no double buffering), advance the pool context,
        repeat. Stage traffic drops K/C-fold; the extra scatters write the
        same total bytes as the single post-scan scatter did.

        A layer with a sliding window runs the same way: its pool part
        sees what the window leaves of the pool (the lower edge a token
        further every substep, `layers/attention.py`), its stage is
        committed through its own group's table (`window_tables`, where
        the model has two groups).
        """
        b = token_ids.shape[0]
        base_pos = positions[:, 0]              # [B] = n-1
        base_ctx = context_lens                 # [B] = n (0 for pad rows)
        kinds = self.layer_kinds
        kv0 = kv_caches[self._kv0][0]
        hkv, d, cache_dtype = kv0.shape[1], kv0.shape[3], kv0.dtype
        # A state layer's arrays, by kind (`ModelConfig.get_state_arrays`):
        # a few rows of activations are gathered into a stage here,
        # carried through the substeps and scattered back after the last;
        # a matrix a head is carried as its pool, which the layer updates
        # in place by slot (a pad row's slot is the pool's pad row). A
        # row without a context is a pad row whatever slot it was given.
        if state_slots is not None:
            state_slots = jnp.where(base_ctx > 0, state_slots,
                                    self.num_state_slots)
        state_stages = {
            i: tuple(pool if in_place else gather_rows(pool, state_slots)
                     for pool, in_place in zip(pools, self._state_in_place))
            for i, pools in enumerate(kv_caches) if kinds[i] == "state"}
        # What the scan closes over: the paged pools. A state layer's
        # arrays are all in its stage.
        fixed = [() if kinds[i] == "state" else tuple(pools)
                 for i, pools in enumerate(kv_caches)]

        # Chunk schedule: full chunks plus a shorter tail when K is not a
        # multiple (e.g. K=40, C=16 → [16, 16, 8]). decode_chunk <= 0
        # disables chunking (one K-wide scan).
        chunk = self.decode_chunk
        if chunk <= 0:
            chunk = num_steps
        chunk_sizes = [chunk] * (num_steps // chunk)
        if num_steps % chunk:
            chunk_sizes.append(num_steps % chunk)

        from intellillm_tpu.ops.kv_cache import (
            commit_staged_chunk, commit_staged_chunk_by_layer)
        if self.model_config.get_packed_latent_layers():
            # One latent pool for every layer: its layers go as rows.
            commit_staged_chunk = commit_staged_chunk_by_layer

        def make_substep(pool_ctx, cur_fixed, chunk_base):
            def substep(carry, k):
                cur_tokens, stages = carry
                if teacher_tokens is not None:
                    # Speculative verification: inputs come from the draft
                    # proposal, not the previous substep's sample.
                    cur_tokens = jnp.take(teacher_tokens,
                                          chunk_base + k, axis=1)
                pos_k = jnp.minimum(base_pos + chunk_base + k,
                                    self.max_model_len - 1)
                meta = AttentionMetadata(
                    slot_mapping=None,
                    context_lens=pool_ctx,
                    block_tables=block_tables,
                    staged=True,
                    stage_index=k,
                    mesh=self.mesh,
                    state_slots=state_slots,
                    window_block_tables=window_tables,
                    live_rows=base_ctx > 0,
                )
                # A layer's cache is (K, V) or the 1-tuple (latent pool,);
                # staged, the layer gets its pools then their stages (a
                # state layer: its stage alone).
                caches4 = [tuple(pools) + tuple(stage)
                           for pools, stage in zip(cur_fixed, stages)]
                hidden, caches4, counters = self._call_model(
                    params, cur_tokens[:, None], pos_k[:, None], caches4,
                    meta, lora)
                stages = [tuple(c[len(pools):])
                          for pools, c in zip(cur_fixed, caches4)]
                g = (chunk_base + k).astype(jnp.uint32)
                seeds_k = seeds + g * _SEED_STRIDE
                (sampled, lp, tk_ids,
                 tk_lp, _, _, _) = self._compute_logits_and_sample(
                    params, hidden[:, 0], temperatures, top_ks, top_ps,
                    min_ps, seeds_k, pres_pen, freq_pen, rep_pen,
                    prompt_tokens, output_tokens, lora, num_samples=1,
                    logprob_k=logprob_k, do_topk=do_topk, do_topp=do_topp,
                    do_minp=do_minp, do_penalties=do_penalties,
                    do_random=do_random)
                next_tokens = sampled[:, 0]
                ys = (next_tokens, lp[:, 0], tk_ids, tk_lp, counters)
                return (next_tokens, stages), ys
            return substep

        cur_caches = fixed
        cur_tokens = token_ids[:, 0]
        ys_chunks = []
        chunk_base = 0
        for csize in chunk_sizes:
            # Tokens already in the pool: everything before this chunk's
            # first input token (stage slot 0 = position
            # base_pos+chunk_base).
            pool_ctx = jnp.where(
                base_ctx > 0,
                jnp.minimum(base_ctx - 1 + chunk_base, self.max_model_len),
                0)
            stages = [state_stages[i] if kinds[i] == "state" else
                      tuple(jnp.zeros((b, csize, hkv, d), cache_dtype)
                            for _ in pools)
                      for i, pools in enumerate(cur_caches)]
            (cur_tokens, stages), ys = jax.lax.scan(
                make_substep(pool_ctx, cur_caches, chunk_base),
                (cur_tokens, stages),
                jnp.arange(csize, dtype=jnp.int32))
            ys_chunks.append(ys)

            # Commit the chunk's staged tokens (positions
            # base_pos+chunk_base .. +csize-1) into the pool,
            # page-granular (see ops/kv_cache.py:commit_staged_chunk).
            # Overshoot tokens past max_model_len are dropped, not
            # clamped onto the last slot — the engine discards them.
            start = base_pos + chunk_base
            n_valid = jnp.where(
                base_ctx > 0,
                jnp.clip(self.max_model_len - start, 0, csize), 0)
            state_stages = {i: stages[i] for i in state_stages}
            cur_caches = [
                pools if kinds[i] == "state" else
                commit_staged_chunk(
                    stage, pools, start, n_valid,
                    window_tables if kinds[i] == "kv_window"
                    else block_tables)
                for i, (pools, stage) in enumerate(zip(cur_caches, stages))]
            chunk_base += csize

        new_caches = [
            tuple(stage if in_place else
                  scatter_rows(pool, state_slots, stage)
                  for pool, stage, in_place in zip(
                      kv_caches[i], state_stages[i], self._state_in_place))
            if kinds[i] == "state" else pools
            for i, pools in enumerate(cur_caches)]
        # [K, B, ...] per ys leaf, chunks concatenated along the step axis.
        ys = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                          *ys_chunks) if len(ys_chunks) > 1 else ys_chunks[0]
        sampled_k, lp_k, tk_ids_k, tk_lp_k = ys[:4]
        # [K, B, ...] → [B, K, ...]
        packed = self._pack(jnp.swapaxes(sampled_k, 0, 1),
                            jnp.swapaxes(lp_k, 0, 1),
                            jnp.swapaxes(tk_ids_k, 0, 1),
                            jnp.swapaxes(tk_lp_k, 0, 1),
                            counters={
                                key: (a.max(0) if key == "mhc_col_error"
                                      else a.sum(0))
                                for key, a in ys[4].items()},
                            live=base_ctx > 0, substeps=num_steps)
        return packed, new_caches

    def _decode_fn_single(self, params, kv_caches, token_ids, positions,
                          block_tables, context_lens, temperatures, top_ks,
                          top_ps, min_ps, seeds, pres_pen, freq_pen, rep_pen,
                          prompt_tokens, output_tokens, lora=None,
                          fetch_indices=None, plp_targets=None,
                          numerics_inject=None, state_slots=None,
                          window_tables=None, run_starts=None,
                          emit_rows=None, *,
                          num_samples=1, plp_k=0, do_numerics=False,
                          logprob_k, do_topk, do_topp, do_minp,
                          do_penalties, do_random=True):
        """Unstaged single-step program — THE mixed dispatch: writes KV to
        the pool before attention, so decode rows and prefill-chunk rows
        run side by side in one flat batch. Also used whenever K == 1.
        `window_tables`: the window group's tables, for a model with two
        cache groups (a row's token goes to the same logical block and
        offset in either). `run_starts` [B]: 0 for a row that continues
        the row before it (`AttentionMetadata.run_starts`).

        `emit_rows` [E] (E < B): the batch rows that emit a token, made
        up to E with B. The head and the sampler then run on those rows'
        hidden states alone, with their rows of the sampling tensors, and
        not at all in a step none of whose rows emits; every other row
        of the packed output's sample columns is zeros (`_head_on_rows`;
        `fetch_indices` then index `emit_rows`). None: every row goes
        through the head, as a step needs whose rows all emit or that
        carries a prompt-logprob panel (`plp_k`).

        num_samples > 1 serves final-chunk `best_of` fan-out (every row
        draws num_samples gumbel streams; a row's sample 0 is bit-equal
        to its num_samples=1 draw, so co-batched decode rows are
        unaffected). plp_k > 0 adds the per-row prompt-logprob panel."""
        bs = self.block_size
        pos = positions[:, 0]

        def slots_of(tables, pool):
            # A pad row's slot is one past the pool.
            slot = (jnp.take_along_axis(tables, (pos // bs)[:, None],
                                        axis=1)[:, 0] * bs + pos % bs)
            return jnp.where(context_lens > 0, slot,
                             pool.shape[0] * bs)[:, None]

        window_kwargs = {}
        if window_tables is not None:
            window_kwargs = dict(
                window_block_tables=window_tables,
                window_slot_mapping=slots_of(
                    window_tables,
                    kv_caches[self.layer_kinds.index("kv_window")][0]))
        meta = AttentionMetadata(
            slot_mapping=slots_of(block_tables, kv_caches[self._kv0][0]),
            context_lens=context_lens,
            block_tables=block_tables,
            mesh=self.mesh,
            state_slots=state_slots,
            run_starts=run_starts,
            live_rows=context_lens > 0,
            **window_kwargs,
        )
        hidden, new_caches, counters = self._call_model(
            params, token_ids, pos[:, None], kv_caches, meta, lora)

        def head(rows, sampling, lora, inject):
            """The head and the sampler over `rows` [N, H] of hidden
            states: (packed samples [N, ...], raw logits of
            `fetch_indices`, prompt-logprob panel, numerics panel)."""
            (sampled, lp, tk_ids, tk_lp, fetched, plp_out,
             num_stats) = self._compute_logits_and_sample(
                params, rows, *sampling, lora,
                num_samples=num_samples, logprob_k=logprob_k,
                do_topk=do_topk, do_topp=do_topp, do_minp=do_minp,
                do_penalties=do_penalties, do_random=do_random,
                fetch_indices=fetch_indices, plp_targets=plp_targets,
                plp_k=plp_k, do_numerics=do_numerics,
                numerics_inject=inject)
            samples = self._pack_samples(sampled, lp, tk_ids[:, None, :],
                                         tk_lp[:, None, :])
            return samples, fetched, plp_out, num_stats

        sampling = (temperatures, top_ks, top_ps, min_ps, seeds, pres_pen,
                    freq_pen, rep_pen, prompt_tokens, output_tokens)
        if emit_rows is None:
            samples, fetched, plp_out, num_stats = head(
                hidden[:, 0], sampling, lora, numerics_inject)
        else:
            assert not plp_k, "a prompt-logprob panel is of every row"
            samples, fetched, plp_out, num_stats = self._head_on_rows(
                head, emit_rows, hidden[:, 0], sampling, lora,
                numerics_inject)
        packed = self._pack_counters(samples, counters,
                                     live=context_lens > 0)
        extras = ()
        if plp_out is not None:
            extras += (plp_out, )
        if fetched is not None:
            extras += (fetched, )
        if num_stats is not None:
            extras += (num_stats, )
        return (packed, ) + extras + (new_caches, )

    @staticmethod
    def _head_on_rows(head, emit_rows, hidden_rows, sampling, lora, inject):
        """`head` (of `_decode_fn_single`) over the rows `emit_rows` [E]
        names of a batch of B, each with its own rows of the per-row
        arguments, and its results back in their rows of a batch of B:
        zeros in every other row. An entry of `emit_rows` that is B names
        no row; where none names one, `head` does not run (the other
        side of a conditional: its weights are not read) and every row
        is zeros. A row's logits come from the same function of the same
        hidden row as in a call over all B rows, and its draw is keyed by
        its own seed, not by its place (`layers/sampler.py`
        `row_gumbel`)."""
        b = hidden_rows.shape[0]
        at = jnp.minimum(emit_rows, b - 1)

        def of_rows(a):
            return None if a is None else a[at]

        def run():
            return head(
                hidden_rows[at], tuple(of_rows(a) for a in sampling),
                None if lora is None else
                {**lora, "row_slots": lora["row_slots"][at]},
                of_rows(inject))

        def skip():
            return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                                jax.eval_shape(run))

        samples, fetched, plp_out, num_stats = jax.lax.cond(
            jnp.any(emit_rows < b), run, skip)

        def in_batch(rows):
            return None if rows is None else scatter_rows(
                jnp.zeros((b, ) + rows.shape[1:], rows.dtype), emit_rows,
                rows)

        return in_batch(samples), fetched, plp_out, in_batch(num_stats)

    # --- batch prep -------------------------------------------------------

    def _walked_tokens(self, seen, query_ends, w: int,
                       walkers=None) -> int:
        """Tokens the attention kernel's page walk moves for rows that
        must see `seen` tokens of their contexts under `w`-wide tables,
        a layer: the mean over the layers that walk a paged pool, of
        which those with a sliding window walk from the page of the
        first position a query that ends at `query_ends` (its position
        + 1) sees. `walkers` (a `mixed` step, `_mixed_walkers`): the
        rows that walk; the others ride in a walker's tile and move
        nothing."""
        return int(self._walks(seen, query_ends, w, walkers)[0])

    def _walks(self, seen, query_ends, w: int, walkers=None):
        """(`_walked_tokens`, the tokens moved by the walks of layers
        that hold no pool and read a full layer's, summed over those
        layers: 0 for a model without such readers), an int64 pair."""
        seen = np.asarray(seen, np.int64)
        full_layers, window_layers = self._layers_by_reach
        walked = shared = 0

        def moved(pages) -> int:
            return int((pages if walkers is None else pages[walkers]).sum())
        if full_layers:
            pages, _ = walk_extent(seen, self.block_size, w)
            full_pages = moved(pages)
            walked += full_layers * full_pages
            shared = self._shared_walkers * full_pages * self.block_size
        if window_layers:
            lo = np.clip(np.asarray(query_ends, np.int64) - self._window,
                         0, None)
            pages, _ = walk_extent(seen, self.block_size, w, lo=lo)
            walked += window_layers * moved(pages)
        return np.array([walked * self.block_size
                         // (full_layers + window_layers), shared], np.int64)

    def _mixed_walkers(self, run_starts, padded_n: int, w: int):
        """(which of a `mixed` step's live rows walk their context, the
        live rows by the tile they run in): the ragged kernel walks a
        run's context once a tile of its rows (`ops/ragged_attention.py`
        `ragged_walkers`), the latent kernels once a row (None)."""
        n = len(run_starts)
        if self._queries_per_kv is None:
            return None, {"one": n}
        from intellillm_tpu.ops.ragged_attention import ragged_walkers
        walkers, alone = ragged_walkers(
            _padded_run_starts(run_starts, padded_n),
            np.arange(padded_n) < n, w, self._queries_per_kv)
        alone = int(alone[:n].sum())
        return walkers[:n], {"one": alone, "many": n - alone}

    def _attended_tokens(self, query_ends, context_tokens: int
                         ) -> Optional[Dict[str, int]]:
        """What queries ending at `query_ends` attend to in one layer of
        each reach (`intellillm_dispatch_attended_tokens_total`); None
        for a model without a window, which counts context tokens only."""
        if not self._window:
            return None
        full_layers, _ = self._layers_by_reach
        ends = np.asarray(query_ends, np.int64)
        return {"full": context_tokens if full_layers else 0,
                "window": int(np.minimum(ends, self._window).sum())}

    def _decode_counts(self, ctx: np.ndarray, num_steps: int, w: int):
        """(walks as `_walks` gives them, attended tokens) of a K-step
        decode program (`_decode_fn`): every substep of a staging chunk
        walks the pool as the chunk found it, the context less the row's
        newest token plus the chunks committed before; substep k's query
        ends at the context + k."""
        chunk = self.decode_chunk if self.decode_chunk > 0 else num_steps
        live = ctx > 0
        walked = np.zeros(2, np.int64)
        for base in range(0, num_steps, chunk):
            pool = np.where(live, np.minimum(ctx - 1 + base,
                                             self.max_model_len), 0)
            substeps = min(chunk, num_steps - base)
            if not self._window:
                walked += substeps * self._walks(pool, None, w)
                continue
            k = np.arange(substeps)[:, None]
            walked += self._walks(
                np.broadcast_to(pool, (substeps, ) + pool.shape),
                pool[None, :] + k + 1, w)
        if not self._window:
            return walked, None
        ends = (ctx[live][None, :] + np.arange(num_steps)[:, None])
        return walked, self._attended_tokens(ends, int(ends.sum()))

    def table_width(self, blocks: int) -> int:
        """The padded width of a step's block tables whose longest holds
        `blocks` entries."""
        return pad_to_bucket(max(blocks, self.min_table_width),
                             self.mixed_token_buckets)

    def decode_batch_bucket(self, rows: int) -> int:
        """The padded batch a fresh decode step of `rows` rows runs at."""
        return pad_to_bucket(rows, self.mixed_token_buckets)

    def _prepare_decode(
        self,
        seq_group_metadata_list: List[SequenceGroupMetadata],
    ) -> Tuple[Dict[str, np.ndarray], List[Tuple[str, int]]]:
        rows: List[Tuple[str, int]] = []
        tokens: List[int] = []
        poss: List[int] = []
        ctxs: List[int] = []
        tables: List[List[int]] = []
        window_tables: List[List[int]] = []
        row_slots: List[int] = []

        for meta in seq_group_metadata_list:
            assert not meta.is_prompt
            for seq_id, data in meta.seq_data.items():
                n = data.get_len()
                rows.append((meta.request_id, seq_id))
                tokens.append(data.get_last_token_id())
                poss.append(n - 1)
                ctxs.append(n)
                tables.append(list(meta.block_tables[seq_id]))
                if self.window_group:
                    window_tables.append(meta.window_block_tables[seq_id])
                if self.stateful:
                    row_slots.append(meta.state_slots[seq_id])

        b = self.decode_batch_bucket(len(rows))
        w = self.table_width(max(len(t) for t in tables))

        token_ids, positions, context_lens, block_tables = \
            build_decode_batch(tables, tokens, poss, ctxs, b, w)

        arrays = {"token_ids": token_ids, "positions": positions,
                  "context_lens": context_lens, "block_tables": block_tables}
        arrays["_state"] = {
            **self.state_slot_kwargs(row_slots, b),
            **self.window_table_kwargs(window_tables, range(len(rows)), b,
                                       w)}
        arrays["_window_tables"] = window_tables
        arrays["_eff"] = {
            "real_rows": len(rows),
            "ctxs": np.asarray(ctxs, np.int64),
            "width_real": max(len(t) for t in tables),
            "width_padded": w,
        }
        return arrays, rows

    def _place_batch_array(self, arr):
        """Shard a [B, ...] host array over the mesh "data" axis (dp > 1),
        else hand it to jit as-is. Batches that don't divide the axis
        (e.g. a single long prompt on a dp mesh) replicate — jit still
        runs them, just without batch-sharded placement."""
        if arr is None:
            return None
        if self._dp <= 1 or arr.shape[0] % self._dp:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P(*(("data", ) + (None, ) * (arr.ndim - 1)))
        return jax.device_put(jnp.asarray(arr),
                              NamedSharding(self.mesh, spec))

    def _first_tokens(self, packed):
        """`_first_tokens_fn` dispatched on a prompt step's output. The
        compile tracker counts the two join programs' executables; the
        kernel ledger, which compiles a program a second time to read
        its cost, lists the model's programs only."""
        return self._compile_tracker.call(
            "join_gather", ("first_tokens", ) + tuple(packed.shape),
            self._jit_first_tokens, packed,
            length=self.mixed_token_buckets[-1])

    def _join_tokens(self, b: int,
                     first_token_rows: Dict["InflightStep", np.ndarray]):
        """A continuation's `join_tokens` [b, 1] on the device: zeros,
        and over them the first tokens of each prompt step in flight
        that a slot joins from (`first_token_rows`: by step, the flat
        row of its output a slot takes, -1 for none). A batch's first
        continuation calls the gather whether or not a row joins, so
        that it compiles where `decode_cont` does."""
        tokens = jax.device_put(np.zeros((b, 1), np.int32), self._whole)
        sources = [(step.first_tokens, rows)
                   for step, rows in first_token_rows.items()]
        if b not in self._join_batches:
            self._join_batches.add(b)
            if not sources:
                sources = [(jax.device_put(
                    np.zeros(self.mixed_token_buckets[-1], np.int32),
                    self._whole), np.full(b, -1, np.int32))]
        for first_tokens, rows in sources:
            tokens = self._compile_tracker.call(
                "join_gather", (b, ) + tuple(first_tokens.shape),
                self._jit_join_gather, tokens, first_tokens, rows)
        return tokens

    def _activate_lora(self, row_loras, padded_n: int):
        """Returns (lora_state, effective vocab width). Extra-vocab LoRA
        widens the logits to vocab+extra; every sampling-tensor build must
        use that width for the top_k "disabled" value and the penalty pad
        sentinel (the sentinel would otherwise scatter into a REAL
        extra-token column)."""
        lora_state = None
        if self.lora_manager is not None:
            # Compile stability: a LoRA-enabled engine passes the pytree
            # on EVERY step (row_loras None means "no adapter rows" —
            # all rows ride the reserved all-zero slot 0), so the jit
            # bucket key's `lora_state is not None` toggle never flips
            # and adapter traffic can't mint new executables.
            lora_state = self.lora_manager.set_active_loras(
                row_loras if row_loras is not None else [], padded_n)
        eff_vocab = self.vocab_size
        if lora_state is not None and "vocab" in lora_state:
            eff_vocab += lora_state["vocab"]["extra_embed"].shape[1]
        return lora_state, eff_vocab

    def _sampling_args_device(self, st: SamplingTensors, padded_n: int):
        """The positional device-arg tuple every step program takes after
        context_lens — order must match _decode_fn/_decode_fn_single."""
        place = self._place_batch_array
        zeros = np.zeros(padded_n, np.float32)
        return (
            place(st.temperatures), place(st.top_ks), place(st.top_ps),
            place(st.min_ps), place(st.seeds),
            place(st.presence_penalties if st.do_penalties else zeros),
            place(st.frequency_penalties if st.do_penalties else zeros),
            place(st.repetition_penalties if st.do_penalties
                  else np.ones(padded_n, np.float32)),
            place(st.prompt_tokens) if st.do_penalties else None,
            place(st.output_tokens) if st.do_penalties else None,
        )

    def _row_seed(self, seq_id: int, step: int, sp, data) -> int:
        if sp.seed is not None:
            # A request that names its seed: a function of (seed, stream
            # within the request, step) only — not of sequence ids, which
            # depend on what else the engine has served. The step term
            # uses the fused program's per-substep fold, so a token's
            # seed is the same whichever program samples it.
            return (sp.seed * 0x9E3779B1 + data.seed_stream * 0x85EBCA77 +
                    step * int(_SEED_STRIDE)) & 0xFFFFFFFF
        # Deterministic per (engine seed, sequence, step).
        h = (self.engine_seed * 0x9E3779B1 + seq_id * 0x85EBCA77 +
             step * 0xC2B2AE3D) & 0xFFFFFFFF
        return h

    # --- execute ----------------------------------------------------------

    def execute_model(
        self,
        seq_group_metadata_list: List[SequenceGroupMetadata],
        kv_caches,
        num_decode_steps: int = 1,
        defer_fetch: bool = False,
    ) -> Tuple[Any, Any]:
        """Returns (outputs_per_substep, new_kv_caches) — or, with
        `defer_fetch`, (InflightStep, new_kv_caches): the device step is
        dispatched but its results not fetched, so the caller can overlap
        the fetch with further dispatched work (pipelined decode)."""
        if not seq_group_metadata_list:
            return [], kv_caches

        if any(m.token_chunk_size is not None
               for m in seq_group_metadata_list):
            assert num_decode_steps == 1, (
                "mixed chunked-prefill steps are single-step")
            return self._execute_mixed(seq_group_metadata_list, kv_caches,
                                       defer_fetch=defer_fetch)

        if any(m.is_prompt for m in seq_group_metadata_list):
            raise ValueError(
                "prompt entry without chunked-prefill metadata reached "
                "execute_model; the legacy homogeneous prefill path is "
                "gone — prompts execute as chunk tokens of the mixed "
                "dispatch (the scheduler sets token_chunk_size).")
        place = self._place_batch_array

        with self._tracer.span("prepare_inputs"):
            arrays, rows = self._prepare_decode(seq_group_metadata_list)

            eff_info = arrays.pop("_eff")
            state_kwargs = arrays.pop("_state")
            row_window_tables = arrays.pop("_window_tables")
            padded_n = arrays["token_ids"].shape[0]

            # Per-row sampling params / seeds / token histories.
            row_params: List[SamplingParams] = []
            row_seeds: List[int] = []
            row_tokens: List[Tuple[List[int], List[int]]] = []
            row_out_lens: List[int] = []
            meta_by_req = {m.request_id: m for m in seq_group_metadata_list}
            for req_id, seq_id in rows:
                meta = meta_by_req[req_id]
                data = meta.seq_data[seq_id]
                row_params.append(meta.sampling_params)
                row_out_lens.append(data.get_output_len())
                row_seeds.append(self._row_seed(seq_id,
                                                data.get_output_len(),
                                                meta.sampling_params, data))
                row_tokens.append(data.token_views())

            row_loras = None
            if self.lora_manager is not None:
                row_loras = [meta_by_req[req_id].lora_request
                             for req_id, _ in rows]
            lora_state, eff_vocab = self._activate_lora(row_loras, padded_n)
            st = SamplingTensors.build(row_params, row_seeds, row_tokens,
                                       eff_vocab, padded_n)

            # logits_processors escape path: rows carrying Python
            # processors get their RAW logits fetched and are re-sampled
            # on host (the scheduler forces K=1 for such batches).
            proc_rows = [i for i, sp in enumerate(row_params)
                         if sp.logits_processors]
            fetch_indices = None
            if proc_rows:
                m = pad_to_bucket(len(proc_rows), self.mixed_token_buckets)
                fetch_indices = np.zeros(m, np.int32)
                fetch_indices[:len(proc_rows)] = proc_rows

            common = dict(
                logprob_k=st.logprob_k,
                do_topk=st.do_topk, do_topp=st.do_topp, do_minp=st.do_minp,
                do_penalties=st.do_penalties, do_random=st.do_random,
            )
            sampling_args = self._sampling_args_device(st, padded_n)

        num_steps = num_decode_steps
        # The engine clamps num_decode_steps to 1 at init for ALiBi (the
        # bias needs the true query position per substep): the staged
        # decode program would be silently wrong for it.
        assert num_steps == 1 or not self._uses_alibi, (
            "fused multi-step decode requested for an ALiBi model; the "
            "engine should have clamped K to 1")
        decode_args = (
            self.params, kv_caches,
            place(arrays["token_ids"]), place(arrays["positions"]),
            place(arrays["block_tables"]), place(arrays["context_lens"]),
            *sampling_args, lora_state)
        fetched = None
        num_stats_dev = None
        program = "mixed" if num_steps == 1 else "decode_fused"
        device_idle = self._tracer.device_dry()
        if num_steps == 1:
            # Numerics sentinels (obs/numerics.py): opt-in extra device
            # output. When OFF the call binds exactly as pre-sentinel
            # code did — no new kwargs, no new jit cache entry, so the
            # default-off path provably adds zero executables.
            num_on = self._numerics.enabled
            numerics_kwargs = {}
            if num_on:
                numerics_kwargs = dict(
                    do_numerics=True,
                    numerics_inject=place(
                        self._numerics.inject_vector(rows, padded_n)))
            # Mirror of jit's dispatch-cache key: padded shapes + static
            # args + pytree-structure toggles (see obs/compile_tracker.py).
            # Same key layout as _execute_mixed — a decode-only step IS a
            # mixed step with zero chunk rows and hits the same
            # executable.
            bucket = (padded_n, arrays["block_tables"].shape[1], 1, 0,
                      fetch_indices.shape[0] if fetch_indices is not None
                      else None,
                      lora_state is not None,
                      tuple(sorted(common.items())))
            if num_on:
                bucket = bucket + ("numerics", )
            with self._tracer.span("execute"):
                result = self._guarded_call(
                    program, bucket, self._jit_decode_single,
                    *decode_args,
                    place(fetch_indices) if fetch_indices is not None
                    else None, **common, **numerics_kwargs, **state_kwargs,
                    **self.run_start_kwargs((), padded_n))
                self._tracer.note_dispatch(program, result[0], bucket)
            result = list(result)
            packed = result.pop(0)
            fetched = result.pop(0) if proc_rows else None
            num_stats_dev = result.pop(0) if num_on else None
            new_caches = result.pop(0)
        else:
            assert not proc_rows, (
                "logits_processors present in a fused K>1 decode batch; "
                "the scheduler should have forced K=1")
            bucket = (padded_n, arrays["block_tables"].shape[1],
                      num_steps,
                      None,
                      lora_state is not None,
                      tuple(sorted(common.items())))
            with self._tracer.span("execute"):
                packed, new_caches = self._guarded_call(
                    program, bucket, self._jit_decode,
                    *decode_args, num_steps=num_steps, **common,
                    **state_kwargs)
                self._tracer.note_dispatch(program, packed, bucket)
        t1 = t2 = num_steps

        # Each substep computes one token per row, pad rows included; a
        # row's substep j walks a context one token longer than j - 1's.
        real_rows = eff_info["real_rows"]
        if num_steps == 1:
            # The single-step program walks a row's context less its own
            # token, as every `mixed` step does.
            walked = self._walks(eff_info["ctxs"] - 1, eff_info["ctxs"],
                                 eff_info["width_padded"])
            attended = self._attended_tokens(eff_info["ctxs"],
                                             int(eff_info["ctxs"].sum()))
            tile_rows = {"one": real_rows}
        else:
            walked, attended = self._decode_counts(
                eff_info["ctxs"], num_steps, eff_info["width_padded"])
            tile_rows = None
        self._efficiency.record_dispatch(
            "decode", real_rows, padded_n,
            real_tokens=real_rows * num_steps,
            padded_tokens=padded_n * num_steps,
            width_real=eff_info["width_real"],
            width_padded=eff_info["width_padded"],
            program=program, device_idle=device_idle,
            row_steps=real_rows * num_steps,
            context_tokens=(int(eff_info["ctxs"].sum()) * num_steps
                            + real_rows * num_steps * (num_steps - 1) // 2),
            attended_tokens=attended, tile_rows=tile_rows,
            head_rows=dict(batch=padded_n * num_steps,
                           computed=padded_n * num_steps,
                           emitting=real_rows * num_steps),
            **self._row_step_counts(num_steps > 1, real_rows * num_steps,
                                    walked))
        self._record_moe_ffn_rows(padded_n, num_steps)

        # ONE device→host transfer for everything, performed by
        # InflightStep.finalize() — immediately on the eager path, or
        # overlapped with later dispatches on the pipelined path.
        step = InflightStep(
            self, packed, seq_group_metadata_list, rows, t1, t2,
            st.logprob_k, False, num_steps,
            proc=((proc_rows, fetched, row_params, row_tokens, row_seeds)
                  if proc_rows else None),
            numerics=num_stats_dev, program=program)
        if num_steps > 1:
            pad = padded_n - len(rows)
            out_lens0 = np.zeros(padded_n, np.int64)
            out_lens0[:len(rows)] = row_out_lens
            step.cont_state = DecodeContState(
                seq_group_metadata_list, rows, np.arange(len(rows)),
                arrays["context_lens"].copy(), out_lens0,
                row_params + [PAD_PARAMS] * pad,
                None if row_loras is None else row_loras + [None] * pad,
                num_steps)
            if self.stateful:
                step.cont_state.state_slots = [
                    meta_by_req[req_id].state_slots[seq_id]
                    for req_id, seq_id in rows]
            if self.window_group:
                step.cont_state.window_tables = row_window_tables
        if defer_fetch:
            return step, new_caches
        return step.finalize(), new_caches

    def _execute_mixed(
        self,
        seq_group_metadata_list: List[SequenceGroupMetadata],
        kv_caches,
        defer_fetch: bool = False,
    ) -> Tuple[Any, Any]:
        """Mixed token-budget step — THE execution path for prefill work:
        decode tokens and prefill-chunk tokens lie in ONE flat
        (token_budget,)-bucketed batch of the single-step program. Each
        row is one token with its own absolute position, block table, and
        context_lens = position + 1; the program writes every row's KV to
        its pool slot BEFORE attention reads, so a chunk token at
        position p attends to the prompt's earlier chunks (already in the
        pool — including a prefix-cache hit's reused blocks, which the
        scheduler skips by starting the first chunk at the computed-token
        count) plus the in-flight chunk's earlier rows — exact
        per-sequence causal attention with no cross-sequence leakage
        (each row reads only its own block table).

        Only decode rows and the final chunk's last row emit samples.
        The features the legacy homogeneous prefill served are flat-row
        concerns here: final-chunk RANDOM `best_of` fan-out raises the
        program's num_samples (co-batched rows' sample 0 is unchanged),
        beam fan-out reads the emitted row's top-k panel in
        _process_sampling, prompt_logprobs rows carry per-row panel
        targets accumulated host-side across chunks, and
        logits_processors rows on the emission subset take the host
        resample escape path."""
        place = self._place_batch_array

        with self._tracer.span("prepare_inputs"):
            rows: List[Tuple[str, int]] = []
            tokens: List[int] = []
            poss: List[int] = []
            ctxs: List[int] = []
            tables: List[List[int]] = []
            row_params: List[SamplingParams] = []
            row_seeds: List[int] = []
            row_tokens: List[Tuple[np.ndarray, np.ndarray]] = []
            row_loras_src: List[Any] = []
            row_slots: List[int] = []
            window_tables: List[List[int]] = []
            # Flat-row emission subset: all decode rows; only the LAST
            # row of a FINAL chunk (mid-prompt rows' samples are
            # meaningless).
            emit_idx: List[int] = []
            emit_rows: List[Tuple[str, int]] = []
            # prompt_logprobs: each chunk row at position p contributes
            # prompt position p+1's panel entry; accumulated on the
            # SequenceData across chunks (see _attach_prompt_logprobs).
            plp_jobs: List[Tuple[int, int, Any, int, int]] = []
            plp_finals: List[Tuple[Any, Any]] = []
            plp_k = 0
            num_samples = 1
            n_chunk_tokens = 0
            n_chunk_groups = 0
            n_decode_rows = 0
            # 0 for a row that continues the row before it: a chunk's
            # rows after its first (`AttentionMetadata.run_starts`).
            run_starts: List[int] = []

            for meta in seq_group_metadata_list:
                sp = meta.sampling_params
                if meta.token_chunk_size is not None:
                    (seq_id,) = meta.seq_data.keys()
                    data = meta.seq_data[seq_id]
                    start = meta.num_computed_tokens
                    size = meta.token_chunk_size
                    final = start + size == data.get_len()
                    all_ids = data.get_token_ids()
                    table = list(meta.block_tables[seq_id])
                    if self.window_group:
                        window_tables += (
                            [meta.window_block_tables[seq_id]] * size)
                    # Same (seed, penalty-window) a whole-prompt prefill
                    # of this prompt would use, so the final chunk's
                    # sample reproduces legacy output exactly.
                    seed = self._row_seed(seq_id, data.get_output_len(),
                                          sp, data)
                    views = data.token_views()
                    want_plp = sp.prompt_logprobs is not None
                    n_prompt = data.get_prompt_len()
                    for j in range(size):
                        pos = start + j
                        rows.append((meta.request_id, seq_id))
                        tokens.append(int(all_ids[pos]))
                        poss.append(pos)
                        ctxs.append(pos + 1)
                        tables.append(table)
                        row_params.append(sp)
                        row_seeds.append(seed)
                        row_tokens.append(views)
                        row_loras_src.append(meta.lora_request)
                        if self.stateful:
                            row_slots.append(meta.state_slots[seq_id])
                        if want_plp and pos + 1 < n_prompt:
                            plp_jobs.append((len(rows) - 1,
                                             sp.prompt_logprobs, data,
                                             int(all_ids[pos + 1]), pos + 1))
                            plp_k = max(plp_k, sp.prompt_logprobs, 1)
                    run_starts += [1] + [0] * (size - 1)
                    n_chunk_tokens += size
                    n_chunk_groups += 1
                    if final:
                        emit_idx.append(len(rows) - 1)
                        emit_rows.append((meta.request_id, seq_id))
                        if (sp.sampling_type == SamplingType.RANDOM
                                and sp.best_of > 1):
                            num_samples = max(num_samples, sp.best_of)
                        if want_plp:
                            plp_finals.append((meta, data))
                else:
                    for seq_id, data in meta.seq_data.items():
                        n = data.get_len()
                        rows.append((meta.request_id, seq_id))
                        tokens.append(data.get_last_token_id())
                        poss.append(n - 1)
                        ctxs.append(n)
                        tables.append(list(meta.block_tables[seq_id]))
                        if self.window_group:
                            window_tables.append(
                                meta.window_block_tables[seq_id])
                        row_params.append(sp)
                        row_seeds.append(
                            self._row_seed(seq_id, data.get_output_len(),
                                           sp, data))
                        row_tokens.append(data.token_views())
                        row_loras_src.append(meta.lora_request)
                        if self.stateful:
                            row_slots.append(meta.state_slots[seq_id])
                        emit_idx.append(len(rows) - 1)
                        emit_rows.append((meta.request_id, seq_id))
                        run_starts.append(1)
                        n_decode_rows += 1

            num_samples = pad_to_bucket(num_samples, _SAMPLE_BUCKETS)
            if plp_jobs:
                plp_k = pad_to_bucket(plp_k, LOGPROB_K_BUCKETS)
            else:
                plp_k = 0
            plp_targets = None
            if plp_k:
                plp_targets = np.zeros(
                    pad_to_bucket(len(rows), self.mixed_token_buckets),
                    np.int32)
                for row, _, _, tgt, _ in plp_jobs:
                    plp_targets[row] = tgt

            padded_n = pad_to_bucket(len(rows), self.mixed_token_buckets)
            # The head runs on the rows that emit, made up to a bucket of
            # their own, where that is fewer rows than the step holds; a
            # prompt-logprob panel is of every row.
            emit_slots = pad_to_bucket(len(emit_idx),
                                       self.mixed_token_buckets)
            head_kwargs = {}
            if emit_slots < padded_n and not plp_k:
                emit_arr = np.full(emit_slots, padded_n, np.int32)
                emit_arr[:len(emit_idx)] = emit_idx
                head_kwargs = {"emit_rows": jnp.asarray(emit_arr)}
            head_rows = dict(
                batch=padded_n, emitting=len(emit_idx),
                computed=(padded_n if not head_kwargs
                          else emit_slots if emit_idx else 0))

            # logits_processors escape: only emitting rows matter (the
            # panel is pre-penalty, mid-chunk samples are discarded).
            proc_rows = [i for i in emit_idx
                         if row_params[i].logits_processors]
            fetch_indices = None
            if proc_rows:
                m = pad_to_bucket(len(proc_rows), self.mixed_token_buckets)
                fetch_indices = np.zeros(m, np.int32)
                # They index the rows the head runs on.
                slot_of = {row: slot for slot, row in enumerate(emit_idx)}
                fetch_indices[:len(proc_rows)] = (
                    [slot_of[i] for i in proc_rows] if head_kwargs
                    else proc_rows)

            w = self.table_width(max(len(t) for t in tables))
            token_ids, positions, context_lens, block_tables = \
                build_decode_batch(tables, tokens, poss, ctxs, padded_n, w)

            row_loras = (row_loras_src if self.lora_manager is not None
                         else None)
            lora_state, eff_vocab = self._activate_lora(row_loras, padded_n)
            st = SamplingTensors.build(row_params, row_seeds, row_tokens,
                                       eff_vocab, padded_n)
            common = dict(
                logprob_k=st.logprob_k,
                do_topk=st.do_topk, do_topp=st.do_topp, do_minp=st.do_minp,
                do_penalties=st.do_penalties, do_random=st.do_random,
            )
            sampling_args = self._sampling_args_device(st, padded_n)
            state_kwargs = {
                **self.state_slot_kwargs(row_slots, padded_n),
                **self.window_table_kwargs(window_tables, range(len(rows)),
                                           padded_n, w),
                **self.run_start_kwargs(run_starts, padded_n)}

        # Numerics sentinels: when OFF the dispatch binds exactly as the
        # pre-sentinel code did (no extra kwargs → identical jit cache
        # key → zero new executables); when ON every mixed step carries
        # the panel output and the (usually all-zero) inject vector.
        num_on = self._numerics.enabled
        numerics_kwargs = {}
        if num_on:
            numerics_kwargs = dict(
                do_numerics=True,
                numerics_inject=place(
                    self._numerics.inject_vector(rows, padded_n)))
        bucket = (padded_n, w, num_samples, plp_k,
                  fetch_indices.shape[0] if fetch_indices is not None
                  else None,
                  lora_state is not None,
                  tuple(sorted(common.items())))
        if num_on:
            bucket = bucket + ("numerics", )
        if head_kwargs:
            bucket = bucket + (("emit_rows", emit_slots), )
        device_idle = self._tracer.device_dry()
        with self._tracer.span("execute"):
            result = self._guarded_call(
                "mixed", bucket, self._jit_decode_single,
                self.params, kv_caches,
                place(token_ids), place(positions),
                place(block_tables), place(context_lens),
                *sampling_args, lora_state,
                place(fetch_indices) if fetch_indices is not None else None,
                place(plp_targets) if plp_k else None,
                num_samples=num_samples, plp_k=plp_k, **common,
                **numerics_kwargs, **state_kwargs, **head_kwargs)
            # (A step whose head does not run is the shorter for it.)
            self._tracer.note_dispatch(
                "mixed", result[0],
                bucket + (bool(emit_idx), ) if head_kwargs else bucket)
        result = list(result)
        packed = result.pop(0)
        plp_dev = result.pop(0) if plp_k else None
        fetched = result.pop(0) if proc_rows else None
        num_stats_dev = result.pop(0) if num_on else None
        new_caches = result.pop(0)

        # Per-phase efficiency attribution: each real token is counted
        # exactly once under its own phase; the flat batch's bucket
        # padding is charged to the decode side (whose row count it
        # extends) unless the step is chunk-only. The dispatch itself
        # (one program, every row one token) is counted once, with
        # whichever side reports first.
        pad_rows = padded_n - len(rows)
        # The walk sees a row's context less the row's own token.
        ctx_arr = np.fromiter(ctxs, np.int64, len(ctxs))
        walkers, tile_rows = self._mixed_walkers(run_starts, padded_n, w)
        dispatch = dict(program="mixed", device_idle=device_idle,
                        row_steps=len(rows), context_tokens=sum(ctxs),
                        attended_tokens=self._attended_tokens(
                            ctx_arr, int(ctx_arr.sum())),
                        tile_rows=tile_rows, head_rows=head_rows,
                        **self._row_step_counts(
                            False, len(rows), self._walks(
                                ctx_arr - 1, ctx_arr, w, walkers)))
        if n_chunk_groups:
            self._efficiency.record_dispatch(
                "prefill", n_chunk_groups, n_chunk_groups,
                real_tokens=n_chunk_tokens,
                padded_tokens=(n_chunk_tokens
                               + (0 if n_decode_rows else pad_rows)),
                **dispatch)
            dispatch = {}
        if n_chunk_groups and n_decode_rows:
            self._efficiency.record_co_batched_step()
        if n_decode_rows:
            self._efficiency.record_dispatch(
                "decode", n_decode_rows, padded_n - n_chunk_tokens,
                real_tokens=n_decode_rows,
                padded_tokens=padded_n - n_chunk_tokens,
                width_real=max(len(t) for t in tables),
                width_padded=w, **dispatch)
        self._record_moe_ffn_rows(padded_n)

        step = InflightStep(
            self, packed, seq_group_metadata_list, rows, num_samples, 1,
            st.logprob_k, False, 1,
            proc=((proc_rows, fetched, row_params, row_tokens, row_seeds)
                  if proc_rows else None),
            mixed_plp=((plp_dev, plp_k, plp_jobs, plp_finals)
                       if (plp_jobs or plp_finals) else None),
            emit=(emit_idx, emit_rows), program="mixed",
            numerics=num_stats_dev)
        if defer_fetch:
            # K = 1 has no continuation program to take them.
            if n_chunk_groups and self.scheduler_config.num_decode_steps > 1:
                step.first_tokens = self._first_tokens(packed)
            return step, new_caches
        return step.finalize(), new_caches

    def execute_decode_cont(
        self,
        cont: DecodeContState,
        tables: List[List[int]],
        first_token_rows: Dict[InflightStep, np.ndarray],
        prev_packed,
        prev_t1: int,
        kv_caches,
        defer_fetch: bool = True,
    ) -> Tuple[Any, Any]:
        """Dispatch a continuation step of a fused decode batch. `cont` is
        this step's own snapshot (contexts advanced numerically by the
        engine: the host sequence state is allowed to trail). A row's
        input token is sliced on device from `prev_packed`, except for a
        row that joins at this step: its prompt step is still in flight,
        and `first_token_rows` has, for that step, the flat row of its
        output the slot's token is gathered from on the device ([B], -1
        for no row). `tables` are the rows' block tables, in `cont.rows`
        order, already grown by the scheduler to cover this step's
        writes."""
        num_steps = cont.num_steps
        with self._tracer.span("prepare_inputs"):
            b = cont.ctx0.shape[0]
            ctx = cont.ctx0.astype(np.int32)
            positions = np.maximum(ctx - 1, 0).astype(np.int32)[:, None]
            w = self.table_width(
                max((len(t) for t in tables), default=1))
            block_tables = np.zeros((b, w), np.int32)
            for slot, t in zip(cont.slots, tables):
                block_tables[slot, :len(t)] = t
            join_mask = np.zeros((b, 1), np.bool_)
            for rows in first_token_rows.values():
                join_mask[:, 0] |= rows >= 0

            # Seeds advance exactly as a fresh (caught-up) dispatch would
            # compute them, so pipelined sampling streams match
            # unpipelined.
            meta_by_req = {m.request_id: m for m in cont.metas}
            row_seeds = [0] * b
            for slot, (req_id, sid) in zip(cont.slots, cont.rows):
                row_seeds[slot] = self._row_seed(
                    sid, int(cont.out_lens0[slot]), cont.row_params[slot],
                    meta_by_req[req_id].seq_data[sid])

            lora_state, eff_vocab = self._activate_lora(cont.row_loras, b)
            st = SamplingTensors.build(cont.row_params, row_seeds, None,
                                       eff_vocab, b)
            # The engine only continues penalty-free, processor-free,
            # non-beam rows: what the fused K>1 program takes.
            assert not st.do_penalties, (
                "decode continuation dispatched for a penalty-bearing batch")

            place = self._place_batch_array
            sampling_args = self._sampling_args_device(st, b)
            state_kwargs = {}
            if self.stateful:
                # By batch slot, not by row order: a continuation's rows
                # keep the seats they had.
                slots_arr = np.full(b, self.num_state_slots, np.int32)
                slots_arr[cont.slots] = cont.state_slots
                state_kwargs = {"state_slots": place(slots_arr)}
            state_kwargs.update(self.window_table_kwargs(
                cont.window_tables or (), cont.slots, b, w))
        flags = dict(logprob_k=st.logprob_k, do_topk=st.do_topk,
                     do_topp=st.do_topp, do_minp=st.do_minp,
                     do_penalties=False, do_random=st.do_random)
        # A joined continuation is the same executable as a plain one:
        # the join arrays are always passed, their shapes follow `b`.
        bucket = (b, w, prev_t1, num_steps, lora_state is not None,
                  tuple(sorted(flags.items())))
        device_idle = self._tracer.device_dry()
        with self._tracer.span("execute"):
            packed, new_caches = self._guarded_call(
                "decode_cont", bucket, self._jit_decode_cont,
                self.params, kv_caches, prev_packed,
                self._join_tokens(b, first_token_rows),
                place(join_mask), place(positions), place(block_tables),
                place(ctx), *sampling_args, lora_state,
                prev_t1=prev_t1, num_steps=num_steps, **flags,
                **state_kwargs)
            self._tracer.note_dispatch("decode_cont", packed, bucket)

        live_rows = len(cont.rows)
        walked, attended = self._decode_counts(ctx.astype(np.int64),
                                               num_steps, w)
        self._efficiency.record_dispatch(
            "decode", live_rows, b,
            real_tokens=live_rows * num_steps,
            padded_tokens=b * num_steps,
            width_real=max((len(t) for t in tables), default=1),
            width_padded=w,
            program="decode_cont", device_idle=device_idle,
            row_steps=live_rows * num_steps,
            context_tokens=(int(ctx.sum()) * num_steps
                            + live_rows * num_steps * (num_steps - 1) // 2),
            attended_tokens=attended,
            head_rows=dict(batch=b * num_steps, computed=b * num_steps,
                           emitting=live_rows * num_steps),
            **self._row_step_counts(True, live_rows * num_steps, walked))
        self._record_moe_ffn_rows(b, num_steps)

        step = InflightStep(self, packed, cont.metas, cont.rows, num_steps,
                            num_steps, st.logprob_k, False, num_steps,
                            emit=(cont.slots, cont.rows),
                            program="decode_cont")
        step.cont_state = cont
        if defer_fetch:
            return step, new_caches
        return step.finalize(), new_caches

    def execute_model_teacher(
        self,
        seq_group_metadata_list: List[SequenceGroupMetadata],
        kv_caches,
        teacher_rows: List[List[int]],
        num_steps: int,
    ) -> Tuple[List[SamplerOutput], Any]:
        """Teacher-forced decode over `num_steps` positions per row
        (speculative verification with the TARGET model): teacher_rows[i]
        holds the `num_steps` input tokens for live row i
        ([last_accepted, draft_1, ..]). Returns the target's per-position
        choices in the usual per-substep SamplerOutput shape."""
        with self._tracer.span("prepare_inputs"):
            arrays, rows = self._prepare_decode(seq_group_metadata_list)
        eff_info = arrays.pop("_eff")
        # Speculative decoding refuses state and window models.
        arrays.pop("_state")
        arrays.pop("_window_tables")
        padded_n = arrays["token_ids"].shape[0]
        teacher = np.zeros((padded_n, num_steps), np.int32)
        for i, toks in enumerate(teacher_rows):
            teacher[i, :len(toks)] = toks

        row_params: List[SamplingParams] = []
        row_seeds: List[int] = []
        meta_by_req = {m.request_id: m for m in seq_group_metadata_list}
        for req_id, seq_id in rows:
            meta = meta_by_req[req_id]
            data = meta.seq_data[seq_id]
            row_params.append(meta.sampling_params)
            row_seeds.append(self._row_seed(seq_id, data.get_output_len(),
                                            meta.sampling_params, data))

        lora_state, eff_vocab = self._activate_lora(None, padded_n)
        st = SamplingTensors.build(row_params, row_seeds, None, eff_vocab,
                                   padded_n)
        assert not st.do_penalties, (
            "speculative verification dispatched for a penalty batch")
        place = self._place_batch_array
        sampling_args = self._sampling_args_device(st, padded_n)
        flags = dict(logprob_k=st.logprob_k, do_topk=st.do_topk,
                     do_topp=st.do_topp, do_minp=st.do_minp,
                     do_penalties=False, do_random=st.do_random)
        bucket = (padded_n, arrays["block_tables"].shape[1], num_steps,
                  lora_state is not None, tuple(sorted(flags.items())))
        with self._tracer.span("execute"):
            packed, new_caches = self._guarded_call(
                "decode_teacher", bucket, self._jit_decode_teacher,
                self.params, kv_caches, place(teacher),
                place(arrays["positions"]), place(arrays["block_tables"]),
                place(arrays["context_lens"]), *sampling_args, lora_state,
                num_steps=num_steps, **flags)
            self._tracer.note_dispatch("decode_teacher", packed,
                                        bucket)
        self._efficiency.record_dispatch(
            "decode", eff_info["real_rows"], padded_n,
            real_tokens=eff_info["real_rows"] * num_steps,
            padded_tokens=padded_n * num_steps,
            width_real=eff_info["width_real"],
            width_padded=eff_info["width_padded"])
        self._record_moe_ffn_rows(padded_n, num_steps)
        step = InflightStep(self, packed, seq_group_metadata_list, rows,
                            num_steps, num_steps, st.logprob_k, False,
                            num_steps)
        return step.finalize(), new_caches

    def _attach_prompt_logprobs(self, plp_packed, k, jobs, finals):
        """Accumulate per-chunk prompt-logprob rows and, on a prompt's
        final chunk, assemble the reference-format PromptLogprobs list
        (None for token 0, then {token_id: logprob} with the top-k panel)
        onto the requesting metadata object; the engine copies it to the
        SequenceGroup.

        plp_packed: [B, 1+2K] per flat row (target logprob bitcast, top
        ids, top logprobs bitcast). jobs: (row, requested_k, seq_data,
        target_token, prompt_position) — the chunk rows whose panel entry
        lands at prompt_position. Entries accumulate on the SequenceData
        (survives across the prompt's chunk steps; reset on recompute
        preemption) keyed by position, so out-of-order recomputation
        simply overwrites."""
        for row, req_k, data, tgt_tok, t in jobs:
            tgt_lp = plp_packed[row, 0:1].view(np.float32)[0]
            top_ids = plp_packed[row, 1:1 + k]
            top_lp = plp_packed[row, 1 + k:1 + 2 * k].view(np.float32)
            d = {int(tgt_tok): float(tgt_lp)}
            for tt, lpv in zip(top_ids[:req_k], top_lp[:req_k]):
                d.setdefault(int(tt), float(lpv))
            acc = data._chunk_prompt_logprobs
            if acc is None:
                acc = data._chunk_prompt_logprobs = {}
            acc[t] = d
        for meta, data in finals:
            n = data.get_prompt_len()
            acc = data._chunk_prompt_logprobs or {}
            meta.computed_prompt_logprobs = (
                [None] + [acc.get(t) for t in range(1, n)])
            data._chunk_prompt_logprobs = None

    # --- sampler post-processing -----------------------------------------

    def _resample_processor_rows(self, proc_rows, fetched, row_params,
                                 row_tokens, row_seeds, sampled, sampled_lp,
                                 topk_ids, topk_lp, t1):
        """Host escape path for `logits_processors` (reference
        `sampler.py:_apply_logits_processors`): the callables run on the
        fetched raw logits row, then penalties/temperature/top-k/p/min-p/
        sampling mirror the device semantics in numpy. Writes the results
        into the unpacked output views (single decode step or the prefill
        sample; the scheduler forces K=1 for processor-bearing batches)."""
        kt = topk_ids.shape[-1]
        for j, row in enumerate(proc_rows):
            sp = row_params[row]
            prompt_ids, output_ids = row_tokens[row]
            logits = np.array(fetched[j, :self.vocab_size], np.float32)
            for proc in sp.logits_processors:
                logits = np.asarray(proc(list(output_ids), logits),
                                    np.float32)
            if (abs(sp.presence_penalty) >= _SAMPLING_EPS
                    or abs(sp.frequency_penalty) >= _SAMPLING_EPS
                    or abs(sp.repetition_penalty - 1.0) >= _SAMPLING_EPS):
                logits = apply_penalties_host(
                    logits, prompt_ids, output_ids, sp.presence_penalty,
                    sp.frequency_penalty, sp.repetition_penalty)
            s, s_lp, tk_i, tk_l = sample_row_host(
                logits, sp, row_seeds[row], num_samples=t1, logprob_k=kt)
            sampled[row, :] = s
            sampled_lp[row, :] = s_lp
            topk_ids[row, 0, :] = tk_i
            topk_lp[row, 0, :] = tk_l

    def _process_sampling(
        self,
        seq_group_metadata_list: List[SequenceGroupMetadata],
        rows: List[Tuple[str, int]],
        sampled: np.ndarray,      # [B, T1]
        sampled_lp: np.ndarray,   # [B, T1]
        topk_ids: np.ndarray,     # [B, T2, Kt]
        topk_lp: np.ndarray,      # [B, T2, Kt]
        is_prompt: bool,
        num_steps: int,
    ) -> List[SamplerOutput]:
        """Build one SamplerOutput per fused substep."""
        row_idx_by_req: Dict[str, List[Tuple[int, int]]] = {}
        for i, (req_id, seq_id) in enumerate(rows):
            row_idx_by_req.setdefault(req_id, []).append((i, seq_id))

        outputs_per_step: List[SamplerOutput] = []
        for k in range(num_steps):
            t = 0 if is_prompt else k
            output: SamplerOutput = []
            for meta in seq_group_metadata_list:
                group_rows = row_idx_by_req.get(meta.request_id, [])
                if not group_rows:
                    # Mid-prompt chunk group in a mixed step: no sample
                    # this step; the engine treats the empty group as
                    # still prefilling.
                    output.append(SequenceGroupOutput([]))
                    continue
                sp = meta.sampling_params
                stype = sp.sampling_type

                def logprob_dict(row, token, token_lp):
                    d = {int(token): float(token_lp)}
                    if sp.logprobs:
                        for tt, lp in zip(topk_ids[row, t, :sp.logprobs],
                                          topk_lp[row, t, :sp.logprobs]):
                            d.setdefault(int(tt), float(lp))
                    return d

                samples: List[SequenceOutput] = []
                if stype == SamplingType.BEAM:
                    assert num_steps == 1
                    bw = sp.best_of
                    if meta.is_prompt:
                        (row, parent_id) = group_rows[0]
                        for j in range(2 * bw):
                            samples.append(SequenceOutput(
                                parent_id, int(topk_ids[row, 0, j]),
                                logprob_dict(row, topk_ids[row, 0, j],
                                             topk_lp[row, 0, j])))
                    else:
                        cands = []
                        for row, seq_id in group_rows:
                            cum = meta.seq_data[seq_id].cumulative_logprob
                            for j in range(2 * bw):
                                cands.append((cum + float(topk_lp[row, 0, j]),
                                              seq_id, row, j))
                        cands.sort(key=lambda c: c[0], reverse=True)
                        for score, seq_id, row, j in cands[:2 * bw]:
                            samples.append(SequenceOutput(
                                seq_id, int(topk_ids[row, 0, j]),
                                logprob_dict(row, topk_ids[row, 0, j],
                                             topk_lp[row, 0, j])))
                elif meta.is_prompt:
                    (row, parent_id) = group_rows[0]
                    for s in range(sp.best_of):
                        tok = int(sampled[row, s])
                        samples.append(SequenceOutput(
                            parent_id, tok,
                            logprob_dict(row, tok, sampled_lp[row, s])))
                else:
                    for row, seq_id in group_rows:
                        tok = int(sampled[row, k])
                        samples.append(SequenceOutput(
                            seq_id, tok,
                            logprob_dict(row, tok, sampled_lp[row, k])))

                output.append(SequenceGroupOutput(
                    samples,
                    prompt_logprobs=(getattr(meta,
                                             "computed_prompt_logprobs",
                                             None)
                                     if meta.is_prompt else None)))
            outputs_per_step.append(output)
        return outputs_per_step
