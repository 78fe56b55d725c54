"""Engine-integrated speculative decoding worker.

Role parity: reference `vllm/worker/spec_decode/multi_step_worker.py:22`
(draft multi-step execution) + `vllm/model_executor/layers/
rejection_sampler.py:9` (acceptance) — components the reference shipped
but never wired into its engine; here they run end-to-end behind
--speculative-model / --num-speculative-tokens.

TPU design:
- The draft model proposes K tokens in ONE fused-scan device call (the
  scan feeds each sample into the next substep on device — the entire
  reference MultiStepWorker host loop collapses into the existing
  `_decode_fn`).
- The target verifies all K proposals plus a bonus token in ONE
  teacher-forced fused call (`_decode_teacher_fn`): substep k's input is
  the draft's token, outputs are the target's own per-position choices.
- Greedy acceptance keeps the longest agreeing prefix + the target's
  token at the first disagreement, so the emitted stream is exactly the
  target's greedy stream (the correctness test).
- No KV rollback: rejected positions simply get overwritten by the next
  step's writes, and the context length governs what attention reads —
  both for the target pool and the draft pool (which shares the
  scheduler's block tables but has its own arrays sized for the draft
  architecture).
- The draft is purely advisory: if its cache goes stale (a fallback
  step ran without it), acceptance drops but outputs stay exact.

Mixed-dispatch integration (per-row speculation): a scheduler round may
contain chunked-prefill rows, spec-ineligible decode rows (sampled,
multi-seq, LoRA, penalties) and spec-eligible greedy rows at once. The
scheduler marks the eligible rows in `SchedulerOutputs.spec_plan`; this
worker splits the batch, runs the draft+teacher pass over the plan rows
and ONE single-step mixed dispatch over everything else (whose chunk KV
is mirrored into the draft pool so finished prompts start speculating
with full draft context), then re-interleaves the per-substep outputs in
the original metadata order — ineligible rows emit exactly one token per
round, plan rows emit a variable accepted+1.

The draft length K is live: `adaptive_num_decode_steps()` consults the
`AdaptiveKController` (SLO-burn / TPOT / acceptance signals) once per
engine step and the boot-time warm-up compiles the full
`[k_min, k_max]` ladder of draft + teacher executables, so a K change
never compiles.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set

from intellillm_tpu.config import (CacheConfig, LoRAConfig, ModelConfig,
                                   ParallelConfig, SchedulerConfig,
                                   SpeculativeConfig)
from intellillm_tpu.logger import init_logger
from intellillm_tpu.sequence import (SamplerOutput, SequenceGroupMetadata,
                                     SequenceGroupOutput)
from intellillm_tpu.worker.spec_decode.adaptive import AdaptiveKController
from intellillm_tpu.worker.spec_decode.eligibility import meta_spec_eligible
from intellillm_tpu.worker.spec_decode.metrics import get_spec_stats
from intellillm_tpu.worker.worker import Worker

logger = init_logger(__name__)


class SpecDecodeWorker(Worker):

    def __init__(
        self,
        model_config: ModelConfig,
        parallel_config: ParallelConfig,
        scheduler_config: SchedulerConfig,
        cache_config: CacheConfig,
        lora_config: Optional[LoRAConfig] = None,
        speculative_config: Optional[SpeculativeConfig] = None,
    ) -> None:
        super().__init__(model_config, parallel_config, scheduler_config,
                         cache_config, lora_config)
        assert speculative_config is not None
        self.spec_config = speculative_config
        self.k_spec = speculative_config.num_speculative_tokens
        self.k_min = getattr(speculative_config, "k_min", self.k_spec)
        self.k_max = getattr(speculative_config, "k_max", self.k_spec)
        # Spec mode never pipelines: skip the continuation-program
        # compile; warm_up_model warms teacher/draft programs instead.
        self.warm_cont_program = False
        # BENCHMARK-ONLY: accept every draft regardless of the target's
        # choices. Dummy-weight perf runs have no meaningful acceptance
        # rate (random draft/target never agree), so this measures the
        # machinery's a=1.0 upper bound; outputs are NOT target-exact.
        import os
        from intellillm_tpu.utils import parse_env_flag
        self.force_accept = parse_env_flag(
            os.environ.get("INTELLILLM_SPEC_FORCE_ACCEPT")) is True
        if self.force_accept:
            logger.warning(
                "INTELLILLM_SPEC_FORCE_ACCEPT=1: acceptance check "
                "bypassed (benchmark mode) — outputs are not meaningful "
                "text, only throughput is.")
        self.draft_runner = None
        self.draft_cache_engine = None
        # Rolling acceptance/goodput stats (process-global so the obs
        # stack — /metrics, history, /debug/spec — reads them without a
        # worker handle). configure() resets the window: one serving
        # engine per process.
        get_spec_stats().configure(self.k_min, self.k_max, self.k_spec)
        self.adaptive = AdaptiveKController(self.k_min, self.k_max,
                                            k_init=self.k_spec)
        # Tokens actually emitted by the most recent decode pass (spec
        # passes emit a VARIABLE count: accepted+1 per row; throughput
        # stats must not assume K+1).
        self.last_pass_emitted = 0

    # --- adaptive K -------------------------------------------------------

    def adaptive_num_decode_steps(self) -> int:
        """The engine calls this once per step BEFORE scheduling: the
        controller's current K (+1 for the bonus position) becomes the
        round's num_decode_steps. Cheap between evaluation windows."""
        k = self.adaptive.tick()
        if k != self.k_spec:
            self.k_spec = k
            get_spec_stats().set_current_k(k)
        return k + 1

    # --- back-compat accessors (pre-rolling-stats API) --------------------

    @property
    def num_draft_tokens(self) -> int:
        return get_spec_stats().total_drafted

    @property
    def num_accepted_tokens(self) -> int:
        return get_spec_stats().total_accepted

    def acceptance_rate(self) -> float:
        """Rolling acceptance over the stats window (0.0 when cold)."""
        return get_spec_stats().acceptance_rate()

    # --- init ------------------------------------------------------------

    def load_model(self) -> None:
        super().load_model()
        from intellillm_tpu.models.model_loader import get_model
        from intellillm_tpu.parallel.mesh import shard_params
        from intellillm_tpu.worker.model_runner import ModelRunner

        draft_mc = self.spec_config.draft_model_config
        self.spec_config.verify_with_model_config(self.model_config)
        draft_model, draft_host = get_model(draft_mc)
        draft_params = shard_params(draft_host, self.mesh, draft_model)
        self.draft_runner = ModelRunner(
            draft_model, draft_params, draft_mc, self.scheduler_config,
            self.cache_config, self.parallel_config, mesh=self.mesh,
            lora_manager=None)
        logger.info("Speculative decoding: draft=%s K=%d (band %d..%d)",
                    draft_mc.model, self.k_spec, self.k_min, self.k_max)

    def init_cache_engine(self, cache_config: CacheConfig) -> None:
        super().init_cache_engine(cache_config)
        from intellillm_tpu.parallel.mesh import shard_kv_cache
        from intellillm_tpu.worker.cache_engine import CacheEngine

        draft_mc = self.spec_config.draft_model_config
        kv_sharding = shard_kv_cache(self.mesh,
                                     draft_mc.get_cache_row_shape()[1])
        # Same block count/size as the target pool: the scheduler's block
        # tables index BOTH pools.
        self.draft_cache_engine = CacheEngine(cache_config, draft_mc,
                                              self.parallel_config,
                                              sharding=kv_sharding)

    def warm_up_model(self):
        """Warm-up for spec serving: the target's standard decode
        programs (the shared mixed path), the DRAFT model's decode
        programs (by re-running the generic warm-up against the draft
        runner/cache), and the FULL K-ladder of draft fused-scan +
        teacher-forced executables for every K in [k_min, k_max] — the
        adaptive controller moves K at runtime, and a K transition must
        reuse a warm executable instead of stalling serving on a
        mid-traffic XLA compile."""
        n = super().warm_up_model()
        if n is None:
            return None
        target_stats = dict(self.warmup_stats)
        saved = (self.model_runner, self.cache_engine, self.params)
        self.model_runner = self.draft_runner
        self.cache_engine = self.draft_cache_engine
        self.params = self.draft_runner.params
        try:
            n_draft = super().warm_up_model()
        finally:
            self.model_runner, self.cache_engine, self.params = saved
        draft_stats = dict(self.warmup_stats)
        import time as _time
        t0 = _time.monotonic()
        n_ladder = 0
        for k in range(self.k_min, self.k_max + 1):
            n_ladder += self._warm_teacher(k + 1)
            n_ladder += self._warm_draft_fused(k + 1)
        ladder_seconds = _time.monotonic() - t0
        total = n + (n_draft or 0) + n_ladder
        self.warmup_stats = {
            "executables": (target_stats.get("executables", 0)
                            + draft_stats.get("executables", 0)
                            + n_ladder),
            "seconds": round(target_stats.get("seconds", 0.0)
                             + draft_stats.get("seconds", 0.0)
                             + ladder_seconds, 3),
        }
        return total

    def _warm_teacher(self, k1: int) -> int:
        """Compile the teacher-forced program for a (K+1)-position verify
        at the max-seat row bucket / narrowest width for the greedy
        sampler variant (spec eligibility is greedy-only)."""
        import numpy as np

        from intellillm_tpu.utils import pad_to_bucket

        runner = self.model_runner
        try:
            b = pad_to_bucket(self.scheduler_config.max_num_seqs,
                              runner.mixed_token_buckets)
            w = runner.mixed_token_buckets[0]
            place = runner._place_batch_array
            args = (place(np.zeros((b, k1), np.int32)),      # teacher
                    place(np.zeros((b, 1), np.int32)),       # positions
                    place(np.zeros((b, w), np.int32)),
                    place(np.zeros(b, np.int32)),
                    place(np.zeros(b, np.float32)),
                    place(np.full(b, -1, np.int32)),
                    place(np.ones(b, np.float32)),
                    place(np.zeros(b, np.float32)),
                    place(np.zeros(b, np.uint32)),
                    place(np.zeros(b, np.float32)),
                    place(np.zeros(b, np.float32)),
                    place(np.ones(b, np.float32)), None, None)
            packed, caches = runner._jit_decode_teacher(
                self.params, self.cache_engine.device_cache, *args,
                num_steps=k1, logprob_k=1, do_topk=False, do_topp=False,
                do_minp=False, do_penalties=False, do_random=False)
            self.cache_engine.device_cache = caches
            import jax
            # lint: allow(host-sync) reason=teacher warm-up runs before serving; block so the teacher executable is compiled and resident before the first speculative step
            jax.block_until_ready(packed)
            return 1
        except Exception as e:  # best-effort, same contract as warm-up
            logger.warning("Teacher warm-up failed for K+1=%d (%s); "
                           "compiling lazily instead", k1, e)
            return 0

    def _warm_draft_fused(self, k1: int) -> int:
        """Compile the DRAFT model's fused-scan proposer for a (K+1)-step
        round (K proposals + the KV-completing extra substep) at the same
        bucket shapes the teacher warm uses — the two programs always run
        on the same row set."""
        import numpy as np

        from intellillm_tpu.utils import pad_to_bucket

        runner = self.draft_runner
        try:
            b = pad_to_bucket(self.scheduler_config.max_num_seqs,
                              runner.mixed_token_buckets)
            w = runner.mixed_token_buckets[0]
            place = runner._place_batch_array
            args = (place(np.zeros((b, 1), np.int32)),       # tokens
                    place(np.zeros((b, 1), np.int32)),       # positions
                    place(np.zeros((b, w), np.int32)),
                    place(np.zeros(b, np.int32)),
                    place(np.zeros(b, np.float32)),
                    place(np.full(b, -1, np.int32)),
                    place(np.ones(b, np.float32)),
                    place(np.zeros(b, np.float32)),
                    place(np.zeros(b, np.uint32)),
                    place(np.zeros(b, np.float32)),
                    place(np.zeros(b, np.float32)),
                    place(np.ones(b, np.float32)), None, None)
            packed, caches = runner._jit_decode(
                runner.params, self.draft_cache_engine.device_cache, *args,
                num_steps=k1, logprob_k=1, do_topk=False, do_topp=False,
                do_minp=False, do_penalties=False, do_random=False)
            self.draft_cache_engine.device_cache = caches
            import jax
            # lint: allow(host-sync) reason=draft-ladder warm-up runs before serving; block so each K's fused proposer executable is compiled and resident before the controller can select it
            jax.block_until_ready(packed)
            return 1
        except Exception as e:  # best-effort, same contract as warm-up
            logger.warning("Draft fused warm-up failed for K+1=%d (%s); "
                           "compiling lazily instead", k1, e)
            return 0

    # --- memory accounting ------------------------------------------------

    def _extra_weights_bytes(self, shard_bytes) -> int:
        import jax
        if self.draft_runner is None:
            return 0
        return sum(shard_bytes(x)
                   for x in jax.tree.leaves(self.draft_runner.params))

    def _extra_block_bytes(self, block_size: int, cache_dtype: str) -> int:
        """Every scheduler block also occupies a mirror block in the
        draft pool (same indices, draft-architecture-sized arrays)."""
        from intellillm_tpu.worker.cache_engine import CacheEngine
        draft_mc = self.spec_config.draft_model_config
        bb = CacheEngine.get_cache_block_size(block_size, cache_dtype,
                                              draft_mc,
                                              self.parallel_config)
        tp = self.parallel_config.tensor_parallel_size
        nkv = draft_mc.get_cache_row_shape()[1]
        return bb // tp if tp > 1 and nkv % tp == 0 else bb

    # --- step ------------------------------------------------------------

    def execute_model(
        self,
        seq_group_metadata_list: List[SequenceGroupMetadata],
        blocks_to_swap_in: Dict[int, int],
        blocks_to_swap_out: Dict[int, int],
        blocks_to_copy: Dict[int, List[int]],
        num_decode_steps: int = 1,
        defer_fetch: bool = False,
        spec_plan: Optional[Set[str]] = None,
    ) -> List[SamplerOutput]:
        if defer_fetch:
            # Unreachable behind EngineArgs.create_engine_configs
            # validation (spec + pipelined dispatch raises there); this
            # backstop keeps a direct-worker misuse loud.
            raise RuntimeError(
                "speculative decoding is incompatible with pipelined "
                "(defer_fetch) dispatch; the engine config validation "
                "should have rejected this combination")
        # Block ops mirror onto BOTH pools (shared block tables).
        for ce in (self.cache_engine, self.draft_cache_engine):
            if blocks_to_swap_out:
                ce.swap_out(blocks_to_swap_out)
            if blocks_to_swap_in:
                ce.swap_in(blocks_to_swap_in)
            if blocks_to_copy:
                ce.copy(blocks_to_copy)

        metas = seq_group_metadata_list
        if not metas:
            return []

        # Per-row split: the scheduler's plan says who MAY speculate this
        # round; the metadata predicate re-checks so worker and scheduler
        # can never disagree about a row.
        spec_pos: List[int] = []
        if spec_plan:
            spec_pos = [i for i, m in enumerate(metas)
                        if m.request_id in spec_plan
                        and meta_spec_eligible(m)]
        elif (num_decode_steps > 1
              and all(meta_spec_eligible(m) for m in metas)):
            # Direct-worker callers (no scheduler plan): an all-eligible
            # multi-step batch speculates wholesale, the legacy contract.
            spec_pos = list(range(len(metas)))

        if not spec_pos:
            return self._plain_pass(metas, num_decode_steps)
        return self._mixed_spec_pass(metas, spec_pos, num_decode_steps)

    def _plain_pass(
        self,
        metas: List[SequenceGroupMetadata],
        num_decode_steps: int,
    ) -> List[SamplerOutput]:
        """No row speculates: one ordinary target dispatch (mixed or
        fused), plus the draft-pool chunk mirror. The draft pool missing
        a fallback decode's tokens can only lower future acceptance,
        never correctness (every emitted token is target-verified)."""
        outputs, new_caches = self.model_runner.execute_model(
            metas, self.cache_engine.device_cache, num_decode_steps)
        self.cache_engine.device_cache = new_caches
        self._draft_mirror_chunks(
            [m for m in metas if m.token_chunk_size is not None])
        self.last_pass_emitted = (
            num_decode_steps * sum(len(m.seq_data) for m in metas
                                   if m.token_chunk_size is None))
        return outputs

    def _draft_mirror_chunks(
            self, chunk_metas: List[SequenceGroupMetadata]) -> None:
        """Write this round's prefill-chunk KV into the DRAFT pool so a
        finishing prompt starts speculating with full draft context
        (otherwise every fresh request would begin with zero-acceptance
        rounds while the draft cache backfills).

        The mirror runs with neutral greedy sampling params: the draft's
        samples are discarded, and the real params must not leak host
        side effects (prompt_logprobs accumulation, logits_processors
        resampling) into a second pass over the same SequenceData — the
        target's pass already did that work."""
        if not chunk_metas:
            return
        import copy

        from intellillm_tpu.sampling_params import SamplingParams
        neutral = SamplingParams(temperature=0.0)
        mirror = []
        for meta in chunk_metas:
            m = copy.copy(meta)
            m.sampling_params = neutral
            mirror.append(m)
        _, dnew = self.draft_runner.execute_model(
            mirror, self.draft_cache_engine.device_cache, 1)
        self.draft_cache_engine.device_cache = dnew

    def _mixed_spec_pass(
        self,
        metas: List[SequenceGroupMetadata],
        spec_pos: List[int],
        num_decode_steps: int,
    ) -> List[SamplerOutput]:
        """Split execution for a round where only SOME rows speculate:
        plan rows take the draft+teacher pass at K = num_decode_steps-1,
        every other row (chunk tokens, ineligible decodes) takes one
        single-step mixed dispatch, and the two output sets re-interleave
        in the original metadata order. Ineligible rows emit exactly one
        token; their later substeps are empty outputs, which the engine's
        output processing already skips."""
        spec_set = set(spec_pos)
        spec_metas = [metas[i] for i in spec_pos]
        rest_pos = [i for i in range(len(metas)) if i not in spec_set]
        rest_metas = [metas[i] for i in rest_pos]

        spec_out = self._spec_decode(spec_metas, num_decode_steps)
        spec_emitted = self.last_pass_emitted

        rest_first: Optional[SamplerOutput] = None
        rest_emitted = 0
        if rest_metas:
            outputs, new_caches = self.model_runner.execute_model(
                rest_metas, self.cache_engine.device_cache, 1)
            self.cache_engine.device_cache = new_caches
            rest_first = outputs[0]
            self._draft_mirror_chunks(
                [m for m in rest_metas if m.token_chunk_size is not None])
            rest_emitted = sum(len(m.seq_data) for m in rest_metas
                               if m.token_chunk_size is None)
        self.last_pass_emitted = spec_emitted + rest_emitted

        n_sub = len(spec_out)
        cols: List[List[SequenceGroupOutput]] = [None] * len(metas)  # type: ignore[list-item]
        for j, i in enumerate(spec_pos):
            cols[i] = [spec_out[s][j] for s in range(n_sub)]
        for j, i in enumerate(rest_pos):
            first = (rest_first[j] if rest_first is not None
                     else SequenceGroupOutput([], None))
            cols[i] = [first] + [SequenceGroupOutput([], None)
                                 for _ in range(n_sub - 1)]
        return [[cols[i][s] for i in range(len(metas))]
                for s in range(n_sub)]

    def _spec_decode(
        self,
        metas: List[SequenceGroupMetadata],
        num_steps: int,
    ) -> List[SamplerOutput]:
        k = num_steps - 1

        # 1. Draft proposes K tokens — run K+1 substeps so the draft pool
        # also gets the KV of the K-th proposal (inputs are
        # [last, d_1..d_K]); the (K+1)-th proposal is discarded. Without
        # this the draft pool keeps a one-position hole per round, which
        # silently degrades acceptance even for a perfect draft.
        d_out, dnew = self.draft_runner.execute_model(
            metas, self.draft_cache_engine.device_cache, num_steps)
        self.draft_cache_engine.device_cache = dnew

        # 2. Teacher-forced target verification over K+1 positions:
        # inputs [last_accepted, d_1 .. d_K] per row.
        teacher_rows: List[List[int]] = []
        for meta in metas:
            (data, ) = meta.seq_data.values()
            teacher_rows.append([data.get_last_token_id()])
        for step_out in d_out[:k]:
            for i, group_out in enumerate(step_out):
                teacher_rows[i].append(group_out.samples[0].output_token)
        t_out, tnew = self.model_runner.execute_model_teacher(
            metas, self.cache_engine.device_cache, teacher_rows, num_steps)
        self.cache_engine.device_cache = tnew

        # 3. Greedy acceptance: longest prefix where the target agrees
        # with the draft, plus the target's token at the first
        # disagreement (the "bonus"). All emitted tokens are the
        # TARGET's choices — t_out[s][i] — so the stream is exactly the
        # target's greedy stream.
        stats = get_spec_stats()
        acc_len: List[int] = []
        accepted_total = 0
        for i, meta in enumerate(metas):
            drafts = teacher_rows[i][1:]
            a = 0
            for j in range(k):
                if (self.force_accept
                        or t_out[j][i].samples[0].output_token
                        == drafts[j]):
                    a += 1
                else:
                    break
            acc_len.append(a + 1)
            accepted_total += a
            stats.record_request_accepted(meta.request_id, a)
        self.last_pass_emitted = sum(acc_len)
        stats.record_pass(drafted=k * len(metas),
                          accepted=accepted_total,
                          emitted=self.last_pass_emitted,
                          verified=num_steps * len(metas))

        outputs: List[SamplerOutput] = []
        for s in range(max(acc_len)):
            step_list: SamplerOutput = []
            for i in range(len(metas)):
                if s < acc_len[i]:
                    step_list.append(t_out[s][i])
                else:
                    step_list.append(SequenceGroupOutput([], None))
            outputs.append(step_list)
        return outputs
