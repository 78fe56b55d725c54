"""Sequence data model: the host-side request state machine.

Role parity: reference `vllm/sequence.py` (SequenceStatus :15, SequenceData
:52, Sequence :112, SequenceGroup :243, SequenceGroupMetadata :352,
SequenceOutput/SequenceGroupOutput/SamplerOutput :389-447) — same roles,
different structure. Token history lives in a grow-only numpy i32 buffer
(not Python lists) so the fused K-step decode commit and the penalty
tensor build hand contiguous windows straight to the device staging path,
and logical KV blocks are *derived* from the token count instead of being
materialized as per-block objects (the block mapper only ever needs the
count). Pure host bookkeeping — nothing here touches the device.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from intellillm_tpu.prefix import Prefix
from intellillm_tpu.sampling_params import SamplingParams

PromptLogprobs = List[Optional[Dict[int, float]]]
SampleLogprobs = List[Dict[int, float]]


def _lora_id(lora_request) -> int:
    """Adapter integer id for a request (0 = base model, no adapter)."""
    return lora_request.lora_int_id if lora_request else 0


class SequenceStatus(enum.Enum):
    """Lifecycle states. Each member carries (ordinal, finished?,
    finish_reason) so the API layer reads `.finish_reason` off the status
    itself. The ordinal keeps every value distinct — equal-valued enum
    members would silently become aliases of each other."""

    WAITING = (0, False, None)
    RUNNING = (1, False, None)
    SWAPPED = (2, False, None)
    FINISHED_STOPPED = (3, True, "stop")
    FINISHED_LENGTH_CAPPED = (4, True, "length")
    FINISHED_ABORTED = (5, True, "abort")
    # Prompt longer than the model/scheduler budget — reported to the
    # OpenAI layer as a length finish, like the reference.
    FINISHED_IGNORED = (6, True, "length")

    @property
    def finished(self) -> bool:
        return self.value[1]

    @property
    def finish_reason(self) -> Optional[str]:
        return self.value[2]

    # Call-site compatible helpers (reference exposes staticmethods).
    @staticmethod
    def is_finished(status: "SequenceStatus") -> bool:
        return status.finished

    @staticmethod
    def get_finished_reason(status: "SequenceStatus") -> Optional[str]:
        return status.finish_reason


class SequenceData:
    """Token history for one stream: a single grow-only i32 buffer whose
    first `_prompt_len` entries are the prompt and whose tail is the
    generated continuation. Doubling growth keeps appends amortized O(1)
    across fused multi-step decode commits."""

    __slots__ = ("_buf", "_len", "_prompt_len", "_prompt_list",
                 "cumulative_logprob", "_num_computed_tokens",
                 "_prefill_complete", "_chunk_prompt_logprobs",
                 "seed_stream")

    def __init__(self, prompt_token_ids: List[int]) -> None:
        n = len(prompt_token_ids)
        self._buf = np.empty(max(16, 2 * n), dtype=np.int32)
        self._buf[:n] = prompt_token_ids
        self._len = n
        self._prompt_len = n
        self._prompt_list: Optional[List[int]] = None
        self.cumulative_logprob = 0.0
        # Chunked-prefill progress (core/scheduler.py): tokens whose KV has
        # been scheduled for computation so far. Only meaningful while
        # `not _prefill_complete` — legacy homogeneous scheduling marks the
        # whole prompt computed at admission and never looks again.
        self._num_computed_tokens = 0
        self._prefill_complete = False
        # prompt_logprobs panel entries accumulated across the prompt's
        # chunk steps ({position: {token: logprob}}); assembled into the
        # reference-format list on the final chunk and cleared
        # (worker/model_runner.py:_attach_prompt_logprobs).
        self._chunk_prompt_logprobs: Optional[dict] = None
        # Which of its request's sampling streams this is (0 = the prompt
        # sequence; best_of/n forks take the next index in creation
        # order). Only read for requests that name a seed.
        self.seed_stream = 0

    def append_token_id(self, token_id: int, logprob: float) -> None:
        if self._len == self._buf.shape[0]:
            grown = np.empty(2 * self._len, dtype=np.int32)
            grown[:self._len] = self._buf
            self._buf = grown
        self._buf[self._len] = token_id
        self._len += 1
        self.cumulative_logprob += logprob

    # -- array views (zero-copy; valid until the next growth) -------------

    def token_views(self) -> Tuple[np.ndarray, np.ndarray]:
        """(prompt, output) windows of the underlying buffer — the batch
        prep path feeds these to numpy penalty tensors without list
        round-trips."""
        return (self._buf[:self._prompt_len],
                self._buf[self._prompt_len:self._len])

    # -- list/scalar accessors (API parity with the reference) ------------

    @property
    def prompt_token_ids(self) -> List[int]:
        # The prompt is immutable — materialize the list once (the output
        # path reads this every engine step).
        if self._prompt_list is None:
            self._prompt_list = self._buf[:self._prompt_len].tolist()
        return self._prompt_list

    @property
    def output_token_ids(self) -> List[int]:
        return self._buf[self._prompt_len:self._len].tolist()

    def get_len(self) -> int:
        return self._len

    def get_prompt_len(self) -> int:
        return self._prompt_len

    def get_output_len(self) -> int:
        return self._len - self._prompt_len

    def get_token_ids(self) -> List[int]:
        return self._buf[:self._len].tolist()

    def get_last_token_id(self) -> int:
        return int(self._buf[self._len - 1])

    # -- chunked-prefill progress (see core/scheduler.py) ------------------

    def get_num_computed_tokens(self) -> int:
        return self._num_computed_tokens

    def get_num_uncomputed_tokens(self) -> int:
        return self._len - self._num_computed_tokens

    def update_num_computed_tokens(self, num_new_tokens: int) -> None:
        self._num_computed_tokens += num_new_tokens
        assert self._num_computed_tokens <= self._len, (
            self._num_computed_tokens, self._len)

    def reset_num_computed_tokens(self) -> None:
        """Recompute preemption: every KV page is discarded, so the whole
        history (prompt + generated tail) must be re-prefilled."""
        self._num_computed_tokens = 0
        self._prefill_complete = False
        self._chunk_prompt_logprobs = None

    @property
    def prefill_complete(self) -> bool:
        return self._prefill_complete

    def mark_prefill_complete(self) -> None:
        self._num_computed_tokens = self._len
        self._prefill_complete = True

    def clone(self) -> "SequenceData":
        twin = SequenceData.__new__(SequenceData)
        twin._buf = self._buf[:self._len].copy()
        twin._len = self._len
        twin._prompt_len = self._prompt_len
        twin._prompt_list = self._prompt_list
        twin.cumulative_logprob = self.cumulative_logprob
        twin._num_computed_tokens = self._num_computed_tokens
        twin._prefill_complete = self._prefill_complete
        twin._chunk_prompt_logprobs = None
        twin.seed_stream = self.seed_stream
        return twin

    def __deepcopy__(self, memo) -> "SequenceData":
        return self.clone()

    def __repr__(self) -> str:
        return (f"SequenceData(prompt_len={self._prompt_len}, "
                f"output_len={self.get_output_len()}, "
                f"cumulative_logprob={self.cumulative_logprob})")


class Sequence:
    """One generation stream: token data + derived KV-block geometry +
    incremental-detokenization cursor."""

    def __init__(
        self,
        seq_id: int,
        prompt: str,
        prompt_token_ids: List[int],
        block_size: int,
        lora_request=None,
    ) -> None:
        self.seq_id = seq_id
        self.prompt = prompt
        self.block_size = block_size
        self.lora_request = lora_request
        self.status = SequenceStatus.WAITING

        self.data = SequenceData(prompt_token_ids)
        self.output_logprobs: SampleLogprobs = []
        self.output_text = ""

        # Incremental detokenization cursor (transformers_utils/
        # detokenizer.py): token pieces decoded so far + the two offsets
        # bounding the not-yet-finalized suffix.
        self.tokens: Optional[List[str]] = None
        self.prefix_offset = 0
        self.read_offset = 0

    @property
    def lora_int_id(self) -> int:
        return _lora_id(self.lora_request)

    def num_logical_blocks(self) -> int:
        """KV blocks this sequence spans. Derived from the token count —
        there are no per-block host objects to keep in sync."""
        return -(-self.data.get_len() // self.block_size)

    def append_token_id(self, token_id: int,
                        logprobs: Dict[int, float]) -> None:
        assert token_id in logprobs
        self.output_logprobs.append(logprobs)
        self.data.append_token_id(token_id, logprobs[token_id])

    # Delegation instead of inheritance: the scheduler/engine address a
    # Sequence, the worker addresses its SequenceData payload.
    def get_len(self) -> int:
        return self.data.get_len()

    def get_prompt_len(self) -> int:
        return self.data.get_prompt_len()

    def get_output_len(self) -> int:
        return self.data.get_output_len()

    def get_token_ids(self) -> List[int]:
        return self.data.get_token_ids()

    def get_last_token_id(self) -> int:
        return self.data.get_last_token_id()

    def get_output_token_ids(self) -> List[int]:
        return self.data.output_token_ids

    def get_cumulative_logprob(self) -> float:
        return self.data.cumulative_logprob

    def get_beam_search_score(
        self,
        length_penalty: float = 1.0,
        seq_len: Optional[int] = None,
        eos_token_id: Optional[int] = None,
    ) -> float:
        """HF-style length-normalized beam score. A trailing EOS is not
        counted toward the normalizing length."""
        if seq_len is None:
            seq_len = self.get_len()
            if (eos_token_id is not None
                    and self.get_last_token_id() == eos_token_id):
                seq_len -= 1
        return self.get_cumulative_logprob() / (seq_len**length_penalty)

    def is_finished(self) -> bool:
        return self.status.finished

    def fork(self, new_seq_id: int) -> "Sequence":
        """Beam/best_of split: a twin with its own copies of the mutable
        state (explicit field copies — no deepcopy walk)."""
        twin = Sequence.__new__(Sequence)
        twin.seq_id = new_seq_id
        twin.prompt = self.prompt
        twin.block_size = self.block_size
        twin.lora_request = self.lora_request
        twin.status = self.status
        twin.data = self.data.clone()
        twin.output_logprobs = [dict(lp) for lp in self.output_logprobs]
        twin.output_text = self.output_text
        twin.tokens = list(self.tokens) if self.tokens is not None else None
        twin.prefix_offset = self.prefix_offset
        twin.read_offset = self.read_offset
        return twin

    def __repr__(self) -> str:
        return (f"Sequence(seq_id={self.seq_id}, status={self.status.name}, "
                f"num_blocks={self.num_logical_blocks()})")


class SequenceGroup:
    """One request: up to best_of candidate streams sharing a prompt."""

    def __init__(
        self,
        request_id: str,
        seqs: List[Sequence],
        sampling_params: SamplingParams,
        arrival_time: float,
        lora_request=None,
        prefix: Optional[Prefix] = None,
        predicted_len: Optional[int] = None,
    ) -> None:
        self.request_id = request_id
        self.seqs_dict: Dict[int, Sequence] = {s.seq_id: s for s in seqs}
        self.sampling_params = sampling_params
        self.arrival_time = arrival_time
        self.lora_request = lora_request
        self.prefix = prefix
        # Fork-specific (IntelliLLM): predicted response length consumed by
        # the SJF policy (reference keeps this in the research dir; here it
        # is first-class request state).
        self.predicted_len = predicted_len
        # Quantile companions stamped by the PredictionService: p90 prices
        # preemption-victim selection; `raw` is the predictor's uncorrected
        # estimate, kept so the online calibrator can restamp p50/p90
        # in-flight when a bucket's correction factor moves (raw stays
        # None for oracle-supplied predicted_len, which is never restamped).
        self.predicted_len_p90: Optional[int] = None
        self.predicted_len_raw: Optional[int] = None
        # Serving-latency markers filled in by the engine/stats layer.
        self.first_scheduled_time: Optional[float] = None
        self.first_token_time: Optional[float] = None
        self.last_token_time: Optional[float] = None
        self._next_seed_stream = 1

    def take_seed_stream(self) -> int:
        """The next unused sampling-stream index of this request (given
        to a fork; see SequenceData.seed_stream)."""
        stream = self._next_seed_stream
        self._next_seed_stream += 1
        return stream

    def _any_seq(self) -> Sequence:
        return next(iter(self.seqs_dict.values()))

    @property
    def prompt(self) -> str:
        return self._any_seq().prompt

    @property
    def prompt_token_ids(self) -> List[int]:
        return self._any_seq().data.prompt_token_ids

    @property
    def lora_int_id(self) -> int:
        return _lora_id(self.lora_request)

    def get_max_num_running_seqs(self) -> int:
        """Most parallel streams this group can still occupy — the
        scheduler's admission unit. Before the first sample a request
        holds one prompt stream but will fan out to best_of."""
        sp = self.sampling_params
        if sp.use_beam_search or sp.best_of > self.num_seqs():
            return sp.best_of
        return self.num_unfinished_seqs()

    def get_seqs(
            self,
            status: Optional[SequenceStatus] = None) -> List[Sequence]:
        seqs = self.seqs_dict.values()
        if status is None:
            return list(seqs)
        return [s for s in seqs if s.status is status]

    def get_unfinished_seqs(self) -> List[Sequence]:
        return [s for s in self.seqs_dict.values() if not s.is_finished()]

    def get_finished_seqs(self) -> List[Sequence]:
        return [s for s in self.seqs_dict.values() if s.is_finished()]

    def num_seqs(self, status: Optional[SequenceStatus] = None) -> int:
        return len(self.get_seqs(status))

    def num_unfinished_seqs(self) -> int:
        return len(self.get_unfinished_seqs())

    def num_finished_seqs(self) -> int:
        return len(self.get_finished_seqs())

    def find(self, seq_id: int) -> Sequence:
        try:
            return self.seqs_dict[seq_id]
        except KeyError:
            raise ValueError(f"Sequence {seq_id} not found.") from None

    def add(self, seq: Sequence) -> None:
        if seq.seq_id in self.seqs_dict:
            raise ValueError(f"Sequence {seq.seq_id} already exists.")
        self.seqs_dict[seq.seq_id] = seq

    def remove(self, seq_id: int) -> None:
        if self.seqs_dict.pop(seq_id, None) is None:
            raise ValueError(f"Sequence {seq_id} not found.")

    def is_finished(self) -> bool:
        return all(s.is_finished() for s in self.seqs_dict.values())

    def __repr__(self) -> str:
        return (f"SequenceGroup(request_id={self.request_id}, "
                f"sampling_params={self.sampling_params}, "
                f"num_seqs={len(self.seqs_dict)})")


@dataclass
class SequenceGroupMetadata:
    """Scheduler → runner payload for one scheduled group (reference
    `sequence.py:352-388` role): which streams to run, their token data,
    their physical block tables, and how to sample them."""

    request_id: str
    is_prompt: bool
    seq_data: Dict[int, SequenceData]
    sampling_params: SamplingParams
    block_tables: Dict[int, List[int]]
    lora_request: object = None
    prefix: Optional[Prefix] = None
    # Chunked prefill (mixed steps only): process `token_chunk_size` prompt
    # tokens starting at absolute position `num_computed_tokens`. None means
    # a whole-phase (legacy homogeneous) entry.
    token_chunk_size: Optional[int] = None
    num_computed_tokens: int = 0
    # seq_id -> the sequence's slot in the per-sequence state pools
    # (models with "state" layers); None for every other model.
    state_slots: Optional[Dict[int, int]] = None
    # seq_id -> the window group's block table (models with "kv_window"
    # layers beside "kv" ones); None for every other model.
    window_block_tables: Optional[Dict[int, List[int]]] = None

    @property
    def lora_int_id(self) -> int:
        return _lora_id(self.lora_request)


@dataclass(eq=True)
class SequenceOutput:
    """One sampled token for one parent stream."""

    parent_seq_id: int
    output_token: int
    logprobs: Dict[int, float] = field(default_factory=dict)

    def __repr__(self) -> str:
        return (f"SequenceOutput(parent_seq_id={self.parent_seq_id}, "
                f"output_token={self.output_token})")


@dataclass(eq=True)
class SequenceGroupOutput:
    """Sampler results for one group at one step."""

    samples: List[SequenceOutput]
    prompt_logprobs: Optional[PromptLogprobs] = None

    def __repr__(self) -> str:
        return (f"SequenceGroupOutput(samples={self.samples}, "
                f"prompt_logprobs={self.prompt_logprobs})")


# One entry per scheduled sequence group, in schedule order.
SamplerOutput = List[SequenceGroupOutput]
