"""Per-request sampling parameters.

Role parity: reference `vllm/sampling_params.py` (SamplingParams :23,
SamplingType :11): the OpenAI-style knob set plus beam search and logits
processors. The field names/defaults mirror the public API; validation is
table-driven here.
"""
from __future__ import annotations

from enum import IntEnum
from functools import cached_property
from typing import Callable, List, Optional, Union

_SAMPLING_EPS = 1e-5

LogitsProcessor = Callable[[List[int], "object"], "object"]
"""Takes (previously generated token ids, logits row) -> new logits row."""


class SamplingType(IntEnum):
    GREEDY = 0
    RANDOM = 1
    BEAM = 2


# Numeric-knob bounds: (attribute, low, high, low-end exclusive?). One
# table instead of a ladder of range checks.
_BOUNDS = (
    ("presence_penalty", -2.0, 2.0, False),
    ("frequency_penalty", -2.0, 2.0, False),
    ("repetition_penalty", 0.0, 2.0, True),
    ("top_p", 0.0, 1.0, True),
    ("min_p", 0.0, 1.0, False),
)


class SamplingParams:
    """Sampling parameters for one request.

    Follows the OpenAI API surface plus beam search, mirroring the
    reference's field set and validation semantics
    (`sampling_params.py:23-226`).
    """

    def __init__(
        self,
        n: int = 1,
        best_of: Optional[int] = None,
        presence_penalty: float = 0.0,
        frequency_penalty: float = 0.0,
        repetition_penalty: float = 1.0,
        temperature: float = 1.0,
        top_p: float = 1.0,
        top_k: int = -1,
        min_p: float = 0.0,
        use_beam_search: bool = False,
        length_penalty: float = 1.0,
        early_stopping: Union[bool, str] = False,
        stop: Optional[Union[str, List[str]]] = None,
        stop_token_ids: Optional[List[int]] = None,
        include_stop_str_in_output: bool = False,
        ignore_eos: bool = False,
        max_tokens: Optional[int] = 16,
        logprobs: Optional[int] = None,
        prompt_logprobs: Optional[int] = None,
        skip_special_tokens: bool = True,
        spaces_between_special_tokens: bool = True,
        logits_processors: Optional[List[LogitsProcessor]] = None,
        seed: Optional[int] = None,
    ) -> None:
        self.n = n
        self.best_of = best_of if best_of is not None else n
        self.presence_penalty = presence_penalty
        self.frequency_penalty = frequency_penalty
        self.repetition_penalty = repetition_penalty
        self.temperature = temperature
        self.top_p = top_p
        self.top_k = top_k
        self.min_p = min_p
        self.use_beam_search = use_beam_search
        self.length_penalty = length_penalty
        self.early_stopping = early_stopping
        self.stop = ([stop] if isinstance(stop, str)
                     else list(stop) if stop else [])
        self.stop_token_ids = list(stop_token_ids or [])
        self.include_stop_str_in_output = include_stop_str_in_output
        self.ignore_eos = ignore_eos
        self.max_tokens = max_tokens
        self.logprobs = logprobs
        self.prompt_logprobs = prompt_logprobs
        self.skip_special_tokens = skip_special_tokens
        self.spaces_between_special_tokens = spaces_between_special_tokens
        self.logits_processors = logits_processors or []
        # With a seed, the request's samples depend on (seed, stream,
        # step) only: the same request repeats its tokens whatever else
        # is in the batch. Without one they derive from the engine seed
        # and the sequence id.
        self.seed = seed

        self._validate()

    # -- validation --------------------------------------------------------

    def _validate(self) -> None:
        self._check_common()
        if self.use_beam_search:
            self._check_beam()
            return
        self._check_no_beam()
        if self.temperature < _SAMPLING_EPS:
            # Greedy: filtering knobs are no-ops — normalize them so the
            # device sampler sees one canonical greedy configuration.
            self.top_p, self.top_k, self.min_p = 1.0, -1, 0.0
            if self.best_of > 1:
                raise ValueError("best_of must be 1 when using greedy "
                                 f"sampling, got {self.best_of}.")

    def _check_common(self) -> None:
        for name, lo, hi, lo_open in _BOUNDS:
            v = getattr(self, name)
            if not ((lo < v if lo_open else lo <= v) and v <= hi):
                span = f"{'(' if lo_open else '['}{lo:g}, {hi:g}]"
                raise ValueError(f"{name} must be in {span}, got {v}.")
        if self.n < 1:
            raise ValueError(f"n must be at least 1, got {self.n}.")
        if self.best_of < self.n:
            raise ValueError(f"best_of must be >= n, got n={self.n}, "
                             f"best_of={self.best_of}.")
        if self.temperature < 0.0:
            raise ValueError("temperature must be non-negative, got "
                             f"{self.temperature}.")
        if self.top_k == 0 or self.top_k < -1:
            raise ValueError("top_k must be -1 (disable), or at least 1, "
                             f"got {self.top_k}.")
        # None = unbounded: generate until EOS / a stop / max_model_len
        # (reference sampling_params.py:111,186).
        if self.max_tokens is not None and self.max_tokens < 1:
            raise ValueError(
                f"max_tokens must be at least 1, got {self.max_tokens}.")
        for name in ("logprobs", "prompt_logprobs"):
            v = getattr(self, name)
            if v is not None and v < 0:
                raise ValueError(f"{name} must be non-negative, got {v}.")

    def _check_beam(self) -> None:
        problem = None
        if self.best_of == 1:
            problem = "best_of must be greater than 1"
        elif self.temperature > _SAMPLING_EPS:
            problem = "temperature must be 0"
        elif self.top_p < 1.0 - _SAMPLING_EPS:
            problem = "top_p must be 1"
        elif self.top_k != -1:
            problem = "top_k must be -1"
        if problem is not None:
            raise ValueError(f"{problem} when using beam search.")
        if self.early_stopping not in (True, False, "never"):
            raise ValueError("early_stopping must be True, False, or "
                             f"'never', got {self.early_stopping}.")

    def _check_no_beam(self) -> None:
        if self.early_stopping is not False:
            raise ValueError("early_stopping is not effective and must be "
                             "False when not using beam search.")
        if abs(self.length_penalty - 1.0) > _SAMPLING_EPS:
            raise ValueError(
                "length_penalty is only effective with beam search.")

    # -- derived -----------------------------------------------------------

    @cached_property
    def sampling_type(self) -> SamplingType:
        if self.use_beam_search:
            return SamplingType.BEAM
        if self.temperature < _SAMPLING_EPS:
            return SamplingType.GREEDY
        return SamplingType.RANDOM

    def __repr__(self) -> str:
        return (f"SamplingParams(n={self.n}, best_of={self.best_of}, "
                f"temperature={self.temperature}, top_p={self.top_p}, "
                f"top_k={self.top_k}, use_beam_search={self.use_beam_search}, "
                f"max_tokens={self.max_tokens}, stop={self.stop})")
