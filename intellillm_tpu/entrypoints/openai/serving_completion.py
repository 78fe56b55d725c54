"""/v1/completions implementation.

Role parity: reference `vllm/entrypoints/openai/serving_completion.py`
(OpenAIServingCompletion :250, merge_async_iterators :220, streaming and
echo/logprobs handling).
"""
from __future__ import annotations

import asyncio
import time
from typing import AsyncIterator, Dict, List, Optional, Tuple, Union

from intellillm_tpu.engine.async_llm_engine import AsyncLLMEngine
from intellillm_tpu.entrypoints.openai.protocol import (
    CompletionRequest, CompletionResponse, CompletionResponseChoice,
    CompletionResponseStreamChoice, CompletionStreamResponse, ErrorResponse,
    LogProbs, UsageInfo)
from intellillm_tpu.entrypoints.openai.serving_engine import OpenAIServing
from intellillm_tpu.outputs import RequestOutput
from intellillm_tpu.sampling_params import SamplingParams
from intellillm_tpu.utils import random_uuid


def parse_prompt_format(prompt) -> Tuple[bool, list]:
    """Returns (prompt_is_tokens, prompts): str | List[str] | List[int] |
    List[List[int]] (reference serving_completion.py:190-218)."""
    prompt_is_tokens = False
    prompts = [prompt]
    if isinstance(prompt, list):
        if len(prompt) == 0:
            raise ValueError("please provide at least one prompt")
        if isinstance(prompt[0], str):
            prompts = prompt
        elif isinstance(prompt[0], int):
            prompt_is_tokens = True
            prompts = [prompt]
        elif isinstance(prompt[0], list) and isinstance(prompt[0][0], int):
            prompt_is_tokens = True
            prompts = prompt
        else:
            raise ValueError(
                "prompt must be a string, array of strings, array of "
                "tokens, or array of token arrays")
    return prompt_is_tokens, prompts


async def merge_async_iterators(
        *iterators: AsyncIterator) -> AsyncIterator[Tuple[int, object]]:
    """Interleave multiple result streams as (index, item)."""
    queue: asyncio.Queue = asyncio.Queue()
    finished = [False] * len(iterators)

    async def producer(i: int, iterator: AsyncIterator):
        try:
            async for item in iterator:
                await queue.put((i, item))
        except Exception as e:
            await queue.put(e)
        finished[i] = True

    tasks = [
        asyncio.create_task(producer(i, it))
        for i, it in enumerate(iterators)
    ]
    try:
        while not all(finished) or not queue.empty():
            item = await queue.get()
            if isinstance(item, Exception):
                raise item
            yield item
    finally:
        for task in tasks:
            task.cancel()


def request_to_sampling_params(request) -> SamplingParams:
    return SamplingParams(
        n=request.n,
        best_of=request.best_of,
        presence_penalty=request.presence_penalty,
        frequency_penalty=request.frequency_penalty,
        repetition_penalty=request.repetition_penalty,
        temperature=request.temperature,
        top_p=request.top_p,
        top_k=request.top_k,
        min_p=request.min_p,
        use_beam_search=request.use_beam_search,
        length_penalty=request.length_penalty,
        early_stopping=request.early_stopping,
        stop=request.stop,
        stop_token_ids=request.stop_token_ids,
        ignore_eos=request.ignore_eos,
        max_tokens=request.max_tokens,
        logprobs=getattr(request, "logprobs", None),
        skip_special_tokens=request.skip_special_tokens,
        spaces_between_special_tokens=request.spaces_between_special_tokens,
        seed=request.seed,
    )


class OpenAIServingCompletion(OpenAIServing):

    async def create_completion(
        self, request: CompletionRequest,
        request_id: Optional[str] = None
    ) -> Union[ErrorResponse, CompletionResponse,
               AsyncIterator[str]]:
        error = await self._check_model(request)
        if error is not None:
            return error
        if request.suffix is not None:
            return self.create_error_response(
                "suffix is not currently supported")
        if request.echo:
            return self.create_error_response(
                "echo is not currently supported")

        # A caller-supplied id (the server handler's validated
        # X-Request-Id — the distributed trace id) wins over a minted one.
        request_id = request_id or f"cmpl-{random_uuid()}"
        created_time = int(time.time())
        model_name = request.model

        try:
            sampling_params = request_to_sampling_params(request)
            prompt_is_tokens, prompts = parse_prompt_format(request.prompt)

            generators = []
            for i, prompt in enumerate(prompts):
                if prompt_is_tokens:
                    input_ids = self._validate_prompt_and_tokenize(
                        request, prompt_ids=prompt)
                    prompt_text = None
                else:
                    input_ids = self._validate_prompt_and_tokenize(
                        request, prompt=prompt)
                    prompt_text = prompt
                generators.append(
                    self.engine.generate(prompt_text, sampling_params,
                                         f"{request_id}-{i}",
                                         prompt_token_ids=input_ids))
        except (ValueError, NotImplementedError) as e:
            return self.create_error_response(str(e))

        result_generator = merge_async_iterators(*generators)

        if request.stream and not request.use_beam_search:
            return self.completion_stream_generator(
                request, result_generator, request_id, created_time,
                model_name, len(prompts))

        return await self.completion_full_generator(
            request, result_generator, request_id, created_time, model_name,
            len(prompts))

    async def completion_full_generator(self, request, result_generator,
                                        request_id, created_time, model_name,
                                        num_prompts) -> CompletionResponse:
        final_res_batch: List[Optional[RequestOutput]] = [None] * num_prompts
        async for i, res in result_generator:
            final_res_batch[i] = res

        choices: List[CompletionResponseChoice] = []
        num_prompt_tokens = 0
        num_generated_tokens = 0
        for i, final_res in enumerate(final_res_batch):
            assert final_res is not None
            for output in final_res.outputs:
                logprobs = None
                if request.logprobs is not None:
                    logprobs = self._create_logprobs(
                        token_ids=output.token_ids,
                        top_logprobs=output.logprobs,
                        num_output_top_logprobs=request.logprobs)
                choices.append(
                    CompletionResponseChoice(
                        index=i * request.n + output.index,
                        text=output.text,
                        logprobs=logprobs,
                        finish_reason=output.finish_reason))
            num_prompt_tokens += len(final_res.prompt_token_ids)
            num_generated_tokens += sum(
                len(output.token_ids) for output in final_res.outputs)

        return CompletionResponse(
            id=request_id,
            created=created_time,
            model=model_name,
            choices=choices,
            usage=UsageInfo(
                prompt_tokens=num_prompt_tokens,
                completion_tokens=num_generated_tokens,
                total_tokens=num_prompt_tokens + num_generated_tokens,
            ))

    async def completion_stream_generator(
            self, request, result_generator, request_id, created_time,
            model_name, num_prompts) -> AsyncIterator[str]:
        previous_texts = {}
        previous_num_tokens = {}
        async for prompt_idx, res in result_generator:
            for output in res.outputs:
                key = (prompt_idx, output.index)
                prev_text = previous_texts.get(key, "")
                prev_n = previous_num_tokens.get(key, 0)
                delta_text = output.text[len(prev_text):]
                previous_texts[key] = output.text
                previous_num_tokens[key] = len(output.token_ids)

                logprobs = None
                if request.logprobs is not None:
                    logprobs = self._create_logprobs(
                        token_ids=output.token_ids[prev_n:],
                        top_logprobs=(output.logprobs[prev_n:]
                                      if output.logprobs else None),
                        num_output_top_logprobs=request.logprobs)

                chunk = CompletionStreamResponse(
                    id=request_id,
                    created=created_time,
                    model=model_name,
                    choices=[
                        CompletionResponseStreamChoice(
                            index=prompt_idx * request.n + output.index,
                            text=delta_text,
                            logprobs=logprobs,
                            finish_reason=output.finish_reason)
                    ])
                yield f"data: {chunk.model_dump_json()}\n\n"
        yield "data: [DONE]\n\n"
