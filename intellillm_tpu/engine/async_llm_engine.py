"""Asynchronous engine wrapper for online serving.

Role parity: reference `vllm/engine/async_llm_engine.py` (AsyncStream :41,
RequestTracker :73, _AsyncLLMEngine.step_async :175, AsyncLLMEngine
:280: generate :477, run_engine_loop :405, AsyncEngineDeadError :19).

TPU redesign: no Ray / engine-as-actor variants — one process, one mesh.
The blocking device step runs in a worker thread (`run_in_executor`) so
the asyncio loop keeps accepting/streaming requests while the TPU works;
JAX dispatch is thread-safe for this single-consumer pattern.
"""
from __future__ import annotations

import asyncio
import contextlib
import time
from functools import partial
from typing import (AsyncIterator, Dict, Iterable, List, Optional, Set,
                    Tuple, Type, Union)

from intellillm_tpu.engine.arg_utils import AsyncEngineArgs
from intellillm_tpu.engine.llm_engine import LLMEngine
from intellillm_tpu.logger import init_logger
from intellillm_tpu.obs.tracing import get_step_tracer
from intellillm_tpu.outputs import RequestOutput
from intellillm_tpu.sampling_params import SamplingParams

logger = init_logger(__name__)


class AsyncEngineDeadError(RuntimeError):
    pass


def _raise_exception_on_finish(task: asyncio.Task,
                               request_tracker: "RequestTracker") -> None:
    msg = ("Task finished unexpectedly. This should never happen! "
           "Please open an issue on Github.")
    try:
        try:
            task.result()
        except asyncio.CancelledError:
            return
        except Exception as exc:
            raise AsyncEngineDeadError(
                msg + " See stack trace above for the actual cause.") from exc
        raise AsyncEngineDeadError(msg)
    except Exception as exc:
        request_tracker.propagate_exception(exc)
        raise exc


class AsyncStream:
    """Per-request stream of RequestOutputs, consumable via async for."""

    def __init__(self, request_id: str) -> None:
        self.request_id = request_id
        self._queue: asyncio.Queue = asyncio.Queue()
        self._finished = False

    def put(self, item: Union[RequestOutput, Exception]) -> None:
        if self._finished:
            return
        self._queue.put_nowait(item)

    def finish(self) -> None:
        self._queue.put_nowait(StopAsyncIteration())
        self._finished = True

    @property
    def finished(self) -> bool:
        return self._finished

    def __aiter__(self):
        return self

    async def __anext__(self) -> RequestOutput:
        result = await self._queue.get()
        if isinstance(result, Exception):
            raise result
        return result


class RequestTracker:
    """Synchronizes request additions/aborts between API handlers and the
    background engine loop."""

    def __init__(self) -> None:
        self._request_streams: Dict[str, AsyncStream] = {}
        self._finished_requests: asyncio.Queue = asyncio.Queue()
        self._new_requests: asyncio.Queue = asyncio.Queue()
        self.new_requests_event: Optional[asyncio.Event] = None

    def __contains__(self, item) -> bool:
        return item in self._request_streams

    def init_event(self) -> None:
        self.new_requests_event = asyncio.Event()

    def propagate_exception(self, exc: Exception,
                            request_id: Optional[str] = None) -> None:
        if request_id is not None:
            self._request_streams[request_id].put(exc)
        else:
            for stream in self._request_streams.values():
                stream.put(exc)

    def process_request_output(self, request_output: RequestOutput,
                               *, verbose: bool = False) -> None:
        request_id = request_output.request_id
        stream = self._request_streams.get(request_id)
        if stream is None:
            return  # aborted
        stream.put(request_output)
        if request_output.finished:
            if verbose:
                logger.info("Finished request %s.", request_id)
            self.abort_request(request_id)

    def add_request(self, request_id: str,
                    **engine_add_request_kwargs) -> AsyncStream:
        if request_id in self._request_streams:
            raise KeyError(f"Request {request_id} already exists.")
        stream = AsyncStream(request_id)
        self._new_requests.put_nowait((stream, {
            "request_id": request_id,
            **engine_add_request_kwargs
        }))
        if self.new_requests_event is not None:
            self.new_requests_event.set()
        return stream

    def abort_request(self, request_id: str, *,
                      verbose: bool = False) -> None:
        if verbose:
            logger.info("Aborted request %s.", request_id)
        self._finished_requests.put_nowait(request_id)
        stream = self._request_streams.pop(request_id, None)
        if stream is not None and not stream.finished:
            stream.finish()

    def get_new_and_finished_requests(self) -> Tuple[List[dict], Set[str]]:
        new_requests: List[dict] = []
        finished_requests: Set[str] = set()

        while not self._finished_requests.empty():
            finished_requests.add(self._finished_requests.get_nowait())

        while not self._new_requests.empty():
            stream, request = self._new_requests.get_nowait()
            if stream.request_id in finished_requests:
                continue  # aborted before scheduling
            self._request_streams[stream.request_id] = stream
            new_requests.append(request)

        if self.new_requests_event is not None:
            self.new_requests_event.clear()
        return new_requests, finished_requests

    async def wait_for_new_requests(self) -> None:
        await self.new_requests_event.wait()


class AsyncLLMEngine:
    """Async facade over LLMEngine with a background step loop."""

    def __init__(self, *args, log_requests: bool = True,
                 start_engine_loop: bool = True, **kwargs) -> None:
        self.engine = LLMEngine(*args, **kwargs)
        self.log_requests = log_requests
        self.start_engine_loop = start_engine_loop
        self.background_loop: Optional[asyncio.Future] = None
        self._background_loop_unshielded = None
        self._request_tracker = RequestTracker()
        self._errored_with: Optional[BaseException] = None

    @classmethod
    def from_engine_args(cls, engine_args: AsyncEngineArgs,
                         **kwargs) -> "AsyncLLMEngine":
        from intellillm_tpu.obs import get_boot_timeline
        with get_boot_timeline().phase("engine_init"):
            configs = engine_args.create_engine_configs()
            return cls(*configs,
                       log_stats=not engine_args.disable_log_stats,
                       log_requests=not engine_args.disable_log_requests,
                       **kwargs)

    @property
    def is_running(self) -> bool:
        return (self.background_loop is not None
                and not self.background_loop.done())

    @property
    def errored(self) -> bool:
        return self._errored_with is not None

    def start_background_loop(self) -> None:
        if self.errored:
            raise AsyncEngineDeadError(
                "Background loop has errored already.") from self._errored_with
        if self.is_running:
            raise RuntimeError("Background loop is already running.")
        self._request_tracker.init_event()
        self._background_loop_unshielded = asyncio.get_event_loop(
        ).create_task(self.run_engine_loop())
        self._background_loop_unshielded.add_done_callback(
            partial(_raise_exception_on_finish,
                    request_tracker=self._request_tracker))
        self.background_loop = asyncio.shield(
            self._background_loop_unshielded)

    async def engine_step(self) -> bool:
        """One schedule+execute+process pass; returns whether any request
        is in flight."""
        # The between-step phases of the loop period (obs/tracing.py):
        # admit, handoff, handback, stream_out.
        tracer = get_step_tracer()
        with tracer.span("admit"):
            new_requests, finished_requests = (
                self._request_tracker.get_new_and_finished_requests())

            for new_request in new_requests:
                try:
                    self.engine.add_request(**new_request)
                except ValueError as e:
                    self._request_tracker.propagate_exception(
                        e, new_request["request_id"])

            if finished_requests:
                self.engine.abort_request(finished_requests)

        # The device step blocks; run it off-loop. step_pipelined keeps
        # the device busy across the fetch (see llm_engine.py).
        loop = asyncio.get_event_loop()
        step_fn = (self.engine.step_pipelined
                   if self.engine.pipeline_enabled else self.engine.step)

        def locked_step():
            # Mutually exclusive with export_kv/import_kv (below), which
            # also run on executor threads and re-bind the device cache.
            # getattr: engine doubles in tests don't carry the lock.
            lock = getattr(self.engine, "_kv_transfer_lock", None)
            with lock if lock is not None else contextlib.nullcontext():
                tracer.end_crossing("handoff")
                try:
                    return step_fn()
                finally:
                    tracer.begin_crossing()

        tracer.begin_crossing()
        try:
            request_outputs = await loop.run_in_executor(None, locked_step)
        finally:
            tracer.end_crossing("handback")

        with tracer.span("stream_out"):
            for request_output in request_outputs:
                self._request_tracker.process_request_output(
                    request_output, verbose=self.log_requests)

        return len(request_outputs) > 0 or self.engine.has_inflight()

    async def run_engine_loop(self) -> None:
        has_requests_in_progress = False
        while True:
            if not has_requests_in_progress:
                await self._request_tracker.wait_for_new_requests()
            has_requests_in_progress = await self.engine_step()
            # The streams' consumers take their turn on the loop; the span
            # ends when this task runs again.
            with get_step_tracer().span("stream_out"):
                await asyncio.sleep(0)

    async def add_request(
        self,
        request_id: str,
        prompt: Optional[str],
        sampling_params: SamplingParams,
        prompt_token_ids: Optional[List[int]] = None,
        arrival_time: Optional[float] = None,
        lora_request=None,
        prefix_pos: Optional[int] = None,
        predicted_len: Optional[int] = None,
    ) -> AsyncStream:
        if self.log_requests:
            logger.info("Received request %s: prompt=%.80r params=%s",
                        request_id, prompt, sampling_params)
        if not self.is_running:
            if self.start_engine_loop:
                self.start_background_loop()
            else:
                raise AsyncEngineDeadError(
                    "Background loop is not running. Start it with "
                    "start_background_loop().")
        if arrival_time is None:
            arrival_time = time.monotonic()
        if prompt_token_ids is None and prompt is not None:
            prompt_token_ids = await self.engine.tokenizer.encode_async(
                prompt, request_id, lora_request)
        return self._request_tracker.add_request(
            request_id,
            prompt=prompt,
            sampling_params=sampling_params,
            prompt_token_ids=prompt_token_ids,
            arrival_time=arrival_time,
            lora_request=lora_request,
            prefix_pos=prefix_pos,
            predicted_len=predicted_len,
        )

    async def generate(
        self,
        prompt: Optional[str],
        sampling_params: SamplingParams,
        request_id: str,
        prompt_token_ids: Optional[List[int]] = None,
        lora_request=None,
        prefix_pos: Optional[int] = None,
        predicted_len: Optional[int] = None,
    ) -> AsyncIterator[RequestOutput]:
        """Stream RequestOutputs for one request; aborts on cancellation."""
        try:
            stream = await self.add_request(
                request_id, prompt, sampling_params,
                prompt_token_ids=prompt_token_ids,
                lora_request=lora_request, prefix_pos=prefix_pos,
                predicted_len=predicted_len)
            async for request_output in stream:
                yield request_output
        except (Exception, asyncio.CancelledError) as e:
            self._abort(request_id)
            raise e

    async def abort(self, request_id: str) -> None:
        if not self.is_running:
            raise AsyncEngineDeadError("Background loop is not running.")
        return self._abort(request_id)

    # --- disaggregated KV handoff (docs/routing.md) ----------------------

    async def export_kv(self, prompt: str) -> bytes:
        """Export the KV prefix pinned for `prompt` (prefill role)."""
        loop = asyncio.get_event_loop()
        return await loop.run_in_executor(
            None, self.engine.export_kv_for_prompt, prompt)

    async def import_kv(self, payload: bytes) -> dict:
        """Install an exported KV payload as a computed prefix."""
        loop = asyncio.get_event_loop()
        return await loop.run_in_executor(None, self.engine.import_kv,
                                          payload)

    # --- multi-tenant adapter lifecycle (docs/multitenancy.md) -----------

    async def load_lora_adapter(self, tenant_id: str, lora_name: str,
                                lora_int_id: int, lora_local_path: str,
                                weight: float = 1.0,
                                token_share_cap=None) -> dict:
        """Register a tenant and hot-load its adapter (POST /tenants)."""
        loop = asyncio.get_event_loop()
        return await loop.run_in_executor(
            None, lambda: self.engine.load_lora_adapter(
                tenant_id, lora_name, lora_int_id, lora_local_path,
                weight=weight, token_share_cap=token_share_cap))

    async def unload_lora_adapter(self, tenant_id: str) -> dict:
        """Unregister a tenant and drop its adapter."""
        loop = asyncio.get_event_loop()
        return await loop.run_in_executor(
            None, self.engine.unload_lora_adapter, tenant_id)

    def _abort(self, request_id: str) -> None:
        self._request_tracker.abort_request(request_id,
                                            verbose=self.log_requests)

    async def get_model_config(self):
        return self.engine.get_model_config()
