"""One general traffic generator and one load driver.

A traffic mix is a data file (traffic/<mix>.json); this module turns it
and `--seed` into a plan, and drives the plan against the server from one
thread (asyncio + aiohttp).

**What the seed may change.** Every seed gets the same multiset of
(prompt length, output length) pairs and the same multiset of arrival
gaps: they are the quantiles of the file's distributions, not draws. The
seed only changes their order and the tokens inside the prompts. So two
seeds ask the same work of the server, and a difference between runs is
the server's.

**Clocks.** In an open loop a request's latency is counted from the
moment it was *due*, not from the moment it was sent: a stalled server
(or a starved generator) then shows in the latency of the requests behind
it. How late the generator sent is reported beside it (`late_s`).
(benchmarks/benchmark_serving.py times from sending; serve_bench.py's
`synth_diurnal` is where the lognormal lengths come from.)

**The probe seat.** In a closed loop one of the `clients` is the seat
of the correctness probes (probes.py): once the window is open it sends
them, two at each of its next two turns, in place of its next ordinary
requests. The batch stays as wide as the clients are many, so the probes
run through the very programs the window measures and add no shape.
"""
from __future__ import annotations

import asyncio
import json
import math
import random
import re
import time
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Callable, List, Optional

_TOKEN_RE = re.compile(r"w\d{6}|<pad>|</s>|<unk>")


# --- the plan (pure: no clock, no I/O) ---------------------------------------

@dataclass
class Planned:
    index: int
    phase: str              # warm | ramp | window | cooldown | probe | closed
    prompt_len: int
    output_len: int
    due_s: Optional[float] = None   # open loop: seconds from traffic start
    prompt: Optional[list] = None   # token ids, filled by fill_prompts
    logprobs: Optional[int] = None  # asked of the server when not None


def _quantile_points(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def length_multiset(spec: dict, n: int) -> List[int]:
    """n lengths at the quantiles of `spec`: {"dist": "lognormal",
    "median", "sigma", "min", "max"}."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    out = []
    for u in _quantile_points(n):
        x = spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(u))
        out.append(int(min(spec["max"], max(spec["min"], round(x)))))
    return out


def length_pairs(traffic: dict, n: int) -> List[tuple]:
    """The fixed multiset of n (prompt, output) pairs: both marginals at
    their quantiles, paired by a permutation that belongs to the traffic
    file (`pairing_seed`), not to the run."""
    prompts = length_multiset(traffic["prompt_len"], n)
    outputs = length_multiset(traffic["output_len"], n)
    random.Random(traffic.get("pairing_seed", 0)).shuffle(outputs)
    return list(zip(prompts, outputs))


def arrival_gaps(rate: float, n: int, duration: float) -> List[float]:
    """n gaps that sum to `duration`: the exponential quantiles (a
    Poisson process's gaps), scaled to fill the span exactly."""
    gaps = [-math.log(1.0 - u) / rate for u in _quantile_points(n)]
    scale = duration / sum(gaps)
    return [g * scale for g in gaps]


def segment(traffic: dict, rate: float, duration: float, phase: str,
            start_s: float, rng: random.Random, first_index: int
            ) -> List[Planned]:
    """One open-loop span: round(rate*duration) requests, fixed multisets
    of lengths and gaps, order by `rng`."""
    n = max(1, round(rate * duration))
    pairs = length_pairs(traffic, n)
    rng.shuffle(pairs)
    gaps = arrival_gaps(rate, n, duration)
    rng.shuffle(gaps)
    times, t = [], 0.0
    for g in gaps:
        times.append(t)
        t += g
    return [Planned(first_index + i, phase, p, o, due_s=start_s + times[i])
            for i, (p, o) in enumerate(pairs)]


def rate_of(traffic: dict) -> float:
    """`rate_rps`: a number, or {"share_of_knee": x} of the file's
    `knee_rps` (which the file records with the sweep that found it)."""
    rate = traffic["rate_rps"]
    if isinstance(rate, dict):
        return float(rate["share_of_knee"]) * float(traffic["knee_rps"])
    return float(rate)


def open_plan(traffic: dict, seed: int, seconds: float) -> List[Planned]:
    """ramp | window | cooldown, each a span of its own, so the window
    always holds the same requests in number and in kind."""
    rng = random.Random(seed)
    rate = rate_of(traffic)
    plan, t = [], 0.0
    for phase, dur in (("ramp", traffic["ramp_s"]), ("window", seconds),
                       ("cooldown", traffic["cooldown_s"])):
        if dur > 0:
            plan += segment(traffic, rate, dur, phase, t, rng, len(plan))
        t += dur
    return plan


def closed_plan(traffic: dict, seed: int, total: int) -> List[Planned]:
    """The closed loop's queue: the first wave (one request a client, see
    `first_wave` in the traffic file) and then blocks of `block` requests,
    each block the same multiset in an order of its own. Clients take the
    next request when their last one ends."""
    rng = random.Random(seed)
    clients = traffic["clients"]
    plan: List[Planned] = []
    wave = traffic.get("first_wave")
    if wave:
        # As if the clients had been running for a while: outputs are
        # what is left of a request caught at a uniform point of its life.
        outs = length_multiset(traffic["output_len"], clients)
        rng.shuffle(outs)
        left = [(k + 1) / clients for k in range(clients)]
        rng.shuffle(left)
        lo = traffic["output_len"]["min"]
        anchors = wave.get("anchors", [])
        for i in range(clients - len(anchors)):
            plan.append(Planned(len(plan), "closed", wave["prompt_len"],
                                max(lo, round(outs[i] * left[i]))))
        for a in anchors:
            plan.append(Planned(len(plan), "closed", a["prompt_len"],
                                a["output_len"]))
    block = traffic.get("block", clients)
    while len(plan) < total:
        pairs = length_pairs(traffic, block)
        rng.shuffle(pairs)
        for p, o in pairs:
            plan.append(Planned(len(plan), "closed", p, o))
    return plan


def fill_prompts(plan: List[Planned], seed: int, vocab: int) -> None:
    """Token ids of every prompt, from the seed and the request's index:
    unique prompts, no shared prefix, ids clear of the special tokens."""
    import numpy as np
    for req in plan:
        rng = np.random.default_rng([seed % (2**32), seed >> 32, req.index])
        req.prompt = rng.integers(3, vocab, size=req.prompt_len).tolist()


# --- samples ------------------------------------------------------------------

@dataclass
class Sample:
    index: int
    phase: str
    prompt_len: int
    asked: int
    due_t: Optional[float]      # perf_counter times from here on
    send_t: float = 0.0
    first_t: Optional[float] = None
    last_t: Optional[float] = None
    end_t: Optional[float] = None
    tokens: int = 0
    finish: Optional[str] = None
    error: Optional[str] = None
    cancelled: bool = False
    chunks: list = field(default_factory=list)     # (t, tokens in chunk)
    text: str = ""
    logprobs: list = field(default_factory=list)   # of the tokens, if asked

    @property
    def done(self) -> bool:
        return self.end_t is not None and self.error is None

    @property
    def well_formed(self) -> bool:
        return (self.done and self.tokens == self.asked
                and self.finish == "length")


def request_body(model: str, req: Planned) -> bytes:
    body = {"model": model, "prompt": req.prompt,
            "max_tokens": req.output_len, "temperature": 0.0,
            "ignore_eos": True, "stream": True,
            "skip_special_tokens": False}
    if req.logprobs is not None:
        body["logprobs"] = req.logprobs
    return json.dumps(body).encode()


# --- the driver ---------------------------------------------------------------

class Driver:
    """Sends planned requests and records client-side samples. One
    asyncio loop, one thread. `keep_text` keeps the streamed text (the
    probes compare it). `stall_gap_s`: a pause this long between any two
    token arrivals, over all requests together, is a stall of the server
    (`last_stall_t`), which the ramp waits out."""

    def __init__(self, base: str, model: str,
                 clock: Callable[[], float] = time.perf_counter,
                 stall_gap_s: float = 1.0):
        self.base = base
        self.model = model
        self.clock = clock
        self.stall_gap_s = stall_gap_s
        self.samples: List[Sample] = []
        self.queue_exhausted = False
        self.last_arrival_t: Optional[float] = None
        self.last_stall_t: Optional[float] = None
        self.window_open = False        # set by the caller; the seat reads it
        self.seat_samples: List[Sample] = []
        self._tasks: set = set()
        self._session = None

    async def __aenter__(self) -> "Driver":
        import aiohttp
        self._session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None, sock_read=600))
        return self

    async def __aexit__(self, *exc) -> None:
        await self.cancel_all()
        await self._session.close()

    async def get_text(self, path: str) -> str:
        async with self._session.get(self.base + path) as resp:
            resp.raise_for_status()
            return await resp.text()

    async def get_json(self, path: str):
        return json.loads(await self.get_text(path))

    async def post_json(self, path: str):
        async with self._session.post(self.base + path, json={}) as resp:
            resp.raise_for_status()
            return await resp.json()

    async def send(self, req: Planned, due_t: Optional[float] = None,
                   keep_text: bool = False, body: bytes = None) -> Sample:
        """One streamed /v1/completions; never raises: what goes wrong is
        the sample's `error`."""
        s = Sample(req.index, req.phase, req.prompt_len, req.output_len,
                   due_t)
        self.samples.append(s)
        body = body or request_body(self.model, req)
        s.send_t = self.clock()
        try:
            async with self._session.post(
                    self.base + "/v1/completions", data=body,
                    headers={"Content-Type": "application/json"}) as resp:
                if resp.status != 200:
                    s.error = f"http {resp.status}: " + (
                        await resp.text())[:200]
                    return s
                async for raw in resp.content:
                    if not raw.startswith(b"data:"):
                        continue
                    payload = raw[5:].strip()
                    if payload == b"[DONE]":
                        break
                    now = self.clock()
                    doc = json.loads(payload)
                    if "choices" not in doc:
                        s.error = str(doc)[:200]
                        return s
                    choice = doc["choices"][0]
                    text = choice.get("text") or ""
                    n = len(_TOKEN_RE.findall(text))
                    if n:
                        if s.first_t is None:
                            s.first_t = now
                        s.last_t = now
                        s.tokens += n
                        s.chunks.append((now, n))
                        if keep_text:
                            s.text += text
                        if req.logprobs is not None:
                            s.logprobs += (choice.get("logprobs") or {}
                                           ).get("token_logprobs") or []
                        self._arrived(now)
                    s.finish = choice.get("finish_reason") or s.finish
            s.end_t = self.clock()
        except asyncio.CancelledError:
            s.cancelled = True
            raise
        except Exception as e:   # a boundary: the run goes on, the sample fails
            s.error = f"{type(e).__name__}: {e}"[:200]
        return s

    def _arrived(self, now: float) -> None:
        last = self.last_arrival_t
        if last is not None and now - last > self.stall_gap_s:
            self.last_stall_t = now
        self.last_arrival_t = now

    def spawn(self, coro) -> asyncio.Task:
        task = asyncio.ensure_future(coro)
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)
        return task

    async def cancel_all(self) -> None:
        tasks = list(self._tasks)
        for t in tasks:
            t.cancel()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)

    async def run_open(self, plan: List[Planned], t0: float) -> None:
        """Send each request when it is due (t0 + due_s); returns when
        the last one has been sent. Responses stream in the background."""
        bodies = [request_body(self.model, r) for r in plan]
        for req, body in zip(plan, bodies):
            due = t0 + req.due_s
            delay = due - self.clock()
            if delay > 0:
                await asyncio.sleep(delay)
            self.spawn(self.send(req, due_t=due, body=body))

    async def run_closed(self, plan: List[Planned], clients: int,
                         seat_probes: List[Planned] = ()) -> None:
        """`clients` workers, each taking the queue's next request when
        its last one ended; runs until cancelled or the queue is empty.
        The last worker is the probe seat: once `window_open` is set it
        sends two of `seat_probes` after each of its requests until none
        is left (`seat_samples`). Two a turn: the longest request of the
        cells' traffic lasts ~19 s, so four probes in two turns end
        inside a 51 s window whatever the seat was doing when it opened."""
        queue = iter(plan)
        pending = list(seat_probes)

        async def client(seat: bool) -> None:
            for req in queue:
                await self.send(req)
                if seat and self.window_open:
                    turn, pending[:] = pending[:2], pending[2:]
                    for probe in turn:
                        self.seat_samples.append(
                            await self.send(probe, keep_text=True))
            self.queue_exhausted = True

        for k in range(clients):
            self.spawn(client(seat=(k == clients - 1)))
