"""The traffic generator and the load driver's clocks."""
import asyncio
import json
import socket
from collections import Counter

import pytest
from chipbench import CHIP_DIR  # noqa: F401  (puts the harness on the path)

from harness import loadgen, stats
from harness.manifest import Manifest

MANIFEST = Manifest()
CLOSED = MANIFEST.traffic("decode-closed")
OPEN = dict(MANIFEST.traffic("chat-steady"), rate_rps=4.0)
SEEDS = [0, 7, 2**31 + 11, 3000000019]


def dump(plan):
    return json.dumps([[r.index, r.phase, r.prompt_len, r.output_len,
                        r.due_s, r.prompt] for r in plan]).encode()


def plan_of(traffic, seed, vocab=32000):
    if traffic["loop"] == "open":
        plan = loadgen.open_plan(traffic, seed, 45.0)
    else:
        plan = loadgen.closed_plan(traffic, seed, 512)
    loadgen.fill_prompts(plan, seed, vocab)
    return plan


@pytest.mark.parametrize("traffic", [CLOSED, OPEN], ids=["closed", "open"])
@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_bytes(traffic, seed):
    assert dump(plan_of(traffic, seed)) == dump(plan_of(traffic, seed))


@pytest.mark.parametrize("traffic", [CLOSED, OPEN], ids=["closed", "open"])
def test_another_seed_another_order_same_work(traffic):
    a, b = plan_of(traffic, SEEDS[1]), plan_of(traffic, SEEDS[2])
    assert dump(a) != dump(b)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    skip = traffic.get("clients", 0)    # the first wave is the ramp's
    for phase in {r.phase for r in a}:
        pairs_a = Counter((r.prompt_len, r.output_len) for r in a[skip:]
                          if r.phase == phase)
        pairs_b = Counter((r.prompt_len, r.output_len) for r in b[skip:]
                          if r.phase == phase)
        assert pairs_a == pairs_b, "every seed asks the same work"


def test_open_window_holds_the_same_arrivals_for_every_seed():
    gaps = []
    for seed in SEEDS:
        win = [r for r in loadgen.open_plan(OPEN, seed, 45.0)
               if r.phase == "window"]
        assert len(win) == round(4.0 * 45.0)
        t0 = OPEN["ramp_s"]
        assert all(t0 <= r.due_s < t0 + 45.0 for r in win)
        due = sorted(r.due_s for r in win)
        gaps.append(sorted(round(b - a, 9) for a, b in zip(due, due[1:])))
    assert all(g[1:] == gaps[0][1:] or sorted(g) == sorted(gaps[0])
               or len(g) == len(gaps[0]) for g in gaps)
    # Poisson-like: gaps differ widely, mean 1/rate.
    assert max(gaps[0]) > 5 * min(x for x in gaps[0] if x > 0)


@pytest.mark.parametrize("spec", [CLOSED["prompt_len"], CLOSED["output_len"],
                                  OPEN["prompt_len"], OPEN["output_len"]])
def test_clamps_and_median(spec):
    xs = loadgen.length_multiset(spec, 501)
    assert min(xs) >= spec["min"] and max(xs) <= spec["max"]
    assert xs == sorted(xs)
    assert abs(xs[250] - spec["median"]) <= 1
    assert xs[0] == spec["min"] or xs[-1] == spec["max"]


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        loadgen.length_multiset({"dist": "zipf"}, 3)


def test_rate_is_a_number_or_a_share_of_the_recorded_knee():
    assert loadgen.rate_of({"rate_rps": 2.5}) == 2.5
    assert loadgen.rate_of({"rate_rps": {"share_of_knee": 0.8},
                            "knee_rps": 5.0}) == pytest.approx(4.0)


def test_gaps_fill_the_span_exactly():
    gaps = loadgen.arrival_gaps(4.0, 180, 45.0)
    assert len(gaps) == 180 and sum(gaps) == pytest.approx(45.0)
    assert gaps == sorted(gaps)             # the exponential's quantiles


def test_closed_plan_first_wave_and_blocks():
    plan = loadgen.closed_plan(CLOSED, 5, 1024)
    n = CLOSED["clients"]
    wave, rest = plan[:n], plan[n:]
    anchors = CLOSED["first_wave"]["anchors"]
    assert all(r.prompt_len == CLOSED["first_wave"]["prompt_len"]
               for r in wave[:n - len(anchors)])
    assert [(r.prompt_len, r.output_len) for r in wave[n - len(anchors):]] \
        == [(a["prompt_len"], a["output_len"]) for a in anchors]
    # 127 four-token prompts and nothing else fit one 512-row step
    assert sum(r.prompt_len for r in wave[:n - len(anchors)]) <= 512
    lo, hi = CLOSED["output_len"]["min"], CLOSED["output_len"]["max"]
    assert all(lo <= r.output_len <= hi for r in wave)
    block = CLOSED["block"]
    first = Counter((r.prompt_len, r.output_len) for r in rest[:block])
    second = Counter((r.prompt_len, r.output_len)
                     for r in rest[block:2 * block])
    assert first == second
    assert [r.index for r in plan] == list(range(len(plan)))
    # a prompt and its output, with the K=8 overshoot, stay inside 1024
    assert all(r.prompt_len + r.output_len + 8 <= 1024 for r in plan)


def test_prompts_are_unique_and_clear_of_special_tokens():
    plan = plan_of(CLOSED, 3, vocab=1000)[200:260]
    assert all(len(r.prompt) == r.prompt_len for r in plan)
    assert all(3 <= t < 1000 for r in plan for t in r.prompt)
    heads = {tuple(r.prompt[:8]) for r in plan}
    assert len(heads) == len(plan)


# --- the driver against a fake server ----------------------------------------

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


async def _fake_server(port, first_token_s, stall_at=None, stall_s=0.0,
                       fail_index=None):
    """One request at a time (a lock), `first_token_s` to the first
    token; the request numbered `stall_at` holds the lock `stall_s`
    longer; the one numbered `fail_index` answers 500."""
    from aiohttp import web
    lock, count = asyncio.Lock(), [0]

    async def completions(request):
        body = await request.json()
        k = count[0]
        count[0] += 1
        if k == fail_index:
            return web.json_response({"error": "boom"}, status=500)
        resp = web.StreamResponse()
        await resp.prepare(request)
        async with lock:
            await asyncio.sleep(first_token_s
                                + (stall_s if k == stall_at else 0))

            async def chunk(words, finish=None):
                choice = {"text": " " + " ".join(words),
                          "finish_reason": finish}
                if body.get("logprobs") is not None:
                    choice["logprobs"] = {
                        "token_logprobs": [-1.5 - 0.001 * k] * len(words)}
                doc = {"choices": [choice]}
                await resp.write(f"data: {json.dumps(doc)}\n\n".encode())

            n = body["max_tokens"]
            await chunk(["w000005"], "length" if n == 1 else None)
            if n > 1:
                await asyncio.sleep(0.02)
                await chunk(["w000006"] * (n - 1), "length")
        await resp.write(b"data: [DONE]\n\n")
        return resp

    app = web.Application()
    app.router.add_post("/v1/completions", completions)
    runner = web.AppRunner(app)
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", port).start()
    return runner


def _open_run(stall_at=None, stall_s=0.0, fail_index=None, n=8, gap=0.05):
    async def go():
        port = _free_port()
        runner = await _fake_server(port, 0.01, stall_at, stall_s,
                                    fail_index)
        plan = [loadgen.Planned(i, "window", 4, 3, due_s=i * gap,
                                prompt=[5, 6, 7, 8]) for i in range(n)]
        async with loadgen.Driver(f"http://127.0.0.1:{port}", "m") as d:
            t0 = d.clock() + 0.05
            await d.run_open(plan, t0)
            await asyncio.sleep(stall_s + 0.5)
            samples = list(d.samples)
            cutoff = d.clock()
        await runner.cleanup()
        return samples, (t0, t0 + n * gap), cutoff
    return asyncio.run(go())


def test_open_loop_latency_is_taken_from_due_time():
    quiet, window, cutoff = _open_run()
    stalled, window_s, cutoff_s = _open_run(stall_at=2, stall_s=0.4)
    q = stats.ttft_values(quiet, window, cutoff)
    s = stats.ttft_values(stalled, window_s, cutoff_s)
    assert len(q) == len(s) == 8
    assert max(q) < 0.2
    by_index = {x.index: x for x in stalled}
    # The stall holds back the requests BEHIND it, and their TTFT shows
    # it although each was sent on time...
    for i in (3, 4, 5):
        x = by_index[i]
        assert x.first_t - x.due_t > 0.25
        assert x.send_t - x.due_t < 0.05
    # ...while the generator's own lateness stays small.
    assert max(stats.late_values(stalled, window_s)) < 0.05
    assert stats.percentile(s, 95) > 3 * stats.percentile(q, 95)


def test_failed_requests_miss_and_are_counted():
    samples, window, cutoff = _open_run(fail_index=1)
    attempted, failed = stats.attempted_failed(samples, window, "open",
                                               cutoff)
    assert (attempted, failed) == (8, 1)
    vals = stats.ttft_values(samples, window, cutoff)
    assert len(vals) == 8
    bad = next(x for x in samples if x.error)
    assert bad.error.startswith("http 500") and bad.first_t is None
    # the miss counts with the time it had waited at the cut-off
    assert max(vals) == pytest.approx(cutoff - bad.due_t)
    assert len(stats.tpot_values(samples, (window[0], cutoff))) == 7


def test_closed_loop_clients_take_the_next_request_when_one_ends():
    async def go():
        port = _free_port()
        runner = await _fake_server(port, 0.005)
        plan = [loadgen.Planned(i, "closed", 4, 3, prompt=[5, 6, 7, 8])
                for i in range(12)]
        async with loadgen.Driver(f"http://127.0.0.1:{port}", "m") as d:
            await d.run_closed(plan, clients=3)
            while not d.queue_exhausted:
                await asyncio.sleep(0.02)
            await asyncio.sleep(0.1)
            samples = list(d.samples)
        await runner.cleanup()
        return samples
    samples = asyncio.run(go())
    assert len(samples) == 12 and all(s.well_formed for s in samples)
    assert all(s.tokens == 3 and len(s.chunks) == 2 for s in samples)
    window = (min(s.send_t for s in samples), max(s.end_t for s in samples)
              + 1e-3)
    assert stats.tokens_in_window(samples, window) == 36
    assert stats.attempted_failed(samples, window, "closed", window[1]) \
        == (12, 0)


def test_the_probe_seat_sends_probes_in_turns_once_the_window_is_open():
    """4 clients, the last is the seat: no probe before the window is
    open, then two after each of its next two requests, never a row more
    than the clients."""
    from harness import probes

    async def go():
        port = _free_port()
        runner = await _fake_server(port, 0.004)
        plan = [loadgen.Planned(i, "closed", 4, 3, prompt=[5, 6, 7, 8])
                for i in range(400)]
        probe_plan = probes.probe_plan(7, 1024)
        async with loadgen.Driver(f"http://127.0.0.1:{port}", "m") as d:
            await d.run_closed(plan, clients=4, seat_probes=probe_plan)
            await asyncio.sleep(0.15)
            before_open = len(d.seat_samples)
            d.window_open = True
            t_open = d.clock()
            while len(d.seat_samples) < 4 and not d.queue_exhausted:
                await asyncio.sleep(0.01)
            await asyncio.sleep(0.05)
            seat, samples = list(d.seat_samples), list(d.samples)
        await runner.cleanup()
        return before_open, t_open, seat, samples, probe_plan
    before_open, t_open, seat, samples, probe_plan = asyncio.run(go())
    assert before_open == 0
    assert [s.index for s in seat] == [p.index for p in probe_plan]
    assert all(s.phase == "probe" and s.well_formed and s.send_t >= t_open
               for s in seat)
    assert all(len(s.logprobs) == s.tokens == 16 for s in seat)
    assert seat[0].text.split()[0] == "w000005"
    # two a turn: an ordinary request of the seat's lies between the pairs
    order = sorted(samples, key=lambda s: s.send_t)
    pos = [order.index(s) for s in seat]
    assert pos[1] == pos[0] + 1 or seat[1].send_t >= seat[0].end_t
    assert seat[2].send_t > seat[1].end_t
    assert any(s.phase == "closed" and seat[1].end_t <= s.send_t
               and s.end_t <= seat[2].send_t for s in samples)
    # the probes are left out of the cell's numbers
    window = (t_open, max(s.end_t for s in samples if s.end_t) + 1e-3)
    assert stats.tokens_in_window(seat, window) == 0
    assert stats.tpot_values(seat, window) == []


def test_the_driver_notes_a_stall_of_the_token_stream():
    async def go():
        port = _free_port()
        runner = await _fake_server(port, 0.005, stall_at=6, stall_s=0.3)
        plan = [loadgen.Planned(i, "closed", 4, 3, prompt=[5, 6, 7, 8])
                for i in range(12)]
        async with loadgen.Driver(f"http://127.0.0.1:{port}", "m",
                                  stall_gap_s=0.2) as d:
            await d.run_closed(plan, clients=2)
            while not d.queue_exhausted:
                await asyncio.sleep(0.02)
            await asyncio.sleep(0.05)
            out = (d.last_stall_t, d.last_arrival_t, list(d.samples))
        await runner.cleanup()
        return out
    stall_t, arrival_t, samples = asyncio.run(go())
    stalled = next(s for s in samples if s.index == 6)
    assert stall_t == pytest.approx(stalled.first_t, abs=0.05)
    assert arrival_t >= stall_t


def _probe(i, words, logprobs, **kw):
    from harness import probes
    s = loadgen.Sample(probes._PROBE_INDEX0 + i, "probe", 12, len(words),
                       None)
    s.tokens, s.finish, s.end_t = len(words), "length", 1.0
    s.text, s.logprobs = " " + " ".join(words), list(logprobs)
    for k, v in kw.items():
        setattr(s, k, v)
    return s


@pytest.mark.parametrize("case,ok,needle", [
    ("same", True, None),
    ("noise", True, None),                       # within the tolerance
    ("flip", True, None),                        # a flip ends the comparison
    ("shifted", False, "differs from the alone run"),
    ("all_shifted", False, "on the mean of"),    # each within, all together not
    ("few_shifted", True, None),                 # too few for a mean
    ("nan", False, "not finite"),
    ("short", False, "logprobs for"),
    ("none_in_window", False, "no probe ran inside the window"),
    ("after_differs", False, "tokens before != after")])
def test_probe_comparison(case, ok, needle):
    from harness import probes
    words = ["w000010", "w000011", "w000012", "w000013"]
    lps = [-2.0, -2.5, -3.0, -3.5]
    before = [_probe(0, words, lps)]
    after = [_probe(0, words, lps)]
    tol = probes.LOGPROB_TOL
    during = [_probe(0, words, lps)]
    if case == "noise":
        during = [_probe(0, words, [x + 0.9 * probes.LOGPROB_MEAN_TOL
                                    for x in lps])]
    elif case == "flip":
        # another choice at position 1, close in logprob; what follows has
        # another context and may be anywhere
        during = [_probe(0, [words[0], "w000099", "w000500", "w000501"],
                         [lps[0], lps[1] - 0.2 * tol, -9.0, -0.1])]
    elif case == "shifted":
        during = [_probe(0, words, [lps[0], lps[1] + 3 * tol] + lps[2:])]
    elif case in ("all_shifted", "few_shifted"):
        n = probes.MEAN_NEEDS if case == "all_shifted" else 4
        long_words = [f"w{k:06d}" for k in range(100, 100 + n)]
        base = [-2.0 - 0.01 * k for k in range(n)]
        before = [_probe(0, long_words, base)]
        after = [_probe(0, long_words, base)]
        shift = 1.5 * probes.LOGPROB_MEAN_TOL
        assert shift < tol
        during = [_probe(0, long_words, [x - shift for x in base])]
    elif case == "nan":
        during = [_probe(0, words, [lps[0], float("nan")] + lps[2:])]
    elif case == "short":
        during = [_probe(0, words, lps[:2])]
    elif case == "none_in_window":
        during = []
    elif case == "after_differs":
        after = [_probe(0, words[:3] + ["w000777"], lps)]
    got = probes.compare(before, during, after)
    assert got["ok"] is ok
    if needle:
        assert any(needle in p for p in got["problems"]), got["problems"]
    if case == "flip":
        row = next(r for r in got["comparisons"] if r["against"] == "window")
        assert row["compared"] == 2 and row["tokens_equal"] == 1
        assert row["first_token_equal"]
