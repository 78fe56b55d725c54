"""The freeze points of obs/heap.py: the long-lived heap leaves the
collector's sight when the engine stands and behind every first call, a
second engine gives the first one's heap back, garbage made after a
freeze is still collected, and the collector's seconds are on /metrics.
"""
import gc
import time
import weakref

import pytest

from intellillm_tpu import LLM, SamplingParams
from intellillm_tpu.obs import get_heap
from intellillm_tpu.obs.compile_tracker import CompileTracker


def _llm(model_dir):
    return LLM(model=model_dir, load_format="dummy", dtype="float32",
               num_device_blocks_override=64, max_model_len=64,
               max_num_seqs=4, disable_log_stats=False)


@pytest.fixture(scope="module")
def llm(tiny_llama_dir):
    llm = _llm(tiny_llama_dir)
    yield llm
    gc.unfreeze()


def _collect_s() -> float:
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        gc.collect()
        best = min(best, time.perf_counter() - t0)
    return best


def test_a_built_engine_has_frozen_its_heap(llm):
    heap = get_heap()
    assert gc.get_freeze_count() > 0
    assert heap.frozen_objects > 0 and heap.freezes >= 1
    assert heap.last_freeze["at"] in ("engine_stands", "first_call")
    frozen_s = _collect_s()
    try:
        gc.unfreeze()
        thawed_s = _collect_s()
    finally:
        gc.collect()
        gc.freeze()
    assert frozen_s < thawed_s / 10, (frozen_s, thawed_s)


def test_a_first_call_freezes_and_a_hit_does_not(llm):
    tracker = CompileTracker()
    heap = get_heap()
    kept = []

    def build(n):
        kept.append([[i] for i in range(n)])
        return n

    before, freezes = gc.get_freeze_count(), heap.freezes
    counted = heap.frozen_objects
    assert tracker.call("mixed", ("bucket", 1), build, 500) == 500
    after = gc.get_freeze_count()
    assert after >= before + 500 and heap.freezes == freezes + 1
    assert heap.last_freeze["at"] == "first_call"
    # (the heap counts what goes in; the collector what is still there)
    assert heap.frozen_objects >= counted + 500
    # the same bucket again: a hit
    assert tracker.call("mixed", ("bucket", 1), build, 500) == 500
    assert gc.get_freeze_count() == after and heap.freezes == freezes + 1


def test_a_failed_first_call_freezes_nothing(llm):
    tracker = CompileTracker()
    freezes = get_heap().freezes

    def boom():
        raise RuntimeError("compile failed")

    with pytest.raises(RuntimeError):
        tracker.call("mixed", ("bucket", 2), boom)
    assert get_heap().freezes == freezes


class _Cyclic:
    def __init__(self):
        self.me = self


def test_a_second_engine_gives_the_firsts_heap_back(tiny_llama_dir, llm):
    """A cycle that was alive at a freeze and dropped afterwards stays (no
    collection walks the permanent generation) until the next engine of
    the process thaws, collects and freezes again."""
    heap = get_heap()
    first = _llm(tiny_llama_dir)
    ring = _Cyclic()
    first.llm_engine._ring = ring         # dies with the engine
    gone = weakref.ref(ring)
    heap.first_call_done()                # frozen while alive
    del ring, first
    gc.collect()
    assert gone() is not None
    freezes = heap.freezes
    second = _llm(tiny_llama_dir)
    assert gone() is None
    assert heap.freezes > freezes and gc.get_freeze_count() > 0
    assert heap.last_freeze["at"] in ("engine_stands", "first_call")
    del second


def test_cycles_made_after_a_freeze_are_still_collected(llm):
    engine = llm.llm_engine
    params = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
    get_heap().first_call_done()
    counts = []
    steps = 0
    while steps < 200:
        if not engine.has_unfinished_requests():
            engine.add_request(f"cyc-{steps}", "the cat runs fast", params)
        engine.step()
        _Cyclic()                         # garbage only a collection frees
        steps += 1
        if steps % 50 == 0:
            counts.append(len(gc.get_objects()))    # tracked, unfrozen
    while engine.has_unfinished_requests():
        engine.step()
    # a step leaves ~1 cycle + its outputs; unbounded growth would add
    # thousands of objects between readings
    assert max(counts) - min(counts) < 5000, counts
    assert gc.get_freeze_count() > 0


def test_the_three_series_are_on_metrics(llm):
    from prometheus_client import REGISTRY, generate_latest

    engine = llm.llm_engine

    def scrape():
        engine.stat_logger._export_heap()
        text = generate_latest(REGISTRY).decode()
        out = {}
        for line in text.splitlines():
            if line.startswith("intellillm_gc_"):
                name, value = line.rsplit(" ", 1)
                out[name] = float(value)
        return out

    def of(series, name, generation):
        hit = [v for k, v in series.items() if k.startswith(name + "{")
               and f'generation="{generation}"' in k]
        assert len(hit) == 1, (name, generation, sorted(series))
        return hit[0]

    before = scrape()
    for generation in (0, 1, 2):
        of(before, "intellillm_gc_pause_seconds_total", generation)
        of(before, "intellillm_gc_collections_total", generation)
    frozen = [v for k, v in before.items()
              if k.startswith("intellillm_gc_frozen_objects")]
    assert frozen == [float(get_heap().frozen_objects)] and frozen[0] > 0
    gc.collect()
    after = scrape()
    assert (of(after, "intellillm_gc_collections_total", 2)
            == of(before, "intellillm_gc_collections_total", 2) + 1)
    assert (of(after, "intellillm_gc_pause_seconds_total", 2)
            > of(before, "intellillm_gc_pause_seconds_total", 2))
    snap = get_heap().snapshot()
    assert snap["gc_collections"]["2"] >= 1
    assert snap["frozen_objects"] == frozen[0]
    assert snap["last_freeze"]["frozen_objects"] > 0


def test_health_detail_has_the_heap_block(llm):
    import asyncio

    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from intellillm_tpu.entrypoints.debug_routes import add_debug_routes

    app = web.Application()
    add_debug_routes(app, lambda: None)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            # (the blocks that need no engine come before it asks for one)
            return (await (await client.get("/health/detail")).json())
        finally:
            await client.close()

    block = asyncio.run(go())["heap"]
    assert block["frozen_objects"] == get_heap().frozen_objects > 0
    assert set(block["gc_pause_s"]) == set(block["gc_collections"]) == {
        "0", "1", "2"}
    assert block["last_freeze"]["at"] in ("engine_stands", "first_call")
    assert block["freezes"] == get_heap().freezes
