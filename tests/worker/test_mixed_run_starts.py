"""What the runner tells the ragged seam about a `mixed` step's rows.

The kernel walks a run's paged context once a tile of its rows, and
nothing in the rows' metadata says which rows continue the row before
them: `_execute_mixed` knows (it expands a chunk's `num_computed_tokens`
/ `token_chunk_size` into rows) and hands the program `run_starts`. The
walked-tokens counter follows the same rule (`ragged_walkers`)."""
import numpy as np
import pytest

from intellillm_tpu import LLM, SamplingParams
from intellillm_tpu.obs import get_efficiency_tracker

PROMPTS = [
    " ".join(["the cat runs fast and the dog"] * 12),  # 84 tokens
    "hello my name is",
    " ".join(["the cat runs fast and the dog"] * 4),  # 28 tokens
    "the capital of france is",
    " ".join(["the president of the united states is"] * 3),
]


@pytest.fixture
def stepped(tiny_opt_dir, monkeypatch):
    """An engine whose 32-token budget cuts prompts into chunks beside
    decode rows, and every `mixed` dispatch's rows as the runner built
    them: (metadata list, run_starts, context_lens, table width). A full
    tile has 16 rows here (128 on the chip: more than this model's
    longest prompt), so that some spans are tiles of many."""
    from intellillm_tpu.ops.pallas import ragged_paged_attention as rpa
    monkeypatch.setattr(rpa, "_tile_height", lambda g: 16)
    eff = get_efficiency_tracker()
    eff.reset_for_testing()
    llm = LLM(model=tiny_opt_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, num_decode_steps=1,
              enable_chunked_prefill=True, max_num_batched_tokens=32)
    eff.reset_for_testing()
    runner = llm.llm_engine.worker.model_runner
    seen = []
    execute, program = runner._execute_mixed, runner._jit_decode_single

    def recording_execute(metas, *args, **kwargs):
        seen.append([metas])
        return execute(metas, *args, **kwargs)

    def recording_program(*args, **kwargs):
        if seen and len(seen[-1]) == 1:
            seen[-1] += [np.asarray(kwargs["run_starts"]),
                         np.asarray(args[5]), args[4].shape[1]]
        return program(*args, **kwargs)

    runner._execute_mixed = recording_execute
    runner._jit_decode_single = recording_program
    for i, p in enumerate(PROMPTS):
        llm.llm_engine.add_request(str(i), p, SamplingParams(
            temperature=0.0, max_tokens=6, ignore_eos=True))
    list(llm._run_engine(use_tqdm=False))
    yield runner, seen, eff.snapshot()
    eff.reset_for_testing()


def test_run_starts_are_what_the_metadata_says(stepped):
    runner, seen, _ = stepped
    chunked = 0
    for metas, starts, ctx, _ in seen:
        want, want_ctx = [], []
        for meta in metas:
            if meta.token_chunk_size is None:
                # (Its sequences have grown since: no context to compare.)
                want += [1] * len(meta.seq_data)
                want_ctx += [None] * len(meta.seq_data)
                continue
            # A chunk's rows continue one another from its first.
            want += [1] + [0] * (meta.token_chunk_size - 1)
            want_ctx += list(range(
                meta.num_computed_tokens + 1,
                meta.num_computed_tokens + meta.token_chunk_size + 1))
            chunked += meta.token_chunk_size > 1
        n = len(want)
        assert starts.dtype == np.int32 and starts.shape == ctx.shape
        assert starts[:n].tolist() == want
        assert (starts[n:] == 1).all() and (ctx[n:] == 0).all()
        # ... and a row that continues another stands one token on.
        assert all(c == w_ for c, w_ in zip(ctx[:n].tolist(), want_ctx)
                   if w_ is not None)
        cont = np.flatnonzero(starts[:n] == 0)
        assert (ctx[cont] == ctx[cont - 1] + 1).all()
    assert chunked >= len(PROMPTS)


def test_walked_tokens_follow_the_tiled_walk(stepped):
    """Only a tile's first row walks: `walk_extent` of its context less
    its own token; a tile of many is a whole span of one run's rows."""
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        launch_rows, tile_rows, tiles_many)
    from intellillm_tpu.ops.ragged_attention import walk_extent
    runner, seen, snap = stepped
    bs = runner.block_size
    walked = a_row_at_a_time = rows_alone = rows_total = 0
    for metas, starts, ctx, w in seen:
        n = int((ctx > 0).sum())
        rows = launch_rows(len(ctx), w)
        t = tile_rows(rows, runner._queries_per_kv)
        # A whole span of rows that continue one another is a tile of
        # many (in a launch as high as a full tile); every other row
        # walks alone.
        whole = [all(i < n and not starts[i] for i in range(s0 + 1, s0 + t))
                 and s0 < n and tiles_many(rows, runner._queries_per_kv)
                 for s0 in range(0, len(ctx), t)]
        walks = [i for i in range(n) if i % t == 0 or not whole[i // t]]
        pages, _ = walk_extent(ctx[walks] - 1, bs, w)
        walked += int(pages.sum()) * bs
        a_row_at_a_time += int(walk_extent(ctx[:n] - 1, bs, w)[0].sum()) * bs
        ends = walks[1:] + [n]
        rows_alone += sum(e - s == 1 for s, e in zip(walks, ends))
        rows_total += n
        # The runner's own rule, a step at a time.
        assert runner._walked_tokens(
            ctx[:n] - 1, ctx[:n], w,
            runner._mixed_walkers(starts[:n], len(starts), w)[0]) == int(pages.sum()) * bs
    # The counters hold these steps and the steps of decode rows alone
    # (every row a tile of one), which walk a row at a time.
    mixed = snap["programs"]["mixed"]
    tiles = snap["mixed_tile_rows"]
    assert tiles["many"] == rows_total - rows_alone > 0
    assert tiles["one"] == mixed["row_steps"] - tiles["many"] >= rows_alone
    assert mixed["walked_tokens"] >= walked
    # Walking once a tile is what makes walked / context fall.
    assert walked < a_row_at_a_time
