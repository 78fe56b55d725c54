"""One K=8 fused decode step of a state model's runner on pools of random
bytes, the 2-d state arrays' rows moved by `ops/state_rows.py` or by the
`.at[]` forms it replaced: tokens and every pool's bytes have to agree."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from intellillm_tpu.worker import model_runner

# case -> (slot a row, rows that are live): 8 rows over 8 slots.
CASES = {
    # Row 2 has no context, so it is a pad row, whatever slot it names:
    # slot 3 (which no live row holds) keeps its bytes.
    "pad_row_with_a_slot": ([0, 1, 3, 2, 4, 5, 6, 7],
                            [1, 1, 0, 1, 1, 1, 0, 1]),
    "permuted_slots": ([5, 2, 7, 0, 3, 6, 1, 4], [1] * 8),
}


def at_forms(monkeypatch):
    """The runner's stage as it was built before `ops/state_rows.py`."""
    monkeypatch.setattr(
        model_runner, "gather_rows",
        lambda pool, slots: pool.at[slots].get(mode="fill", fill_value=0))
    monkeypatch.setattr(
        model_runner, "scatter_rows",
        lambda pool, slots, rows: pool.at[slots].set(rows, mode="drop"))


def bits(a):
    a = np.asarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def random_pools(llm, seed=0):
    """Arrays of the engine's pools' shapes and dtypes, every byte drawn."""
    rng = np.random.default_rng(seed)
    return [tuple(jnp.asarray(rng.normal(size=p.shape), p.dtype)
                  for p in pools)
            for pools in llm.llm_engine.worker.cache_engine.device_cache]


def k_step_decode(llm, pools, case, k=8):
    """(tokens [B, K], the pools after the step)."""
    runner = llm.llm_engine.worker.model_runner
    slots, live = (np.asarray(x, np.int32) for x in CASES[case])
    b, w = len(slots), 2
    assert runner.num_state_slots == b
    rng = np.random.default_rng(3)
    ctx = np.where(live, rng.integers(3, 14, size=b), 0).astype(np.int32)
    f32, i32 = jnp.float32, jnp.int32
    args = (jnp.asarray(rng.integers(3, 60, size=(b, 1)), i32),
            jnp.asarray(np.maximum(ctx - 1, 0)[:, None]),
            jnp.asarray(np.arange(b * w, dtype=np.int32).reshape(b, w)),
            jnp.asarray(ctx), jnp.zeros(b, f32), jnp.zeros(b, i32),
            jnp.ones(b, f32), jnp.zeros(b, f32), jnp.zeros(b, jnp.uint32),
            jnp.zeros(b, f32), jnp.zeros(b, f32), jnp.ones(b, f32),
            None, None)
    step = jax.jit(functools.partial(
        runner._decode_fn, num_steps=k, logprob_k=1, do_topk=False,
        do_topp=False, do_minp=False, do_penalties=False, do_random=False))
    packed, new_pools = step(runner.params, pools, *args,
                             state_slots=jnp.asarray(slots))
    return np.asarray(packed)[:, :k], new_pools


def assert_same_step(llm, monkeypatch, case):
    """The step both ways: the same tokens, every pool the same bytes; in
    every 2-d state pool the slots of live rows are written and the others
    (a pad row's too, whatever slot it names) keep their bytes. Returns
    how many such pools there were."""
    pools = random_pools(llm)
    tokens, after = k_step_decode(llm, pools, case)
    with monkeypatch.context() as m:
        at_forms(m)
        tokens_at, after_at = k_step_decode(llm, pools, case)
    slots, live = (np.asarray(x) for x in CASES[case])
    live = live.astype(bool)
    np.testing.assert_array_equal(tokens[live], tokens_at[live])
    for got, want in zip(jax.tree.leaves(after), jax.tree.leaves(after_at)):
        np.testing.assert_array_equal(bits(got), bits(want))
    held = np.sort(slots[live])
    free = np.setdiff1d(np.arange(len(slots)), held)
    kinds = llm.llm_engine.model_config.get_layer_cache_kinds()
    rows = [(bits(b), bits(a))
            for kind, before, now in zip(kinds, pools, after)
            if kind == "state" for b, a in zip(before, now) if b.ndim == 2]
    for b, a in rows:
        assert (a[free] == b[free]).all()
        assert (a[held] != b[held]).any(1).all()
    return len(rows)
