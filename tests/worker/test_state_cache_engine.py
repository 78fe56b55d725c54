"""The cache engine of a model whose layers differ in what they cache:
paged K/V pools for the attention layers only, one state pool a conv layer,
and the state ops by slot (swap out, swap in, fork's copy)."""
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.config import CacheConfig, ModelConfig, ParallelConfig
from intellillm_tpu.transformers_utils.configs import Lfm2MoeConfig
from intellillm_tpu.worker.cache_engine import CacheEngine


def model_config(dtype="float32"):
    hf = Lfm2MoeConfig(
        vocab_size=64, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=6,
        layer_types=["conv", "conv", "full_attention", "conv", "conv",
                     "conv"],
        num_attention_heads=4, num_key_value_heads=2, num_experts=8,
        max_position_embeddings=128)
    return ModelConfig.from_hf_config(hf, dtype=dtype, max_model_len=128)


def engine(dtype="float32", cache_dtype="auto", slots=4):
    cache = CacheConfig(block_size=16, swap_space_gib=0.001,
                        cache_dtype=cache_dtype,
                        num_device_blocks_override=8)
    cache.num_device_blocks, cache.num_cpu_blocks = 8, 8
    cache.num_state_slots = slots
    return CacheEngine(cache, model_config(dtype), ParallelConfig())


def test_pools_follow_the_layers():
    ce = engine()
    assert ce.layer_kinds == ["state", "state", "kv", "state", "state",
                              "state"]
    assert (ce.kv_layers, ce.state_layers) == ([2], [0, 1, 3, 4, 5])
    for i, pools in enumerate(ce.device_cache):
        if i == 2:
            assert [p.shape for p in pools] == [(8, 2, 16, 128)] * 2
        else:
            assert [p.shape for p in pools] == [(4, 2 * 64)]
    assert [c[0].shape for c in ce.cpu_cache if len(c) == 1] == [
        (4, 128)] * 5
    # a block's bytes count the one layer that has a pool
    assert ce.device_block_bytes == 2 * 2 * 16 * 128 * 4
    assert ce.state_slot_bytes == 5 * 128 * 4


def test_state_keeps_the_models_dtype_under_an_fp8_pool():
    ce = engine(dtype="bfloat16", cache_dtype="fp8_e5m2")
    assert ce.device_cache[2][0].dtype == jnp.float8_e5m2
    assert ce.device_cache[0][0].dtype == jnp.bfloat16
    assert ce.state_slot_bytes == 5 * 128 * 2


def test_state_ops_move_slots_and_leave_blocks_alone():
    ce = engine()
    rng = np.random.default_rng(0)
    for i in ce.state_layers:
        ce.device_cache[i] = (jnp.asarray(
            rng.normal(size=(4, 128)).astype(np.float32)), )
    k0 = rng.normal(size=(8, 2, 16, 128)).astype(np.float32)
    ce.device_cache[2] = (jnp.asarray(k0), jnp.asarray(k0 + 1))
    before = [np.asarray(ce.device_cache[i][0]) for i in ce.state_layers]
    ce.execute_state_ops([("fork", 1, 3), ("swap_out", 1, 2)])
    for i, was in zip(ce.state_layers, before):
        now = np.asarray(ce.device_cache[i][0])
        np.testing.assert_array_equal(now[3], was[1])       # the copy
        np.testing.assert_array_equal(ce.cpu_cache[i][0][2], was[1])
        np.testing.assert_array_equal(now[[0, 1, 2]], was[[0, 1, 2]])
    ce.execute_state_ops([("swap_in", 2, 0)])
    for i, was in zip(ce.state_layers, before):
        np.testing.assert_array_equal(
            np.asarray(ce.device_cache[i][0])[0], was[1])
    np.testing.assert_array_equal(np.asarray(ce.device_cache[2][0]), k0)
    # block ops touch the pools and not the state
    ce.swap_out({3: 5})
    ce.copy({3: [4]})
    np.testing.assert_array_equal(ce.cpu_cache[2][1][5], (k0 + 1)[3])
    np.testing.assert_array_equal(np.asarray(ce.device_cache[2][0])[4],
                                  k0[3])
    for i, was in zip(ce.state_layers, before):
        np.testing.assert_array_equal(
            np.asarray(ce.device_cache[i][0])[3], was[1])
    with pytest.raises(ValueError, match="unknown state op"):
        ce.execute_state_ops([("shuffle", 0, 1)])


@pytest.mark.parametrize("ops, calls", [
    # consecutive moves of one kind are one call
    ([("swap_out", 0, 0), ("swap_out", 1, 1), ("swap_out", 2, 3)],
     [("swap_state_out", {0: 0, 1: 1, 2: 3})]),
    # a fork to several children is one source with its destinations
    ([("fork", 1, 2), ("fork", 1, 3)], [("copy_state", {1: [2, 3]})]),
    # another kind, or a source an earlier move of the run wrote, starts
    # a new call: the order the block manager made them in holds
    ([("fork", 1, 3), ("fork", 3, 0), ("swap_out", 0, 2), ("swap_in", 1, 1)],
     [("copy_state", {1: [3]}), ("copy_state", {3: [0]}),
      ("swap_state_out", {0: 2}), ("swap_state_in", {1: 1})]),
    ([("w_swap_out", 4, 0), ("w_swap_out", 5, 1), ("w_copy", 2, 6),
      ("w_swap_in", 0, 7)],
     [("swap_out", {4: 0, 5: 1}), ("copy", {2: [6]}), ("swap_in", {0: 7})]),
])
def test_state_ops_run_as_one_call_a_run(monkeypatch, ops, calls):
    ce = engine()
    seen = []
    for name in ("swap_state_out", "swap_state_in", "copy_state",
                 "swap_out", "swap_in", "copy"):
        monkeypatch.setattr(
            ce, name,
            lambda mapping, window=None, name=name: seen.append(
                (name, mapping) if window is None
                else (name, mapping, window)))
    ce.execute_state_ops(ops)
    assert seen == [c if "state" in c[0] else c + (True, )
                    for c in calls]


def test_a_fork_of_a_fork_in_one_list_copies_the_copy():
    ce = engine()
    rng = np.random.default_rng(1)
    for i in ce.state_layers:
        ce.device_cache[i] = (jnp.asarray(
            rng.normal(size=(4, 128)).astype(np.float32)), )
    before = [np.asarray(ce.device_cache[i][0]) for i in ce.state_layers]
    ce.execute_state_ops([("fork", 1, 3), ("fork", 3, 0)])
    for i, was in zip(ce.state_layers, before):
        now = np.asarray(ce.device_cache[i][0])
        np.testing.assert_array_equal(now[3], was[1])
        np.testing.assert_array_equal(now[0], was[1])


def test_models_the_repo_had_describe_every_layer_alike():
    from transformers import LlamaConfig
    hf = LlamaConfig(vocab_size=64, hidden_size=64, intermediate_size=128,
                     num_hidden_layers=3, num_attention_heads=4,
                     num_key_value_heads=2, max_position_embeddings=128)
    mc = ModelConfig.from_hf_config(hf, dtype="float32", max_model_len=128)
    assert mc.get_layer_cache_kinds() == ["kv"] * 3
    assert mc.get_state_arrays() == ()
    assert (mc.get_num_kv_layers(), mc.get_num_state_layers()) == (3, 0)
    assert mc.get_cache_row_shape() == (2, 2, 16)
    assert CacheEngine.get_state_slot_size(mc) == 0
    cache = CacheConfig(block_size=16, swap_space_gib=0.001,
                        num_device_blocks_override=4)
    cache.num_device_blocks, cache.num_cpu_blocks = 4, 4
    ce = CacheEngine(cache, mc, ParallelConfig())
    assert ce.state_layers == [] and ce.num_state_slots == 0
    assert [len(p) for p in ce.device_cache] == [2, 2, 2]


# --- a state layer of two arrays, of two dtypes -------------------------------

def matrix_state_engine(dtype="bfloat16", slots=4):
    from intellillm_tpu.transformers_utils.configs import SolarOpen2Config
    hf = SolarOpen2Config(
        vocab_size=64, hidden_size=64, moe_intermediate_size=32,
        num_hidden_layers=4, gqa_layers=[0], num_attention_heads=4,
        num_key_value_heads=2, head_dim=16,
        linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                            "num_heads": 4, "num_kv_heads": None},
        n_routed_experts=8, num_experts_per_tok=4,
        max_position_embeddings=128)
    mc = ModelConfig.from_hf_config(hf, dtype=dtype, max_model_len=128)
    cache = CacheConfig(block_size=16, swap_space_gib=0.001,
                        num_device_blocks_override=8)
    cache.num_device_blocks, cache.num_cpu_blocks = 8, 8
    cache.num_state_slots = slots
    return CacheEngine(cache, mc, ParallelConfig())


def test_a_state_layer_is_a_tuple_of_pools_each_with_its_dtype():
    """The short convolution's one array is the 1-tuple case of the same
    description."""
    assert model_config().get_state_arrays() == (((2, 64), "float32"), )
    ce = matrix_state_engine()
    assert ce.layer_kinds == ["kv", "state", "state", "state"]
    assert ce.state_arrays == (((3, 192), "bfloat16"),
                               ((4, 16, 16), "float32"))
    for i in ce.state_layers:
        taps, matrices = ce.device_cache[i]
        # taps a row a slot; the matrices with the pad row the step form's
        # pad rows point at, which the host pool does not need
        assert (taps.shape, taps.dtype) == ((4, 576), jnp.bfloat16)
        assert (matrices.shape, matrices.dtype) == ((5, 4, 16, 16),
                                                    jnp.float32)
        assert [c.shape for c in ce.cpu_cache[i]] == [(4, 576),
                                                      (4, 4, 16, 16)]
        assert ce.cpu_cache[i][1].dtype == np.float32
    assert ce.state_slot_bytes == 3 * (640 * 2 + 4 * 16 * 128 * 4)


def test_state_ops_move_every_array_of_a_slot():
    ce = matrix_state_engine(dtype="float32")
    rng = np.random.default_rng(0)
    for i in ce.state_layers:
        ce.device_cache[i] = tuple(
            jnp.asarray(rng.normal(size=p.shape).astype(np.float32))
            for p in ce.device_cache[i])
    before = [[np.asarray(p) for p in ce.device_cache[i]]
              for i in ce.state_layers]
    ce.execute_state_ops([("fork", 1, 3), ("swap_out", 1, 2)])
    for i, was in zip(ce.state_layers, before):
        for pool, host, old in zip(ce.device_cache[i], ce.cpu_cache[i], was):
            now = np.asarray(pool)
            np.testing.assert_array_equal(now[3], old[1])   # the copy
            np.testing.assert_array_equal(host[2], old[1])
            np.testing.assert_array_equal(now[[0, 1, 2]], old[[0, 1, 2]])
    ce.execute_state_ops([("swap_in", 2, 0)])
    for i, was in zip(ce.state_layers, before):
        for pool, old in zip(ce.device_cache[i], was):
            np.testing.assert_array_equal(np.asarray(pool)[0], old[1])
        # the pad row is nobody's slot and no move touches it
        np.testing.assert_array_equal(
            np.asarray(ce.device_cache[i][1])[4], was[1][4])
