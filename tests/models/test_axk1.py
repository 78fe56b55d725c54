"""A.X-K1 (latent attention + sigmoid-routed experts, an expert-parallel
share) against its plain float32 reference, `tests/models/axk1_reference.py`,
at a tiny preset that keeps every structural feature: q/kv low rank,
nope/rope split, YaRN, 1 dense + 2 MoE layers, 8 groups / 4 kept, sigmoid
scores, the scaling factor, a shared expert.

The engine side is the normal path: a checkpoint under the published tensor
names -> `load_weights` -> scheduler, block manager, the `mixed` program
(chunked prefill) and the K=8 fused decode through the latent cache. The
reference is the full causal forward pass in the expanded form, teacher-forced
on the engine's tokens; what is compared is log-softmax values (the engine's
top-5 logprobs at the prefill's last position and every decoded one), never
sampled tokens.
"""
import json
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from tests.conftest import _build_word_tokenizer
from tests.models import axk1_reference as ref

ROUTER = 16          # the router's published width at the tiny preset
TINY = dict(
    model_type="axk1", architectures=["AXK1ForCausalLM"],
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_shared_experts=1,
    n_routed_experts=ROUTER, num_experts_per_tok=4, n_group=8, topk_group=4,
    topk_method="none", scoring_func="sigmoid", norm_topk_prob=True,
    routed_scaling_factor=2.5, first_k_dense_replace=1, moe_layer_freq=1,
    ep_size=1, hidden_act="silu", max_position_embeddings=128,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 32},
    tie_word_embeddings=False, torch_dtype="float32")


def published_weights(cfg: dict, seed: int = 0) -> dict:
    """Seeded random weights under the published names, [out, in]."""
    rng = np.random.default_rng(seed)
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    d_n, d_r, d_v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    r_q, r_kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]

    def lin(out, inp):
        return (rng.normal(size=(out, inp)) / np.sqrt(inp)).astype(np.float32)

    def norm(n):
        return (1 + 0.1 * rng.normal(size=n)).astype(np.float32)

    def mlp(prefix, inter):
        return {prefix + "gate_proj.weight": lin(inter, e),
                prefix + "up_proj.weight": lin(inter, e),
                prefix + "down_proj.weight": lin(e, inter)}

    w = {"model.embed_tokens.weight":
         rng.normal(size=(cfg["vocab_size"], e)).astype(np.float32),
         "model.norm.weight": norm(e),
         "lm_head.weight": lin(cfg["vocab_size"], e)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        w.update({
            p + "input_layernorm.weight": norm(e),
            p + "post_attention_layernorm.weight": norm(e),
            a + "q_a_proj.weight": lin(r_q, e),
            a + "q_a_layernorm.weight": norm(r_q),
            a + "q_b_proj.weight": lin(h * (d_n + d_r), r_q),
            a + "kv_a_proj_with_mqa.weight": lin(r_kv + d_r, e),
            a + "kv_a_layernorm.weight": norm(r_kv),
            a + "kv_b_proj.weight": lin(h * (d_n + d_v), r_kv),
            a + "o_proj.weight": lin(e, h * d_v)})
        if i >= cfg["first_k_dense_replace"]:
            w[p + "mlp.gate.weight"] = lin(cfg["n_routed_experts"], e)
            for j in range(cfg["n_routed_experts"]):
                w.update(mlp(f"{p}mlp.experts.{j}.",
                             cfg["moe_intermediate_size"]))
            w.update(mlp(p + "mlp.shared_experts.",
                         cfg["moe_intermediate_size"]))
        else:
            w.update(mlp(p + "mlp.", cfg["intermediate_size"]))
    return w


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """(cfg, weights, {name: model dir}): the uncut tiny model and share
    1 of 4 (experts [4, 8) of 16) of the SAME checkpoint."""
    import safetensors.numpy
    root = tmp_path_factory.mktemp("axk1")
    dirs = {}
    cfg = None
    for name, over in (("full", {}),
                       ("share", {"ep_size": 4, "ep_rank": 1,
                                  "n_routed_experts": ROUTER // 4})):
        d = str(root / name)
        _, vocab = _build_word_tokenizer(d)
        cfg = dict(TINY, vocab_size=vocab)
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump(dict(cfg, **over), f)
        weights = published_weights(cfg)
        safetensors.numpy.save_file(weights,
                                    os.path.join(d, "model.safetensors"))
        dirs[name] = d
    return cfg, weights, dirs


def make_llm(model_dir, dtype="float32", blocks=64, **kw):
    from intellillm_tpu import LLM
    kw.setdefault("max_num_batched_tokens", 32)
    return LLM(model=model_dir, dtype=dtype,
               num_device_blocks_override=blocks, max_model_len=128,
               max_num_seqs=8, max_paddings=512, swap_space=0.01,
               enable_chunked_prefill=True, **kw)


def generate(llm, prompts, params):
    engine = llm.llm_engine
    for i, (p, sp) in enumerate(zip(prompts, params)):
        engine.add_request(str(i), None, sp, prompt_token_ids=list(p))
    outs = {o.request_id: o for o in llm._run_engine(use_tqdm=False)}
    return [outs[str(i)] for i in range(len(prompts))]


def prompts_of(cfg, lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg["vocab_size"], size=n).tolist() for n in lens]


def dlogprobs(cfg, weights, prompts, outs, ep=(None, 0)):
    """|engine logprob - reference log-softmax| over the top-5 of every
    position that gave a token, teacher-forced on the engine's tokens."""
    diffs = []
    for p, out in zip(prompts, outs):
        c = out.outputs[0]
        lsm = np.asarray(jax.nn.log_softmax(
            ref.forward(cfg, weights, p + list(c.token_ids), *ep), -1))
        for j, lp in enumerate(c.logprobs):
            for tok, v in lp.items():
                v = float(getattr(v, "logprob", v))
                diffs.append(abs(lsm[len(p) - 1 + j, tok] - v))
    assert len(diffs) >= 5 * sum(len(o.outputs[0].token_ids) for o in outs)
    return np.asarray(diffs)


def worst_dlogprob(*args, **kw):
    return float(dlogprobs(*args, **kw).max())


def greedy(n, **kw):
    from intellillm_tpu import SamplingParams
    return SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True,
                          logprobs=5, **kw)


# Float32 on both sides: what is left is the order of summation (the
# absorbed form, the chunked online softmax, XLA's own reductions).
F32_TOL = 5e-5
# bf16 against float32 on the same bf16-rounded weights, at this size:
# measured mean 0.0133, max 0.25 (one position, where a near-tie of the
# router or of two logits falls the other way). Three times each; fp8
# latents read mean 0.07 and a dropped term 0.36-0.51 / max 1.9-2.1 at this
# size (reference_check.py --rehearse-on-cpu).
BF16_MEAN_TOL, BF16_MAX_TOL = 0.04, 0.75


@pytest.mark.parametrize("which,ep", [("full", (None, 0)),
                                      ("share", (4, 1))])
def test_engine_matches_reference_float32(ckpt, which, ep):
    """Chunked prefill (70- and 33-token prompts against a 32-token
    budget), then K=8 fused decode through the latent cache, against the
    full forward pass; the absorbed form against the expanded one."""
    cfg, weights, dirs = ckpt
    llm = make_llm(dirs[which])
    assert llm.llm_engine.scheduler_config.num_decode_steps == 8
    prompts = prompts_of(cfg, (9, 70, 33))
    outs = generate(llm, prompts, [greedy(20)] * 3)
    from intellillm_tpu.obs import get_compile_tracker
    programs = get_compile_tracker().snapshot()["compiles"]
    assert programs.get("mixed") and programs.get("decode_fused")
    assert worst_dlogprob(cfg, weights, prompts, outs, ep) < F32_TOL


@pytest.mark.parametrize("which,ep", [("full", (None, 0)),
                                      ("share", (4, 1))])
def test_a_step_over_256_rows_takes_the_grouped_experts(ckpt, which, ep):
    """Three prompts of 100, 100 and 90 tokens under a 512-token budget
    make one `mixed` step of 290 rows: over `layers/moe.py` `DENSE_ROWS`,
    so the expert layers run the grouped form (for the share: the choices
    of experts held elsewhere in a last group that nothing computes) and
    still meet the float32 reference."""
    from intellillm_tpu.obs import get_compile_tracker

    def traces():
        seen = get_compile_tracker().snapshot()["kernel_dispatch"]
        return sum(v for k, v in seen.items() if k.endswith(":grouped_ffn"))

    cfg, weights, dirs = ckpt
    before = traces()
    llm = make_llm(dirs[which], max_num_batched_tokens=512)
    prompts = prompts_of(cfg, (100, 100, 90), seed=3)
    outs = generate(llm, prompts, [greedy(10)] * 3)
    assert traces() > before
    assert worst_dlogprob(cfg, weights, prompts, outs, ep) < F32_TOL


def test_engine_matches_reference_bfloat16(ckpt):
    """bf16 weights and latents, float32 reference on the same bf16-rounded
    weights: every activation is rounded to 8 bits of mantissa after each
    of ~40 operations in 3 layers, so the log-softmax values differ by a
    few bf16 steps on average and more where a choice flips."""
    cfg, weights, dirs = ckpt
    rounded = {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
               for k, v in weights.items()}
    prompts = prompts_of(cfg, (9, 70, 33))
    outs = generate(make_llm(dirs["full"], dtype="bfloat16"), prompts,
                    [greedy(20)] * 3)
    d = dlogprobs(cfg, rounded, prompts, outs)
    assert d.mean() < BF16_MEAN_TOL and d.max() < BF16_MAX_TOL


def test_recompute_preemption_still_matches_reference(ckpt, monkeypatch):
    """A pool too small for four sequences: the scheduler preempts by
    recompute, and the re-prefilled latents give the reference's logits."""
    from intellillm_tpu.core import scheduler as sched_mod
    cfg, weights, dirs = ckpt
    hits = {"n": 0}
    orig = sched_mod.Scheduler._preempt_by_recompute

    def counting(self, seq_group):
        hits["n"] += 1
        return orig(self, seq_group)

    monkeypatch.setattr(sched_mod.Scheduler, "_preempt_by_recompute",
                        counting)
    prompts = prompts_of(cfg, (20, 25, 30, 22), seed=2)
    outs = generate(make_llm(dirs["share"], blocks=10), prompts,
                    [greedy(40)] * 4)
    assert hits["n"] > 0, "the pool was sized to force a preemption"
    assert worst_dlogprob(cfg, weights, prompts, outs, (4, 1)) < F32_TOL


def test_swap_restores_the_latent_pool(ckpt, monkeypatch):
    """best_of=2 groups preempt by SWAP: latent blocks go to the host pool
    and come back, and the outputs equal the unpressured run's."""
    from intellillm_tpu import SamplingParams
    from intellillm_tpu.worker import cache_engine as ce
    cfg, _, dirs = ckpt
    prompts = prompts_of(cfg, (20, 25, 30, 22), seed=3)
    params = [SamplingParams(temperature=0.8, best_of=2, n=2, max_tokens=40,
                             ignore_eos=True)] * 4
    roomy = generate(make_llm(dirs["full"]), prompts, params)
    swaps = {"out": 0, "in": 0}
    for way in ("out", "in"):
        orig = getattr(ce.CacheEngine, "swap_" + way)

        def counting(self, mapping, way=way, orig=orig):
            swaps[way] += 1
            return orig(self, mapping)

        monkeypatch.setattr(ce.CacheEngine, "swap_" + way, counting)
    tight = generate(make_llm(dirs["full"], blocks=14), prompts, params)
    assert swaps["out"] > 0 and swaps["in"] > 0, swaps
    for r, t in zip(roomy, tight):
        assert (sorted(c.token_ids for c in r.outputs) ==
                sorted(c.token_ids for c in t.outputs))


def test_tensor_parallel_is_refused_with_a_reason(ckpt):
    from intellillm_tpu import LLM
    _, _, dirs = ckpt
    with pytest.raises(NotImplementedError, match="one chip"):
        LLM(model=dirs["full"], dtype="float32", tensor_parallel_size=2,
            num_device_blocks_override=32, max_model_len=128)


# --- the router --------------------------------------------------------------

def routing_of(cfg, held_start=0):
    from intellillm_tpu.layers.moe import Routing
    return Routing(scoring="sigmoid", renormalize=True,
                   n_group=cfg["n_group"], topk_group=cfg["topk_group"],
                   scale=cfg["routed_scaling_factor"], held_start=held_start)


def test_router_matches_reference_including_ties():
    """Group-limited sigmoid top-k against the reference's `route`, on
    random scores and on rows built to tie: equal best scores in two
    groups at the edge of the kept four, equal experts at the edge of the
    chosen k, all experts equal. Both break ties to the lower index."""
    from intellillm_tpu.layers.moe import _route
    cfg = dict(TINY, n_routed_experts=32, n_group=8, topk_group=4,
               num_experts_per_tok=6)
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(64, 32)).astype(np.float32)
    logits[0] = 0.0                                   # everything ties
    logits[1] = np.repeat(np.arange(8, 0, -1), 4)     # groups 3 and 4 ...
    logits[1, 16:20] = logits[1, 12:16]               # ... tie at the edge
    logits[2, :] = -3.0
    logits[2, [0, 4, 8, 12]] = 2.0                    # four clear groups,
    logits[2, [1, 5, 9]] = 1.0                        # then a 3-way tie
    logits[2, 13] = 1.0                               # for two places
    x = jnp.asarray(logits)
    topw, topi = _route(x, jnp.eye(32, dtype=jnp.float32), 6,
                        routing=routing_of(cfg))
    want_w, want_i = ref.route(cfg, jax.nn.sigmoid(x))
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(topw), np.asarray(want_w),
                               rtol=1e-6)
    # chosen experts lie in at most 4 groups, weights sum to the scale
    groups = np.asarray(topi) // 4
    assert max(len(set(g)) for g in groups) <= 4
    np.testing.assert_allclose(np.asarray(topw).sum(-1), 2.5, rtol=1e-5)


def test_softmax_routing_is_what_it_was():
    """The default `Routing` is the path Mixtral and DeepSeek-MoE take:
    softmax over all experts, top-k, optional renormalisation."""
    from intellillm_tpu.layers.moe import Routing, _route
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    for renorm in (True, False):
        topw, topi = _route(x, jnp.eye(8, dtype=jnp.float32), 2,
                            Routing(renormalize=renorm))
        probs = np.asarray(jax.nn.softmax(x, -1))
        order = np.argsort(-probs, -1)[:, :2]
        np.testing.assert_array_equal(np.asarray(topi), order)
        want = np.take_along_axis(probs, order, 1)
        if renorm:
            want = want / want.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(topw), want, rtol=1e-6)


# --- the share ---------------------------------------------------------------

def moe_weights(cfg, seed=0):
    return {k: v for k, v in published_weights(
        dict(cfg, vocab_size=8, num_hidden_layers=2), seed).items()
        if k.startswith("model.layers.1.mlp.")}


def stacks_of(w, prefix, experts):
    """The engine's [N, I, D] stacks of a range of experts."""
    def stack(name, t=False):
        return jnp.stack([jnp.asarray(
            w[f"{prefix}experts.{j}.{name}_proj.weight"].T if t else
            w[f"{prefix}experts.{j}.{name}_proj.weight"]) for j in experts])
    return stack("gate"), stack("down", t=True), stack("up")


@pytest.mark.parametrize("rows,path", [(8, "grouped"), (64, "dense"),
                                       (600, "grouped")])
def test_the_shares_add_up_to_the_uncut_layer(monkeypatch, rows, path):
    """THE SHARE TEST. Four shares of a 16-expert layer, each through
    `moe_ffn` holding 4 experts of a 16-wide router: their routed parts,
    plus the shared expert counted once, add up to what the uncut
    reference gives for the whole layer; each share alone equals the
    reference given the same share. On both of `moe_ffn`'s paths: 8 rows
    of top-4 of 16 leave experts unchosen (grouped), 64 do not (dense),
    600 are more than one dense chunk (grouped)."""
    from intellillm_tpu.layers import moe
    cfg = dict(TINY)
    p = "model.layers.1.mlp."
    w = moe_weights(cfg)
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(rows, cfg["hidden_size"])).astype(np.float32))
    gate = jnp.asarray(w[p + "gate.weight"].T)
    taken = []
    for name in ("moe_ffn_dense", "moe_ffn_grouped"):
        def spy(*a, name=name, orig=getattr(moe, name), **kw):
            taken.append(name)
            return orig(*a, **kw)
        monkeypatch.setattr(moe, name, spy)
    total = jnp.zeros_like(x)
    for rank in range(4):
        w1, w2, w3 = stacks_of(w, p, range(rank * 4, rank * 4 + 4))
        part = moe.moe_ffn(x, gate, w1, w2, w3, 4,
                           routing=routing_of(cfg, rank * 4))
        want = ref.moe(cfg, w, p, x, ep_size=4, ep_rank=rank, shared=False)
        np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
        total = total + part
    assert set(taken) == {"moe_ffn_" + path}, taken
    total = total + ref.swiglu(x, w, p + "shared_experts.")
    whole = ref.moe(cfg, w, p, x)                     # uncut, with shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-4, atol=2e-5)


def test_held_assignments_count_the_choices_here():
    from intellillm_tpu.layers.moe import held_assignments
    topi = jnp.asarray([[0, 3, 4, 15], [4, 5, 6, 7], [8, 9, 10, 11]])
    assert held_assignments(topi, 4, 4).tolist() == [1, 4, 0]
    assert held_assignments(topi, 4, 0).tolist() == [2, 0, 0]


@pytest.mark.parametrize("name,widths,rows,path", [
    # So few rows that experts go unchosen (`CHOSEN_SHARE_CUT`).
    ("deepseek-moe-16b", (64, 64, 6), 16, "grouped"),
    ("deepseek-moe-16b", (64, 64, 6), 32, "dense"),
    ("mixtral-8x7b", (8, 8, 2), 16, "dense"),
    ("ax-k1 share", (12, 192, 8), 32, "grouped"),
    ("ax-k1 share", (12, 192, 8), 64, "dense"),
    ("deepseek-moe-16b", (64, 64, 6), 128, "dense"),
    ("deepseek-moe-16b", (64, 64, 6), 256, "dense"),
    ("deepseek-moe-16b", (64, 64, 6), 512, "grouped"),
    ("mixtral-8x7b", (8, 8, 2), 128, "dense"),
    ("mixtral-8x7b", (8, 8, 2), 512, "grouped"),
    ("mixtral-8x7b", (8, 8, 2), 2048, "grouped"),
    # 12 of 192 held: a 512-row step routes 512 x 8 x 12/192 = 256 rows
    # here in expectation, where two dense chunks multiply 12 x 512.
    ("ax-k1 share", (12, 192, 8), 128, "dense"),
    ("ax-k1 share", (12, 192, 8), 256, "dense"),
    ("ax-k1 share", (12, 192, 8), 512, "grouped"),
    ("ax-k1 share", (12, 192, 8), 4096, "grouped"),
])
def test_moe_form_by_the_rows_of_the_step(monkeypatch, name, widths, rows,
                                          path):
    """The models the benchmark has, at the rows their programs have:
    a 128-row decode step and a `mixed` step of up to 256 rows stay
    dense, a `mixed` step of more goes grouped (layers/moe.py
    `DENSE_ROWS`), and so does a step of so few rows that experts go
    unchosen."""
    from intellillm_tpu.layers import moe
    n, n_router, top_k = widths
    took = []
    monkeypatch.setattr(moe, "moe_ffn_dense",
                        lambda *a, **k: took.append("dense"))
    monkeypatch.setattr(moe, "moe_ffn_grouped",
                        lambda *a, **k: took.append("grouped"))
    stack = jnp.zeros((n, 4, 8))
    moe.moe_ffn(jnp.zeros((rows, 8)), jnp.zeros((8, n_router)), stack, stack,
                stack, top_k)
    assert took == [path]


# --- the latent cache --------------------------------------------------------

def latent_cache_engine(cfg, dtype="bfloat16", blocks=8, cpu_blocks=4):
    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig)
    from intellillm_tpu.transformers_utils.configs import AXK1Config
    from intellillm_tpu.worker.cache_engine import CacheEngine
    hf = AXK1Config(**{k: v for k, v in cfg.items()
                       if k not in ("model_type", "architectures")})
    mc = ModelConfig.from_hf_config(hf, dtype=dtype, max_model_len=128,
                                    load_format="dummy")
    cc = CacheConfig(block_size=16, num_device_blocks_override=blocks,
                     swap_space_gib=0.01)
    cc.num_device_blocks, cc.num_cpu_blocks = blocks, cpu_blocks
    return mc, cc, CacheEngine(cc, mc, ParallelConfig())


@pytest.mark.parametrize("rank,rope,dtype,want_width,want_bytes", [
    # the published widths: 512 + 64 = 576 -> 640 lanes, 3 layers, bf16:
    # 1 pool x 3 layers x 16 tokens x 640 x 2 B
    (512, 64, "bfloat16", 640, 3 * 16 * 640 * 2),
    (32, 8, "float32", 128, 3 * 16 * 128 * 4),
])
def test_latent_pool_geometry_and_bytes(rank, rope, dtype, want_width,
                                        want_bytes):
    from intellillm_tpu.config import ParallelConfig
    from intellillm_tpu.worker.cache_engine import CacheEngine
    cfg = dict(TINY, vocab_size=64, kv_lora_rank=rank,
               qk_rope_head_dim=rope)
    mc, cc, ce = latent_cache_engine(cfg, dtype)
    assert mc.get_latent_cache_width() == want_width
    assert mc.get_cache_row_shape() == (1, 1, want_width)
    # The attention's own numbers keep their meaning: the cache's shape
    # is not read out of them.
    assert mc.get_total_num_kv_heads() == cfg["num_attention_heads"]
    assert mc.get_head_size() == cfg["qk_nope_head_dim"] + rope
    assert len(ce.device_cache) == 3
    for pools in ce.device_cache:
        assert len(pools) == 1                   # one pool a layer, no V
        assert pools[0].shape == (8, 1, 16, want_width)
    assert CacheEngine.get_cache_block_size(16, "auto", mc,
                                            ParallelConfig()) == want_bytes
    assert CacheEngine.get_logical_cache_block_size(16, "auto",
                                                    mc) == want_bytes
    # the pool the allocator is charged for is the pool that is there
    held = sum(p.nbytes for pools in ce.device_cache for p in pools)
    assert held == want_bytes * 8


def test_latent_pool_copy_swap_and_refused_transfer():
    cfg = dict(TINY, vocab_size=64)
    _, _, ce = latent_cache_engine(cfg, "float32")
    rng = np.random.default_rng(0)
    ce.device_cache = [(jnp.asarray(rng.normal(size=p[0].shape).astype(
        np.float32)), ) for p in ce.device_cache]
    before = [np.asarray(p[0]) for p in ce.device_cache]
    ce.copy({1: [5, 6]})
    for b, (pool, ) in zip(before, ce.device_cache):
        np.testing.assert_array_equal(np.asarray(pool[5]), b[1])
        np.testing.assert_array_equal(np.asarray(pool[6]), b[1])
        np.testing.assert_array_equal(np.asarray(pool[2]), b[2])
    ce.swap_out({1: 0, 2: 3})
    for b, (host, ) in zip(before, ce.cpu_cache):
        np.testing.assert_array_equal(host[0], b[1])
        np.testing.assert_array_equal(host[3], b[2])
    ce.swap_in({0: 7, 3: 4})
    for b, (pool, ) in zip(before, ce.device_cache):
        np.testing.assert_array_equal(np.asarray(pool[7]), b[1])
        np.testing.assert_array_equal(np.asarray(pool[4]), b[2])
    # The KV handoff's wire format is (K, V) slabs: refused, not mangled.
    with pytest.raises(NotImplementedError, match="latent pool"):
        ce.export_blocks([1])
    with pytest.raises(NotImplementedError, match="latent pool"):
        ce.import_blocks([], [1])


def test_per_head_models_keep_two_pools(tiny_llama_dir):
    from intellillm_tpu.config import ModelConfig
    mc = ModelConfig(model=tiny_llama_dir, dtype="float32",
                     max_model_len=64)
    assert mc.get_latent_cache_width() is None
    assert mc.get_cache_row_shape() == (
        2, mc.get_total_num_kv_heads(), mc.get_head_size())


# --- rope, counters ----------------------------------------------------------

def test_yarn_mscale_rule_of_this_family():
    """cos/sin at the RATIO of the two mscales (1 here), m**2 on the whole
    score; without the two keys the Llama-style rule stands."""
    from intellillm_tpu.layers.rotary_embedding import (_yarn_get_mscale,
                                                        get_rope)
    sc = {"type": "yarn", "factor": 32, "beta_fast": 32, "beta_slow": 1,
          "original_max_position_embeddings": 64}
    family = get_rope(64, 64, 2048, 10000, False,
                      dict(sc, mscale=1, mscale_all_dim=1))
    llama = get_rope(64, 64, 2048, 10000, False, sc)
    assert float(jnp.abs(family.cos_cache[0]).max()) == pytest.approx(1.0)
    assert float(llama.cos_cache[0, 0]) == pytest.approx(1.3466, abs=1e-4)
    assert _yarn_get_mscale(32, 1) == pytest.approx(1.3466, abs=1e-4)
    cfg = dict(TINY, qk_rope_head_dim=64, rope_scaling=dict(
        sc, mscale=1, mscale_all_dim=1))
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(1, 40, 1, 64)).astype(np.float32))
    pos = jnp.arange(40)[None]
    got, _ = family(pos, x, x)
    want = ref.rope(x[0], np.arange(40), cfg)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_share_counts_its_assignments(ckpt):
    """intellillm_moe_assignments_total: every live row's choices, split
    by whether this chip holds the expert; the uncut model counts none."""
    from prometheus_client import REGISTRY

    def read():
        return {s.labels["held"]: s.value
                for fam in REGISTRY.collect()
                if fam.name == "intellillm_moe_assignments"
                for s in fam.samples if s.name.endswith("_total")}

    cfg, _, dirs = ckpt
    prompts = prompts_of(cfg, (9, 33))
    start = read()
    generate(make_llm(dirs["full"]), prompts, [greedy(10)] * 2)
    assert read() == start
    generate(make_llm(dirs["share"]), prompts, [greedy(10)] * 2)
    here = read()["here"] - start.get("here", 0)
    absent = read()["absent"] - start.get("absent", 0)
    # Tokens through the experts: prompts (42) + decoded positions. The
    # K=8 decode runs 16 substeps for 10 tokens; 9 are needed past the
    # prefill's own sample: at least 42 + 2 x 9 rows, and no more than three K=8 steps a row.
    assert (42 + 18) * 8 <= here + absent <= (42 + 48) * 8
    assert (here + absent) % 8 == 0            # top-4 x 2 MoE layers a row
    assert 0 < here < absent


# --- the projection block moved into a layer (PR 45) --------------------------

def _attention_as_the_parent_held_it(mla):
    """`AXK1ForCausalLM._attention` as models/axk1.py held it at commit
    95850e3 (before `layers/mla_attention.py` `LatentAttentionBlock`),
    verbatim but for where its widths live."""
    from intellillm_tpu.layers.normalization import rms_norm

    def attention(lp, h, kv_cache, attn_metadata, positions):
        b, l, _ = h.shape
        hh = mla.num_heads
        with jax.named_scope("mla_q_proj"):
            c_q = rms_norm(h @ lp["q_a"], lp["q_a_norm"], mla.rms_eps)
            q = (c_q @ lp["q_b"]).reshape(b, l, hh, mla.d_nope + mla.d_rope)
            q_nope, q_rope = q[..., :mla.d_nope], q[..., mla.d_nope:]
        with jax.named_scope("mla_kv_proj"):
            ckv = h @ lp["kv_a"]
            c_kv = rms_norm(ckv[..., :mla.kv_rank], lp["kv_a_norm"],
                            mla.rms_eps)
            k_rope = ckv[..., mla.kv_rank:][:, :, None, :]
        q_rope, k_rope = mla.rope(positions, q_rope, k_rope)
        with jax.named_scope("mla_absorb_q"):
            q_abs = jnp.einsum("blhn,hnr->blhr", q_nope, lp["w_uk"])
        q_lat = jnp.concatenate([q_abs, q_rope], axis=-1)
        kv_new = jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1)
        o_lat, kv_cache = mla.attn(q_lat, kv_new, kv_cache, attn_metadata)
        with jax.named_scope("mla_absorb_o"):
            o = jnp.einsum("blhr,hrv->blhv", o_lat, lp["w_uv"])
        return o.reshape(b, l, hh * mla.d_v) @ lp["o"], kv_cache

    return attention


@pytest.mark.parametrize("staged", [False, True],
                         ids=["mixed", "decode_substep"])
def test_the_moved_projection_block_traces_what_the_parent_traced(staged):
    """A.X-K1's programs are unchanged by the move: the model's jaxpr over
    the `mixed` step's call and over a staged decode substep's, with the
    block in `layers/mla_attention.py`, equals the jaxpr with the method
    the parent held (same operations, same order, same shapes)."""
    from intellillm_tpu.layers.attention import AttentionMetadata
    from intellillm_tpu.models.axk1 import AXK1ForCausalLM
    cfg = dict(TINY, vocab_size=64)
    mc, _, _ = latent_cache_engine(cfg, "float32")
    model = AXK1ForCausalLM(mc)
    f32, i32 = jnp.float32, jnp.int32
    rows, width, blocks, chunk = 8, 4, 8, 8

    def spec(tree):
        if isinstance(tree, dict):
            return {k: spec(v) for k, v in tree.items()}
        if isinstance(tree, list):
            return [spec(v) for v in tree]
        return jax.ShapeDtypeStruct(tree, f32)

    pool = jax.ShapeDtypeStruct((blocks, 1, 16, 128), f32)
    stage = jax.ShapeDtypeStruct((rows, chunk, 1, 128), f32)
    caches = [(pool, stage) if staged else (pool, )] * model.num_layers
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, i32)      # noqa: E731

    def call(params, ids, positions, caches, slots, ctx, tables, k):
        meta = AttentionMetadata(
            slot_mapping=None if staged else slots, context_lens=ctx,
            block_tables=tables, staged=staged,
            stage_index=k if staged else None)
        return model(params, ids, positions, caches, meta)

    args = (spec(model._param_shapes()), ints(rows, 1), ints(rows, 1),
            caches, ints(rows, 1), ints(rows), ints(rows, width), ints())
    now = str(jax.make_jaxpr(call)(*args))
    model.mla = _attention_as_the_parent_held_it(model.mla)
    then = str(jax.make_jaxpr(call)(*args))
    assert "dot_general" in now and len(now) > 5000
    assert now == then
