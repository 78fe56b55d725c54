"""Xing4.0 (latent attention under a four-stream manifold-constrained
hyper-connection residual, sigmoid experts chosen by score + bias, an
expert-parallel share) against its plain float32 reference,
`tests/models/xing4_reference.py`, at a tiny preset that keeps every
structural feature: 4 streams, 20 sweeps, the clamp, q/kv low rank, nope/rope
split, YaRN, a dense and a MoE layer (the CPU compiles a sublayer's unrolled
sweeps in ~1.5 s a program: the rehearsal preset of the benchmark has 2 + 2),
the choice bias, the scaling factor, a shared expert.

The engine side is the normal path: a checkpoint under the assumed tensor
names -> `load_weights` -> scheduler, block manager, the `mixed` program
(chunked prefill) and the K=8 fused decode through the latent cache, with
sequences joining and leaving a batch of four seats. The reference is the
full causal forward pass (expanded attention, Sinkhorn as a loop),
teacher-forced on the engine's tokens; what is compared is log-softmax
values (the engine's top-5 logprobs at the prefill's last position and every
decoded one), never sampled tokens.
"""
import json
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from tests.conftest import _build_word_tokenizer
from tests.models import xing4_reference as ref

ROUTER = 16          # the router's published width at the tiny preset
TINY = dict(
    model_type="xing4_0", architectures=["Xing4ForCausalLM"],
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_shared_experts=1,
    n_routed_experts=ROUTER, num_experts_per_tok=4, n_group=1, topk_group=1,
    topk_method="noaux_tc", scoring_func="sigmoid", norm_topk_prob=True,
    routed_scaling_factor=2.0, first_k_dense_replace=1, moe_layer_freq=1,
    num_nextn_predict_layers=1, hc_mult=4, hc_sinkhorn_iters=20,
    hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
    ep_size=1, hidden_act="silu", max_position_embeddings=128,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 32},
    tie_word_embeddings=False, torch_dtype="float32")
# The sublayer whose `b_res` has pairs of logits beyond the clamp in one
# column (45 and 38, 52 and 44): clamped they tie at 30 and share the
# column, unclamped the larger takes it. Not the last sublayer: the head
# sums the streams, so the last `Hres` counts through its column sums only.
WIDE = "model.layers.0.ffn_hc."
BEYOND_THE_CLAMP = np.asarray([[45, 0, 0, 0], [38, 0, 0, 0],
                               [0, 52, 0, 0], [0, 44, 0, 0]], np.float32)


def published_weights(cfg: dict, seed: int = 0) -> dict:
    """Seeded random weights under the assumed names, [out, in]. The mixes
    are drawn far from the paper's initial values (gates of scale 1, not
    0.01: at the initial values every `Hres` is near-uniform and a wrong
    Sinkhorn would pass), and what writes to the streams at full scale, so
    that the streams differ from one another."""
    rng = np.random.default_rng(seed)
    e, h, n = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["hc_mult"])
    d_n, d_r, d_v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    r_q, r_kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]

    def lin(out, inp):
        return (rng.normal(size=(out, inp)) / np.sqrt(inp)).astype(np.float32)

    def norm(k):
        return (1 + 0.1 * rng.normal(size=k)).astype(np.float32)

    def mlp(prefix, inter):
        return {prefix + "gate_proj.weight": lin(inter, e),
                prefix + "up_proj.weight": lin(inter, e),
                prefix + "down_proj.weight": lin(e, inter)}

    def mix(prefix):
        wide = BEYOND_THE_CLAMP if prefix == WIDE else 0.0
        return {
            prefix + "phi.weight": lin(2 * n + n * n, n * e),
            prefix + "alpha": rng.uniform(0.5, 1.5, 3).astype(np.float32),
            prefix + "b_pre": rng.normal(size=n).astype(np.float32),
            prefix + "b_post": rng.normal(size=n).astype(np.float32),
            prefix + "b_res": (wide + rng.normal(size=(n, n))).astype(
                np.float32)}

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
        w.update(mix(p + "attn_hc."))
        w.update(mix(p + "ffn_hc."))
        if i >= cfg["first_k_dense_replace"]:
            w[p + "mlp.gate.weight"] = lin(cfg["n_routed_experts"], e)
            # as large as the scores' own spread: the choice by score +
            # bias and the choice by score alone differ for most tokens
            w[p + "mlp.gate.e_score_correction_bias"] = (
                0.5 * rng.normal(size=cfg["n_routed_experts"])).astype(
                    np.float32)
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
    root = tmp_path_factory.mktemp("xing4")
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


def make_llm(model_dir, dtype="float32", blocks=64, seats=4, **kw):
    from intellillm_tpu import LLM
    kw.setdefault("max_num_batched_tokens", 32)
    return LLM(model=model_dir, dtype=dtype,
               num_device_blocks_override=blocks, max_model_len=128,
               max_num_seqs=seats, max_paddings=512, swap_space=0.01,
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
# absorbed form, the chunked online softmax, the sweeps' sums as adds of
# slices, XLA's own reductions), through four layers at full writer scale
# and one sublayer whose logits sit at the clamp. The share reads 1.7e-6:
# ten times that.
F32_TOL = 2e-5
# The least a wrong reference has to read to count as seen: fifty times
# the tolerance (the subtlest, rows then columns, reads 1.5e-3; bfloat16
# coefficients 3.3e-3, no clamp 0.04, the others 0.12 to 1.0).
WRONG_MIN = 50 * F32_TOL

LENS = (9, 70, 33, 5, 21, 40)
# Every sequence ends 90 tokens long: the reference, run eagerly, compiles
# each of its operations once a length (7 s a new length on this CPU).
TOTAL = 90


def outputs_to(lens, total=TOTAL):
    return [greedy(total - n) for n in lens]


@pytest.fixture(scope="module")
def served(ckpt):
    """The share's engine over six requests on four seats, with prompts of
    70, 33 and 40 tokens against a 32-token budget (chunked prefill) and
    outputs of 20 to 85 tokens: sequences leave the K=8 decode batch and
    the waiting ones join it. (prompts, outs, what the run added to the
    tracker's mix counters), shared by the tests that only compute a
    reference."""
    from intellillm_tpu.obs import (get_compile_tracker,
                                    get_efficiency_tracker)
    cfg, _, dirs = ckpt
    llm = make_llm(dirs["share"])
    assert llm.llm_engine.scheduler_config.num_decode_steps == 8
    prompts = prompts_of(cfg, LENS)

    def counted():
        body = get_efficiency_tracker().snapshot()
        return dict(body["mhc"], row_steps=sum(
            p["row_steps"] for p in body["programs"].values()))

    before = counted()
    outs = generate(llm, prompts, outputs_to(LENS))
    after = counted()
    programs = get_compile_tracker().snapshot()["compiles"]
    assert programs.get("mixed") and programs.get("decode_fused")
    added = {k: after[k] - before[k] for k in ("stream_mix_bytes",
                                               "row_steps")}
    return prompts, outs, dict(added, col_error_max=after["col_error_max"])


def test_share_matches_reference_float32_as_sequences_join_and_leave(
        ckpt, served):
    cfg, weights, _ = ckpt
    prompts, outs, _ = served
    assert [len(p) + len(o.outputs[0].token_ids)
            for p, o in zip(prompts, outs)] == [TOTAL] * len(LENS)
    worst = worst_dlogprob(cfg, weights, prompts, outs, (4, 1))
    print("float32 share reads", worst)
    assert worst < F32_TOL


def test_uncut_model_matches_reference_float32(ckpt):
    cfg, weights, dirs = ckpt
    prompts = prompts_of(cfg, (9, 70, 33))
    outs = generate(make_llm(dirs["full"], seats=8), prompts,
                    outputs_to((9, 70, 33)))
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL


# --- each of these computed wrong is seen ------------------------------------

def _identity_hres(cfg, z):
    return jnp.broadcast_to(jnp.eye(z.shape[-1], dtype=z.dtype), z.shape)


def _rows_then_columns(cfg, z):
    m = jnp.exp(jnp.clip(z, cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (m.sum(axis=2, keepdims=True) + cfg["hc_eps"])
        m = m / (m.sum(axis=1, keepdims=True) + cfg["hc_eps"])
    return m


def _coefficients_with(change):
    right = ref.coefficients

    def wrong(cfg, w, prefix, x):
        return change(*right(cfg, w, prefix, x))
    return wrong


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _bf16_coefficients(cfg, w, prefix, x):
    """What a mix computed in the streams' dtype would give: the statistic,
    the projection and the three coefficient sets rounded to bfloat16."""
    t, n, c = x.shape
    flat = _bf16(x.reshape(t, n * c))
    r = _bf16(1.0 / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True)
                             + cfg["rms_norm_eps"]))
    m = _bf16(_bf16(flat * r) @ _bf16(jnp.asarray(
        w[prefix + "phi.weight"], jnp.float32)).T)
    a = jnp.asarray(w[prefix + "alpha"], jnp.float32)
    pre = _bf16(jax.nn.sigmoid(a[0] * m[:, :n] + w[prefix + "b_pre"]))
    post = _bf16(2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n]
                                      + w[prefix + "b_post"]))
    res = _bf16(ref.sinkhorn(cfg, _bf16(
        a[2] * m[:, 2 * n:].reshape(t, n, n) + w[prefix + "b_res"])))
    return pre, post, res


def _bias_in_the_weights(cfg, scores, bias):
    biased = scores + bias[None, :]
    _, experts = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(biased, experts, axis=1)
    weights = weights / weights.sum(axis=-1, keepdims=True)
    return weights * cfg["routed_scaling_factor"], experts


WRONG = {
    # name: (attribute of the reference to replace or None, replacement,
    #        config overrides)
    "hres_identity": ("sinkhorn", _identity_hres, {}),
    "hpre_uniform": ("coefficients", _coefficients_with(
        lambda pre, post, res: (jnp.full_like(pre, 0.25), post, res)), {}),
    "hpost_without_its_2": ("coefficients", _coefficients_with(
        lambda pre, post, res: (pre, post / 2.0, res)), {}),
    "one_sweep": (None, None, {"hc_sinkhorn_iters": 1}),
    "rows_then_columns": ("sinkhorn", _rows_then_columns, {}),
    "no_clamp": (None, None, {"mhc_h_res_clamp_min": -1e9,
                              "mhc_h_res_clamp_max": 1e9}),
    "bias_in_the_weights": ("route", _bias_in_the_weights, {}),
    "bf16_coefficients": ("coefficients", _bf16_coefficients, {}),
}


@pytest.mark.parametrize("how", sorted(WRONG))
def test_a_wrong_reference_is_seen(ckpt, served, monkeypatch, how):
    """The reference computed wrong one way at a time, against the same
    engine outputs: the worst |d logprob| has to pass `WRONG_MIN`, fifty
    times what the right reference is held to. (`no_clamp` shows because
    one sublayer's `b_res` holds pairs of logits beyond the clamp.)"""
    cfg, weights, _ = ckpt
    prompts, outs, _ = served
    attr, replacement, over = WRONG[how]
    if attr:
        monkeypatch.setattr(ref, attr, replacement)
    worst = worst_dlogprob(dict(cfg, **over), weights, prompts[:3],
                           outs[:3], (4, 1))
    print(how, "reads", worst)
    assert not worst < WRONG_MIN, (how, worst)


def test_streams_averaged_at_the_head_cannot_be_seen(ckpt, served,
                                                     monkeypatch):
    """ISSUE 45 lists "the streams averaged in place of summed at the
    head" among the wrong computations a tolerance must catch. It cannot:
    the final RMSNorm divides the scale out, so sum and mean differ by
    eps / variance (1e-6 against O(1)) and no tolerance above float32
    rounding tells them apart. Held here so that the claim is a checked
    one: the averaged head passes the reference's own tolerance."""
    cfg, weights, _ = ckpt
    prompts, outs, _ = served
    monkeypatch.setattr(ref, "merge_streams", lambda x: x.mean(axis=1))
    assert worst_dlogprob(cfg, weights, prompts[:2], outs[:2],
                          (4, 1)) < F32_TOL


# --- precision, preemption, refusals -----------------------------------------

# bf16 against float32 on the same bf16-rounded weights, at this size and
# full writer scale: measured mean 0.016, max 0.34 (a near-tie of the router
# or of two logits falling the other way). About three times each.
BF16_MEAN_TOL, BF16_MAX_TOL = 0.05, 1.0


def test_engine_matches_reference_bfloat16(ckpt):
    """bf16 weights, streams and latents (the mixes' coefficients float32),
    float32 reference on the same bf16-rounded weights."""
    cfg, weights, dirs = ckpt
    rounded = {k: (v if ("_hc." in k or k.endswith(("gate.weight",
                                                     "correction_bias")))
                   else v.astype(ml_dtypes.bfloat16).astype(np.float32))
               for k, v in weights.items()}
    prompts = prompts_of(cfg, (9, 70, 33))
    outs = generate(make_llm(dirs["full"], dtype="bfloat16", seats=8),
                    prompts, outputs_to((9, 70, 33)))
    d = dlogprobs(cfg, rounded, prompts, outs)
    assert d.mean() < BF16_MEAN_TOL and d.max() < BF16_MAX_TOL


def test_recompute_preemption_mid_prompt_still_matches_reference(
        ckpt, monkeypatch):
    """A pool too small for four sequences: the scheduler preempts by
    recompute (a 60-token prompt is in its chunks when the pool runs out),
    and the re-prefilled latents give the reference's logits."""
    from intellillm_tpu.core import scheduler as sched_mod
    cfg, weights, dirs = ckpt
    hits = {"n": 0}
    orig = sched_mod.Scheduler._preempt_by_recompute

    def counting(self, seq_group):
        hits["n"] += 1
        return orig(self, seq_group)

    monkeypatch.setattr(sched_mod.Scheduler, "_preempt_by_recompute",
                        counting)
    prompts = prompts_of(cfg, (20, 25, 60, 22), seed=2)
    outs = generate(make_llm(dirs["share"], blocks=10), prompts,
                    outputs_to((20, 25, 60, 22)))
    assert hits["n"] > 0, "the pool was sized to force a preemption"
    assert worst_dlogprob(cfg, weights, prompts, outs, (4, 1)) < F32_TOL


def test_swap_restores_the_latent_pools(ckpt, monkeypatch):
    """best_of=2 groups preempt by SWAP: latent blocks go to the host pool
    and come back, and the outputs equal the unpressured run's."""
    from intellillm_tpu import SamplingParams
    from intellillm_tpu.worker import cache_engine as ce
    cfg, _, dirs = ckpt
    prompts = prompts_of(cfg, (20, 25, 30, 22), seed=3)
    params = [SamplingParams(temperature=0.8, best_of=2, n=2, max_tokens=24,
                             ignore_eos=True)] * 4
    roomy = generate(make_llm(dirs["full"], seats=8), prompts, params)
    swaps = {"out": 0, "in": 0}
    for way in ("out", "in"):
        orig = getattr(ce.CacheEngine, "swap_" + way)

        def counting(self, mapping, way=way, orig=orig):
            swaps[way] += 1
            return orig(self, mapping)

        monkeypatch.setattr(ce.CacheEngine, "swap_" + way, counting)
    tight = generate(make_llm(dirs["full"], blocks=14, seats=8), prompts,
                     params)
    assert swaps["out"] > 0 and swaps["in"] > 0, swaps
    for r, t in zip(roomy, tight):
        assert (sorted(c.token_ids for c in r.outputs) ==
                sorted(c.token_ids for c in t.outputs))


def test_what_it_cannot_do_is_refused_with_a_reason(ckpt):
    from intellillm_tpu import LLM
    from intellillm_tpu.engine.arg_utils import EngineArgs
    from intellillm_tpu.engine.llm_engine import LLMEngine
    _, _, dirs = ckpt
    kw = dict(model=dirs["full"], dtype="float32",
              num_device_blocks_override=32, max_model_len=128)
    with pytest.raises(NotImplementedError, match="one chip"):
        LLM(tensor_parallel_size=2, **kw)
    with pytest.raises(NotImplementedError, match="ROADMAP R5"):
        LLMEngine.from_engine_args(EngineArgs(
            speculative_model=dirs["full"], num_speculative_tokens=3, **kw))


# --- the counters ------------------------------------------------------------

def test_the_mixes_are_counted_and_their_matrices_doubly_stochastic(served):
    """What `served`'s run added to the tracker: the streams' bytes a
    row-substep (6 sublayers x 3 x 4 streams x 64 x 4 B), and the largest column error of a live row's
    matrices (the one sublayer with logits at the clamp has the largest:
    its matrices are far from uniform, where 20 sweeps converge slowest)."""
    _, _, mhc = served
    assert mhc["stream_mix_bytes"] == (
        mhc["row_steps"] * 6 * 3 * 4 * 64 * 4) > 0
    assert 0.0 < mhc["col_error_max"] < 1.0


# --- the share ---------------------------------------------------------------

def moe_weights(cfg, seed=0):
    return {k: v for k, v in published_weights(
        dict(cfg, vocab_size=8), seed).items()
        if k.startswith("model.layers.1.mlp.")}


@pytest.mark.parametrize("rows,path", [(8, "grouped"), (64, "dense")])
def test_the_shares_add_up_to_the_uncut_layer(monkeypatch, rows, path):
    """THE SHARE TEST. Four shares of a 16-expert layer, each through
    `routed_experts` (the block the model calls, its shared expert taken
    off) holding 4 experts of a 16-wide router with the choice bias: their
    routed parts, plus the shared expert counted once, add up to what the
    uncut reference gives for the whole layer; each share alone equals the
    reference given the same share. On both of `moe_ffn`'s forms."""
    from intellillm_tpu.layers import moe
    cfg = dict(TINY)
    p = "model.layers.1.mlp."
    w = moe_weights(cfg)
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(rows, cfg["hidden_size"])).astype(np.float32))
    taken = []
    for name in ("moe_ffn_dense", "moe_ffn_grouped"):
        def spy(*a, name=name, orig=getattr(moe, name), **kw):
            taken.append(name)
            return orig(*a, **kw)
        monkeypatch.setattr(moe, name, spy)

    def stack(name, experts, t=False):
        return jnp.stack([jnp.asarray(
            w[f"{p}experts.{j}.{name}_proj.weight"].T if t else
            w[f"{p}experts.{j}.{name}_proj.weight"]) for j in experts])

    total = jnp.zeros_like(x)
    for rank in range(4):
        share = moe.ExpertShare(4, 4, 1, ep_size=4, ep_rank=rank)
        routing = moe.Routing(scoring="sigmoid", select_bias=True,
                              renormalize=True, scale=2.0,
                              held_start=share.held_start)
        lp = {"gate_router": jnp.asarray(w[p + "gate.weight"].T),
              "expert_bias": jnp.asarray(
                  w[p + "gate.e_score_correction_bias"]),
              "w1": stack("gate", share.held),
              "w2": stack("down", share.held, t=True),
              "w3": stack("up", share.held)}
        part, (_, held) = moe.routed_experts(x[None], lp, share, routing)
        want = ref.moe(cfg, w, p, x, ep_size=4, ep_rank=rank, shared=False)
        np.testing.assert_allclose(np.asarray(part[0]), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
        assert held.shape == (1, rows)
        total = total + part[0]
    assert set(taken) == {"moe_ffn_" + path}, taken
    total = total + ref.swiglu(x, w, p + "shared_experts.")
    whole = ref.moe(cfg, w, p, x)                     # uncut, with shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-4, atol=2e-5)


def test_the_family_copies_no_block():
    """PR 44's claim, tested by the first family after it: the model file
    holds no routed-experts block, no share arithmetic and no latent
    projections of its own."""
    import inspect

    from intellillm_tpu.models import xing4
    text = inspect.getsource(xing4)
    for theirs in ("moe_ffn(", "held_assignments(", "_route(", "top_k(",
                   "held_start +", "get_rope(", "LatentAttention(",
                   '"w_uk"', "q_a_norm", "einsum("):
        assert theirs not in text, theirs


# --- forty layers in one latent pool ------------------------------------------

def test_latent_pool_at_the_published_widths():
    """The published widths: 512 + 64 = 576 -> 640 lanes, 40 layers, bf16:
    51,200 B a token; the forty layers in ONE pool, where a per-head pool
    has its heads (so that the model can walk them in a scan)."""
    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig)
    from intellillm_tpu.transformers_utils.configs import Xing4Config
    from intellillm_tpu.worker.cache_engine import CacheEngine
    hf = Xing4Config(n_routed_experts=8, ep_size=8, vocab_size=16384)
    mc = ModelConfig.from_hf_config(hf, dtype="bfloat16", max_model_len=4096,
                                    load_format="dummy")
    assert mc.get_latent_cache_width() == 640
    assert mc.get_packed_latent_layers() == 40
    assert mc.get_cache_row_shape() == (1, 40, 640)
    assert mc.get_layer_cache_kinds() == ["kv"]
    assert CacheEngine.get_cache_block_size(
        16, "auto", mc, ParallelConfig()) == 16 * 51200
    assert CacheEngine.get_logical_cache_block_size(
        16, "auto", mc) == 16 * 51200
    cc = CacheConfig(block_size=16, num_device_blocks_override=4,
                     swap_space_gib=0.01)
    cc.num_device_blocks, cc.num_cpu_blocks = 4, 2
    ce = CacheEngine(cc, mc, ParallelConfig())
    assert [[pool.shape for pool in pools] for pools in ce.device_cache] == [
        [(4, 40, 16, 640)]]


def test_a_layer_sees_its_own_rows_of_the_one_pool():
    """`_layer_view`: layer l of a pool [NB, layers, BS, Dk] reads block b
    at b * layers + l of the same bytes viewed [NB * layers, 1, BS, Dk],
    a token's slot moves with its block, and a pad row's slot (one past
    the pool, or -1) stays outside."""
    import jax.numpy as jnp
    from intellillm_tpu.layers.mla_attention import _layer_view
    nb, layers, bs, dk = 3, 5, 4, 8
    pool = jnp.arange(nb * layers * bs * dk, dtype=jnp.float32).reshape(
        nb, layers, bs, dk)
    tables = jnp.array([[2, 0], [1, 1]], jnp.int32)
    slots = jnp.array([2 * bs + 3, 0, nb * bs, -1], jnp.int32)
    for layer in (0, 3):
        view, t, s = _layer_view(pool, tables, slots, jnp.int32(layer))
        assert view.shape == (nb * layers, 1, bs, dk)
        np.testing.assert_array_equal(np.asarray(view[t[0, 0], 0]),
                                      np.asarray(pool[2, layer]))
        np.testing.assert_array_equal(
            np.asarray(view.reshape(-1, dk)[s[0]]),
            np.asarray(pool[2, layer, 3]))
        assert int(s[1]) == layer * bs and list(np.asarray(s[2:])) == [-1, -1]
    same = _layer_view(pool, tables, slots, None)
    assert same[0] is pool and same[1] is tables and same[2] is slots


def test_the_routed_layers_are_one_scan():
    """The step programs hold ONE routed layer whatever the depth: the
    jaxpr of a call at 2 + 6 layers has the operations of one at 2 + 2
    (what took XLA 90 s a forty-layer program and the cell 21 minutes of
    set-up, PERF.md section 6, PR 45)."""
    import jax
    from intellillm_tpu.config import ModelConfig
    from intellillm_tpu.layers.attention import AttentionMetadata
    from intellillm_tpu.models.xing4 import Xing4ForCausalLM
    from intellillm_tpu.transformers_utils.configs import Xing4Config

    def operations(layers):
        cfg = {k: v for k, v in TINY.items()
               if k not in ("model_type", "architectures", "torch_dtype")}
        model = Xing4ForCausalLM(ModelConfig.from_hf_config(
            Xing4Config(**dict(cfg, num_hidden_layers=layers)),
            dtype="float32", max_model_len=128, load_format="dummy"))
        f32, i32 = jnp.float32, jnp.int32

        def spec(tree):
            if isinstance(tree, dict):
                return {k: spec(v) for k, v in tree.items()}
            if isinstance(tree, list):
                return [spec(v) for v in tree]
            return jax.ShapeDtypeStruct(tree, f32)

        pool = jax.ShapeDtypeStruct((8, layers, 16, 128), f32)
        ints = lambda *shape: jax.ShapeDtypeStruct(shape, i32)  # noqa: E731

        def call(params, ids, positions, pool, slots, ctx, tables):
            meta = AttentionMetadata(slot_mapping=slots, context_lens=ctx,
                                     block_tables=tables)
            return model(params, ids, positions, [(pool, )], meta)

        jaxpr = jax.make_jaxpr(call)(
            spec(model._param_shapes()), ints(8, 1), ints(8, 1), pool,
            ints(8, 1), ints(8), ints(8, 4))
        return str(jaxpr).count(" = "), str(jaxpr).count("scan[")

    few, scans_few = operations(4)
    many, scans_many = operations(8)
    assert scans_few == scans_many >= 1
    assert many == few
