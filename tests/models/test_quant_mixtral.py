"""QuantMixtral: GPTQ/AWQ-quantized Mixtral checkpoints serve losslessly.

Role parity: reference `vllm/model_executor/models/mixtral_quant.py`
(whole file) — per-expert quantized linears, TP-sharded. Here the
per-expert packed int4 tensors stack to [N, in/2, out] and dequantize
through the exact codes inside the MoE layer; attention projections go
through the shared load_linear resolution.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
import torch

MAX_TOKENS = 8


def _pack_rows_int32(m):
    in_, out = m.shape
    packed = np.zeros((in_ // 8, out), np.int32)
    for j in range(8):
        packed |= m[j::8].astype(np.int32) << (4 * j)
    return packed


def _pack_cols_int32(m):
    g, out = m.shape
    packed = np.zeros((g, out // 8), np.int32)
    for j in range(8):
        packed |= m[:, j::8].astype(np.int32) << (4 * j)
    return packed


def _gptq_quantize(w, group):
    """[in, out] fp → (qweight, qzeros, scales, g_idx, dequant)."""
    in_, out = w.shape
    g = in_ // group
    g_idx = (np.arange(in_) // group).astype(np.int32)
    wg = w.reshape(g, group, out)
    wmin, wmax = wg.min(1), wg.max(1)
    s = np.maximum((wmax - wmin) / 15.0, 1e-8).astype(np.float32)
    z = np.round(-wmin / s).clip(1, 15).astype(np.uint8)
    q = np.clip(np.round(w / s[g_idx] + z[g_idx]), 0, 15).astype(np.uint8)
    deq = (q.astype(np.float32) - z[g_idx]) * s[g_idx]
    return (_pack_rows_int32(q),
            _pack_cols_int32((z.astype(np.int32) - 1).astype(np.uint8)),
            s, g_idx, deq)


@pytest.fixture(scope="module")
def quant_mixtral_dirs(tmp_path_factory):
    """(gptq_dir, fp_twin_dir) tiny Mixtral checkpoints: experts AND
    attention projections GPTQ-quantized; twin holds the dequants."""
    import safetensors.numpy
    from tests.conftest import _build_word_tokenizer
    from transformers import (AutoTokenizer, MixtralConfig,
                              MixtralForCausalLM)

    base = tmp_path_factory.mktemp("quant-mixtral")
    d = str(base / "build")
    os.makedirs(d, exist_ok=True)
    _, vocab_size = _build_word_tokenizer(d)
    torch.manual_seed(0)
    config = MixtralConfig(
        vocab_size=vocab_size, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=128, tie_word_embeddings=False,
        pad_token_id=0, bos_token_id=1, eos_token_id=1,
        torch_dtype=torch.float32)
    model = MixtralForCausalLM(config)
    model.eval()
    sd = {k: v.numpy() for k, v in model.state_dict().items()}

    group = 16
    targets = [k for k in sd
               if ("experts." in k and k.endswith(".weight"))
               or (("self_attn" in k) and k.endswith("_proj.weight"))]
    tensors = {k: v for k, v in sd.items() if k not in targets}
    twin_sd = dict(sd)
    for name in targets:
        w = sd[name].T.astype(np.float32)
        qweight, qzeros, scales, g_idx, deq = _gptq_quantize(w, group)
        prefix = name[:-len(".weight")]
        tensors[prefix + ".qweight"] = qweight
        tensors[prefix + ".qzeros"] = qzeros
        tensors[prefix + ".scales"] = scales
        tensors[prefix + ".g_idx"] = g_idx
        twin_sd[name] = np.ascontiguousarray(deq.T.astype(np.float32))

    gq_dir = str(base / "gptq")
    os.makedirs(gq_dir, exist_ok=True)
    safetensors.numpy.save_file(
        {k: np.ascontiguousarray(v) for k, v in tensors.items()},
        os.path.join(gq_dir, "model.safetensors"))
    cfg = json.loads(config.to_json_string())
    cfg["architectures"] = ["QuantMixtralForCausalLM"]
    cfg["quantization_config"] = {"quant_method": "gptq", "bits": 4,
                                  "group_size": group, "desc_act": False}
    with open(os.path.join(gq_dir, "config.json"), "w") as f:
        json.dump(cfg, f)
    AutoTokenizer.from_pretrained(d).save_pretrained(gq_dir)

    twin_dir = str(base / "twin")
    model.load_state_dict({k: torch.from_numpy(np.ascontiguousarray(v))
                           for k, v in twin_sd.items()})
    model.save_pretrained(twin_dir, safe_serialization=True)
    AutoTokenizer.from_pretrained(d).save_pretrained(twin_dir)
    return gq_dir, twin_dir


def _greedy(model_dir, prompts, tp=1):
    from intellillm_tpu import LLM, SamplingParams
    llm = LLM(model=model_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=64,
              max_num_seqs=8, swap_space=0.01, tensor_parallel_size=tp)
    outs = llm.generate(prompts, SamplingParams(temperature=0.0,
                                                max_tokens=MAX_TOKENS))
    return [o.outputs[0].token_ids for o in outs]


def test_quant_mixtral_loads_int4_stacks(quant_mixtral_dirs):
    """Checkpoint loads WITHOUT NotImplementedError; expert stacks are
    packed int4 that dequantize bit-exactly to the fp twin's values."""
    from intellillm_tpu.config import ModelConfig
    from intellillm_tpu.layers.quantization import dequant_int4_stack
    from intellillm_tpu.models.model_loader import get_model

    gq_dir, twin_dir = quant_mixtral_dirs
    mc = ModelConfig(model=gq_dir, dtype="float32")
    assert mc.quantization == "gptq"
    _, params_q = get_model(mc)
    _, params_fp = get_model(ModelConfig(model=twin_dir, dtype="float32"))

    n_stacks = 0
    for lq, lf in zip(params_q["layers"], params_fp["layers"]):
        for wname in ("w1", "w2", "w3"):
            assert isinstance(lq[wname], dict), (
                f"{wname} did not load as a packed int4 stack")
            deq = np.asarray(dequant_int4_stack(
                {k: jnp.asarray(v) for k, v in lq[wname].items()},
                jnp.float32))
            if wname != "w2":
                # Packed as the checkpoint has it, [N, in/2, out]; the fp
                # twin's w1/w3 are stored [N, out, in] (layers/moe.py).
                deq = deq.swapaxes(1, 2)
            np.testing.assert_array_equal(deq, np.asarray(lf[wname]))
            n_stacks += 1
        for p in ("q", "k", "v", "o"):
            assert isinstance(lq[p], dict) and "q4" in lq[p]
    assert n_stacks == 6


def test_quant_mixtral_greedy_matches_twin(quant_mixtral_dirs,
                                           example_prompts):
    gq_dir, twin_dir = quant_mixtral_dirs
    golden = _greedy(twin_dir, example_prompts)
    ours = _greedy(gq_dir, example_prompts)
    for g, o in zip(golden, ours):
        assert g[0] == o[0]           # first token exact; fp32-accum
        # order may diverge later — same contract as the AWQ/GPTQ tests


def test_quant_mixtral_tp2(quant_mixtral_dirs, example_prompts):
    """TP=2 on the virtual CPU mesh: sharded packed stacks produce the
    same greedy stream as single-chip."""
    gq_dir, _ = quant_mixtral_dirs
    single = _greedy(gq_dir, example_prompts)
    tp2 = _greedy(gq_dir, example_prompts, tp=2)
    assert tp2 == single


def test_dense_fallback_inherits_quant_sharding(quant_mixtral_dirs):
    """Dense-fallback leaves at quantized-spec paths (dummy weights,
    irregular layouts) must inherit the packed form's sharding instead of
    silently replicating multi-GiB expert stacks on TP meshes."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from intellillm_tpu.config import ModelConfig
    from intellillm_tpu.models.model_loader import get_model
    from intellillm_tpu.parallel.mesh import shard_params

    gq_dir, _ = quant_mixtral_dirs
    # Dummy load: quantization="gptq" but expert stacks come out DENSE.
    mc = ModelConfig(model=gq_dir, dtype="float32", load_format="dummy")
    model, params = get_model(mc, load_format="dummy")
    devs = np.array(jax.devices()[:2]).reshape(1, 2)
    mesh = Mesh(devs, ("data", "model"))
    placed = shard_params(params, mesh, model)
    w1 = placed["layers"][0]["w1"]
    assert not isinstance(w1, dict)          # really the dense fallback
    spec = w1.sharding.spec
    assert "model" in tuple(spec), (
        f"dense expert stack replicated instead of sharded: {spec}")
