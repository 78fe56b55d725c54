"""Golden invariance across the kernel selection.

`set_use_pallas` (what INTELLILLM_USE_PALLAS sets from outside) chooses a
path at trace time inside the same jit programs — flipping it must not
change greedy outputs anywhere the reference runs (these tiny models'
head sizes and adapter widths are ones no kernel supports, so forced on
or off every seam resolves to the same reference composition: outputs
are bit-identical BY CONSTRUCTION and this pins the construction), and
must not change the executable count or bucketing (the
zero-new-executables acceptance criterion, checked via CompileTracker
deltas). On TPU the same tests compare the Pallas kernels against the
reference for real.

The workload is deliberately a MIXED batch: several prompts admitted
together with a small token budget, so steps interleave decode rows
with prefill-chunk rows — the exact shape the ragged fused kernel
serves.
"""
import pytest

from intellillm_tpu import LLM, SamplingParams
from intellillm_tpu.obs import get_compile_tracker


def _build(model_dir, **kw):
    args = dict(dtype="float32", num_device_blocks_override=128,
                max_model_len=128, max_num_seqs=4, max_paddings=512,
                swap_space=0.01, num_decode_steps=1,
                max_num_batched_tokens=16)
    args.update(kw)
    return LLM(model=model_dir, **args)


def _greedy(llm, prompts, max_tokens=8):
    params = SamplingParams(temperature=0.0, max_tokens=max_tokens)
    outs = llm.generate(prompts, params)
    return [tuple(o.outputs[0].token_ids) for o in outs]


def _run_forced(model_dir, prompts, force_pallas, on):
    force_pallas(on)
    before = get_compile_tracker().snapshot()
    llm = _build(model_dir)
    tokens = _greedy(llm, prompts)
    after = get_compile_tracker().snapshot()
    compiles = {p: after["compiles"].get(p, 0)
                - before["compiles"].get(p, 0)
                for p in set(before["compiles"]) | set(after["compiles"])}
    # Dispatches of the mixed program during THIS run (fresh compiles +
    # warm cache hits): proves the workload actually drove the mixed
    # hot path regardless of what earlier tests in the process warmed.
    mixed = sum(after[k].get("mixed", 0) - before[k].get("mixed", 0)
                for k in ("compiles", "cache_hits"))
    del llm
    return tokens, {p: n for p, n in compiles.items() if n}, mixed


def test_mixed_greedy_identical_across_kernel_flags(tiny_llama_dir,
                                                    example_prompts,
                                                    force_pallas):
    """Flag flip: identical greedy tokens AND identical per-program
    compile deltas on the same mixed workload (chunked prefill + decode
    rows interleaved under a 16-token budget)."""
    prompts = example_prompts[:4]
    tok_off, _, mixed_off = _run_forced(
        tiny_llama_dir, prompts, force_pallas, False)
    tok_on, compiles_on, mixed_on = _run_forced(
        tiny_llama_dir, prompts, force_pallas, True)
    assert tok_on == tok_off
    # Both runs must actually exercise the mixed hot path. (Earlier
    # tests in the same process may have warmed the identical buckets —
    # CompileTracker keys are process-global — so compile deltas alone
    # can't prove the workload ran; dispatch counts can.)
    assert mixed_off > 0 and mixed_on > 0
    # The flags-on run must land in (program, bucket) keys the process
    # has already compiled — the flags-off run just dispatched the very
    # same workload — so its compile delta is empty. Any key here means
    # the kernel-selection flags leaked into jit bucketing.
    assert compiles_on == {}, (
        "kernel-selection flags created new jit buckets: "
        f"{compiles_on}")


def test_lora_mixed_batch_identical_across_bgmv_flag(tmp_path_factory,
                                                     example_prompts,
                                                     force_pallas):
    """Adapter rows and no-adapter rows in the same batch, kernels off
    vs on: identical outputs (slot-0 rows ride the exact +0.0 guarantee
    on either path)."""
    pytest.importorskip("safetensors")
    from intellillm_tpu.lora.request import LoRARequest
    from tests.lora.test_lora import make_adapter
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM
    from tests.conftest import _build_word_tokenizer

    root = tmp_path_factory.mktemp("kernel-golden-lora")
    base = str(root / "base")
    _, vocab_size = _build_word_tokenizer(base)
    torch.manual_seed(0)
    config = LlamaConfig(
        vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-6, pad_token_id=0,
        eos_token_id=1, bos_token_id=1, tie_word_embeddings=False,
        torch_dtype=torch.float32)
    LlamaForCausalLM(config).eval().save_pretrained(
        base, safe_serialization=True)
    adapter = make_adapter(str(root / "ad"), seed=11, rank=4, alpha=8.0)

    def run(on):
        force_pallas(on)
        llm = _build(base, enable_lora=True, max_loras=2, max_lora_rank=8,
                     max_model_len=64)
        params = SamplingParams(temperature=0.0, max_tokens=6)
        reqs = [LoRARequest("ad", 1, adapter), None,
                LoRARequest("ad", 1, adapter)]
        engine = llm.llm_engine
        for i, (prompt, req) in enumerate(zip(example_prompts[:3], reqs)):
            engine.add_request(str(i), prompt, params, lora_request=req)
        outs = {o.request_id: o for o in llm._run_engine(use_tqdm=False)}
        toks = [tuple(outs[str(i)].outputs[0].token_ids)
                for i in range(3)]
        del llm
        return toks

    assert run(False) == run(True)
