"""Adaptive-K compile-ladder guarantee: after the first pass over the
configured [k_min, k_max] band, runtime K transitions dispatch only
warm executables — zero new compiles in the CompileTracker.

Boot warm-up calls the jitted programs directly (bypassing the
tracker), so the tracker's first-seen accounting registers each
(program, shape-key) on its FIRST runtime dispatch. The assertion is
therefore two-cycle: sweep every K in the band once (registers every
key), snapshot, sweep again — the second sweep must add nothing.
"""
import pytest
import torch

from intellillm_tpu import LLM, SamplingParams
from intellillm_tpu.obs import get_compile_tracker


@pytest.fixture(scope="module")
def draft_llama_dir(tmp_path_factory):
    from tests.conftest import _build_word_tokenizer
    from transformers import LlamaConfig, LlamaForCausalLM

    d = str(tmp_path_factory.mktemp("tiny-llama-draft-ladder"))
    _, vocab_size = _build_word_tokenizer(d)
    torch.manual_seed(7)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=vocab_size, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False,
        pad_token_id=0, bos_token_id=1, eos_token_id=1,
        torch_dtype=torch.float32))
    model.eval()
    model.save_pretrained(d, safe_serialization=True)
    return d


def test_k_transitions_reuse_warm_executables(tiny_llama_dir,
                                              draft_llama_dir,
                                              monkeypatch, force_pallas):
    # Run the whole ladder with the Pallas kernels selected: the
    # selection is a trace-time choice inside the same programs, so the
    # K-ladder executable count and the warm-reuse guarantee must be
    # identical to the default.
    force_pallas(True)
    k_min, k_max = 1, 3
    llm = LLM(model=tiny_llama_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, swap_space=0.01,
              speculative_model=draft_llama_dir,
              num_speculative_tokens=2, spec_k_min=k_min,
              spec_k_max=k_max)
    engine = llm.llm_engine
    worker = engine.worker

    # Drive K deterministically: each engine step consumes the next K
    # from the schedule (two full sweeps of the band), pinning the
    # controller out of the loop.
    schedule = []

    def scripted_steps():
        if schedule:
            worker.k_spec = schedule.pop(0)
        return worker.k_spec + 1

    monkeypatch.setattr(worker, "adaptive_num_decode_steps",
                        scripted_steps)

    engine.add_request(
        "0", "the cat runs fast and the dog",
        SamplingParams(temperature=0.0, max_tokens=64, ignore_eos=True))

    def sweep(ks):
        """One engine step per K; returns when the schedule drained."""
        schedule.extend(ks)
        while schedule and engine.has_unfinished_requests():
            engine.step()
        assert not schedule, "request finished before the sweep completed"

    band = list(range(k_min, k_max + 1))
    # Cycle 1: first runtime dispatch at every K registers its
    # (program, key) pairs with the tracker.
    sweep(band + band[::-1])
    snap1 = get_compile_tracker().snapshot()

    # Cycle 2: every K transition again — all keys must be warm now.
    sweep(band[::-1] + band)
    snap2 = get_compile_tracker().snapshot()

    assert snap2["compiles"] == snap1["compiles"], (
        "a runtime K transition triggered a fresh compile: "
        f"{snap1['compiles']} -> {snap2['compiles']} — the K-ladder "
        "warm-up (or shape bucketing) no longer covers the band")
    # The second cycle really dispatched (cache hits grew).
    assert (sum(snap2["cache_hits"].values())
            > sum(snap1["cache_hits"].values()))
