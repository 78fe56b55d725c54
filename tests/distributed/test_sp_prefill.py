"""Multi-device prefill correctness through the SERVING engine.

Prompts prefill as chunked mixed-dispatch rows on every topology. What
must hold is output equality: long prompts prefilled under a
tensor x data-parallel mesh produce greedy tokens identical to a
single-device run.
"""
import jax
import pytest

from intellillm_tpu import LLM, SamplingParams

requires_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")


def _llm(model_dir, **kw):
    kw.setdefault("max_paddings", 512)
    return LLM(model=model_dir, dtype="float32",
               num_device_blocks_override=128, max_model_len=128,
               max_num_seqs=8, swap_space=0.01, **kw)


@requires_8_devices
def test_multi_device_prefill_matches_single_device(tiny_llama_dir):
    # A long prompt plus short ones in the same workload: all prefill as
    # mixed-dispatch chunks over the tp=2 x dp=4 mesh and must match the
    # single-device run token for token.
    long_prompt = " ".join(["the cat runs fast and the dog is slow"] * 12)
    prompts = [long_prompt, "hello my name is",
               "the capital of france is"]
    params = SamplingParams(temperature=0.0, max_tokens=12)

    ref = [o.outputs[0].token_ids
           for o in _llm(tiny_llama_dir).generate(prompts, params)]

    llm = _llm(tiny_llama_dir, tensor_parallel_size=2,
               data_parallel_size=4, max_paddings=40)
    got = [o.outputs[0].token_ids for o in llm.generate(prompts, params)]

    assert got == ref
