"""Golden bit-equality suites for the features folded into the mixed
token-budget dispatch (PR 12): prefix-cache hit/miss, prompt_logprobs
panels, and best_of/beam fan-out.

The fixtures in `golden_mixed_fixtures.json` were RECORDED against the
legacy homogeneous prefill path at commit 0e40190 (the last commit where
that path existed), with `enable_chunked_prefill` off. After the
unification every feature executes through the mixed `(token_budget,)`
dispatch, and these tests assert the outputs still match the recorded
legacy outputs token for token — both at the default token budget
(whole prompts land in one chunk) and at a tiny budget that forces
multi-step chunk splits mid-prompt.

Sampled token ids must match exactly: the per-row gumbel noise depends
only on (seed, num_samples bucket, vocab) — not on batch padding — so
the mixed rows draw the same noise the legacy prefill rows drew.
prompt_logprob VALUES are compared with a small tolerance (flash
full-prompt attention vs per-row paged attention differ in float
reduction order); the token ids and top-k membership stay exact.

Regenerate (only meaningful against a pre-unification checkout):
    INTELLILLM_REGEN_GOLDEN=1 python -m pytest \
        tests/engine/test_mixed_golden.py -q
"""
import json
import os
import pathlib

import pytest

from intellillm_tpu import LLM, SamplingParams

FIXTURES = pathlib.Path(__file__).parent / "golden_mixed_fixtures.json"
REGEN = os.environ.get("INTELLILLM_REGEN_GOLDEN") == "1"

PREFIX = ("you are a helpful assistant and the user would like to know "
          "about the city of paris in france where the")
PREFIX_QUERIES = [
    "capital is big",
    "river runs fast and the water is blue",
    "people make red wine",
]
PLP_PROMPTS = [
    "hello my name is",
    "the president of the united states is",
    "the cat runs fast and the dog",
]
SAMPLED_PROMPTS = [
    "hello my name is",
    "the capital of france is",
]


def _llm(model_dir, **kw):
    return LLM(model=model_dir, dtype="float32",
               num_device_blocks_override=128, max_model_len=128,
               max_num_seqs=8, max_paddings=512, swap_space=0.01, **kw)


def _budget_variants(model_dir):
    """Engine configs the suites run under: the default budget and a
    tiny budget that forces real chunk splits and decode+prefill mixed
    steps. When regenerating, only the legacy default-path engine is
    built."""
    if REGEN:
        return {"default": _llm(model_dir)}
    return {
        "default": _llm(model_dir),
        "split": _llm(model_dir, max_num_batched_tokens=8),
    }


def _prefix_pos(llm):
    return len(llm.llm_engine.tokenizer.encode(PREFIX))


def _token_ids(outs):
    return [[list(o.token_ids) for o in r.outputs] for r in outs]


def _serialize_plp(request_output):
    plp = request_output.outputs and request_output.prompt_logprobs
    if not plp:
        return None
    out = []
    for entry in plp:
        if entry is None:
            out.append(None)
        else:
            out.append(sorted([int(t), float(lp)]
                              for t, lp in entry.items()))
    return out


def _run_prefix(llm):
    prompts = [PREFIX + " " + q for q in PREFIX_QUERIES]
    params = SamplingParams(temperature=0.0, max_tokens=12)
    ppos = _prefix_pos(llm)
    miss = llm.generate(prompts, params, prefix_pos=ppos)
    hit = llm.generate(prompts, params, prefix_pos=ppos)
    return {"miss": _token_ids(miss), "hit": _token_ids(hit)}


def _run_plp(llm):
    params = SamplingParams(temperature=0.0, max_tokens=4,
                            prompt_logprobs=2, logprobs=2, ignore_eos=True)
    outs = llm.generate(PLP_PROMPTS, params)
    return {
        "ids": _token_ids(outs),
        "plp": [_serialize_plp(o) for o in outs],
    }


def _run_best_of(llm):
    """Seeded: the samples depend on (seed, stream, step) only, not on
    the jax RNG stream of whichever version recorded a fixture, nor on
    how many sequences the engine has numbered before."""
    params = SamplingParams(temperature=0.8, n=3, best_of=3,
                            max_tokens=8, ignore_eos=True, seed=1234)
    return {"ids": _token_ids(llm.generate(SAMPLED_PROMPTS, params))}


def _run_beam(llm):
    params = SamplingParams(use_beam_search=True, temperature=0.0,
                            n=2, best_of=4, max_tokens=8)
    return {"ids": _token_ids(llm.generate(SAMPLED_PROMPTS, params))}


SUITES = {
    "prefix": _run_prefix,
    "prompt_logprobs": _run_plp,
    "beam": _run_beam,
}


def test_regen_golden_fixtures(tiny_opt_dir):
    """Not a test in normal runs: rewrites the fixture file when
    INTELLILLM_REGEN_GOLDEN=1 (meaningful only on a checkout that still
    has the legacy prefill path)."""
    if not REGEN:
        pytest.skip("fixture regeneration disabled")
    llm = _budget_variants(tiny_opt_dir)["default"]
    data = {name: fn(llm) for name, fn in SUITES.items()}
    FIXTURES.write_text(json.dumps(data, indent=1, sort_keys=True))


@pytest.fixture(scope="module")
def golden():
    if not FIXTURES.exists():
        pytest.skip("golden fixtures not recorded")
    return json.loads(FIXTURES.read_text())


@pytest.mark.skipif(REGEN, reason="regenerating fixtures")
@pytest.mark.parametrize("budget", ["default", "split"])
class TestMixedGolden:

    @pytest.fixture(scope="class")
    def llms(self, tiny_opt_dir):
        return _budget_variants(tiny_opt_dir)

    def test_prefix_cache_hit_and_miss(self, llms, golden, budget):
        got = _run_prefix(llms[budget])
        assert got["miss"] == golden["prefix"]["miss"]
        assert got["hit"] == golden["prefix"]["hit"]
        pool = llms[budget].llm_engine.scheduler.prefix_pool
        assert any(p.computed for p in pool.prefixes.values())

    def test_prompt_logprobs_panels(self, llms, golden, budget):
        got = _run_plp(llms[budget])
        want = golden["prompt_logprobs"]
        assert got["ids"] == want["ids"]
        assert len(got["plp"]) == len(want["plp"])
        for got_req, want_req in zip(got["plp"], want["plp"]):
            assert (got_req is None) == (want_req is None)
            if got_req is None:
                continue
            assert len(got_req) == len(want_req)
            for got_entry, want_entry in zip(got_req, want_req):
                assert (got_entry is None) == (want_entry is None)
                if got_entry is None:
                    continue
                got_toks = sorted(t for t, _ in got_entry)
                want_toks = sorted(t for t, _ in want_entry)
                assert got_toks == want_toks
                got_lp = dict((t, lp) for t, lp in got_entry)
                for t, lp in want_entry:
                    assert abs(got_lp[t] - lp) < 1e-3, (
                        f"token {t}: {got_lp[t]} vs {lp}")

    def test_best_of_fan_out(self, llms, budget):
        """No recorded ids: the same seed gives the same samples under
        either token budget (whole-prompt or split chunks, co-batched or
        not) and on a second run, and the three samples of a prompt are
        three different streams."""
        got = _run_best_of(llms[budget])["ids"]
        assert got == _run_best_of(llms["default"])["ids"]
        assert got == _run_best_of(llms[budget])["ids"]
        for samples in got:
            assert len(samples) == 3
            assert len({tuple(ids) for ids in samples}) == 3

    def test_beam_search(self, llms, golden, budget):
        got = _run_beam(llms[budget])
        assert got["ids"] == golden["beam"]["ids"]
