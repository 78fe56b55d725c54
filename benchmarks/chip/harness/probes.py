"""The correctness probes: a fixed set of greedy prompts from the seed.

Each probe is sent three times: alone before the traffic, *inside the
measured window* among the cell's other requests, and alone again after
it. Every time it asks for the logprob of each token it is given
(`logprobs: 1`).

- Alone before against alone after: same shapes, same programs, so the
  tokens must be equal and the logprobs too: the weights and the pool
  survived the load.
- Inside the window against alone: the probe's rows run in the programs
  the window measures (the wide `mixed` buckets, the full-width fused
  decode), beside other requests' rows. The logprob of the token chosen
  must agree with the alone run, within `LOGPROB_TOL` at every position
  that can be compared and within `LOGPROB_MEAN_TOL` on their mean: the
  first token, and each later one for as long as the tokens before it
  were the same in both runs. Where two
  candidates lie within the noise the greedy choice may flip between
  bucket shapes (random weights: PERF.md, PR 21); the logprob *of the
  choice* still agrees then, because both candidates' do, and what comes
  after a flip has another context and is not compared.
- Every logprob is finite.

Why `logprobs: 1` and not 5. The sampler's logprob panel width is a
static argument of every step program, in buckets (1, 8, 16, ...). Width
1 is what requests without logprobs run, so a probe that asks for 1
rides in the window's own executables and is a check of *them*. A probe
that asked for 5 would put every step it is in through the width-8
variants: other executables than the ones measured, each compiled anew
(my chip run c4, PR 23). The logprob of the choice is its logit less the
log-sum-exp over the whole vocabulary, so a NaN, an inf, a lost expert or
a coarser matmul anywhere in the row moves it.

In a closed loop the probes take one client's seat (loadgen.py), so the
decode batch keeps its width: one row more than the clients moved it
into the next row bucket, whose programs then compiled inside the window
(129 against 128: my chip run c4, PR 23).
"""
from __future__ import annotations

import math
from typing import List

from .loadgen import Planned, Sample, fill_prompts

# Distinct lengths, each alone in its step: they also give the `mixed`
# program its first call at four of the token buckets (16, 32, 64, 128).
PROBE_PROMPT_LENS = (12, 24, 48, 96)
PROBE_TOKENS = 16
_PROBE_INDEX0 = 10**9          # clear of the traffic's request indices

# |logprob in the window - logprob alone| of the chosen token, in nats:
# the most accepted at one position, and the most accepted for the mean
# over all the positions compared in a run when there are at least
# MEAN_NEEDS of them. On the chip every difference was a whole number of
# steps of a bfloat16 logit (2**-6 at the MoE cell's logits, 2**-5 at
# Mistral's): 0 or 1 step at 94% of ~370 positions, 3 steps at the worst
# three, and a run's mean 0.006-0.027 (my chip runs y1 and y2, PR 23;
# PERF.md section 6). So: 8 steps of 2**-5 at a position, which four
# times the runs made would not reach, and 2 such steps for the mean. The
# chosen token's logprob itself varies by 0.2-0.35 nats (standard
# deviation) from one position to the next.
LOGPROB_TOL = 0.25
LOGPROB_MEAN_TOL = 0.0625
MEAN_NEEDS = 16


def probe_plan(seed: int, vocab: int) -> List[Planned]:
    plan = [Planned(_PROBE_INDEX0 + i, "probe", n, PROBE_TOKENS, logprobs=1)
            for i, n in enumerate(PROBE_PROMPT_LENS)]
    fill_prompts(plan, seed, vocab)
    return plan


async def send_alone(driver, plan: List[Planned]) -> List[Sample]:
    """One after the other, nothing else running."""
    return [await driver.send(req, keep_text=True) for req in plan]


def _number(s: Sample) -> int:
    return s.index - _PROBE_INDEX0


def comparable(a: Sample, b: Sample) -> int:
    """Positions whose logprobs can be compared: the first, and each
    later one while the tokens before it are equal in both."""
    ta, tb = a.text.split(), b.text.split()
    n = 0
    for x, y in zip(ta, tb):
        n += 1
        if x != y:
            break
    return min(n, len(a.logprobs), len(b.logprobs))


def compare(before: List[Sample], during: List[Sample],
            after: List[Sample]) -> dict:
    """What the probes say; `ok` is their part of `correct`. `during`
    holds the probes that were sent inside the window, in any number up
    to the set's (a short rehearsal window has room for fewer); none at
    all is a problem of its own."""
    problems = []
    for name, group in (("before", before), ("in the window", during),
                        ("after", after)):
        for s in group:
            if not s.well_formed:
                problems.append(
                    f"probe {name} #{_number(s)}: {s.tokens}/{s.asked} "
                    f"tokens, finish {s.finish!r}, error {s.error!r}")
            elif len(s.logprobs) != s.tokens:
                problems.append(f"probe {name} #{_number(s)}: "
                                f"{len(s.logprobs)} logprobs for "
                                f"{s.tokens} tokens")
            elif not all(isinstance(x, (int, float)) and math.isfinite(x)
                         for x in s.logprobs):
                problems.append(f"probe {name} #{_number(s)}: a logprob "
                                f"is not finite: {s.logprobs}")
    if len(before) != len(after):
        problems.append(f"{len(before)} probes before, {len(after)} after")
    same = [b.text == a.text for b, a in zip(before, after)]
    if not all(same):
        problems.append(f"tokens before != after for probes "
                        f"{[i for i, x in enumerate(same) if not x]}")
    if not during:
        problems.append("no probe ran inside the window")
    alone = {_number(s): s for s in before}
    rows, in_window = [], []
    for label, group in (("after", after), ("window", during)):
        for s in group:
            ref = alone.get(_number(s))
            if ref is None or not (s.well_formed and ref.well_formed):
                continue
            n = comparable(ref, s)
            deltas = [abs(x - y) for x, y in
                      zip(ref.logprobs[:n], s.logprobs[:n])]
            worst = max(deltas, default=0.0)
            if label == "window":
                in_window += deltas
            rows.append({"probe": _number(s), "against": label,
                         "first_token_equal":
                             ref.text.split()[:1] == s.text.split()[:1],
                         "tokens_equal": sum(
                             x == y for x, y in zip(ref.text.split(),
                                                    s.text.split())),
                         "compared": n, "max_abs_dlogprob": worst,
                         "dlogprob_first": deltas[0] if deltas else None})
            if not worst <= LOGPROB_TOL:
                problems.append(
                    f"probe #{_number(s)} ({label}): the chosen token's "
                    f"logprob differs from the alone run by {worst:.4f} "
                    f"nats over {n} comparable positions "
                    f"(tolerance {LOGPROB_TOL})")
    mean = sum(in_window) / len(in_window) if in_window else None
    if len(in_window) >= MEAN_NEEDS and not mean <= LOGPROB_MEAN_TOL:
        problems.append(
            f"in the window the chosen tokens' logprobs differ from the "
            f"alone runs by {mean:.4f} nats on the mean of "
            f"{len(in_window)} positions (tolerance {LOGPROB_MEAN_TOL})")
    return {"ok": not problems, "problems": problems,
            "tokens_equal_before_after": same,
            "in_window": len(during), "logprob_tol": LOGPROB_TOL,
            "logprob_mean_tol": LOGPROB_MEAN_TOL,
            "compared_in_window": len(in_window),
            "mean_abs_dlogprob_in_window": mean, "comparisons": rows,
            "tokens": [s.text.split() for s in before],
            "logprobs": [s.logprobs for s in before],
            "tokens_in_window": [s.text.split() for s in during],
            "logprobs_in_window": [s.logprobs for s in during]}
