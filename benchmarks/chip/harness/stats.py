"""From client-side samples to the end-to-end metrics.

Each function takes the samples and the window (t_start, t_end on the
client's clock) and returns a number, or None when the cell's traffic
gives it nothing to read. What a metric measures is fixed here, with the
yardstick: a rate is over all the tokens and all the time of the window,
a tail is the tail of all requests, a failed request misses.
"""
from __future__ import annotations

import math
import re
import statistics
from typing import List, Optional

from .loadgen import Sample


def percentile(values: List[float], p: float) -> float:
    """Linear interpolation between order statistics (numpy's default);
    (copied from benchmarks/common.py `percentiles`, without numpy)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def iqr_spread(values: List[float]) -> float:
    """The spread the builder's contract bounds are set from: the
    distance between the first and third quartile as
    `statistics.quantiles(values, n=4)` gives them, over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def due_in_window(samples: List[Sample], window) -> List[Sample]:
    t0, t1 = window
    return [s for s in samples
            if s.due_t is not None and t0 <= s.due_t < t1
            and s.phase != "probe"]


def ttft_values(samples: List[Sample], window, cutoff_t: float
                ) -> List[float]:
    """Seconds from *due* to first token, for every request due in the
    window. One that failed, or has no first token by `cutoff_t`, misses:
    it counts with the time it had waited by then, which is a lower
    bound, and is listed as failed."""
    out = []
    for s in due_in_window(samples, window):
        if s.first_t is not None and s.error is None:
            out.append(s.first_t - s.due_t)
        else:
            out.append(cutoff_t - s.due_t)
    return out


def tpot_values(samples: List[Sample], window,
                whole_requests: bool = False) -> List[float]:
    """Seconds per output token, one value for each request that was
    given tokens at two or more moments inside the window: (last such
    moment - first such moment) / (tokens that came after the first).
    The K=8 fused decode delivers tokens in bursts of 8; taken over all
    of a request's bursts in the window the burst does not matter.

    Every request that decoded in the window counts, with the part of
    it that lay inside: the requests in flight when the window opened
    and those still running when it closed as much as those that began
    and ended in it. So a stall anywhere in the window reaches every
    request in flight at that moment, and nothing before the window (the
    ramp's compile stalls) reaches any. A failed request has no value
    here; it is counted in `failed`.

    `whole_requests` gives the narrower set this metric was first taken
    over (requests whose first and last token both arrived inside the
    window: it leaves out the requests in flight at either edge and so
    favours short ones), for the record."""
    t0, t1 = window
    out = []
    for s in samples:
        if s.phase in ("probe", "warm") or s.error is not None:
            continue
        if whole_requests and not (s.done and s.first_t >= t0
                                   and s.end_t < t1):
            continue
        inside = [(t, n) for t, n in s.chunks if t0 <= t < t1]
        after_first = sum(n for _, n in inside[1:])
        if after_first:
            out.append((inside[-1][0] - inside[0][0]) / after_first)
    return out


def tokens_in_window(samples: List[Sample], window) -> int:
    t0, t1 = window
    return sum(n for s in samples if s.phase != "probe"
               for t, n in s.chunks if t0 <= t < t1)


def late_values(samples: List[Sample], window) -> List[float]:
    return [s.send_t - s.due_t for s in due_in_window(samples, window)]


def finished_in_window(samples: List[Sample], window) -> List[Sample]:
    t0, t1 = window
    return [s for s in samples if s.phase != "probe"
            and s.end_t is not None and t0 <= s.end_t < t1]


def attempted_failed(samples: List[Sample], window, loop: str,
                     cutoff_t: float):
    """Open loop: attempted = due in the window, failed = errored or no
    first token by the cut-off. Closed loop: attempted = requests that
    ended (or failed) inside the window, failed = those that errored or
    came back with the wrong token count or finish reason."""
    t0, t1 = window
    if loop == "open":
        reqs = due_in_window(samples, window)
        bad = [s for s in reqs
               if s.error is not None or s.first_t is None
               or (s.end_t is not None and not s.well_formed)]
        return len(reqs), len(bad)
    ended = [s for s in samples if s.phase != "probe" and not s.cancelled
             and ((s.end_t is not None and t0 <= s.end_t < t1)
                  or (s.error is not None and t0 <= s.send_t < t1))]
    bad = [s for s in ended if not s.well_formed]
    return len(ended), len(bad)


def client_stat(params: dict, samples: List[Sample], window, chips: int,
                cutoff_t: float) -> Optional[float]:
    """The `client` reader: a statistic of the client's own samples.
    `of`: ttft_s | tpot_s | late_s | out_tokens; `stat`: pNN | mean | max
    | rate_per_chip (out_tokens only); `scale` multiplies the result
    (1000 for milliseconds)."""
    of, stat = params["of"], params["stat"]
    scale = params.get("scale", 1.0)
    if of == "out_tokens":
        if stat != "rate_per_chip":
            raise ValueError("out_tokens has only the stat rate_per_chip")
        seconds = window[1] - window[0]
        return tokens_in_window(samples, window) / seconds / chips * scale
    if of == "ttft_s":
        vals = ttft_values(samples, window, cutoff_t)
    elif of == "tpot_s":
        vals = tpot_values(samples, window)
    elif of == "late_s":
        vals = late_values(samples, window)
    else:
        raise ValueError(f"the client reader has no samples of {of!r}")
    if not vals:
        return None
    if stat == "mean":
        return statistics.fmean(vals) * scale
    if stat == "max":
        return max(vals) * scale
    if re.fullmatch(r"p\d+(\.\d+)?", stat):
        return percentile(vals, float(stat[1:])) * scale
    raise ValueError(f"unknown stat {stat!r}")
