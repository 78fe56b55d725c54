"""The generic readers a metric file names (metrics/<metric>.json:
{"reader": ..., ...parameters}). Most later metrics are data only.

A reader takes its parameters and the run's context and returns a number,
or None when there is nothing to read; the harness then leaves the metric
out of the line.

    prom_delta          a Prometheus series: value at window end - start
    prom_ratio          two such deltas (each a sum of series), divided
    prom_hist_quantile  a histogram's window delta, a quantile of it
    health_json         a path into /health/detail at window end
    trace_idle          1 - union of device-operation intervals / window
    trace_op_share      device operations whose name matches a regex,
                        share of busy time
    client              a statistic of the client's own samples (stats.py)

Context keys: prom_start, prom_end (parsed /metrics), detail_end
(/health/detail), trace (trace_reduce.reduce's result or None), samples,
window, chips, cutoff_t.
"""
from __future__ import annotations

import math
import re
from typing import Dict, List, Optional, Tuple

from . import stats

_SERIES_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')

Series = Dict[str, List[Tuple[Dict[str, str], float]]]


def parse_prom(text: str) -> Series:
    """Prometheus text exposition -> {name: [(labels, value), ...]}."""
    out: Series = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _SERIES_RE.match(line)
        if not m:
            continue
        name, labels, value = m.groups()
        try:
            v = float(value)
        except ValueError:
            continue
        out.setdefault(name, []).append(
            (dict(_LABEL_RE.findall(labels or "")), v))
    return out


def _matches(labels: Dict[str, str], want: dict) -> bool:
    for k, v in want.items():
        have = labels.get(k)
        if isinstance(v, dict):
            if "not" in v and have == v["not"]:
                return False
            if "regex" in v and not re.search(v["regex"], have or ""):
                return False
        elif have != v:
            return False
    return True


def series_sum(prom: Series, spec: dict) -> Optional[float]:
    """Sum of the series {"name", "labels"} selects; None if none does."""
    vals = [v for labels, v in prom.get(spec["name"], [])
            if _matches(labels, spec.get("labels", {}))]
    return sum(vals) if vals else None


def _delta(ctx: dict, specs) -> Optional[float]:
    """Window delta of a sum of series. A series that appears only at
    the end started from zero."""
    if isinstance(specs, dict):
        specs = [specs]
    total, found = 0.0, False
    for spec in specs:
        end = series_sum(ctx["prom_end"], spec)
        if end is None:
            continue
        found = True
        total += end - (series_sum(ctx["prom_start"], spec) or 0.0)
    return total if found else None


def prom_delta(p: dict, ctx: dict) -> Optional[float]:
    d = _delta(ctx, p["series"])
    if d is None:
        return p.get("absent")      # e.g. 0 for a counter never touched
    return d * p.get("scale", 1.0)


def prom_ratio(p: dict, ctx: dict) -> Optional[float]:
    num, den = _delta(ctx, p["numerator"]), _delta(ctx, p["denominator"])
    if num is None or not den:
        return None
    return num / den * p.get("scale", 1.0)


def hist_quantile(buckets: List[Tuple[float, float]], q: float
                  ) -> Optional[float]:
    """Prometheus' histogram_quantile on cumulative (le, count) pairs:
    linear inside the bucket; the +Inf bucket answers its lower edge."""
    buckets = sorted(buckets)
    total = buckets[-1][1] if buckets else 0
    if total <= 0:
        return None
    rank = q * total
    prev_le, prev_n = 0.0, 0.0
    for le, n in buckets:
        if n >= rank:
            if math.isinf(le):
                return prev_le
            if n == prev_n:
                return le
            return prev_le + (le - prev_le) * (rank - prev_n) / (n - prev_n)
        prev_le, prev_n = le, n
    return prev_le


def prom_hist_quantile(p: dict, ctx: dict) -> Optional[float]:
    name = p["name"] + "_bucket"
    want = p.get("labels", {})

    def cumulative(prom: Series) -> Dict[float, float]:
        out: Dict[float, float] = {}
        for labels, v in prom.get(name, []):
            if _matches(labels, want):
                le = float(labels["le"].replace("+Inf", "inf"))
                out[le] = out.get(le, 0.0) + v
        return out

    end, start = cumulative(ctx["prom_end"]), cumulative(ctx["prom_start"])
    if not end:
        return None
    q = hist_quantile([(le, n - start.get(le, 0.0))
                       for le, n in end.items()], p["quantile"])
    return None if q is None else q * p.get("scale", 1.0)


def _walk(node, parts: List[str]) -> list:
    if not parts:
        return [node]
    head, rest = parts[0], parts[1:]
    if head == "*":
        kids = node.values() if isinstance(node, dict) else node
        return [x for k in kids for x in _walk(k, rest)]
    if isinstance(node, dict) and head in node:
        return _walk(node[head], rest)
    return []


def health_json(p: dict, ctx: dict) -> Optional[float]:
    """`path`: dotted, `*` for every child; `reduce`: max | sum | first."""
    vals = [v for v in _walk(ctx["detail_end"], p["path"].split("."))
            if isinstance(v, (int, float)) and not isinstance(v, bool)]
    if not vals:
        return None
    how = p.get("reduce", "first")
    v = {"max": max, "sum": sum, "first": lambda xs: xs[0]}[how](vals)
    return v * p.get("scale", 1.0)


def trace_idle(p: dict, ctx: dict) -> Optional[float]:
    t = ctx.get("trace")
    if not t or not t["window_s"]:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0


def trace_op_share(p: dict, ctx: dict) -> Optional[float]:
    t = ctx.get("trace")
    if not t or not t["busy_s"]:
        return None
    pat = re.compile(p["regex"])
    hit = sum(sec for name, sec, _ in t["ops"] if pat.search(name))
    return hit / t["op_s"] * 100.0 if t["op_s"] else None


def client(p: dict, ctx: dict) -> Optional[float]:
    return stats.client_stat(p, ctx["samples"], ctx["window"], ctx["chips"],
                             ctx["cutoff_t"])


READERS = {"prom_delta": prom_delta, "prom_ratio": prom_ratio,
           "prom_hist_quantile": prom_hist_quantile,
           "health_json": health_json, "trace_idle": trace_idle,
           "trace_op_share": trace_op_share, "client": client}


def read_metric(metric_file: dict, ctx: dict) -> Optional[float]:
    reader = READERS.get(metric_file["reader"])
    if reader is None:
        raise KeyError(f"metric {metric_file.get('name')!r} names the "
                       f"unknown reader {metric_file['reader']!r}")
    return reader(metric_file, ctx)
