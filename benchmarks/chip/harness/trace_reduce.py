"""From a profiler trace to device busy/idle, time by operation and the
longest idle gaps.

Two steps, so that the arithmetic can be tested on a small recorded
trace without a profiler:

    load_xplane(dir) -> {"planes": [{"name", "lines": [{"name",
                         "events": [[name, start_ns, dur_ns], ...]}]}]}
    reduce(doc)      -> {"window_s", "busy_s", "op_s", "ops", "modules",
                         "idle_gaps", "devices", ...}

(After obs/kernels.py `parse_trace_dir`, which sums every complete event
on the device lanes by name: an enclosing event — a program on the
"XLA Modules" line, a `while` around its body on the "XLA Ops" line — is
then counted on top of the operations inside it, and there is no busy
union and no idle. Here: operations come from the op line only, each with
its *self* time (its duration minus the events nested inside it on the
same line), busy time is the union of the op line's intervals, and idle
is the window minus that.)

Run as a program (`python trace_reduce.py <trace_dir> <out.json>`) it
loads with jax's own reader, so run.py starts it as a child with
JAX_PLATFORMS=cpu after the server has gone: the parent imports no jax.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from typing import Dict, List, Tuple

DEVICE_PLANE = r"^/device:TPU:\d+$"
OP_LINE = r"^XLA Ops$"
MODULE_LINE = r"^XLA Modules$"
TOP = 10


def load_xplane(trace_dir: str, plane_regex: str = r"^/device:") -> dict:
    """The newest *.xplane.pb under trace_dir, device planes only (host
    planes are large and nothing reads them yet)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    pat = re.compile(plane_regex)
    planes, all_names = [], []
    for plane in data.planes:
        all_names.append(plane.name)
        if not pat.search(plane.name):
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "all_planes": all_names,
            "file": os.path.basename(paths[-1]),
            "file_bytes": os.path.getsize(paths[-1])}


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events: List[list]) -> Dict[str, List[float]]:
    """name -> [self nanoseconds, count] on one line: an event's duration
    minus the events nested directly inside it."""
    totals: Dict[str, List[float]] = {}
    stack: List[list] = []      # [name, end, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            cell = totals.setdefault(name, [0.0, 0])
            cell[0] += max(0, self_ns)
            cell[1] += 1

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return totals


_LAYOUT = re.compile(r"\{[^{}]*\}")
_SHAPE = re.compile(r"\(?((?:[a-z]+\d*\[[\d,]*\](?:, )?)+)\)?")


def op_kind(name: str) -> str:
    """The trace names a device operation by its whole HLO instruction
    (`%fusion.663 = bf16[256,2048]{...} fusion(...operands...), ...`).
    Its kind is what the same operation in another layer shares with it:
    the instruction's base name, its output shapes and the parameters
    (weights) it reads, layer index dropped."""
    if " = " not in name:
        return name[:160]
    lhs, rhs = name.split(" = ", 1)
    base = re.sub(r"[.\d]+$", "", lhs.lstrip("%"))
    m = _SHAPE.match(_LAYOUT.sub("", rhs))
    out = m.group(1).rstrip(", ") if m else ""
    params = sorted({re.sub(r"___\d+___", ".*.", w).strip("_")
                     for w in re.findall(
                         r"%params__(\w+?)__(?:\.\d+)?(?=[,)\s]|$)", rhs)})
    return " ".join(x for x in (base, out, ",".join(params)) if x)[:160]


def _lines(plane: dict, regex: str) -> List[dict]:
    pat = re.compile(regex)
    return [ln for ln in plane["lines"] if pat.search(ln["name"])]


def reduce(doc: dict, device_plane: str = DEVICE_PLANE,
           op_line: str = OP_LINE, module_line: str = MODULE_LINE) -> dict:
    """Busy union, idle, self time by operation, time by program, and the
    idle gaps by the program that ended them — averaged over the device
    planes (the chips the cell used)."""
    pat = re.compile(device_plane)
    planes = [p for p in doc["planes"] if pat.search(p["name"])]
    if not planes:
        raise ValueError(
            f"no plane matches {device_plane!r}; the trace has "
            f"{[p['name'] for p in doc['planes']]}")
    starts, ends = [], []
    per_device = []
    ops: Dict[str, List[float]] = {}
    modules: Dict[str, List[float]] = {}
    gaps: Dict[str, List[float]] = {}
    for plane in planes:
        op_events = [e for ln in _lines(plane, op_line)
                     for e in ln["events"]]
        mod_events = [e for ln in _lines(plane, module_line)
                      for e in ln["events"]]
        if not op_events:
            raise ValueError(
                f"plane {plane['name']} has no events on a line matching "
                f"{op_line!r}; its lines are "
                f"{[ln['name'] for ln in plane['lines']]}")
        busy = union([(s, s + d) for _, s, d in op_events])
        busy_ns = sum(e - s for s, e in busy)
        per_device.append({"plane": plane["name"], "busy_s": busy_ns / 1e9,
                           "op_events": len(op_events)})
        starts.append(busy[0][0])
        ends.append(busy[-1][1])
        for name, (ns, n) in self_times(op_events).items():
            cell = ops.setdefault(name, [0.0, 0])
            cell[0] += ns
            cell[1] += n
        for name, _, d in mod_events:
            cell = modules.setdefault(name, [0.0, 0])
            cell[0] += d
            cell[1] += 1
        # An idle gap is named by what ended it: the program (module)
        # that starts at its end, else the operation.
        mod_starts = sorted((s, name) for name, s, _ in mod_events)
        # (of two operations that start together, the enclosing one)
        op_starts = {s: name for name, s, _ in
                     sorted(op_events, key=lambda e: e[2])}
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            k = bisect.bisect_left(mod_starts, (e0, ""))
            if k < len(mod_starts) and mod_starts[k][0] <= s1:
                label = re.sub(r"\(\d+\)$", "", mod_starts[k][1])
            else:
                label = op_kind(op_starts.get(s1, "?"))
            cell = gaps.setdefault("before " + label, [0.0, 0])
            cell[0] += s1 - e0
            cell[1] += 1
    n = len(planes)
    window_ns = max(ends) - min(starts)
    busy_s = sum(d["busy_s"] for d in per_device) / n

    def top(table: Dict[str, List[float]], k: int = None) -> list:
        rows = sorted(([name, ns / 1e9 / n, cnt]
                       for name, (ns, cnt) in table.items()),
                      key=lambda r: -r[1])
        return rows[:k] if k else rows

    op_rows = top(ops)
    kinds: Dict[str, List[float]] = {}
    for name, (ns, cnt) in ops.items():
        cell = kinds.setdefault(op_kind(name), [0.0, 0])
        cell[0] += ns
        cell[1] += cnt
    return {"window_s": window_ns / 1e9, "busy_s": busy_s,
            "op_s": sum(r[1] for r in op_rows),
            "ops": op_rows, "op_kinds": top(kinds), "modules": top(modules),
            "idle_gaps": top(gaps), "devices": per_device,
            "gap_labels": "by the device program (or operation) that "
                          "ended the gap: the program writes no host "
                          "spans into the trace yet"}


def breakdown(reduced: dict) -> dict:
    """The `breakdown` of a --trace 1 result line."""
    return {"device_ops": [[n, s] for n, s, _ in reduced["op_kinds"][:TOP]],
            "idle_gaps": [[n, s] for n, s, _ in reduced["idle_gaps"][:TOP]]}


def describe(doc: dict, k: int = 5) -> dict:
    """What a trace holds, for reading one by hand: planes, lines, event
    counts and the first few event names of each line."""
    return {"all_planes": doc.get("all_planes"),
            "planes": [{"name": p["name"], "lines": [
                {"name": ln["name"], "events": len(ln["events"]),
                 "first": [e[0] for e in ln["events"][:k]]}
                for ln in p["lines"]]} for p in doc["planes"]]}


def main(argv: List[str]) -> int:
    trace_dir, out_path = argv[1], argv[2]
    doc = load_xplane(trace_dir)
    result = {"describe": describe(doc)}
    try:
        result["reduced"] = reduce(doc)
    except ValueError as e:
        result["error"] = str(e)
    with open(out_path, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
