"""One run of one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One run = one new process. This parent never imports jax. It reads the
cell from BENCHMARK.json, starts the real OpenAI server as a child with
seeded dummy weights, warms every shape the cell's traffic touches (set-up),
measures for --seconds, checks the outputs, stops the child and prints the
result as the last line of stdout; earlier lines are JSON objects with what
else is worth keeping. See README.md beside this file.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import (loadgen, probes, readers, stats,  # noqa: E402
                     trace_reduce)
from harness.manifest import Manifest, ManifestError  # noqa: E402
from harness.server import (ROOT, WORK_DIR, HarnessError,  # noqa: E402
                            Server)

BOOT_TIMEOUT_S = 900.0


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def local_chips() -> int:
    """TPU chips on this host, read off the device nodes without loading
    libtpu (copied from intellillm_tpu.utils.count_local_chips)."""
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def make_plan(traffic: dict, seed: int, seconds: float, vocab: int):
    if traffic["loop"] == "open":
        plan = loadgen.open_plan(traffic, seed, seconds)
    elif traffic["loop"] == "closed":
        plan = loadgen.closed_plan(traffic, seed, traffic["queue"])
    else:
        raise ManifestError(f"loop must be open or closed, not "
                            f"{traffic['loop']!r}")
    loadgen.fill_prompts(plan, seed, vocab)
    return plan


async def snapshot(driver: loadgen.Driver) -> dict:
    metrics, detail, kernels = await asyncio.gather(
        driver.get_text("/metrics"), driver.get_json("/health/detail"),
        driver.get_json("/debug/kernels?top=512"))
    return {"t": driver.clock(), "prom": readers.parse_prom(metrics),
            "detail": detail, "selection": kernels.get("selection") or {},
            "executables": sorted(
                f"{e['program']} {e['bucket']}"
                for e in kernels.get("executables", []))}


async def wait_idle(driver: loadgen.Driver, timeout_s: float = 60.0) -> bool:
    """Until the server holds no request (cancelled streams are aborted
    by the server when their connection closes)."""
    deadline = driver.clock() + timeout_s
    while driver.clock() < deadline:
        depths = (await driver.get_json("/health/detail"))["queue_depths"]
        if not any(depths.values()):
            return True
        await asyncio.sleep(0.25)
    return False


async def ramp(d: loadgen.Driver, traffic: dict, t0: float) -> float:
    """The window opens `ramp_s` after the traffic started, and with a
    `ramp_until` not before the traffic has settled, by signs that do not
    depend on what the program calls its programs: every client has had
    a first token, and for `quiet_s` seconds the server has listed no new
    executable and the clients have seen no stall (no pause longer than
    `stall_gap_s` between token arrivals, all requests together). On a
    cold compile cache the ramp is longer; the window's shapes are the
    same."""
    until = traffic.get("ramp_until")
    t_ramp = t0 + traffic["ramp_s"]
    if not until:
        await sleep_until(d, t_ramp)
        return t_ramp
    deadline = t0 + until["timeout_s"]
    first_wave = traffic.get("clients", 0)
    known, t_new = None, t0
    while True:
        have = sorted(f"{e['program']} {e['bucket']}" for e in
                      (await d.get_json("/debug/kernels?top=512")
                       ).get("executables", []))
        now = d.clock()
        if have != known:
            known, t_new = have, now
        wave = [s for s in d.samples if s.phase == "closed"][:first_wave]
        started = (len(wave) == first_wave
                   and all(s.first_t is not None for s in wave))
        flowing = (d.last_arrival_t is not None
                   and now - d.last_arrival_t <= d.stall_gap_s)
        quiet_since = max(t0, t_new, d.last_stall_t or t0)
        if (started and flowing and now >= t_ramp
                and now - quiet_since >= until["quiet_s"]):
            return now
        if now > deadline:
            raise HarnessError(
                f"the traffic did not settle in {until['timeout_s']} s: "
                f"clients started {started}, tokens flowing {flowing}, "
                f"{len(have)} executables, the last new one "
                f"{now - t_new:.1f} s ago, the last stall "
                f"{now - quiet_since:.1f} s ago")
        await asyncio.sleep(0.5)


async def probes_in_open_window(d: loadgen.Driver, probe_plan,
                                t_start: float, seconds: float,
                                done: list) -> None:
    """Open loop: the probes are arrivals of their own, spread over the
    middle of the window. Each goes into `done` as it ends, so a slow
    server loses the probes it did not finish and not the others."""
    for i, req in enumerate(probe_plan):
        await sleep_until(d, t_start + seconds * (i + 1)
                          / (len(probe_plan) + 2))
        done.append(await d.send(req, keep_text=True))


async def sleep_until(driver: loadgen.Driver, t: float) -> None:
    delay = t - driver.clock()
    if delay > 0:
        await asyncio.sleep(delay)


async def measure(server: Server, traffic: dict, plan, probe_plan,
                  seed: int, seconds: float, trace: bool,
                  trace_dir: str) -> dict:
    out: dict = {}
    clock = time.perf_counter
    stall_gap_s = (traffic.get("ramp_until") or {}).get("stall_gap_s", 1.0)
    async with loadgen.Driver(server.base, server.model_dir, clock,
                              stall_gap_s) as d:
        # -- set-up: the probes alone, then the warm set ------------------
        t = clock()
        out["probes_before"] = await probes.send_alone(d, probe_plan)
        emit({"event": "probes_before", "seconds": clock() - t})
        warm_index = 2 * 10**9       # clear of the traffic's and the probes'
        for step in traffic.get("warm", []):
            t = clock()
            group = [loadgen.Planned(warm_index + i, "warm", n,
                                     step["max_tokens"])
                     for i, n in enumerate(step["prompts"])]
            warm_index += len(group)
            loadgen.fill_prompts(group, seed, server.vocab)
            res = await asyncio.gather(*(d.send(r) for r in group))
            bad = [s.error or f"{s.tokens}/{s.asked}" for s in res
                   if not s.well_formed]
            emit({"event": "warm", "step": step.get("name"),
                  "requests": len(group), "seconds": clock() - t,
                  "bad": bad})
            if bad:
                raise HarnessError(f"warm step failed: {bad[:3]}")
        # -- traffic: ramp, then the window -------------------------------
        t0 = clock()
        if traffic["loop"] == "open":
            d.spawn(d.run_open(plan, t0))
        else:
            await d.run_closed(plan, traffic["clients"],
                               seat_probes=probe_plan)
        t_start = await ramp(d, traffic, t0)
        t_end = t_start + seconds
        d.window_open = True
        during = None
        if traffic["loop"] == "open":
            during = []
            d.spawn(probes_in_open_window(d, probe_plan, t_start, seconds,
                                          during))
        start = await snapshot(d)
        out["setup_s"] = t_start - T_PROCESS
        emit({"event": "window_open", "setup_s": out["setup_s"],
              "ramp_s": t_start - t0,
              "queue_depths": start["detail"]["queue_depths"],
              "executables": start["executables"]})
        await sleep_until(d, t_end)
        end = await snapshot(d)
        cooldown = traffic.get("cooldown_s", 0.0) \
            if traffic["loop"] == "open" else 0.0
        if trace:
            # After the window, with the traffic still running: stopping
            # a trace stalls the server for tens of seconds, which the
            # window's counters must not see.
            await sleep_until(d, t_end + traffic["trace"]["offset_s"])
            t_tr = clock()
            await d.post_json(f"/debug/profiler/start?dir={trace_dir}")
            await asyncio.sleep(traffic["trace"]["seconds"])
            await d.post_json("/debug/profiler/stop")
            emit({"event": "traced", "asked_s": traffic["trace"]["seconds"],
                  "start_to_stopped_s": clock() - t_tr})
        await sleep_until(d, t_end + cooldown)
        cutoff_t = min(clock(), t_end + cooldown + 1.0)
        if d.queue_exhausted:
            raise HarnessError("the closed loop's queue ran dry: raise "
                               "`queue` in the traffic file")
        sent = d.seat_samples if during is None else list(during)
        out["probes_during"] = [s for s in sent if s.send_t < t_end]
        await d.cancel_all()
        out["samples"] = [s for s in d.samples if s.phase != "probe"
                          and s.phase != "warm"]
        idle = await wait_idle(d)
        t = clock()
        out["probes_after"] = await probes.send_alone(d, probe_plan)
        emit({"event": "probes_after", "seconds": clock() - t,
              "server_idle_first": idle})
        final = await snapshot(d)
    out.update(window=(t_start, t_end), cutoff_t=cutoff_t, start=start,
               end=end, final=final)
    return out


def reduce_trace(trace_dir: str) -> dict:
    """trace_reduce.py as a child, on the CPU backend: the parent stays
    off jax, and the chip is not touched again."""
    out_path = os.path.join(trace_dir, "reduced.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "harness", "trace_reduce.py")
    r = subprocess.run([sys.executable, script, trace_dir, out_path],
                       env=env, capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        raise HarnessError(f"trace_reduce failed:\n{r.stderr[-3000:]}")
    with open(out_path) as f:
        return json.load(f)


def tpot_summary(values: list) -> dict:
    if not values:
        return {}
    out = {f"p{p}": stats.percentile(values, p) * 1e3
           for p in (50, 90, 95, 99)}
    out.update(n=len(values), mean=sum(values) / len(values) * 1e3)
    return out


def kernel_problems(detail: dict, selection: dict) -> list:
    """On a TPU no Pallas seam may resolve to a reference (as
    chip_smoke.py checks it)."""
    problems = []
    refs = {k: v for k, v in
            detail["compiles"]["kernel_dispatch"].items()
            if k.startswith("reference")}
    if refs:
        problems.append(f"kernel seams resolved to a reference: {refs}")
    if not (selection.get("use_pallas") and selection.get("ragged")):
        problems.append(f"Pallas seams are off: {selection}")
    return problems


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="run the control flow on the CPU backend at the "
                    "configuration's tiny preset; prints no device metric")
    ap.add_argument("--manifest", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--data-dir", action="append", default=[],
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    rehearsal = args.rehearse_on_cpu

    manifest = Manifest(args.manifest, args.data_dir)
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    chips = cell["chips"]
    if not os.path.isdir(os.path.join(ROOT, "intellillm_tpu")):
        raise HarnessError("the program (intellillm_tpu/) is not in this "
                           "checkout: nothing to measure")
    have = local_chips()
    if not rehearsal and have < chips:
        raise HarnessError(
            f"no accelerator: this host has {have} TPU chips, the "
            f"cell needs {chips} (a CPU rehearsal needs --rehearse-on-cpu)")
    if rehearsal and "rehearsal" in traffic:
        traffic.update(traffic["rehearsal"])

    trace = bool(args.trace)
    trace_dir = os.path.join(WORK_DIR, "trace", cell["name"])
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    with Server(config, args.seed, rehearsal=rehearsal, profiling=trace,
                boot_timeout_s=BOOT_TIMEOUT_S) as server:
        # While the server boots: the plan, its prompts, the probes.
        plan = make_plan(traffic, args.seed, args.seconds, server.vocab)
        probe_plan = probes.probe_plan(args.seed, server.vocab)
        emit({"event": "planned", "workload": cell["name"],
              "seed": args.seed, "loop": traffic["loop"],
              "requests": len(plan),
              "plan_s": time.perf_counter() - T_PROCESS})
        server.wait_healthy()
        boot = server.detail()["boot"]
        runtime = boot["runtime"]
        emit({"event": "booted",
              "wall_s": time.perf_counter() - T_PROCESS,
              "compile_cache_cap": server.cache_cap,
              "boot_phases_s": boot["phases_s"], "warmup": boot["warmup"],
              "cache": boot["cache"], "runtime": runtime})
        if not rehearsal:
            if runtime["platform"] != "tpu":
                raise HarnessError(f"the server ran on "
                                   f"{runtime['platform']}, not the TPU")
            if runtime["device_count"] != chips:
                raise HarnessError(
                    f"the server sees {runtime['device_count']} devices, "
                    f"the cell is for {chips}")
        try:
            m = asyncio.run(measure(server, traffic, plan, probe_plan,
                                    args.seed, args.seconds, trace,
                                    trace_dir))
        except BaseException:
            print(server.log_tail(), file=sys.stderr, flush=True)
            raise
    # -- the server has stopped; what follows is arithmetic ---------------
    reduced = None
    if trace:
        tr = reduce_trace(trace_dir)
        emit({"event": "trace", "describe": tr["describe"],
              "error": tr.get("error")})
        reduced = tr.get("reduced")
        if reduced:
            emit({"event": "trace_reduced",
                  **{k: reduced[k] for k in ("window_s", "busy_s", "op_s",
                                             "devices", "gap_labels")},
                  "modules": reduced["modules"][:20],
                  "idle_gaps": reduced["idle_gaps"][:20],
                  "op_kinds": reduced["op_kinds"][:40]})
        shutil.rmtree(trace_dir, ignore_errors=True)

    samples, window = m["samples"], m["window"]
    selection = m["final"]["selection"]
    loop = traffic["loop"]
    attempted, failed = stats.attempted_failed(samples, window, loop,
                                               m["cutoff_t"])
    ctx = {"prom_start": m["start"]["prom"], "prom_end": m["end"]["prom"],
           "detail_end": m["final"]["detail"], "trace": reduced,
           "samples": samples, "window": window, "chips": chips,
           "cutoff_t": m["cutoff_t"]}
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for name in manifest.metrics_of_cell(cell["name"], group):
        unit = getattr(manifest, group)[name]["unit"]
        if name == "setup_s":
            value = m["setup_s"]
        else:
            value = readers.read_metric(manifest.metric_file(name), ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}

    probe = probes.compare(m["probes_before"], m["probes_during"],
                           m["probes_after"])
    malformed = [s for s in samples if s.end_t is not None
                 and s.error is None and not s.well_formed]
    problems = list(probe["problems"])
    if malformed:
        problems.append(f"{len(malformed)} completed requests without "
                        f"exactly max_tokens tokens / finish 'length'")
    if attempted == 0:
        problems.append("no request was attempted in the window")
    if not rehearsal:
        problems += kernel_problems(m["final"]["detail"], selection)
    new_in_window = sorted(set(m["end"]["executables"])
                           - set(m["start"]["executables"]))
    lates = stats.late_values(samples, window)
    emit({"event": "window",
          "requests_due": len(stats.due_in_window(samples, window)),
          "requests_finished": len(stats.finished_in_window(samples,
                                                            window)),
          "tokens_in_window": stats.tokens_in_window(samples, window),
          "ttft_samples": len(stats.ttft_values(samples, window,
                                                m["cutoff_t"])),
          "tpot_samples": len(stats.tpot_values(samples, window)),
          "tpot_ms": tpot_summary(stats.tpot_values(samples, window)),
          "tpot_ms_whole_requests": tpot_summary(
              stats.tpot_values(samples, window, whole_requests=True)),
          "generator_late_max_ms": max(lates) * 1e3 if lates else None,
          "errors": sorted({s.error for s in samples if s.error})[:5],
          "executables_new_in_window": new_in_window,
          "queue_depths_end": m["end"]["detail"]["queue_depths"],
          "compile_time_seconds":
              m["final"]["detail"]["compiles"]["compile_time_seconds"],
          "compiles": m["final"]["detail"]["compiles"]["compiles"],
          "kernel_selection": selection})
    emit({"event": "probes", **probe})
    if problems:
        emit({"event": "incorrect", "problems": problems})

    mem = [v.get("peak_bytes") for v in
           m["final"]["detail"]["device_telemetry"]["devices"].values()]
    device = {"platform": runtime["platform"],
              "kind": runtime["device_kind"],
              "count": runtime["device_count"],
              "memory_peak_bytes": max((x for x in mem if x), default=0)}
    result = {"correct": not problems, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_reduce.breakdown(reduced)
    if rehearsal:
        # A CPU run's numbers never stand under a device metric's name.
        result["rehearsal_values"] = result.pop("metrics")
        result["metrics"] = {}
        result["rehearsal"] = True
    emit(result)
    return 0


def _terminated(signum, frame):
    raise SystemExit(128 + signum)      # unwinds through `with Server`


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, _terminated)
    try:
        sys.exit(main())
    except (HarnessError, ManifestError) as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
