"""One run of one benchmark cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Boots the real server with the cell's configuration and weights made from
the seed, lets a load generator in a child process drive it over HTTP and
websockets for ``--seconds`` seconds, checks what the window returned
against the plain reference, and prints one JSON object as its last line.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the last seconds of the same window and reports the per-layer metrics.
Without a TPU it exits 1 and prints no result.

This file holds what is true of every cell: the phases, the generator
child, the window's rules, counters before and after, spans, the profiler,
the result line. What a cell needs of its architecture (assets, schedule,
weights, the server, the unit of work and its cost, the check) it asks of
the *family* the cell's configuration names (``benchmark/families/``).

Four flags are for the builder, never for the driver:
``--rehearsal`` runs the same phases on the CPU at the tiny size of
``benchmark/tests/tiny.<family>.json``; its result says ``"platform":
"cpu"`` and ``"rehearsal": true`` and is no measurement. ``--control
<precision>`` puts the reference, computed in a precision below the one the
configuration states (``fp8`` under bfloat16), in the program's place in
the comparison: ``correct`` has to come out false. ``--override`` merges
JSON over the cell's files, for a sweep (the knee of a new traffic mix).
``--manifest`` names another manifest than ``BENCHMARK.json``, whose own
directories are searched first (``benchmark/tests/stub``).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.spec import (  # noqa: E402
    BENCH_DIR,
    CACHE_DIR,
    MANIFEST,
    Spec,
    peaks_for,
)
from benchmark.reduce import readers  # noqa: E402
from benchmark.reduce import trace as trace_reduce  # noqa: E402

TRACE_SECONDS = 6.0     # the traced part: the window's last seconds
TRACE_LEAD_S = 1.5      # the profiler is started this long before it
GRACE_S = 60.0          # how long after the window a frame may still come


def say(message: str) -> None:
    print(message, flush=True)


TIMELINE: dict = {}


def tick(name: str) -> None:
    """Seconds since the process began, by phase: where a run's wall time
    went (the result carries it as ``timeline``)."""
    TIMELINE[name] = round(time.monotonic() - T_PROCESS_START, 3)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        out[key] = (merge(out[key], value)
                    if isinstance(value, dict) and isinstance(out.get(key),
                                                              dict)
                    else value)
    return out


class Generator:
    """The child process that plays the clients (``harness/loadgen.py``)."""

    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "harness", "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def ask(self, **cmd) -> dict:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()
        line = self.proc.stdout.readline()
        if not line:
            raise SystemExit("the load generator died "
                             f"(exit {self.proc.poll()})")
        reply = json.loads(line)
        if not reply.get("ok"):
            raise SystemExit(f"the load generator refused: {reply}")
        return reply

    def close(self) -> None:
        try:
            if self.proc.poll() is None:
                self.proc.stdin.write(json.dumps({"cmd": "quit"}) + "\n")
                self.proc.stdin.close()
            self.proc.wait(timeout=15)
        except (OSError, subprocess.TimeoutExpired):
            self.proc.kill()
            self.proc.wait()


def sleep_until(t: float) -> None:
    left = t - time.monotonic()
    if left > 0:
        time.sleep(left)


def read_stamps(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return {s["i"]: s for s in map(json.loads, f)}


def counters_now(obs) -> dict:
    return {inst.name: float(sum(inst.collect().values()))
            for inst in obs.REGISTRY.instruments() if inst.kind == "counter"}


def histograms_since(obs, window_s: float) -> dict:
    out = {}
    for inst in obs.REGISTRY.instruments():
        if inst.kind != "histogram":
            continue
        series = {}
        for labels in inst.series_counts():
            named = dict(zip(inst.labelnames, labels))
            series[labels] = inst.window_samples(window_s, **named)
        out[inst.name] = series
    return out


class SpanPoller(threading.Thread):
    """Copies the program tracer's ring out before it wraps (the ring holds
    4096 spans; a window makes many more)."""

    def __init__(self, tracer):
        super().__init__(daemon=True, name="bench-span-poller")
        self.tracer, self.seen, self.stop = tracer, {}, threading.Event()

    def run(self) -> None:
        while not self.stop.wait(0.5):
            self.poll()

    def poll(self) -> None:
        for s in self.tracer.spans():
            self.seen[s.span_id] = (s.name, s.start_s, s.dur_s)

    def finish(self) -> list:
        self.stop.set()
        self.join(timeout=5)
        self.poll()
        return list(self.seen.values())


def start_profiler(jax, trace_dir: str) -> None:
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # host Python frames: not read here
        options.host_tracer_level = 2     # TraceAnnotation marks are
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    except (AttributeError, TypeError):
        jax.profiler.start_trace(trace_dir)


def mark(jax, name: str, marks: dict) -> None:
    marks[name] = time.monotonic()
    with jax.profiler.TraceAnnotation(trace_reduce.MARK_PREFIX + name):
        time.sleep(0.0005)


def window_numbers(sched: dict, stamps: dict, t0: float, seconds: float,
                   wait_end: float) -> dict:
    """What the window's requests say: attempted, failed, latencies (ms) and
    rows (the family's unit of work) answered, by the rules in PERF.md
    section 2."""
    requests = sched["requests"]
    rows_answered = 0
    latencies, window_ids, failed, lost_before = [], [], 0, 0
    for r in requests:
        s = stamps.get(r["i"])
        if s is None:
            continue
        if sched["arrivals"] == "open":
            in_window = r["due"] >= 0.0
            start = t0 + r["due"]
        else:
            in_window = t0 <= s["send"] < t0 + seconds
            start = s["send"]
        answered = s.get("status") == 200 and "recv" in s
        if answered and t0 <= s["recv"] < t0 + seconds:
            rows_answered += r["rows"]
        if not in_window:
            # The warm phase is not judged, but a frame lost there is said.
            lost_before += s.get("status") == 200 and not answered
            continue
        window_ids.append(r["i"])
        if answered:
            latencies.append((s["recv"] - start) * 1e3)
        else:
            failed += 1
            latencies.append((wait_end - start) * 1e3)
    return {"attempted": len(window_ids), "failed": failed,
            "latencies_ms": latencies, "window_ids": window_ids,
            "rows_per_s": rows_answered / seconds,
            "lost_before_window": int(lost_before)}


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--control", default=None, metavar="PRECISION",
                    help="builder only: the reference in this lower "
                         "precision (fp8) takes the program's place")
    ap.add_argument("--override", type=json.loads, default={},
                    help="builder's sweeps only: JSON merged over the files, "
                         '{"config": {...}, "traffic": {...}}')
    ap.add_argument("--manifest", default=MANIFEST,
                    help="builder only: another manifest than "
                         "BENCHMARK.json")
    return ap.parse_args(argv)


def cell_files(args, spec: Spec) -> tuple:
    """(configuration, traffic mix, the comparison's limits) as this run
    uses them."""
    config, traffic_file, limits = spec.config, spec.traffic, spec.limits
    if args.rehearsal:
        tiny = spec.rehearsal_sizes()
        config = merge(config, tiny["config"])
        traffic_file = merge(traffic_file, tiny["traffic"])
        limits = merge(limits, tiny["limits"])
    return (merge(config, args.override.get("config", {})),
            merge(traffic_file, args.override.get("traffic", {})), limits)


def find_device(jax, args, spec: Spec) -> tuple:
    """(first device, what the result says of it); no chip, no run."""
    if args.rehearsal:
        jax.config.update("jax_platforms", "cpu")
    else:
        from vilbert_multitask_tpu.config import require_tpu

        require_tpu("benchmark/run.py")
        if jax.device_count() < spec.cell["chips"]:
            raise SystemExit(f"{args.workload} needs {spec.cell['chips']} "
                             f"chips; JAX has {jax.device_count()}")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    say(f"device: {device}  jax {jax.__version__}")
    return dev, device


def set_up(jax, args, family, config, traffic_file, generator,
           state_dir) -> dict:
    """Everything before the first timed request, each step the family's:
    its assets on disk, the schedule, weights from the seed, and the server
    booted and warmed for this cell's traffic. Returns what the window and
    the check need."""
    assets, phases = family.assets(config, traffic_file, CACHE_DIR)
    sched = family.schedule(traffic_file, args.seed, args.seconds, assets)
    with open(os.path.join(state_dir, "schedule.json"), "w",
              encoding="utf-8") as f:
        json.dump(sched, f)

    from vilbert_multitask_tpu.engine import cachedir

    # Before the first compile: every program of a run after the
    # checkout's first comes out of the persistent cache.
    cachedir.enable_compilation_cache()
    t = time.monotonic()
    params, n_params = family.weights(config, args.seed)
    jax.block_until_ready(params)
    phases["weights_s"] = time.monotonic() - t
    say(f"parameters: {n_params}")
    app, boot_phases = family.boot(config, traffic_file, params, assets,
                                   state_dir, args.rehearsal)
    phases.update(boot_phases)
    try:
        generator.ask(cmd="connect", http_port=app.http_port,
                      ws_port=app.ws.bound_port,
                      sockets=traffic_file["sockets"])
    except BaseException:
        app.stop()
        raise
    return {"app": app, "params": params, "sched": sched,
            "assets": assets, "phases": phases}


def drive_window(jax, args, family, traffic_file, generator, app, sched,
                 state_dir, trace_dir) -> dict:
    """The warm phase and the window: the generator sends, this process
    only watches (counters before and after, the tracer's spans, and with
    ``--trace 1`` the profiler over the window's last seconds)."""
    from vilbert_multitask_tpu import obs

    t0 = time.monotonic() + 0.25 + sched["warm_seconds"]
    t_end = t0 + args.seconds
    answer = {}
    worker = threading.Thread(
        target=lambda: answer.update(generator.ask(
            cmd="run", t0=t0, threads=traffic_file["sender_threads"],
            schedule=os.path.join(state_dir, "schedule.json"),
            out=os.path.join(state_dir, "window.stamps"),
            grace_s=GRACE_S)),
        name="bench-generator-wait")
    worker.start()
    sleep_until(t0)
    tick("window_start")
    seen = {"t0": t0, "t_end": t_end, "setup_s": t0 - T_PROCESS_START,
            "before": counters_now(obs), "marks": {}, "spans": [],
            "histograms": {}, "units_in_trace": 0.0}
    traced = args.trace == 1
    poller = None
    if traced:
        poller = SpanPoller(obs.default_tracer())
        poller.start()
    if traced:
        trace_len = min(TRACE_SECONDS, args.seconds)
        sleep_until(t_end - trace_len - TRACE_LEAD_S)
        start_profiler(jax, trace_dir)
        sleep_until(t_end - trace_len)
        mark(jax, "start", seen["marks"])
    sleep_until(t_end)
    tick("window_end")
    if traced:
        mark(jax, "end", seen["marks"])
        seen["units_in_trace"] = family.units_since(app,
                                                    seen["marks"]["start"])
        jax.profiler.stop_trace()
    seen["after"] = counters_now(obs)
    if poller:
        seen["histograms"] = histograms_since(obs, time.monotonic() - t0)
    worker.join()
    if not answer.get("ok"):
        raise SystemExit("the load generator gave no answer")
    seen["wait_end"] = time.monotonic()
    tick("last_frame_waited_for")
    if poller:
        seen["spans"] = poller.finish()
    return seen


def read_memory(dev, family, app, config) -> int:
    """Prints the device's memory as the window left it; returns the peak.
    ``written_share`` leaves out what the family says the device holds
    reserved and unwritten: reserved, not held."""
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    limit = stats.get("bytes_limit") or 0
    in_use = stats.get("bytes_in_use", 0)
    unwritten, details = family.unwritten_bytes(app, config)
    say("memory_stats: " + json.dumps({
        "bytes_in_use": in_use, "peak_bytes_in_use": peak,
        "bytes_limit": limit, **details,
        "resident_share": in_use / limit if limit else None,
        "written_share": (in_use - unwritten) / limit if limit else None,
        "peak_share": peak / limit if limit else None}))
    return peak


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, the compared numbers each beside its limit)."""
    beside = {name: {"value": numbers[name], "limit": limits[name]}
              for name in limits}
    ok = all(numbers[name] <= limits[name] and numbers["compared"] > 0
             for name in limits)
    return ok, beside


def judge(args, family, config, limits, up, stamps, window_ids,
          compiles) -> tuple:
    """(correct, each compared number beside its limit): a sample of the
    window's answered requests against the reference. The compared numbers
    are the keys of the cell's limits file; the family's ``compare`` gives
    each, with ``compared`` (how many requests) and ``unanswered``."""
    picked = family.sample(
        [r for r in up["sched"]["requests"] if r["i"] in window_ids],
        {i: s for i, s in stamps.items() if "recv" in s},
        args.seed, limits["requests"])
    t = time.monotonic()
    outputs = family.run_reference(config, up["params"], picked,
                                   up["assets"])
    compared = family.compare(picked, stamps, outputs)
    say(f"reference: {len(picked)} requests in "
        f"{time.monotonic() - t:.1f}s; " + json.dumps(
            {k: v for k, v in compared.items()
             if k not in limits["limits"]}))
    # A frame that never came, however long it was waited for, is a wrong
    # answer; a refused submit is only a failed request.
    compared["unanswered"] += sum(
        1 for i in window_ids
        if stamps[i].get("status") == 200 and "recv" not in stamps[i])
    if args.control:
        say("program: " + json.dumps(
            {k: compared[k] for k in limits["limits"]}))
        lower = family.run_reference(config, up["params"], picked,
                                     up["assets"], lower=args.control)
        compared = family.compare(
            picked, {r["i"]: {"result": family.frame_of(r, out)}
                     for r, out in zip(picked, lower)}, outputs)
    correct, beside = verdict(compared, limits["limits"])
    beside["compiles_in_window"] = {"value": compiles, "limit": 0}
    return correct and not compiles, beside


def device_trace(args, trace_dir, seen) -> tuple:
    """(the readers' ``trace`` context, the result's ``breakdown``) from the
    profiler's file, on the busiest device, between the run's two marks."""
    xplane = trace_reduce.find_xplane(trace_dir)
    planes = trace_reduce.read_xplane(xplane)
    marks = seen["marks"]
    offset = trace_reduce.clock_offset(planes["marks"], marks)
    a, b = marks["start"] + offset, marks["end"] + offset
    busiest = max(planes["devices"].values(),
                  key=lambda d: trace_reduce.busy_and_gaps(d["ops"], a, b)[0])
    busy, gaps = trace_reduce.busy_and_gaps(busiest["ops"], a, b)
    context = {"ops": trace_reduce.clip(busiest["ops"], a, b),
               "modules": trace_reduce.clip(busiest["modules"], a, b),
               "busy_s": busy, "window_s": marks["end"] - marks["start"]}
    breakdown = {
        "device_ops": trace_reduce.top_events(busiest["ops"], a, b),
        "idle_gaps": trace_reduce.attribute_gaps(
            gaps, [(n, s + offset, d) for n, s, d in seen["spans"]])}
    return context, breakdown


def main(argv=None) -> int:
    args = parse_args(argv)
    spec = Spec(args.workload, args.manifest)
    family = spec.family
    config, traffic_file, limits = cell_files(args, spec)
    traced = args.trace == 1

    # The generator first: a child started before JAX is touched.
    generator = Generator()
    app = None
    state_dir = os.path.join(CACHE_DIR, "state")
    trace_dir = os.path.join(CACHE_DIR, "trace")
    for d in (state_dir, trace_dir):
        shutil.rmtree(d, ignore_errors=True)
    os.makedirs(state_dir)
    try:
        import jax

        dev, device = find_device(jax, args, spec)
        tick("device_found")
        peaks = None if args.rehearsal else peaks_for(dev.device_kind)
        up = set_up(jax, args, family, config, traffic_file, generator,
                    state_dir)
        app = up["app"]
        tick("server_ready")
        seen = drive_window(jax, args, family, traffic_file, generator, app,
                            up["sched"], state_dir, trace_dir)
        compiles = (seen["after"].get("vmt_engine_compiles_total", 0.0)
                    - seen["before"].get("vmt_engine_compiles_total", 0.0))
        device["memory_peak_bytes"] = read_memory(dev, family, app, config)

        # The program is done: stop it and free its state.
        stamps = read_stamps(os.path.join(state_dir, "window.stamps"))
        app.stop()
        app = None
        generator.close()
        gc.collect()
        tick("server_stopped")

        numbers = window_numbers(up["sched"], stamps, seen["t0"],
                                 args.seconds, seen["wait_end"])
        correct, beside = judge(args, family, config, limits, up, stamps,
                                set(numbers["window_ids"]), compiles)
        tick("reference_done")

        lat = numbers["latencies_ms"]
        values = {"setup_s": seen["setup_s"],
                  "latency_p50_ms": readers.percentile(lat, 50),
                  "rows_per_s": numbers["rows_per_s"]}
        say("window: " + json.dumps(values))  # in a traced run too
        metrics, breakdown = {}, None
        if traced:
            ctx = {
                "spans": [s for s in seen["spans"]
                          if seen["t0"] <= s[1] < seen["t_end"]],
                "histograms": seen["histograms"],
                "counters": {"before": seen["before"],
                             "after": seen["after"]},
                "stamps": [stamps[i] for i in numbers["window_ids"]],
                "setup": dict(up["phases"], setup_s=seen["setup_s"]),
                "seconds": args.seconds,
                "flops_per_unit": family.flops_per_unit(config),
                "peaks": peaks, "units_in_trace": seen["units_in_trace"],
            }
            if not args.rehearsal:
                ctx["trace"], breakdown = device_trace(args, trace_dir, seen)
                device["busy_s"] = ctx["trace"]["busy_s"]
                device["window_s"] = ctx["trace"]["window_s"]
            for entry, reader in spec.per_layer():
                value = readers.read(reader, ctx)
                if value is not None:
                    metrics[entry["name"]] = {"value": value,
                                              "unit": entry["unit"]}
        else:
            metrics = {e["name"]: {"value": values[e["name"]],
                                   "unit": e["unit"]}
                       for e in spec.end_to_end()}

        result = {"correct": bool(correct),
                  "attempted": numbers["attempted"],
                  "failed": numbers["failed"], "metrics": metrics,
                  "device": device}
        if breakdown:
            result["breakdown"] = breakdown
        result["lost_before_window"] = numbers["lost_before_window"]
        if args.rehearsal:
            result["rehearsal"] = True
        if args.control:
            result["control"] = args.control
        if args.override:
            result["override"] = args.override
        result["setup_phases"] = up["phases"]
        tick("trace_reduced")
        result["timeline"] = TIMELINE
        result["compared"] = beside
        shutil.rmtree(trace_dir, ignore_errors=True)
        shutil.rmtree(state_dir, ignore_errors=True)
        for name, pair in beside.items():
            print(f"compared {name}: {pair['value']} (limit "
                  f"{pair['limit']})", file=sys.stderr)
        print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
        say(json.dumps(result))
        return 0
    finally:
        if app is not None:
            app.stop()
        generator.close()


if __name__ == "__main__":
    sys.exit(main())
