"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Knows no cell, configuration, traffic mix or metric by name: a cell is
``workloads/<cell>.json`` -> ``configs/<config>.json`` + ``traffic/<mix>.json``
-> ``drivers/<driver>.py`` and ``reference/<file>.py``; a per-layer metric is
``metrics/<name>.json`` + ``metrics/<name>.py``. See README.md.

The last line of standard output is the result; everything else worth reading
goes to standard error. Fails (non-zero, no result) where JAX finds no TPU,
fewer chips than the cell asks for, or a ``device_kind`` not in peaks.json.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_PROCESS:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    cell = load_json("workloads", name + ".json")
    cell["name"] = name
    cell["config_name"], cell["traffic_name"] = cell["config"], cell["traffic"]
    cell["config"] = load_json("configs", cell["config_name"] + ".json")
    cell["traffic"] = load_json("traffic", cell["traffic_name"] + ".json")
    return cell


def load_metrics(cell_name: str) -> list:
    """Every per-layer metric whose files are there and whose ``workloads``
    (where given) lists this cell: [(description, reader function)]."""
    out = []
    mdir = os.path.join(HERE, "metrics")
    for fn in sorted(os.listdir(mdir)):
        if not fn.endswith(".json"):
            continue
        desc = load_json("metrics", fn)
        desc["name"] = fn[:-5]
        if "workloads" in desc and cell_name not in desc["workloads"]:
            continue
        spec = importlib.util.spec_from_file_location(
            "metric_" + desc["name"].replace(".", "_").replace("-", "_"),
            os.path.join(mdir, desc["name"] + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out.append((desc, mod.read))
    return out


def read_metrics(ctx: dict, readers: list) -> dict:
    """``{name: {"value", "unit"}}`` of the readers that found something to
    read. A share of a roofline or of the peak over 100 is printed as it was
    read, and named on standard error with the two numbers it was divided
    from: its operations are counted too high or its time leaves out part of
    the work, and the driver refuses a run that reads one over 105."""
    out = {}
    for desc, read in readers:
        value = read(ctx)
        if value is None:
            continue
        name = desc["name"]
        out[name] = {"value": value, "unit": desc["unit"]}
        if (desc["unit"] == "%" and ("roofline" in name or "mfu" in name)
                and value > 100):
            operands = getattr(value, "operands", {})
            log(f"IMPOSSIBLE SHARE: {name} reads {value:.6g} % of a bound it "
                f"cannot pass: " + (" over ".join(
                    f"{k} {v:.6g}" for k, v in operands.items())
                    or "its reader gives no operands"))
    return out


def find_devices(chips: int, platform: str = "tpu"):
    """The chips the cell asks for and their row of peaks.json, or an error:
    no fallback to another platform, no default peak."""
    import jax

    devs = [d for d in jax.devices() if d.platform == platform]
    if len(devs) < chips:
        raise SystemExit(f"cell asks for {chips} {platform} chip(s), JAX "
                         f"found {len(devs)}: {jax.devices()}")
    return devs[:chips], peak_for(devs[0].device_kind)


def peak_for(kind: str) -> dict:
    peaks = load_json("peaks.json")
    if kind not in peaks:
        raise SystemExit(f"device_kind {kind!r} is not in peaks.json "
                         f"({sorted(peaks)}): add its published peaks")
    return peaks[kind]


def device_peak_bytes(stats: dict) -> int:
    """Peak bytes one chip held: the allocator's peak of live buffers plus
    what the runtime reserved outside it for the programs' temporaries (the
    TPU runtime keeps a compiled program's scratch out of ``bytes_in_use``)."""
    reserved = stats.get("peak_bytes_reserved", stats.get("bytes_reserved", 0))
    return int(stats.get("peak_bytes_in_use", 0)) + int(reserved or 0)


class Monitor:
    """jax.monitoring events with the time they arrived: backend compiles
    and persistent-cache reads, for set-up and for the in-window count."""

    def __init__(self):
        import jax

        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        self.events.append((time.perf_counter(), event, duration))

    def between(self, t0, t1, suffix):
        return [d for t, e, d in self.events
                if t0 <= t <= t1 and e.endswith(suffix)]


def counters_snapshot() -> dict:
    """The program's registry, flattened: ``name{k=v,...}`` -> value, and for
    a histogram ``..._sum`` and ``..._count``."""
    from deeplearning4j_tpu.observability.metrics import global_registry

    flat = {}
    for name, fam in global_registry().snapshot().items():
        for s in fam["series"]:
            lab = ",".join(f"{k}={v}" for k, v in sorted(s["labels"].items()))
            key = f"{name}{{{lab}}}" if lab else name
            if fam["type"] == "histogram":
                flat[key + "_sum"], flat[key + "_count"] = s["sum"], s["count"]
            else:
                flat[key] = s["value"]
    return flat


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


class TraceSlice:
    """Profiles ``seconds`` of the window, starting ``delay`` into it, from a
    timer thread: the traced slice holds steady dispatches only."""

    def __init__(self, out_dir: str, delay: float, seconds: float):
        self.dir, self.delay, self.seconds = out_dir, delay, seconds
        self.t0 = self.t1 = None
        self.error = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-trace")

    def start(self):
        self._thread.start()

    def _run(self):
        import jax

        try:
            time.sleep(self.delay)
            # no runtime host events: at any level they include every chunk
            # of the host-side layout transposes, millions a second, which
            # slows the staging that is being measured; the Python tracer
            # says what the host was doing
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 0
            opts.python_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t0 = time.perf_counter()
            time.sleep(self.seconds)
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()
        except Exception as e:   # reported by the harness, which then fails
            self.error = e

    def join(self):
        self._thread.join()
        if self.error is not None:
            raise self.error

    def xplane(self):
        for root, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(root, f)
        raise FileNotFoundError(f"no .xplane.pb under {self.dir}")


class Tools:
    log = staticmethod(log)


def run(args, find=find_devices, driver_cls=None) -> int:
    """One run of one cell. ``find`` and ``driver_cls`` are seams for the
    tests (the look for a chip; a driver with the timed path broken)."""
    cell = load_cell(args.workload)
    # the compile cache lives in the checkout unless the machine names one
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    sys.path[:0] = [p for p in (ROOT, HERE) if p not in sys.path]
    import jax

    devices, peak = find(int(cell["chips"]))
    monitor = Monitor()
    if driver_cls is None:
        driver_cls = importlib.import_module(
            "drivers." + cell["traffic"]["driver"]).Driver
    driver = driver_cls(cell, args.seed, Tools)
    driver.setup()
    jax.effects_barrier()
    t_setup = time.perf_counter()
    setup_s = t_setup - T_PROCESS
    setup_counters = counters_snapshot()

    tracer = None
    if args.trace:
        tdir = os.path.join(ROOT, ".bench_out", "trace", cell["name"])
        shutil.rmtree(tdir, ignore_errors=True)
        slice_s = min(float(cell["traffic"].get("trace_seconds", 3.0)),
                      0.6 * args.seconds)
        tracer = TraceSlice(tdir, 0.25 * args.seconds, slice_s)
        tracer.start()
    win = driver.window(args.seconds)
    if tracer:
        tracer.join()
    counters = delta(counters_snapshot(), setup_counters)
    mem = [d.memory_stats() or {} for d in devices]
    log("memory_stats: " + json.dumps(mem[0]))
    peak_bytes = max((device_peak_bytes(m) for m in mem), default=0)

    compiles = monitor.between(win["t_start"], win["t_end"],
                               "backend_compile_duration")
    loads = monitor.between(win["t_start"], win["t_end"],
                            "cache_retrieval_time_sec")
    log(f"window {win['elapsed_s']:.3f}s, {win['steps']} steps in "
        f"{win['dispatches']} dispatches, final score {win['final_score']:.4f}")
    log(f"compiles inside the window: {len(compiles)} backend, {len(loads)} "
        f"cache loads" + ("   <-- NOT ZERO" if compiles or loads else ""))
    log("kernels engaged: " + json.dumps(
        {k: v for k, v in setup_counters.items() if "pallas_dispatch" in k}))
    log("compile cache: " + json.dumps(
        {k: round(v, 3) for k, v in setup_counters.items()
         if "compile_cache" in k and "bucket" not in k}))

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    metrics, breakdown = {}, None
    if args.trace:
        import trace_reduce

        t0 = time.perf_counter()
        trace = trace_reduce.reduce(tracer.xplane(), limit_s=tracer.seconds)
        log(f"trace reduced in {time.perf_counter() - t0:.1f}s: busy "
            f"{trace['busy_s']:.3f}s of {trace['window_s']:.3f}s, "
            f"{trace['dispatches']} dispatches of {trace['step_module']}, "
            f"longest gap between dispatches {trace['dispatch_gap_ms_max']} ms")
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        breakdown = trace["breakdown"]
        ctx = {"cell": cell, "window": win, "counters": counters,
               "setup_counters": setup_counters, "trace": trace, "peak": peak,
               "device": device, "setup_s": setup_s,
               "setup_events": [(e, d) for t, e, d in monitor.events
                                if t <= t_setup]}
        metrics = read_metrics(ctx, load_metrics(cell["name"]))
    else:
        for name, value in win["end_to_end"].items():
            metrics[name] = {"value": value, "unit": cell["traffic"]["units"][name]}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    # correct: once the window has closed, the peak has been read and the
    # program's state is freed, the plain reference follows the first dispatch
    import compare

    readings = driver.readings
    driver.release()
    t0 = time.perf_counter()
    ref = driver.reference()
    log(f"reference followed {len(ref['losses'])} steps in "
        f"{time.perf_counter() - t0:.1f}s, losses "
        + " ".join(f"{l:.4f}" for l in ref["losses"]))
    ok, report = compare.decide(readings, ref, cell.get("limits", {}))
    ok = ok and win["failed"] == 0 and not compiles
    result = {"correct": bool(ok), "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = report
    for name, r in report.items():
        log(f"compared {name}: {r['value']:.6g} limit {r['limit']} at {r['at']}")
    log(f"correct: {ok}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
