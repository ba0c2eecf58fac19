"""One cell, one run, one process.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  Everything that belongs to one cell is data
found by name (``BENCHMARK.json`` -> ``configs/``, ``traffic/``,
``layer_metrics/``, ``reducers/``, ``drivers/``); this file has no branch
on a workload's name.  The last line of standard output is the result
object; whatever else is worth keeping goes on earlier lines, standard
error or ``chipbench/out/``.

It exits non-zero with no result line when the program is not in the
checkout, when JAX's first device is not a TPU, when there are fewer chips
than the cell asks for, or when the device kind has no row in
``peaks.py``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()          # set-up is counted from here

import argparse                   # noqa: E402
import gc                         # noqa: E402
import importlib                  # noqa: E402
import json                       # noqa: E402
import os                         # noqa: E402
import shutil                     # noqa: E402
import sys                        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(*a) -> None:
    print("[chipbench]", *a, file=sys.stderr, flush=True)


# -- the manifest -------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(manifest: dict, workload: str, root: str = ROOT) -> dict:
    """Everything one cell is made of, each piece found by its name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    bench_dir = os.path.join(root, manifest["paths"][0])
    reports = lambda m: cell["name"] in m.get(
        "workloads", [w["name"] for w in manifest["workloads"]])
    layer_metrics = []
    for m in manifest["per_layer"]:
        if reports(m):
            spec = load_json(os.path.join(
                bench_dir, "layer_metrics", m["name"] + ".json"))
            layer_metrics.append(spec)
    return {
        "cell": cell,
        "config": load_json(os.path.join(root, cfg_entry["file"])),
        "traffic": load_json(os.path.join(
            bench_dir, "traffic", cell["traffic"] + ".json")),
        "end_to_end": [m for m in manifest["end_to_end"] if reports(m)],
        "layer_metrics": layer_metrics,
    }


# -- compile clock (after chip_smoke.py's _CompileClock) ----------------------

class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from
    the persistent cache), and how many backend compiles it made, from
    jax.monitoring's duration events."""

    def __init__(self) -> None:
        import jax
        self.total = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration
            if event.endswith("backend_compile_duration"):
                self.compiles += 1


# -- what a driver is handed --------------------------------------------------

class Run:
    """The harness's side of a run: the window's clock, the profiler and
    the counters.  A driver calls ``open_window`` when set-up is done,
    ``poll`` between units of work, and ``close_window`` after its fence."""

    def __init__(self, parts: dict, seed: int, seconds: float, trace: bool,
                 devices, out_dir: str) -> None:
        self.__dict__.update(parts)
        self.seed, self.seconds, self.trace = seed, float(seconds), trace
        self.devices = devices
        self.out_dir = out_dir
        self.clock = CompileClock()
        self.counters: dict = {}
        self.marks: list = []
        self.controls: dict = {}          # --control 1: what each read
        self.t_open = self.t_close = None
        self._profile = "off"
        self.profile_span = None          # (start, end) on perf_counter
        plan = self.traffic.get("profile", {})
        self._p_start = float(plan.get("start_s", 2.0))
        self._p_len = float(plan.get("seconds", 3.0))

    def mark(self, what: str) -> None:
        """A point of set-up, in seconds since the process started."""
        self.marks.append((what, round(time.perf_counter() - T0, 2)))

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation("chipbench." + name)

    def open_window(self) -> float:
        self.counters["compile_s"] = self.clock.total
        self._compiles_at_open = self.clock.compiles
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - T0
        log(f"window opens after {self.setup_s:.2f} s of set-up "
            f"({self.clock.total:.2f} s compiling or loading programs); "
            f"reached at: {self.marks}")
        return self.t_open

    def poll(self) -> float:
        """Start and stop the profiler at its planned times; returns now."""
        now = time.perf_counter()
        if not self.trace:
            return now
        import jax
        at = now - self.t_open
        if self._profile == "off" and at >= self._p_start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self.trace_dir = os.path.join(self.out_dir, "trace")
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._profile, self._p_t0 = "on", time.perf_counter()
        elif self._profile == "on" and now - self._p_t0 >= self._p_len:
            self.stop_profile()
        return time.perf_counter()

    def stop_profile(self) -> None:
        if self._profile != "on":
            return
        import jax
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self._profile = "done"
        self.profile_span = (self._p_t0, t1)
        log(f"profiled {t1 - self._p_t0:.2f} s of the window; writing the "
            f"trace took {time.perf_counter() - t1:.2f} s")

    def close_window(self) -> float:
        self.stop_profile()
        self.t_close = time.perf_counter()
        self.counters["compiles_in_window"] = \
            self.clock.compiles - self._compiles_at_open
        return self.t_close - self.t_open


# -- one run ------------------------------------------------------------------

def find_devices(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX's first device is "
                         f"{devices[0].platform!r}, not a TPU; nothing ran")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devices)}")
    return devices[:chips]


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return max((p for p in peaks if p is not None), default=None)


def execute(manifest: dict, workload: str, seed: int, seconds: float,
            trace: bool, *, root: str = ROOT, require_tpu: bool = True,
            control: bool = False) -> dict:
    """Drive one cell once and return the result object."""
    from . import peaks as peaks_mod, trace as trace_mod

    parts = load_cell(manifest, workload, root)
    cell = parts["cell"]
    devices = find_devices(cell["chips"], require_tpu)
    kind = devices[0].device_kind
    peaks = peaks_mod.peaks_for(kind) if require_tpu else \
        peaks_mod.PEAKS.get(kind, peaks_mod.PEAKS["TPU v5 lite"])
    out_dir = os.path.join(root, manifest["paths"][0], "out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)

    run = Run(parts, seed, seconds, trace, devices, out_dir)
    run.peaks = peaks
    run.mark("devices")
    driver = importlib.import_module(
        "chipbench.drivers." + parts["traffic"]["driver"])
    res = driver.run(run)
    # res: attempted, failed, end_to_end{}, counters{}, notes[], release(),
    # check(control) -> [(name, value, limit)]

    peak_bytes = memory_peak(devices)
    res["release"]()
    gc.collect()
    checks = res["check"](control)
    correct = all(v <= lim for _, v, lim in checks)
    run.counters.update(res.get("counters", {}), profile_span=run.profile_span)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    values = dict(res["end_to_end"], setup_s=run.setup_s)
    out = {"correct": bool(correct), "attempted": res["attempted"],
           "failed": res["failed"]}
    if trace:
        red = trace_mod.reduce_run(run)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = red["breakdown"]
        for note in red["notes"]:
            log(note)
        metrics = {}
        for spec in parts["layer_metrics"]:
            reducer = importlib.import_module(
                "chipbench.reducers." + spec["reducer"])
            value = reducer.reduce(red, run.counters, dict(
                parts, spec=spec, peaks=peaks, chips=len(devices)))
            if value is not None:
                metrics[spec["name"]] = {"value": value,
                                         "unit": spec["unit"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in parts["end_to_end"]}
    out["metrics"] = metrics
    out["device"] = device
    for note in res.get("notes", []):
        log(note() if callable(note) else note)
    if run.controls:
        out["controls"] = run.controls
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, v, lim in checks:
        log(f"check {n}: {v:.6g} (limit {lim:.6g})"
            f"{'' if v <= lim else '  <-- over'}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control and the "
                         "planted faults (never set by the driver)")
    args = ap.parse_args(argv)

    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if not os.path.isdir(os.path.join(ROOT, "horovod_tpu")):
        raise SystemExit("chipbench: the program (horovod_tpu/) is not in "
                         "this checkout; nothing ran")
    sys.path.insert(0, ROOT)
    import horovod_tpu
    if os.path.dirname(os.path.dirname(
            os.path.abspath(horovod_tpu.__file__))) != ROOT:
        raise SystemExit("chipbench: horovod_tpu was imported from "
                         f"{horovod_tpu.__file__}, not from this checkout")
    import jax
    # Every program, however quick to compile, goes to the persistent
    # cache, so that only a checkout's first run of a cell compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from horovod_tpu.utils.compile_cache import ensure_compile_cache
    log("compile cache:", ensure_compile_cache())

    out = execute(manifest, args.workload, args.seed, args.seconds,
                  bool(args.trace), control=bool(args.control))
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
