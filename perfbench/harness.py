"""The benchmark harness: the run of one cell, with every part of the cell
found by name in files of its own.

A cell of BENCHMARK.json names a configuration and a traffic mix. From
those names the harness loads:

  * the configuration file (`file` in BENCHMARK.json), whose `reference`
    key names the plain reference model, `references/<name>.py`;
  * the traffic mix, `traffic/<name>.json`, whose `runner` key names the
    general runner that drives that kind of traffic, `runners/<name>.py`
    (a class `Runner`);
  * the correctness limits, `limits/<cell>.json`;
  * one reader per per-layer metric, `metrics/<metric>.py` (`read(ctx)`).

A later cell of a new kind brings its runner, reference, mix and readers
as new files; this module stays as it is.

Order of a run: set-up (imports, device, inputs, warm-up of every shape
the window uses), the measured window, the reading of device memory, the
release of the program's state, then the comparison with the plain
reference and, with `--trace 1`, the reading of the profiler trace. The
result is one JSON line, last on standard output; the numbers compared
are printed beside their limits as the last lines of standard error and
under the result's last key, `checks`.

A runner is built as `Runner(spec, devices, seed)` and has `setup()`,
`window(seconds, profiler) -> {"window_s", "lane_intervals", "steps",
"slowest_step_ms"}` (plus `"end_to_end"`, the runner's own end-to-end
values by name; a step is a call or a tick),
`release()`, `check(limits, control=None) -> {"numbers", "answers",
"off", "worst"}` and `layer_context() -> dict` (what the metric readers
read besides the trace).
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".perfbench_cache"
# Length of the profiled part of a --trace 1 window (the whole window
# still runs; only this much of it is traced).
TRACE_SECONDS = 2.0


class NoAccelerator(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# Lookup by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


_MODULES: dict = {}


def load_module(kind: str, name: str):
    """`<kind>/<name>.py` under the benchmark's directory, loaded once."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} ({path})")
    mod_name = f"perfbench_{kind}_" + "".join(
        c if c.isalnum() else "_" for c in name)
    if mod_name not in _MODULES:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        _MODULES[mod_name] = mod
    return _MODULES[mod_name]


def load_cell(name: str, bench_path: Optional[Path] = None) -> dict:
    """The cell `name` with its configuration, traffic mix, limits, plain
    reference and metrics, found by the names BENCHMARK.json gives."""
    if str(BENCH_DIR) not in sys.path:
        sys.path.insert(0, str(BENCH_DIR))
    bench = load_json(bench_path or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    mix = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    reference = load_module("references", config["reference"])
    reference.check_config(config)
    return {"cell": cell, "config": config, "mix": mix,
            "reference": reference,
            "limits": load_json(BENCH_DIR / "limits" / f"{name}.json"),
            "end_to_end": [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])],
            "per_layer": [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]}


def seed_key_words(seed: int) -> np.ndarray:
    """The run's root PRNG key (raw threefry words) from any whole seed."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


# ---------------------------------------------------------------------------
# JAX, the device and compile accounting
# ---------------------------------------------------------------------------

class CompileClock:
    """Sums the backend compile durations JAX reports (XLA and Mosaic)
    and counts the compilations and the persistent-cache hits."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._hit)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def _hit(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


_CLOCK: Optional[CompileClock] = None


def start_jax():
    """Import JAX with the benchmark's compile cache, a fixed directory
    inside the checkout; returns (jax, the process's CompileClock)."""
    global _CLOCK
    cache = CACHE_DIR / "jax"
    cache.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if _CLOCK is None:
        _CLOCK = CompileClock(jax)
    return jax, _CLOCK


def require_accelerator(jax, chips: int) -> list:
    """The first `chips` TPU devices; raises when there are not enough."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(f"JAX found no device: {e}") from None
    if not devices or devices[0].platform != "tpu":
        raise NoAccelerator(
            f"JAX found no TPU (default backend "
            f"{devices[0].platform if devices else 'none'}); this benchmark "
            f"measures the accelerator only")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices[:chips]


# ---------------------------------------------------------------------------
# Shared by the runners
# ---------------------------------------------------------------------------

def sim_config(config: dict):
    """The program's SimConfig for a configuration file."""
    from repro.core.constants import NetworkConfig
    from repro.core.gateway_controller import ControllerConfig
    from repro.core.simulator import Arch, SimConfig

    net = NetworkConfig(
        n_chiplets=config["n_chiplets"], mesh_x=config["mesh_x"],
        mesh_y=config["mesh_y"],
        max_gateways_per_chiplet=config["max_gateways_per_chiplet"],
        memory_gateways=config["memory_gateways"],
        gateway_buffer_flits=config["gateway_buffer_flits"],
        router_buffer_flits=config["router_buffer_flits"],
        noc_freq_ghz=config["noc_freq_ghz"],
        link_gbps_per_wavelength=config["link_gbps_per_wavelength"],
        flit_bits=config["flit_bits"], packet_flits=config["packet_flits"],
        reconfig_interval_cycles=config["reconfig_interval_cycles"],
        sim_cycles=config["sim_cycles"],
        router_pitch_mm=config["router_pitch_mm"])
    ctl = ControllerConfig(l_m=config["l_m"],
                           max_gateways=config["max_gateways"],
                           min_gateways=config["min_gateways"])
    return SimConfig(arch=Arch(config["arch"]), cfg=net, ctl=ctl,
                     wavelengths=config["wavelengths"])


def n_intervals(config: dict) -> int:
    return config["sim_cycles"] // config["reconfig_interval_cycles"]


class Profiler:
    """Profiles the first TRACE_SECONDS of a window (or nothing)."""

    def __init__(self, directory: Optional[Path]):
        self.dir = directory
        self.on = False
        self.done = directory is None
        self.window_span = None

    def update(self, elapsed: float) -> None:
        import jax
        if self.done:
            return
        if not self.on:
            if self.dir.exists():
                shutil.rmtree(self.dir)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.window_span = jax.profiler.TraceAnnotation("bench.window")
            self.window_span.__enter__()
            self.on = True
        elif elapsed >= TRACE_SECONDS:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.on:
            self.window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False
            self.done = True

    @property
    def active(self) -> bool:
        return self.on

    def annotate(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name) if self.on \
            else contextlib.nullcontext()


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def parse_args(argv):
    ap = argparse.ArgumentParser(prog="perfbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_record(jax, devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             process_t0: float, control: Optional[str] = None) -> dict:
    """One run of cell `name`; returns the result dict (the JSON line).
    `control` puts the plain reference at that precision in the
    program's place (perfbench/control.py; the benchmark's own runs
    never do)."""
    spec = load_cell(name)
    jax, clock = start_jax()
    compiles0, compile_s0, hits0 = clock.count, clock.seconds, clock.hits
    marks = [("imports", time.perf_counter())]
    devices = require_accelerator(jax, spec["cell"]["chips"])
    marks.append(("device", time.perf_counter()))
    from repro.core.simulator import engine_stats

    runner = load_module("runners", spec["mix"]["runner"]).Runner(
        spec, devices, seed)
    marks.append(("inputs", time.perf_counter()))
    runner.setup()
    marks.append(("warm-up", time.perf_counter()))
    setup_s = time.perf_counter() - process_t0
    setup_compiles = clock.count
    stats0 = engine_stats()
    trace_dir = CACHE_DIR / "trace" / name if trace else None
    win = runner.window(seconds, Profiler(trace_dir))
    window_compiles = clock.count - setup_compiles
    retraces = engine_stats()["simulate_traces"] - stats0["simulate_traces"]
    dev = device_record(jax, devices)
    runner.release()
    prev, phases = process_t0, []
    for what, t in marks:
        phases.append(f"{what}={t - prev:.3f}")
        prev = t
    say(f"[setup] setup_s={setup_s:.3f} ({' '.join(phases)}) "
        f"compiles={setup_compiles - compiles0} "
        f"compile_s={clock.seconds - compile_s0:.3f} "
        f"cache_hits={clock.hits - hits0}")
    say(f"[window] window_s={win['window_s']:.3f} steps={win['steps']} "
        f"slowest_step_ms={win['slowest_step_ms']:.3f} "
        f"compiles_in_window={window_compiles} "
        f"scan_retraces_in_window={retraces}")
    check = runner.check(spec["limits"], control=control)
    correct = all(v <= lim for v, lim in check["numbers"].values())

    metrics = {}
    breakdown = None
    if not trace:
        values = {"setup_s": setup_s,
                  "lane_intervals_per_s": win["lane_intervals"]
                  / win["window_s"]}
        values.update(win.get("end_to_end", {}))
        for m in spec["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        import trace_reduce
        red = trace_reduce.reduce_dir(trace_dir, runner.layer_context())
        ctx = dict(runner.layer_context(), trace=red,
                   peaks=trace_reduce.peaks_for(devices[0].device_kind),
                   n_devices=len(devices))
        for m in spec["per_layer"]:
            v = load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}

    for k, (v, lim) in check["numbers"].items():
        say(f"[check] {k} {v!r} limit {lim!r}")
    if check.get("worst"):
        say(f"[check] worst: {check['worst']}")
    result = {"correct": bool(correct), "attempted": check["answers"],
              "failed": check["off"], "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in check["numbers"].items()}
    return result


def main(argv, *, process_t0: float) -> int:
    args = parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), process_t0=process_t0)
    except NoAccelerator as e:
        say(f"[perfbench] {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0
