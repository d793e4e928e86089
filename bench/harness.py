"""The benchmark harness: one run of one cell of ``BENCHMARK.json``.

A run loads the cell's configuration (``bench/configs/<config>.json``) and
traffic mix (``bench/traffic/<mix>.json``), hands both to the traffic's
driver (``bench/drivers/<driver>.py``), and then

1. sets up: imports, device check, persistent compile cache, the fields
   made from ``--seed``, whatever the traffic needs before its first
   request, and one warm-up session (``setup_s``);
2. measures for ``--seconds``: a closed loop of sessions, with the
   compilations inside the window counted (there should be none) and,
   with ``--trace 1``, the profiler on;
3. reads the device's peak memory, frees the program's state, and checks
   a sample of the window's answers against the configuration's plain
   reference (``bench/references/<reference>.py``);
4. prints each metric the cell reports, read by its own reader
   (``bench/metrics/<metric>.py``), as the last line of standard output.

Nothing here names a cell, a configuration or a metric: a later cell,
configuration or metric is a new file and a new ``BENCHMARK.json`` entry.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: the program's spans that label the device's idle gaps
SPANS = ("compress", "open", "read", "refine")
TRACE_DIR = ROOT / ".bench_trace"


class NoDevice(Exception):
    """The run cannot start: no accelerator, too few chips, or no program
    beside the benchmark."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file of the benchmark by its path (names may hold dots)."""
    name = "bench._files." + str(path.relative_to(BENCH)).replace(
        "/", ".").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def resolve(workload: str, root: Path = ROOT):
    """(spec, cell, config, traffic) of ``workload``, all from files."""
    spec = load_json(root / "BENCHMARK.json")
    cell = find(spec["workloads"], workload, "workload")
    entry = find(spec["configs"], cell["config"], "config")
    config = load_json(root / entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return spec, cell, config, traffic


def cell_metrics(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics the cell reports: end-to-end ones untraced, per-layer
    ones traced; a metric with ``workloads`` only in the cells it lists."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def import_program():
    """Put the program's package on the path and import it."""
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
        from repro import compile_cache
    except ImportError as e:
        raise NoDevice(f"the program (package 'repro' under src/) is not "
                       f"beside the benchmark: {e}") from None
    return compile_cache


def accelerator(chips: int):
    """The devices of the run; raises NoDevice unless JAX finds a TPU
    with at least ``chips`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no accelerator: JAX found {devs[0].platform!r} "
                       "devices")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs


class CompileCounter:
    """Counts JAX's tracing, backend compiles and persistent-cache loads
    (its monitoring events)."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "traces",
        "/jax/core/compile/backend_compile_duration": "compiles",
        "/jax/compilation_cache/cache_hits": "cache_loads",
    }

    def __init__(self):
        import jax

        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def _duration(self, event, duration, **_):
        self._event(event)

    def take(self) -> dict:
        """The counts so far; starts counting afresh."""
        out, self.counts = self.counts, dict.fromkeys(self.counts, 0)
        return out

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)


def device_info(devs, chips: int) -> dict:
    used = devs[:chips]
    peaks = []
    for d in used:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": max(peaks)}


def read_metrics(metrics: List[dict], ctx) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py").read
        v = reader(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True,
        patch: Optional[Callable] = None,
        config_override: Optional[dict] = None,
        traffic_override: Optional[dict] = None) -> dict:
    """One run of ``workload``; returns the result line's object.

    ``require_chip=False`` skips the look for an accelerator, and
    ``config_override`` and ``traffic_override`` replace the cell's
    configuration and traffic: the benchmark's tests use them to drive a
    whole run on the CPU at a small size, and the generator's read and
    refine sessions.  ``patch``, given the driver's session object after
    set-up, may break the timed path underneath (the tests' planted
    faults).
    """
    spec, cell, config, traffic = resolve(workload)
    if config_override is not None:
        config = config_override
    if traffic_override is not None:
        traffic = traffic_override
    compile_cache = import_program()
    devs = accelerator(cell["chips"]) if require_chip else None
    compile_cache.enable(ROOT)
    import jax

    if devs is None:
        devs = jax.devices()
    counter = CompileCounter()
    try:
        return _run(workload, seed, seconds, trace, t_start, require_chip,
                    patch, spec, cell, config, traffic, devs, counter)
    finally:
        counter.close()


def _run(workload, seed, seconds, trace, t_start, require_chip, patch,
         spec, cell, config, traffic, devs, counter) -> dict:
    import jax
    from repro.kernels import dispatch

    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    reference = load_module(BENCH / "references" /
                            f"{config['reference']}.py")
    sess = driver.Sessions(config, traffic, seed, reference)
    sess.setup()
    if patch is not None:
        patch(sess)
    setup_s = time.perf_counter() - t_start
    print(f"compiles in set-up: {counter.take()}", file=sys.stderr)

    interp_before = dict(dispatch.interpreted_counts())
    trace_dir = TRACE_DIR / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with dispatch.measure() as launches:
            window = sess.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = counter.take()
    device = device_info(devs, cell["chips"])

    summary = None
    if trace:
        from bench import tracereduce

        summary = tracereduce.summarize(trace_dir, cell["chips"], SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    interpreted = {k: v - interp_before.get(k, 0)
                   for k, v in dispatch.interpreted_counts().items()
                   if v - interp_before.get(k, 0)}

    answers = sess.release()
    gc.collect()
    checks = sess.check(answers)
    ok = all(v <= lim for _, v, lim in checks) and window.failed == 0
    if require_chip and interpreted:
        print(f"kernels ran in the Pallas interpreter: {interpreted}",
              file=sys.stderr)
        ok = False

    ctx = SimpleNamespace(
        cell=cell, config=config, traffic=traffic, seed=seed,
        setup_s=setup_s, window=window, launches=dict(launches),
        trace=summary, device=device)
    metrics = read_metrics(cell_metrics(spec, workload, trace), ctx)

    print(f"compiles in the window: {in_window}", file=sys.stderr)
    print("sessions in the window (field, seconds): "
          f"{[(r['field'], r['calls'][-1]['t']) for r in window.sessions]}",
          file=sys.stderr)
    if interpreted:
        print(f"interpreted launches in the window: {interpreted}",
              file=sys.stderr)
    for name, v, lim in checks:
        print(f"check {name}: {v!r} limit {lim!r}", file=sys.stderr)
    out = {"correct": ok, "attempted": window.attempted,
           "failed": window.failed, "metrics": metrics, "device": device,
           "compiles_in_window": in_window}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary.top_ops,
                            "idle_gaps": summary.top_gaps}
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in checks}
    return out
