"""The benchmark's run: set-up, the timed window, the traced window, the
per-layer readers and the check against the plain reference.

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json``, its configuration in ``configs/<config>.json`` (with
the family module ``families/<family>.py`` that holds its shapes, FLOPs
and reference), its traffic in ``traffic/<traffic>.json`` (whose ``kind``
names the generator ``kinds/<kind>.py``), its limits in
``workloads/<cell>.json``, and each per-layer metric's reader in
``metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_key(jax, seed: int):
    """A PRNG key from any non-negative seed: ``jax.random.key`` keeps
    only the low 32 bits, so the rest is folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed >> 32)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    family: object
    kind: object
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    rehearse: bool

    @classmethod
    def load(cls, name: str, rehearse: bool = False) -> "Cell":
        bench = load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(known: {sorted(cells)})")
        w = cells[name]
        config = load_json(HERE / "configs" / f"{w['config']}.json")
        family = load_module(HERE / "families" / f"{config['family']}.py")
        if rehearse:
            config = {**config, **family.REHEARSE}
        traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
        kind = load_module(HERE / "kinds" / f"{traffic['kind']}.py")
        limits = load_json(HERE / "workloads" / f"{name}.json")["limits"]
        e2e = [m for m in bench["end_to_end"]
               if name in m.get("workloads", [name])]
        moved = {m["name"] for m in e2e}
        layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
        return cls(name, w["chips"], config, traffic, family, kind, limits,
                   e2e, layer, rehearse)


def setup_jax(cache: bool = True):
    """Imports JAX with the persistent compile cache inside the checkout,
    where every program, however quick to compile, is kept."""
    if cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    if cache:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    else:
        jax.config.update("jax_enable_compilation_cache", False)
    return jax


def devices(jax, chips: int, rehearse: bool) -> list:
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r}, no TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class Counters:
    """Compiles and persistent-cache hits and misses, from JAX's own
    monitoring events."""

    def __init__(self, jax):
        from jax import monitoring
        self.compiles = 0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **kw):
            if event == COMPILE_EVENT:
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


@dataclasses.dataclass
class Window:
    seconds: float
    units: int
    compiles: int


def timed_window(jax, run, seconds: float, counters: Counters,
                 unit: str) -> Window:
    """Calls ``run(i)`` (one whole step or round, ended by
    ``block_until_ready``) until ``seconds`` have passed; the window is
    the time from the first call to the end of the last."""
    before = counters.compiles
    t0 = time.perf_counter()
    i = 0
    with jax.profiler.TraceAnnotation("bench:window"):
        while True:
            with jax.profiler.TraceAnnotation("bench:" + unit):
                run(i)
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
    return Window(time.perf_counter() - t0, i, counters.compiles - before)


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def per_layer(cell: Cell, ctx) -> Dict[str, dict]:
    """Each per-layer metric of the cell that its reader finds."""
    out = {}
    for m in cell.per_layer:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def read_trace(path_glob: str):
    import trace_reduce
    files = glob.glob(path_glob, recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    return trace_reduce.load_xplane(files[0])


@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader reads: the reduced trace of the traced
    window, the units (steps or rounds) completed in it, the cell's FLOPs
    and least bytes per unit, the bytes of each Pallas call, the peaks."""

    trace: object
    dev: int
    units: int
    window_s: float
    peaks: dict
    chips: int
    flops_per_unit: Optional[float]
    least_bytes_per_unit: Optional[float]
    kernel_bytes: Dict[str, float]


def layer_context(cell: Cell, runner, trace, peaks: dict) -> LayerContext:
    import trace_reduce as T
    return LayerContext(
        trace, min(trace.devices), T.spans_in_window(trace, runner.unit),
        trace.window_ns * 1e-9, peaks, cell.chips, runner.flops_per_unit(),
        runner.least_bytes_per_unit(),
        T.custom_call_bytes(runner.hlo_text()))


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        rehearse: bool = False, variant: str = "program", t0: float,
        log=print) -> Optional[dict]:
    """One run of a cell.  Returns the result line, or raises
    :class:`NoChip`."""
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    if not (ROOT / "src" / "repro").is_dir():
        raise FileNotFoundError(f"the program is not in this checkout: "
                                f"{ROOT / 'src' / 'repro'} is missing")
    cell = Cell.load(workload, rehearse)
    jax = setup_jax(cache=not rehearse)
    counters = Counters(jax)
    devs = devices(jax, cell.chips, rehearse)
    peaks = None
    if not rehearse:
        table = load_json(HERE / "peaks.json")["devices"]
        if devs[0].device_kind not in table:
            raise KeyError(f"no peaks for device kind "
                           f"{devs[0].device_kind!r} in peaks.json")
        peaks = table[devs[0].device_kind]
    log(f"[bench] {workload} seed={seed} seconds={seconds} trace={trace} "
        f"platform={devs[0].platform} kind={devs[0].device_kind} "
        f"devices={len(jax.devices())} jax={jax.__version__}")

    runner = cell.kind.Runner(cell, jax, devs, variant=variant, log=log)
    runner.build()
    runner.prepare(seed)
    setup_s = time.perf_counter() - t0
    log(f"[bench] set-up {setup_s:.6f} s; compiles {counters.compiles}, "
        f"cache hits {counters.hits}, misses {counters.misses}")

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    try:
        window = timed_window(jax, runner.one, seconds, counters,
                              runner.unit)
    finally:
        if trace:
            jax.profiler.stop_trace()
    log(f"[bench] window {window.seconds:.6f} s, {window.units} "
        f"{runner.unit}s, compiles inside {window.compiles}")
    peak = memory_peak(devs)
    log(f"[bench] peak_bytes_in_use {peak}")

    metrics, breakdown, busy, ctx = {}, None, None, None
    if trace and rehearse:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log("[bench] rehearsal: the CPU trace has no device to reduce")
    elif trace:
        import trace_reduce as T
        tr = read_trace(str(TRACE_DIR / "**" / "*.xplane.pb"))
        ctx = layer_context(cell, runner, tr, peaks)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        busy = sum(T.busy_ns(tr, d) for d in tr.devices) \
            / max(len(tr.devices), 1) * 1e-9
        if not rehearse:
            metrics = per_layer(cell, ctx)
        breakdown = {"device_ops": T.top_ops(tr, ctx.dev),
                     "idle_gaps": T.longest_gaps(tr, ctx.dev)}
        log(f"[bench] traced {ctx.units} {runner.unit}s in "
            f"{ctx.window_s:.6f} s, busy {busy:.6f} s; breakdown "
            f"{json.dumps(breakdown)}")
    else:
        values = runner.end_to_end(window.seconds, window.units)
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}

    runner.finish()
    gc.collect()
    t_check = time.perf_counter()
    numbers = runner.check(seed)
    checks = {}
    for name, value in numbers.items():
        limit = cell.limits[name]
        checks[name] = {"value": float(value), "limit": float(limit)}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values()) and runner.failed == 0
    log(f"[bench] check {time.perf_counter() - t_check:.3f} s; cache hits "
        f"{counters.hits}, misses {counters.misses}")
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result = {
        "correct": bool(correct),
        "attempted": window.units,
        "failed": runner.failed,
        "metrics": metrics,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": peak},
    }
    if ctx is not None:
        result["device"].update(busy_s=busy, window_s=ctx.window_s)
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
