"""Harness pieces shared by every cell: device checks, the compile clock,
host spans, percentiles, the result line and the per-layer readers.

A cell is ``<config>.<traffic>`` in ``BENCHMARK.json``.  Its configuration
lives in ``configs/<config>.json`` (sizes) beside ``configs/<config>.py``
(the plain reference), its traffic in ``traffic/<traffic>.json``, whose
``driver`` key names the module under ``drivers/`` that runs it, and each
per-layer metric in ``metrics/<metric>.py``.  Adding a cell, a mix or a
metric adds files and edits none.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list
    seed: int
    seconds: float
    trace: bool
    control: bool = False     # also read the lower-precision control

    def reference(self):
        """The configuration's plain reference module."""
        return load_module(BENCH / "configs" / f"{self.config_name}.py",
                           f"ref_{self.config_name.replace('-', '_')}")


def find_cell(name: str, seed: int, seconds: float, trace: bool) -> Cell:
    """The cell ``BENCHMARK.json`` lists under ``name``; a cell it does not
    list yet resolves as ``<config>.<traffic>`` on one chip, so that its
    files can be tried before it is listed."""
    cells = {w["name"]: w for w in benchmark_spec()["workloads"]}
    if name in cells:
        w = cells[name]
        return make_cell(name, w["config"], w["traffic"], int(w["chips"]),
                         seed, seconds, trace)
    config, _, traffic = name.rpartition(".")
    if not ((BENCH / "configs" / f"{config}.json").exists()
            and (BENCH / "traffic" / f"{traffic}.json").exists()):
        raise SystemExit(f"chipbench: no workload {name!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    return make_cell(name, config, traffic, 1, seed, seconds, trace)


def make_cell(name: str, config: str, traffic: str, chips: int, seed: int,
              seconds: float, trace: bool) -> Cell:
    """A cell from its configuration and traffic files, with the metrics
    that ``BENCHMARK.json`` lists for it."""
    spec = benchmark_spec()
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name, config, chips, cfg, mix,
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)], int(seed),
                float(seconds), bool(trace))


def require_chips(n: int):
    """The cell's TPU chips, or exit non-zero naming what is missing."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chipbench: no TPU attached (JAX platform "
                     f"{platform!r}); this benchmark measures a TPU chip")
    if len(devices) < n:
        raise SystemExit(f"chipbench: the cell needs {n} TPU chips, "
                     f"{len(devices)} present")
    return devices[:n]


def compile_cache_dir() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else the fixed ``<checkout>/.jax_cache`` (the program's own default),
    so that every run after a cell's first finds its programs."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and how many
    backend compilations ran (a copy of the bring-up run's clock)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        self.compiles = 0

        def listen(event, duration, **_):
            if event in self.EVENTS:
                self.total += duration
            if event == self.EVENTS[2]:
                self.compiles += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


class Spans:
    """Host spans of the benchmark's own calls into the program, kept in
    memory, and mirrored into the profiler trace when one is recording."""

    def __init__(self, profiling: bool = False):
        self.events: list = []     # (name, t0, t1) perf_counter seconds
        self.profiling = profiling

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        ann = (jax.profiler.TraceAnnotation(f"bench.{name}")
               if self.profiling else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.events.append((name, t0, time.perf_counter()))


def start_trace(trace_dir) -> None:
    """Start the profiler without its Python function tracer (which
    would record every Python call of the host loop) and without HLO
    protos: device operations and annotated host spans only."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def percentile_with_tail(values, min_beyond: int = 10):
    """The highest of p50/p90/p95/p99 with at least ``min_beyond``
    samples beyond it -> (label, value)."""
    import numpy as np
    v = np.sort(np.asarray(values, np.float64))
    best = ("p50", float(np.percentile(v, 50))) if len(v) else ("p50",
                                                                math.nan)
    for q in (90, 95, 99):
        if len(v) * (1 - q / 100.0) >= min_beyond:
            best = (f"p{q}", float(np.percentile(v, q)))
    return best


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def device_info(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} "
                         f"in peaks.json (have {sorted(table['devices'])})")
    return table["devices"][kind]


def read_per_layer(cell: Cell, ctx: dict) -> dict:
    """Run each per-layer metric's reader; a reader with nothing to read
    returns None and its metric stays out of the line."""
    out = {}
    for m in cell.per_layer:
        path = BENCH / "metrics" / f"{m['name']}.py"
        mod = load_module(path, "metric_" + m["name"].replace(".", "_")
                          .replace("-", "_"))
        v = mod.read(ctx)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def stage_ms(ctx: dict, stage: str):
    walls = ctx.get("stage_walls")
    if not walls:
        return None
    windows = sum(w for _, w in walls)
    if stage not in walls[0][0] or not windows:
        return None
    return 1e3 * sum(d[stage] for d, _ in walls) / windows


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
