"""What every cell shares: finding its files by name, the trace window,
the per-layer readers, and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration file is named there; its traffic mix is
``bench/traffic/<traffic>.json``; its limits are
``bench/limits/<cell>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  The configuration's ``"reference"``
names its plain reference (``bench/configs/<reference>.py``) and its
layout (``bench/layouts/<reference>.py``); the mix's ``kind`` names the
driver (``bench/<kind>_cell.py``).  Nothing here lists cells.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

from bench import layouts

ROOT = Path(__file__).resolve().parents[1]
RUNS = ROOT / ".bench_runs"          # traces and scratch of runs (ignored)


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclass
class Cell:
    name: str
    spec: dict
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    root: Path = ROOT

    def model_config(self):
        """The program's ``ModelConfig``, from the published keys by the
        configuration's layout (``bench/layouts``)."""
        return layouts.of(self.config).model_config(self.config)

    def end_to_end(self) -> List[dict]:
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[dict]:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if m["moves"] in mine
                and self.name in m.get("workloads", [self.name])]


def find_cell(name: str, *, seed: int, seconds: float, trace: bool,
              root: Path = ROOT, spec: Optional[dict] = None) -> Cell:
    spec = spec or load_spec(root)
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    layouts.check(config)
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{wl['traffic']}.json").read_text())
    lim_path = root / "bench" / "limits" / f"{name}.json"
    limits = json.loads(lim_path.read_text()) if lim_path.exists() else {}
    return Cell(name, spec, wl, config, traffic, limits, int(seed),
                float(seconds), bool(trace), root)


def driver(kind: str):
    return importlib.import_module(f"bench.{kind}_cell")


def reference(cell: Cell):
    return importlib.import_module(f"bench.configs.{cell.config['reference']}")


# --------------------------------------------------------------------- #
# the measured window
# --------------------------------------------------------------------- #


@dataclass
class Window:
    """Host-clock bounds of the measured window and, in a traced run, the
    events of its device trace."""
    t0: float = 0.0
    t1: float = 0.0
    mono0_ns: int = 0
    events: Optional[dict] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def reduce(self, tracer=None) -> Optional[dict]:
        """The reduced trace, the program's spans (``tracer``) mapped onto
        the trace's clock to name what the host did in each idle gap."""
        if self.events is None:
            return None
        from bench import trace_reduce
        lo, _ = trace_reduce.window_of(self.events, "bench_window")
        off = lo - self.mono0_ns
        extra = [] if tracer is None else [
            [e.lane, e.name, e.t0_ns + off, e.t1_ns - e.t0_ns]
            for e in tracer.spans()]
        return trace_reduce.reduce(self.events, "bench_window",
                                   extra_host=extra)


@contextlib.contextmanager
def window(cell: Cell):
    """Time the window; with ``--trace 1`` also record the device trace
    of it (python tracing off) and reduce it afterwards."""
    import jax
    w = Window()
    tdir = RUNS / "trace" / cell.name
    if cell.trace:
        import shutil
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench_window"):
            w.mono0_ns = time.monotonic_ns()
            w.t0 = time.perf_counter()
            yield w
            w.t1 = time.perf_counter()
    finally:
        if cell.trace:
            jax.profiler.stop_trace()
    if cell.trace:
        from bench import trace_reduce
        w.events = trace_reduce.load(next(tdir.rglob("*.xplane.pb")))


def init_jax(root: Path = ROOT):
    """Import JAX with the compile cache at a fixed path inside this
    checkout (every program cached, however quick to compile, and never
    evicted: the directory belongs to this checkout alone) and the TPU
    runtime's logs inside it too.  Returns the module."""
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", str(root / ".bench_runs" / "tpu_logs"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def log_memory(tag: str) -> None:
    """Device memory in use and its peak so far, on standard error."""
    import jax
    st = jax.local_devices()[0].memory_stats() or {}
    print(f"memory {tag}: in use {st.get('bytes_in_use', 0) / 2 ** 30:.3f} GiB, "
          f"peak {st.get('peak_bytes_in_use', 0) / 2 ** 30:.3f} GiB, limit "
          f"{st.get('bytes_limit', 0) / 2 ** 30:.3f} GiB", file=sys.stderr,
          flush=True)


def stage(tag: str, t_start: float) -> None:
    """Seconds since the process's start of set-up at the end of one of
    its stages, on standard error."""
    print(f"setup {tag}: {time.perf_counter() - t_start:.3f} s",
          file=sys.stderr, flush=True)


def peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


# --------------------------------------------------------------------- #
# per-layer readers
# --------------------------------------------------------------------- #


def read_metric(name: str, ctx: dict, root: Path = ROOT):
    """Run ``bench/metrics/<name>.py``'s ``read(ctx)``; None when it finds
    nothing to read."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


# --------------------------------------------------------------------- #
# the result
# --------------------------------------------------------------------- #


@dataclass
class Outcome:
    """What a driver hands back."""
    setup_s: float
    values: Dict[str, float]               # end-to-end, by metric name
    checks: List[tuple]                    # (name, value, limit)
    attempted: int
    failed: int
    ctx: Dict[str, Any] = field(default_factory=dict)   # for the readers
    memory_peak_bytes: int = 0


def verdict(checks) -> bool:
    return all(math.isfinite(v) and v <= lim for _, v, lim in checks)


def result_line(cell: Cell, out: Outcome, device: dict) -> dict:
    metrics = {}
    if cell.trace:
        for m in cell.per_layer():
            v = read_metric(m["name"], out.ctx, cell.root)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        vals = dict(out.values, setup_s=out.setup_s)
        for m in cell.end_to_end():
            if m["name"] in vals:
                metrics[m["name"]] = {"value": vals[m["name"]],
                                      "unit": m["unit"]}
    line = {"correct": verdict(out.checks), "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    tr = out.ctx.get("trace")
    if cell.trace and tr is not None:
        line["breakdown"] = {"device_ops": tr["top_ops"],
                             "idle_gaps": tr["top_gaps"]}
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in out.checks}
    return line


def print_checks(checks) -> None:
    for n, v, lim in checks:
        ok = "ok" if (math.isfinite(v) and v <= lim) else "FAILED"
        print(f"check {n}: {v!r} (limit {lim!r}) {ok}", file=sys.stderr,
              flush=True)
