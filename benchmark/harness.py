"""What every driver of the benchmark shares: the manifest, the chip and
native-build requirements, compile counting, percentiles and the trace
capture.  Drivers take the system under test from ``ceph_tpu``; nothing
here or below imports ``bench.py``, ``chip_smoke.py`` or
``ceph_tpu.loadgen``'s generator (see README.md).
"""

from __future__ import annotations

import glob
import importlib
import json
import math
import os
import shutil
import subprocess
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"
SCRATCH = ROOT / ".benchmark_out"          # traces; listed in .gitignore

T0 = time.monotonic()       # run.py moves it back to its own first line


class HarnessError(RuntimeError):
    """The run cannot measure: no chip, no native build, a manifest or
    trace it cannot read.  The one kind of failure that exits non-zero."""


def elapsed() -> float:
    """Seconds since the process started: ``setup_s`` at a window's start."""
    return time.monotonic() - T0


def say(msg: str) -> None:
    print(f"[benchmark +{elapsed():7.1f}s] {msg}", flush=True)


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError) as e:
        raise HarnessError(f"cannot read {path}: {e}") from e


class Cell:
    """One entry of ``workloads`` with the files its names point to."""

    def __init__(self, name: str, manifest: dict | None = None) -> None:
        m = manifest or load_json(ROOT / "BENCHMARK.json")
        by_name = {w["name"]: w for w in m["workloads"]}
        if name not in by_name:
            raise HarnessError(f"no workload {name!r} in BENCHMARK.json "
                               f"(has {sorted(by_name)})")
        self.name = name
        self.entry = by_name[name]
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in m["configs"]}[self.entry["config"]]
        self.config = load_json(ROOT / cfg["file"])
        self.traffic = load_json(
            BENCH / "traffic" / f"{self.entry['traffic']}.json")
        self.end_to_end = {e["name"]: e["unit"] for e in m["end_to_end"]
                           if name in e.get("workloads", [name])}
        self.per_layer = [p["name"] for p in m["per_layer"]
                          if name in p.get("workloads", [name])]

    def driver(self):
        return importlib.import_module(
            f"benchmark.drivers.{self.traffic['driver']}")


def layer_metric(name: str) -> dict:
    return load_json(BENCH / "layer_metrics" / f"{name}.json")


def read_layer_metrics(names: list[str], facts: dict) -> dict:
    """Each metric's reader over the run's facts; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for name in names:
        spec = layer_metric(name)
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(spec["spec"], facts)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def flatten(prefix: str, tree: dict, out: dict) -> dict:
    """Numbers of a nested dict as ``prefix.key.key`` facts."""
    for key, val in tree.items():
        if isinstance(val, dict):
            flatten(f"{prefix}.{key}", val, out)
        elif isinstance(val, (int, float)) and not isinstance(val, bool):
            out[f"{prefix}.{key}"] = val
    return out


def counter_delta(prefix: str, before: dict, after: dict, out: dict) -> None:
    for key, val in after.items():
        if isinstance(val, (int, float)):
            out[f"{prefix}.{key}"] = val - before.get(key, 0)


# -- requirements -------------------------------------------------------------

def require_chips(chips: int) -> dict:
    """The device as jax reports it; fails unless it is ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or len(devs) < chips:
        raise HarnessError(f"needs {chips} TPU chip(s), jax found {device}")
    return device


def build_native() -> None:
    """native/ from the committed sources (``make`` decides what is
    stale); the store is not measured on its numpy fallbacks."""
    try:
        subprocess.run(["make", "-C", str(ROOT / "native"), "all"],
                       check=True, stdout=subprocess.DEVNULL)
    except (OSError, subprocess.CalledProcessError) as e:
        raise HarnessError(f"native/ did not build: {e}") from e
    from ceph_tpu import native
    if not native.available() or native.get_dencfast() is None:
        raise HarnessError("native library failed to load after make")


def enable_compile_cache() -> str:
    """The program's own placement of jax's persistent cache (a fixed
    directory in the checkout, or JAX_COMPILATION_CACHE_DIR), with every
    program cached however quickly it compiled."""
    import jax
    from ceph_tpu.common.compile_cache import enable_compile_cache as on
    path = on()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileMeter:
    """Programs built and persistent-cache traffic, from jax's own
    monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


# -- arithmetic ---------------------------------------------------------------

def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of the samples themselves."""
    if not values:
        raise ValueError("no samples")
    ordered = sorted(values)
    return ordered[max(1, math.ceil(q / 100.0 * len(ordered))) - 1]


# -- trace capture ------------------------------------------------------------

class Trace:
    """One profiler session with the Python tracer off, written under a
    fixed directory of the checkout.  ``start``/``stop`` block for as
    long as the profiler takes: a driver with an event loop calls them
    through ``loop.run_in_executor``.  ``mark()`` brackets the steady
    slice on the calling thread, so the reduction can clip to it."""

    def __init__(self, workload: str) -> None:
        self.dir = SCRATCH / "trace" / workload
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def mark(self):
        import jax
        from benchmark.xplane import SLICE_MARK
        return jax.profiler.TraceAnnotation(SLICE_MARK)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def file(self) -> Path:
        found = sorted(glob.glob(
            str(self.dir / "plugins" / "profile" / "*" / "*.xplane.pb")))
        if not found:
            raise HarnessError(f"the profiler wrote no trace under {self.dir}")
        return Path(found[-1])


def require_program() -> None:
    """The system under test sits beside benchmark/, or nothing runs."""
    if not (ROOT / "ceph_tpu").is_dir() or not (ROOT / "native").is_dir():
        raise HarnessError("no program to measure: ceph_tpu/ and native/ "
                           "must sit beside benchmark/")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
