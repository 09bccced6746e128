"""Chip benchmark: one cell of ``BENCHMARK.json``, one process.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell
asks for.  The run refuses to start without them (exit 2, no result) and
turns on JAX's persistent compilation cache.  The cell's traffic mix names
its driver (``drivers/<driver>.py``), which builds the cell's session from
the seed, drives its first steps and warms every shape it will use
(set-up), and measures for ``--seconds``; then the run frees the program's
state, checks what the timed path produced against the configuration's
plain reference, and prints the result as the last line of standard
output:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, "breakdown": {...}, "checks": {...}}

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` profiles
up to the first four seconds of the window and reports its per-layer
metrics, with ``device.busy_s``/``window_s`` and the breakdown.  The
numbers compared with the reference are printed beside their limits as the
last lines of standard error and under ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
REPO_ROOT = CHIP_DIR.parents[1]
sys.path.insert(0, str(CHIP_DIR))


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int) -> list:
    import jax

    devs = jax.devices()
    if not devs or any(d.platform != "tpu" for d in devs) or len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX found "
                     f"{[f'{d.platform}:{d.device_kind}' for d in devs]}")
    return devs[:n]


def enable_cache() -> str:
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class CompileCounter:
    """Counts compile requests (persistent-cache hits included) and backend
    compiles; reset at the window's start."""

    def __init__(self):
        import jax

        self.requests = self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def _duration(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def reset(self):
        self.requests = self.compiles = 0


def _peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             cell=None, devices=None, say=print) -> dict:
    """One run; returns the result object.  ``cell`` and ``devices`` let a
    test pass a cut-down cell and skip the look for a chip."""
    import jax

    from chipbench import cells, correct, peaks
    from chipbench import trace as trace_mod

    cell = cells.load_cell(name) if cell is None else cell
    devs = require_chips(cell.chips) if devices is None else devices
    say(json.dumps({"compile_cache": enable_cache(), "jax": jax.__version__}))
    counter = CompileCounter()
    driver = cells.driver_module(cell.traffic["driver"])
    drv = driver.Driver(cell.config, cell.traffic, seed, obs=trace)
    drv.setup(seconds)
    setup_s = time.perf_counter() - T_START
    counter.reset()
    trace_dir = None
    if trace:
        trace_dir = Path(tempfile.mkdtemp(prefix="trace_", dir=_scratch()))
    win = drv.window(seconds, trace_dir)
    devs = list(devs)
    peak = _peak_bytes(devs) if devices is None else 0
    say(json.dumps({"window": win, "compile_requests_in_window":
                    counter.requests, "backend_compiles_in_window":
                    counter.compiles, "memory_peak_bytes": peak}))
    context = {"window": win, "cfg": cell.config, "traffic": cell.traffic,
               "chips": cell.chips, **drv.layer_context()}
    drv.free()

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": int(win["attempted"]),
              "failed": int(win["failed"])}
    metrics = {}
    breakdown = None
    if trace:
        red = trace_mod.reduce(trace_mod.read_trace(
            trace_mod.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        context.update(reduced=red, peaks=peaks.peaks_for(dev.device_kind))
        for m in cell.per_layer:
            value = cells.metric_reader(m["name"]).reduce(context)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = red.breakdown()
    else:
        e2e = {"setup_s": setup_s, **win["end_to_end"]}
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                raise KeyError(f"driver {cell.traffic['driver']!r} reports "
                               f"no {m['name']!r}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    numbers = drv.check(cells.reference_module(cell.config), win)
    ok, checks = correct.verdict(numbers, cell.limits)
    result.update(correct=ok and result["failed"] == 0, metrics=metrics,
                  device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _scratch() -> Path:
    """Run-time files go inside the checkout (listed in .gitignore)."""
    d = REPO_ROOT / "build" / "chipbench"
    d.mkdir(parents=True, exist_ok=True)
    return d


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (REPO_ROOT / "src" / "repro").is_dir():
        print(f"no program under {REPO_ROOT / 'src'}: nothing to measure",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO_ROOT / "src"))
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
