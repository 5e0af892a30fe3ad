#!/usr/bin/env python3
"""Benchmark of the social-learning and Byzantine engines on a TPU.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name, starting from the cell's entry in
``BENCHMARK.json`` at the root of the checkout:

* the configuration file the entry's ``config`` names (``file``), whose
  ``engine`` picks the driver ``chipbench/engines/<engine>.py``;
* the traffic mix ``chipbench/traffic/<traffic>.json``;
* the cell's check ``chipbench/workloads/<workload>.json``: how many calls
  of the window are compared with the plain reference, and the limit of
  each number compared;
* each per-layer metric ``chipbench/metrics/<name>.py`` listed for the
  cell, read in a ``--trace 1`` run from the profiler's trace
  (:class:`chipbench.trace.Context`: the device ops and their name
  stacks, the benchmark's and the program's host spans).

The cell is built over the devices its entry asks for (``chips``), the
ones this run checked are there.

A run: make the inputs from ``--seed`` and build the program's state
(set-up), make one warm call of the cell's own shape, then run a closed
loop of back-to-back calls of the program's public ``run_*`` entry, each
ending in ``block_until_ready``, call ``i`` with seed ``seed * 1000 + i``.
The window closes at the end of the first call that ends after
``--seconds`` (at most ``TRACE_SECONDS`` in a traced run). Then the peak
device memory is read, the program's state is dropped, and a sample of
the window's calls, drawn from the seed, is compared with the plain
reference.

``agent_rounds_per_s`` counts agents x scenarios x rounds of every call in
the window over the window's wall time; ``setup_s`` runs from the start of
this process to the end of the warm call. The last line of standard
output is one JSON object: ``correct``, ``attempted`` (calls in the
window), ``failed`` (compared calls outside a limit), ``metrics``,
``device``, ``breakdown`` in a traced run (the device ops that took most
time, each with its phase, and the longest idle gaps, each named by the
innermost host span: :meth:`chipbench.trace.Context.breakdown`), and last
``checks``: each number compared with its limit. The same numbers and
limits are the last lines of standard error. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run
# writes only inside its checkout and its own TMPDIR
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TRACE_SECONDS = 6.0    # longest traced window: the trace stays small
CACHE_DIR = os.path.join(ROOT, ".jax-cache")
SPAN_CALL, SPAN_BLOCK = "chipbench.call", "chipbench.block"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import the file at ``path`` as a fresh module."""
    name = "chipbench_file_" + re.sub(r"\W", "_", os.path.abspath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str, root: str = ROOT) -> dict:
    """Every file of one cell, by name, in the checkout at ``root``:
    ``{entry, config, engine_path, traffic, check, metrics}``; ``metrics``
    is the cell's per-layer entries of ``BENCHMARK.json``."""
    here = os.path.join(root, os.path.basename(HERE))
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r}; known: {sorted(entries)}")
    entry = entries[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = load_json(os.path.join(root, conf_entry["file"]))
    return {
        "entry": entry,
        "config": config,
        "engine_path": os.path.join(here, "engines",
                                    config["engine"] + ".py"),
        "metrics_dir": os.path.join(here, "metrics"),
        "traffic": load_json(os.path.join(here, "traffic",
                                          entry["traffic"] + ".json")),
        "check": load_json(os.path.join(here, "workloads",
                                        workload + ".json")),
        "metrics": [m for m in bench["per_layer"]
                    if workload in m.get("workloads", [workload])],
    }


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the fixed ``<checkout>/
    .jax-cache`` (the path is part of the key: a moving directory never
    hits); every program is cached, however fast it compiled, and nothing
    is evicted (a size limit set in the environment turns on eviction,
    whose bookkeeping failed on the chip's machine and left every run
    compiling again; the cells' programs take a few MB)."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return CACHE_DIR


class CompileCounter:
    """Counts traces and backend compilations (cache misses), from JAX's
    own monitoring events."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        self.counts = {"traces": 0, "compiles": 0}
        self.compile_s = 0.0

    def __call__(self, event: str, duration: float, **_):
        kind = self.EVENTS.get(event)
        if kind:
            self.counts[kind] += 1
            if kind == "compiles":
                self.compile_s += duration


class GcPauses:
    """Wall time of the host's garbage collections, from ``gc.callbacks``:
    a stall in the window that is a collection shows here."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t = 0.0

    def __call__(self, phase: str, info: dict):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)


def devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def reservoir(gen, k: int):
    """Keeps a uniform sample of ``k`` of the calls offered to it."""
    kept: list = []
    seen = [0]

    def offer(i, value):
        seen[0] += 1
        if len(kept) < k:
            kept.append((i, value))
        else:
            j = int(gen.integers(seen[0]))
            if j < k:
                kept[j] = (i, value)
    return kept, offer


def worst(values: list) -> float:
    """The largest reading; NaN if any reading is NaN."""
    return math.nan if any(v != v for v in values) else max(values)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, require_tpu: bool = True, cell_hook=None,
             log=sys.stderr) -> dict:
    """One run of one cell of the checkout at ``root``; returns the
    result object.

    ``require_tpu=False`` and ``cell_hook(cell)``, which may wrap the built
    cell before the warm call, are for the tests: they run a cell on the
    CPU at a small size, and break its timed path.
    """
    import jax

    from chipbench import inputs

    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    found = find_cell(bench, workload, root)
    entry = found["entry"]
    devs = devices(entry["chips"], require_tpu)
    print(f"chipbench: {workload} on {devs[0].platform} "
          f"{devs[0].device_kind} x{len(devs)}; compile cache "
          f"{enable_compile_cache()}", file=log, flush=True)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    engine = load_module(found["engine_path"])
    cell = engine.Cell(found["config"], found["traffic"], seed,
                       devices=devs)
    if cell_hook is not None:
        cell = cell_hook(cell)
    jax.block_until_ready(cell.call(0))                  # warm-up
    # what the set-up made stays alive all run: the collector need not
    # walk it again in the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    print(f"chipbench: set-up {setup_s:.3f} s, "
          f"{counter.counts['compiles']} backend compilations in "
          f"{counter.compile_s:.3f} s", file=log, flush=True)

    seconds = min(seconds, TRACE_SECONDS) if trace else seconds
    kept, offer = reservoir(inputs.rng(seed, 1), found["check"]["calls"])
    before = dict(counter.counts)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    host_s, call_s = [], []
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    if trace:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    i = 0
    while True:
        i += 1
        c0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_CALL):
            out = cell.call(i)
        c1 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_BLOCK):
            jax.block_until_ready(out)
        c2 = time.perf_counter()
        host_s.append(c1 - c0)
        call_s.append(c2 - c0)
        offer(i, cell.keep(out))
        del out
        if c2 - t0 >= seconds:
            break
    window_s = c2 - t0
    gc.callbacks.remove(pauses)
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    calls = i
    in_window = {k: counter.counts[k] - before[k] for k in before}
    print(f"chipbench: window {window_s:.6f} s, {calls} calls, "
          f"{in_window['traces']} traces and {in_window['compiles']} "
          f"compilations inside it; seconds a call: "
          f"{' '.join(f'{c:.4f}' for c in call_s)}; of them on the host "
          f"before the wait: {' '.join(f'{h:.4f}' for h in host_s)}; "
          f"garbage collections "
          f"{len(pauses.pauses)}, {sum(pauses.pauses):.4f} s, longest "
          f"{max(pauses.pauses, default=0.0):.4f} s", file=log, flush=True)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": None, "attempted": calls, "failed": None}

    if trace:
        from chipbench import trace as tr

        ctx = tr.Context.from_dir(
            trace_dir, device_kind=devs[0].device_kind, calls=calls,
            rounds=calls * cell.rounds_per_call, host_call_s=host_s,
            work=cell.work)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for m in found["metrics"]:
            value = load_module(os.path.join(
                found["metrics_dir"], m["name"] + ".py")).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        result["metrics"] = metrics
        result["breakdown"] = ctx.breakdown()
    else:
        result["metrics"] = {
            "agent_rounds_per_s": {
                "value": calls * cell.agent_rounds_per_call / window_s,
                "unit": "agent-rounds/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    result["device"] = device

    # the reference runs once the window is closed and the program's
    # state is dropped, so it sets neither the peak nor the window
    cell.free()
    limits = found["check"]["limits"]
    readings = cell.check(kept)
    numbers = {k: worst([r[k] for r in readings]) for k in limits}
    failed = sum(any(not r[k] <= limits[k] for k in limits)
                 for r in readings)
    result["correct"] = bool(readings) and failed == 0
    result["failed"] = failed
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in limits}
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=log)
    log.flush()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
