"""A small copy of the benchmark's checkout for the CPU tests, and the runs
of its cells.

:func:`checkout` lays out ``BENCHMARK.json`` and the cell files in a
temporary root (:func:`layout`), the code directories (engines, metrics)
and the tests' own per-cell files (sizes, faults) linked to the real ones,
so that ``run.run_cell(..., root=...)`` finds everything by name exactly as
it does in the real checkout; then it cuts each configuration and traffic
file that a cell uses to a size the CPU runs in seconds (:func:`shrink`).
Each such file ``<stem>.json`` brings its cut in
``chipbench/tests/sizes/<stem>.json``: the keys to change, ``{}`` where
the file is small already.

:func:`on_devices` runs a function in this process where JAX has as many
devices as a cell asks for, and otherwise in a child interpreter on that
many CPU devices; :func:`run_small` is the function that runs one cell,
sound, with a fault of its engine planted (``chipbench/tests/faults/
<engine>.py``), or with its control in the program's place.
"""
from __future__ import annotations

import inspect
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import run  # noqa: E402

NAME = os.path.basename(BENCH)
TESTS = os.path.basename(HERE)
CONTROL = "control"    # run_small's fault that puts the control in place
SECONDS = 0.2                # the window of a small run
CHILD_TIMEOUT_S = 600


def _bench(root: str) -> dict:
    return run.load_json(os.path.join(root, "BENCHMARK.json"))


def layout(root: str) -> str:
    """Lay out the full-size files of a checkout at ``root``; returns
    ``root``."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for sub in ("configs", "traffic", "workloads"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(root, NAME, sub))
    for sub in ("engines", "metrics"):
        os.symlink(os.path.join(BENCH, sub), os.path.join(root, NAME, sub))
    os.mkdir(os.path.join(root, NAME, TESTS))
    for sub in ("sizes", "faults"):
        os.symlink(os.path.join(HERE, sub),
                   os.path.join(root, NAME, TESTS, sub))
    return root


def cell_files(root: str) -> list[str]:
    """The configuration and traffic files the cells of the checkout at
    ``root`` use, relative to its benchmark directory."""
    bench = _bench(root)
    conf = {c["name"]: c["file"] for c in bench["configs"]}
    files = set()
    for w in bench["workloads"]:
        files.add(os.path.relpath(conf[w["config"]], NAME))
        files.add(os.path.join("traffic", w["traffic"] + ".json"))
    return sorted(files)


def sizes_file(root: str, rel: str) -> str:
    """The sizes file of the cell file ``rel`` in the checkout at
    ``root``."""
    return os.path.join(root, NAME, TESTS, "sizes", os.path.basename(rel))


def shrink(root: str, extra: dict | None = None) -> None:
    """Cut every file :func:`cell_files` lists by its sizes file, then by
    ``extra`` (``{rel: changes}``) where it names the file. A file with no
    sizes file is an error: the CPU tests would build it at full size."""
    for rel in cell_files(root):
        sizes = sizes_file(root, rel)
        if not os.path.isfile(sizes):
            raise FileNotFoundError(
                f"{rel} has no sizes file {os.path.relpath(sizes, root)}")
        changes = run.load_json(sizes)
        changes.update((extra or {}).get(rel, {}))
        path = os.path.join(root, NAME, rel)
        data = run.load_json(path)
        data.update(changes)
        with open(path, "w") as f:
            json.dump(data, f)


def checkout(root: str, sizes: dict | None = None) -> str:
    """Lay out a small checkout at ``root``; ``sizes``, ``{rel: changes}``,
    cuts further (the recorded traces); returns ``root``."""
    layout(root)
    shrink(root, sizes)
    return root


def chips(root: str, workload: str) -> int:
    """The chips the cell asks for."""
    return {w["name"]: w["chips"]
            for w in _bench(root)["workloads"]}[workload]


def faults(root: str, workload: str) -> list:
    """The faults of the cell's engine, from its faults file."""
    engine = run.find_cell(_bench(root), workload, root)["config"]["engine"]
    return run.load_module(os.path.join(root, NAME, TESTS, "faults",
                                        engine + ".py")).FAULTS


def fault_ref(fault) -> list[str]:
    """A fault as :func:`run_small` takes it across processes: its file and
    its name."""
    return [inspect.getsourcefile(fault), fault.__name__]


def on_devices(n: int, fn, *args):
    """``fn(*args)`` in this process where JAX has ``n`` devices or more;
    otherwise in a child interpreter on ``n`` CPU devices (``JAX_PLATFORMS=
    cpu``, ``--xla_force_host_platform_device_count``). ``fn`` is a
    module-level function of a file; its arguments and its result go
    through JSON."""
    import jax

    if n <= len(jax.devices()):
        return fn(*args)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, os.path.join(ROOT, "src"),
                    os.environ.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         inspect.getsourcefile(fn), fn.__name__, json.dumps(args)],
        env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=CHILD_TIMEOUT_S)
    if proc.returncode:
        raise RuntimeError(f"{fn.__name__} on {n} CPU devices exited "
                           f"{proc.returncode}:\n{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_small(root: str, workload: str, seed: int, fault=None) -> dict:
    """One run of ``workload`` in the small checkout at ``root``, skipping
    only the harness's look for a chip: sound, with ``fault``
    (:func:`fault_ref`) planted, or with the control (:data:`CONTROL`): the
    program's own bfloat16 storage path, the step below the float32 the
    configuration states, in the program's place."""
    import pytest

    with pytest.MonkeyPatch.context() as mp:
        hook = None
        if fault == CONTROL:
            found = run.find_cell(_bench(root), workload, root)
            engine = run.load_module(found["engine_path"])

            def hook(cell):
                return engine.Cell(found["config"], found["traffic"],
                                   cell.seed, policy="bf16",
                                   devices=run.devices(
                                       found["entry"]["chips"], False))
        elif fault:
            path, name = fault
            hook = getattr(run.load_module(path), name)(mp, workload)
        return run.run_cell(workload, seed, SECONDS, False, root=root,
                            require_tpu=False, cell_hook=hook)


def small_cell(root: str, workload: str, seed: int) -> dict:
    """What a cell of the small checkout builds: its counts, and the bytes
    a round of each of its work functions moves."""
    found = run.find_cell(_bench(root), workload, root)
    cell = run.load_module(found["engine_path"]).Cell(
        found["config"], found["traffic"], seed,
        devices=run.devices(found["entry"]["chips"], False))
    return {"agent_rounds_per_call": cell.agent_rounds_per_call,
            "rounds_per_call": cell.rounds_per_call,
            "bytes_per_round": {
                name: run.load_module(os.path.join(
                    run.HERE, "work", name + ".py")).bytes_per_round(**args)
                for name, args in cell.work.items()}}


if __name__ == "__main__":
    # the child of on_devices: <file> <function> <JSON arguments>
    result = getattr(run.load_module(sys.argv[1]), sys.argv[2])(
        *json.loads(sys.argv[3]))
    print(json.dumps(result), flush=True)
