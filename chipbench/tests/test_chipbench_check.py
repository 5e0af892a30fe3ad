"""The check that decides ``correct``: a sound run passes it, and a run
with its timed path broken (each fault of the cell's engine, ``chipbench/
tests/faults/<engine>.py``), or with the control (the program's bfloat16
storage path) in its place, does not. Each cell runs end to end on the
CPU at a small size, skipping only the harness's look for a chip, on as
many CPU devices as it asks for (:func:`small.on_devices`)."""
import os

import jax
import pytest

from chipbench.tests import small
from chipbench import run

BENCH = run.load_json(os.path.join(small.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
FAULTS = [(w, f) for w in CELLS for f in small.faults(small.ROOT, w)]


@pytest.fixture
def root(tmp_path):
    return small.checkout(str(tmp_path))


@pytest.fixture
def fresh_programs():
    """Compiled programs of a broken path must not outlive the test."""
    from repro.core import sweeps

    jax.clear_caches()
    sweeps._BYZ_GRID_COMPILED.clear()
    yield
    jax.clear_caches()
    sweeps._BYZ_GRID_COMPILED.clear()


def _run(root, workload, fault=None, seed=2 ** 31 + 3):
    return small.on_devices(small.chips(root, workload), small.run_small,
                            root, workload, seed, fault)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(root, workload):
    res = _run(root, workload)
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert list(res)[-1] == "checks"
    assert all(v["value"] <= v["limit"] for v in res["checks"].values())


@pytest.mark.parametrize("workload, fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, f in FAULTS])
def test_broken_timed_path_is_not_correct(root, fresh_programs, workload,
                                          fault):
    res = _run(root, workload, small.fault_ref(fault))
    assert res["correct"] is False and res["failed"] >= 1, res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(root, workload):
    """The control: the program's own bfloat16 storage path, the step
    below the float32 the configuration states, in the program's place."""
    res = _run(root, workload, small.CONTROL)
    assert res["correct"] is False, res["checks"]
