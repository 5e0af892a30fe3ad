"""The check that decides ``correct``: a sound run passes it, and a run
with its timed path broken, or with the control (the program's bfloat16
storage path) in its place, does not. Each cell runs end to end on the
CPU at a small size, skipping only the harness's look for a chip."""
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench.tests import small
from chipbench import run

BENCH = run.load_json(os.path.join(small.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]


def _engine(workload):
    return run.find_cell(BENCH, workload)["config"]["engine"]


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


def _run(root, workload, hook=None, seed=2 ** 31 + 3):
    return run.run_cell(workload, seed, 0.2, False, root=root,
                        require_tpu=False, cell_hook=hook)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(root, workload):
    res = _run(root, workload)
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert list(res)[-1] == "checks"
    assert all(v["value"] <= v["limit"] for v in res["checks"].values())


# --- the faults a cell can have; each must come out not correct ---

def _exchange_returns_state(monkeypatch, workload):
    """The exchange step returns its state unchanged: push-sum delivers
    nothing (social), the trimmed gossip hears no neighbour (Byzantine)."""
    from repro.core import social, sweeps

    if _engine(workload) == "social":
        monkeypatch.setattr(social, "sparse_pushsum_step",
                            lambda state, *a, **k: state)
    else:
        monkeypatch.setattr(
            sweeps, "_sparse_gossip",
            lambda key, t, r, rt, F, **k: (jnp.zeros_like(r),
                                           jnp.zeros(r.shape[:1], r.dtype)))
    return None


def _half_the_batch(monkeypatch, workload):
    """Half of the batch left out: the second half of the edges carries
    nothing (social), the second half of the grid's seeds repeats the
    first half (Byzantine)."""
    def hook(cell):
        if _engine(workload) == "social":
            valid = cell._rt.valid
            cell._rt = cell._rt._replace(
                valid=valid.at[valid.shape[0] // 2:].set(False))
        else:
            from repro.core.sweeps import run_byzantine_grid

            def call(i):
                half = cell.seeds(i)[: cell.K // 2]
                return run_byzantine_grid(cell._model, [cell._cfg], cell.T,
                                          seeds=(half * 2)[: cell.K],
                                          plan=cell._plan)
            cell.call = call
        return cell
    return hook


def _ps_fusion_broken(monkeypatch, workload):
    """The parameter server's fusion broken: left out (social: the
    representatives keep their own values), or its trim dropped
    (Byzantine: the pool's F largest and F smallest answers, a Byzantine
    representative's among them, are averaged in)."""
    from repro.core import hps, social

    if _engine(workload) == "social":
        monkeypatch.setattr(social, "hps_fusion",
                            lambda z, m, *a, **k: (z, m))
    else:
        pool = hps.ps_trimmed_pool
        monkeypatch.setattr(hps, "ps_trimmed_pool",
                            lambda vals, valid, F, **k: pool(vals, valid, 0,
                                                             **k))
    return None


def _answer_altered(monkeypatch, workload):
    """One answer altered where it is produced."""
    def hook(cell):
        keep = cell.keep

        def altered(out):
            kept = list(keep(out))
            if _engine(workload) == "social":
                kept[0] = kept[0].at[0].set(kept[0][0][::-1])
            else:
                kept[0] = kept[0].at[0, 5].multiply(-1.0)
            return tuple(kept)
        cell.keep = altered
        return cell
    return hook


@pytest.mark.parametrize("fault", [_exchange_returns_state, _half_the_batch,
                                   _ps_fusion_broken, _answer_altered])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(root, fresh_programs, monkeypatch,
                                          workload, fault):
    res = _run(root, workload, fault(monkeypatch, workload))
    assert res["correct"] is False and res["failed"] >= 1, res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(root, workload):
    """The control: the program's own bfloat16 storage path, the step
    below the float32 the configuration states, in the program's place."""
    def hook(cell):
        found = run.find_cell(run.load_json(os.path.join(
            root, "BENCHMARK.json")), workload, root)
        engine = run.load_module(found["engine_path"])
        return engine.Cell(found["config"], found["traffic"], cell.seed,
                           policy="bf16")
    res = _run(root, workload, hook)
    assert res["correct"] is False, res["checks"]
