"""The reference's inputs (``chipbench/inputs.py``) are the program's: its
own copies of the program's builders draw the same graph and signal model
from the same seed (CPU only; nothing here touches a TPU)."""
import os

import numpy as np
import pytest

from chipbench.tests import small
from chipbench import inputs, run

BENCH = run.load_json(os.path.join(small.ROOT, "BENCHMARK.json"))


def _config(name):
    conf = {c["name"]: c for c in BENCH["configs"]}[name]
    return run.load_json(os.path.join(small.ROOT, conf["file"]))


def test_complete_blocks_are_the_programs_hierarchy():
    from repro.core.graphs import hier_edge_list, make_hierarchy

    el, rep = hier_edge_list([8] * 40, "complete", rep_choice="first")
    src, dst, rep_ref = inputs.complete_blocks(40, 8)
    # the same edges in the same order: edge e draws the e-th link uniform
    assert np.array_equal(el.src, src) and np.array_equal(el.dst, dst)
    assert np.array_equal(rep, rep_ref)
    adj = np.zeros((320, 320), bool)
    adj[src, dst] = True
    assert np.array_equal(make_hierarchy([8] * 40, "complete").adj, adj)


@pytest.mark.parametrize("N,confusion,seed", [(96, 0.75, 1),
                                              (4096, 0.15, 2 ** 31 + 3),
                                              (3, 1.0, 7)])
def test_confused_tables_are_the_programs_signal_model(N, confusion, seed):
    from repro.core.signals import make_confused_model

    model = make_confused_model(N=N, m=3, S=4, truth=0, confusion=confusion,
                                sharpness=2.0, seed=seed)
    assert np.array_equal(np.asarray(model.tables),
                          inputs.confused_tables(N, 3, 4, confusion, 2.0,
                                                 seed))


@pytest.mark.parametrize("N", [_config("hier-byzantine-4k")["N"],
                               run.load_json(small.sizes_file(
                                   small.ROOT, "hier-byzantine-4k.json"))
                               ["N"]])
def test_byzantine_deployment_has_networks_outside_c(N):
    """The reference's healthy set is the program's, and on the seed the
    tests run, some networks fall outside C (so the fusion moves them) and
    both Byzantine agents' networks are in C (so the attack reaches the
    trimmed gossip)."""
    from repro.core.byzantine import healthy_networks
    from repro.core.graphs import make_hierarchy
    from repro.core.signals import make_confused_model
    from chipbench.reference.byzantine import healthy

    conf = _config("hier-byzantine-4k")
    n, seed = conf["network_size"], 2 ** 31 + 3
    byz = np.zeros(N, bool)
    byz[conf["byzantine"]] = True
    model = make_confused_model(N=N, m=conf["hypotheses"],
                                S=conf["signals"], truth=conf["truth"],
                                confusion=conf["confusion"],
                                sharpness=conf["sharpness"], seed=seed)
    program = healthy_networks(make_hierarchy([n] * (N // n), "complete"),
                               byz, conf["F"], model)
    ref = healthy(inputs.confused_tables(N, conf["hypotheses"],
                                         conf["signals"], conf["confusion"],
                                         conf["sharpness"], seed),
                  byz, N // n, n, conf["F"])
    assert list(np.nonzero(ref)[0]) == list(program)
    assert 0 < (~ref).sum() < N // n
    assert all(ref[b // n] for b in conf["byzantine"])
