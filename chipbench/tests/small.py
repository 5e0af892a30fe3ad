"""A small copy of the benchmark's checkout for the CPU tests.

:func:`checkout` lays out ``BENCHMARK.json`` and the cell files in a
temporary root, with each configuration and traffic file cut to a size the
CPU runs in seconds, and the code directories (engines, metrics) linked to
the real ones, so that ``run.run_cell(..., root=...)`` finds everything by
name exactly as it does in the real checkout.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# the CPU sizes: the same deployments with fewer agents and rounds (the
# Byzantine cell keeps 32 networks, so that some fall outside C)
SMALL = {
    "configs/hier-social-1m.json": {"networks": 32},
    "configs/hier-byzantine-4k.json": {"N": 256},
    "traffic/grid-32x100.json": {"scenarios_per_call": 4,
                                 "rounds_per_call": 30},
}


def checkout(root: str, small: dict = SMALL) -> str:
    """Lay out a small checkout at ``root``; returns ``root``."""
    name = os.path.basename(BENCH)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for sub in ("configs", "traffic", "workloads"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(root, name, sub))
    for sub in ("engines", "metrics"):
        os.symlink(os.path.join(BENCH, sub), os.path.join(root, name, sub))
    for rel, changes in small.items():
        path = os.path.join(root, name, rel)
        with open(path) as f:
            data = json.load(f)
        data.update(changes)
        with open(path, "w") as f:
            json.dump(data, f)
    return root
