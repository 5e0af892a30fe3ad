#!/usr/bin/env python3
"""Record the small chip traces the CPU tests read, on one TPU:

    python3 chipbench/tests/record.py --out chipbench/tests/data

Each is a cell of the benchmark cut to a few agents and rounds (a small
checkout, ``chipbench/tests/small.py``), traced for two calls of its closed
loop under the benchmark's spans by :func:`chipbench.phases.profile`, with
the profiler's Python tracer off (the program's and the benchmark's spans
are kept; the Python function events would take most of the file):

* ``social-small-faults``: ``social-1m-faults`` at N = 1024 (128
  sub-networks), E = 7168, two calls of 4 rounds;
* ``byzantine-small``: ``byzantine-4k-grid`` at N = 64, K = 4 scenarios,
  two calls of 20 rounds.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench.tests import small  # noqa: E402

SEED = 2147483001
TRACES = {
    "social-small-faults": ("social-1m-faults", {
        "configs/hier-social-1m.json": {"networks": 128},
        "traffic/faults-1x8.json": {"rounds_per_call": 4}}),
    "byzantine-small": ("byzantine-4k-grid", {
        "configs/hier-byzantine-4k.json": {"N": 64},
        "traffic/grid-32x100.json": {"scenarios_per_call": 4,
                                     "rounds_per_call": 20}}),
}


def record(name: str, out: str, require_tpu: bool = True) -> dict:
    """Record trace ``name`` into ``out/<name>.xplane.pb``."""
    import jax

    from chipbench import phases

    workload, sizes = TRACES[name]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as root:
        small.checkout(root, sizes)
        path, ph, call_s, _ = phases.profile(
            workload, SEED, os.path.join(root, "trace"), calls=2, root=root,
            require_tpu=require_tpu, options=options)
        dest = os.path.join(out, name + ".xplane.pb")
        shutil.copy(path, dest)
    return {"trace": dest, "bytes": os.path.getsize(dest),
            "call_s": call_s, "metrics": ph.metrics()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    for name in TRACES:
        print(json.dumps(record(name, args.out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
