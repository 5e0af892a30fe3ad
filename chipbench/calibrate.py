#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python3 chipbench/calibrate.py --workload <name> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--out readings.json]

For each seed it builds the cell as a run does (inputs from the seed, the
program's state), makes as many calls as a run compares (the workload
file's ``calls``, calls 1, 2, ... with a run's call seeds), and compares
each with the plain reference: the program's readings, whose largest over
a dozen seeds or more is the lower reading of each limit. For each control
seed it does the same with the control in the program's place: the
program's own lower-precision path (``ExecutionPlan(policy="bf16")``:
bfloat16 storage of every persistent value, the step below the float32
the configuration states), whose smallest reading is the upper one; with
``--reference-control`` the control is the plain reference in bfloat16
storage instead, for a program whose own bfloat16 path cannot run there.
All in one process, so the program compiles once per precision. It runs on the
machine it is started on and does not look for a chip; the benchmark's own
runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import run  # noqa: E402

CONTROL_POLICY = "bf16"


def readings(found: dict, seed: int, policy, reference: bool = False) -> list:
    """One seed's readings: of the program (``policy=None``), of its own
    lower-precision path (``policy="bf16"``), or with ``reference`` of the
    reference in bfloat16 put in the program's place."""
    import jax

    engine = run.load_module(found["engine_path"])
    cell = engine.Cell(found["config"], found["traffic"], seed,
                       policy=policy)
    kept = []
    for i in range(1, found["check"]["calls"] + 1):
        out = cell.reference_control(i) if reference else cell.call(i)
        jax.block_until_ready(out)
        kept.append((i, out if reference else cell.keep(out)))
        del out
    cell.free()
    return cell.check(kept)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--reference-control", action="store_true",
                    help="the control is the reference in bfloat16, for a "
                         "program whose own bfloat16 path cannot run")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    run.enable_compile_cache()
    found = run.find_cell(run.load_json(os.path.join(run.ROOT,
                                                     "BENCHMARK.json")),
                          args.workload)
    limits = found["check"]["limits"]
    table = {"program": {}, "control": {}}
    for side, seeds, policy in (("program", args.seeds, None),
                                ("control", args.control_seeds,
                                 CONTROL_POLICY)):
        for seed in seeds:
            t0 = time.perf_counter()
            got = readings(found, seed, policy,
                           side == "control" and args.reference_control)
            table[side][seed] = got
            print(f"{side} seed {seed} ({time.perf_counter() - t0:.1f} s): "
                  + " ".join(f"{k}={run.worst([g[k] for g in got])!r}"
                             for k in got[0]), flush=True)
    for side in table:
        if table[side]:
            vals = {k: [run.worst([g[k] for g in got])
                        for got in table[side].values()] for k in limits}
            agg = max if side == "program" else min
            print(f"{side} {'largest' if agg is max else 'smallest'}: "
                  + " ".join(f"{k}={agg(v)!r}" for k, v in vals.items()),
                  flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
