"""Driver of the edge-partitioned push-sum cells: robust push-sum
(arXiv:2307.14952) over one overlay cut by edges over several chips,
through the program's public ``repro.core.sweeps.run_pushsum_sweep`` with
``ExecutionPlan(mesh=sweep_mesh(1, S, devices=...), graph_shards=S)``.

The configuration file gives the deployment (the Chord overlay of
``2^n_bits`` peers with ``fingers`` fingers each, ``d`` values a peer, the
links, ``graph_shards`` chips sharing the graph); the traffic file gives
what one call of the closed loop asks for (``scenarios_per_call``
scenarios of ``rounds_per_call`` rounds). The set-up builds the overlay
with the program's public builders (``graphs.chord_edge_list``,
``graphs.partition_edge_list``), as a user does, over a mesh of exactly
the devices it is given, and the peers' values from the run's seed as
data. Each call starts from those values with its own seed, as a
researcher's run of a fresh scenario does.

What a call's answer is compared on (``check``), against the plain
reference (``chipbench/reference/pushsum.py``) run on its own copy of the
overlay (``chipbench/inputs_chord.py``) and the call's seed: the final
``z / m`` (``ratio_gap``, the largest absolute difference over the
reference's largest magnitude) and the per-round error curve
(``err_gap``, the largest absolute difference). Together they cover the
mask draw and its window on every chip, the edge step on each shard and
the halo that sums the shards.
"""
from __future__ import annotations

import numpy as np

from chipbench import inputs, inputs_chord

NUMBERS = ("ratio_gap", "err_gap")


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 policy: str | None = None, devices=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.graphs import chord_edge_list, partition_edge_list
        from repro.core.plan import ExecutionPlan
        from repro.distributed.sharding import sweep_mesh

        if traffic["scenarios_per_call"] != 1:
            raise ValueError("a call runs one scenario")
        if config["occupancy"] != 1.0:
            raise ValueError("the builder makes a fully populated ring only")
        self.seed = seed
        self.S = config["graph_shards"]
        self.devices = list(devices or jax.devices()[:self.S])
        if len(self.devices) != self.S:
            raise ValueError(f"{self.S} graph shards need {self.S} devices, "
                             f"got {len(self.devices)}")
        self.n_bits, self.L = config["n_bits"], config["fingers"]
        self.N, self.d = 2 ** self.n_bits, config["d"]
        self.drop, self.B = config["drop_prob"], config["B"]
        self.T = traffic["rounds_per_call"]
        # the program's own builders, as a user of it calls them
        self._shards = partition_edge_list(
            chord_edge_list(self.n_bits, self.L), self.S)
        mesh = sweep_mesh(1, self.S, devices=self.devices)
        self._w = jax.device_put(
            inputs_chord.values(seed, self.N, self.d),
            NamedSharding(mesh, P()))
        self._plan = ExecutionPlan(mesh=mesh, graph_shards=self.S,
                                   policy=policy)
        self.agent_rounds_per_call = self.N * self.T
        self.rounds_per_call = self.T
        self.work = {
            "pushsum_edge": {"N": self.N, "E": self._shards.e_shard,
                             "d": self.d, "K": 1,
                             "storage_bytes": 2 if policy == "bf16" else 4},
            "halo": {"N": self.N, "d": self.d, "S": self.S}}

    def call(self, i: int):
        from repro.core.sweeps import run_pushsum_sweep

        return run_pushsum_sweep(
            self._w, self._shards, self.T, drop_probs=self.drop,
            seeds=inputs.call_seed(self.seed, i), B=self.B, plan=self._plan)

    @staticmethod
    def keep(out):
        """What the check reads of a call's answer (the rest is freed): the
        (1, N, d) final ratios and the (1, T) error curve, as the call left
        them (no device work in the window)."""
        return out.final_ratio, out.err

    def free(self):
        """Drop the program's device state before the reference runs."""
        self._shards = self._w = None

    def _reference_inputs(self):
        """The reference's own overlay and values, made from the seed by
        ``chipbench/inputs_chord.py`` and not taken from the program."""
        return (inputs_chord.chord_in_neighbours(self.n_bits, self.L),
                inputs_chord.values(self.seed, self.N, self.d))

    def _reference(self, args, i: int, dtype=None):
        import jax.numpy as jnp
        from chipbench.reference import pushsum as ref

        return ref.run(*args, inputs.call_seed(self.seed, i), self.drop,
                       self.B, T=self.T, dtype=dtype or jnp.float32,
                       devices=self.devices)

    def reference_control(self, i: int):
        """Call ``i`` answered by the reference in bfloat16 storage, in the
        form ``keep`` gives: the control where the program's own bfloat16
        path cannot run."""
        import jax.numpy as jnp

        ratio, err = self._reference(self._reference_inputs(), i,
                                     jnp.bfloat16)
        return ratio[None], err[None]

    def check(self, kept: list) -> list:
        """Each kept call's numbers, for the kept calls ``[(i, keep)]``."""
        import jax

        args = self._reference_inputs()
        out = []
        for i, (ratio, err) in kept:
            ratio, err = (np.asarray(x, np.float32)[0] for x in (ratio, err))
            ratio_r, err_r = jax.device_get(self._reference(args, i))
            out.append({
                "ratio_gap": float(np.abs(ratio - ratio_r).max()
                                   / np.abs(ratio_r).max()),
                "err_gap": float(np.abs(err - err_r).max()),
            })
        return out
