"""Driver of the Byzantine cells: Algorithm 2 through the program's public
``repro.core.sweeps.run_byzantine_grid``.

The configuration file gives the deployment (complete sub-networks, the
Byzantine agents and their attack, F, the fusion period, the signal
model); the traffic file gives what one call of the closed loop asks for:
one Monte-Carlo grid of ``scenarios_per_call`` seeds of
``rounds_per_call`` rounds, all with the same F (so the program's static-F
trim path serves it). The set-up builds the hierarchy and the signal model
from the run's seed with the program's public builders, as a user does.

What a call's answer is compared on (``check``), against the plain
reference (``chipbench/reference/byzantine.py``) run on its own copy of
the inputs (``chipbench/inputs.py``, the same seed) and the same scenario
seeds: the final pairwise statistics of every scenario (``r_gap``,
the largest absolute difference over the largest magnitude of the
reference's), which every round's innovation, trimmed gossip and trimmed
fusion feed: agents of healthy networks (the set C) gossip, and the
representatives of the networks outside C adopt the fusion's value. The
per-round decisions, a readout of those statistics, are counted where
they differ (``decision_miss``) and reported, not held to a limit: a
flip at a near-tie is rounding, and the lower-precision control moves
that count too little to separate it from rounding.
"""
from __future__ import annotations

import numpy as np

from chipbench import inputs

NUMBERS = ("r_gap",)


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 policy: str | None = None, devices=None):
        from repro.core import attacks
        from repro.core.byzantine import ByzantineConfig
        from repro.core.graphs import make_hierarchy
        from repro.core.plan import ExecutionPlan
        from repro.core.signals import make_confused_model

        if config["attack"] != "large_value":
            raise ValueError(f"no reference for attack {config['attack']!r}")
        if config["topology"] != "complete":
            raise ValueError("the reference knows complete sub-networks only")
        self.seed = seed
        self.config = config
        self.n = config["network_size"]
        self.N = config["N"]
        self.M = self.N // self.n
        self.m, self.S = config["hypotheses"], config["signals"]
        self.truth = config["truth"]
        self.F, self.gamma = config["F"], config["gamma_period"]
        self.magnitude = config["attack_magnitude"]
        self.K = traffic["scenarios_per_call"]
        self.T = traffic["rounds_per_call"]
        # the program's own builders, as a user of it calls them
        topo = make_hierarchy([self.n] * self.M, config["topology"],
                              rep_choice="first")
        self._cfg = ByzantineConfig(
            topo=topo, F=self.F, byz=tuple(config["byzantine"]),
            gamma_period=self.gamma,
            attack=attacks.large_value(self.magnitude))
        self._model = make_confused_model(
            N=self.N, m=self.m, S=self.S, truth=self.truth,
            confusion=config["confusion"], sharpness=config["sharpness"],
            seed=seed)
        self._plan = ExecutionPlan(store="decisions", policy=policy)
        self.agent_rounds_per_call = self.N * self.K * self.T
        self.rounds_per_call = self.T
        self.work = {"byz_trim": {
            "N": self.N, "deg": self.n - 1, "P": self.m * self.m,
            "K": self.K, "storage_bytes": 2 if policy == "bf16" else 4}}

    def seeds(self, i: int) -> list:
        base = inputs.call_seed(self.seed, i)
        return [(base * self.K + k) % inputs.SEED_SPACE
                for k in range(self.K)]

    def call(self, i: int):
        from repro.core.sweeps import run_byzantine_grid

        return run_byzantine_grid(self._model, [self._cfg], self.T,
                                  seeds=self.seeds(i), plan=self._plan)

    @staticmethod
    def keep(out):
        """What the check reads of a call's answer."""
        return out.r, out.decisions

    def free(self):
        """Drop the program's device state before the reference runs."""
        self._cfg = self._model = None

    def check(self, kept: list) -> list:
        """Each kept call's numbers, for the kept calls ``[(i, keep)]``."""
        import jax
        import jax.numpy as jnp
        from chipbench.reference import byzantine as ref

        # the reference's own inputs, made from the seed
        tables = inputs.confused_tables(
            self.N, self.m, self.S, self.config["confusion"],
            self.config["sharpness"], self.seed)
        byz = np.zeros(self.N, bool)
        byz[list(self.config["byzantine"])] = True
        in_c = np.repeat(ref.healthy(tables, byz, self.M, self.n, self.F),
                         self.n)
        args = [jnp.asarray(a) for a in (tables, byz, in_c)]

        def one(seed):
            return ref.run(*args, seed, jnp.int32(self.gamma),
                           jnp.float32(self.magnitude), T=self.T,
                           truth=self.truth, n=self.n, F=self.F)

        batch = jax.jit(jax.vmap(one))
        out = []
        for i, (r, decisions) in kept:
            r_ref, dec_ref = jax.device_get(
                batch(jnp.asarray(self.seeds(i), jnp.uint32)))
            r = np.asarray(r, np.float32)
            out.append({
                "r_gap": float(np.abs(r - r_ref).max()
                               / np.abs(r_ref).max()),
                "decision_miss": float(
                    (np.asarray(decisions) != dec_ref).sum()),
            })
        return out
