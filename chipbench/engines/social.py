"""Driver of the social-learning cells: Algorithm 3 through the program's
public ``repro.core.social.run_social_runtime``.

The configuration file gives the deployment (the hierarchy, the signal
model, the links); the traffic file gives what one call of the closed loop
asks for (``scenarios_per_call`` scenarios of ``rounds_per_call`` rounds)
and the fault plane it runs under (``faults``: Gilbert-Elliott links,
churn, parameter-server crashes; ``null`` for i.i.d. drops only).
The set-up builds the graph and the signal model from the run's seed with
the program's public builders, as a user does. Each call starts from the
zero accumulator with its own seed, as a researcher's run of a fresh
scenario does.

What a call's answer is compared on (``check``), each against the plain
reference (``chipbench/reference/social.py``) run on its own copy of the
inputs (``chipbench/inputs.py``, the same seed) and the call's seed: the final beliefs (``belief_gap``, the largest absolute
difference), the final ``z / m`` (``z_over_m_gap``, the largest absolute
difference over the largest magnitude of the reference's), and the
per-round worst log-ratio curve (``curve_gap``, the largest absolute
difference). Together they cover the consensus edge step, the innovation,
the belief, the parameter server's fusion and, where it is on, the fault
plane.
"""
from __future__ import annotations

import numpy as np

from chipbench import inputs

NUMBERS = ("belief_gap", "z_over_m_gap", "curve_gap")


def fault_params(f: dict | None):
    """The reference's fault plane from the traffic file's ``faults``:
    ``(p_gb, p_bg, drop_bad, leave_prob, join_prob, ps_crash_prob)`` as
    float32, or ``None``. A Gilbert-Elliott chain of mean burst ``b``
    rounds and bad fraction ``f`` leaves the bad state with ``1 / b`` and
    enters it with ``f / (b (1 - f))``."""
    if f is None:
        return None
    p_bg = 1.0 / f["mean_burst"]
    p_gb = f["bad_frac"] * p_bg / (1.0 - f["bad_frac"])
    return np.asarray([p_gb, p_bg, f["drop_bad"], f["leave_prob"],
                       f["join_prob"], f["ps_crash_prob"]], np.float32)


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 policy: str | None = None, devices=None):
        from repro.core.graphs import hier_edge_list
        from repro.core.plan import ExecutionPlan
        from repro.core.signals import make_confused_model
        from repro.core.social import social_runtime_from_edge_list

        if traffic["scenarios_per_call"] != 1:
            raise ValueError("run_social_runtime runs one scenario per call")
        if (config["topology"], config["representative"]) != ("complete",
                                                              "first"):
            raise ValueError("the reference knows complete sub-networks "
                             "with the first agent as representative only")
        self.seed = seed
        self.config = config
        self.n = config["network_size"]
        self.M = config["networks"]
        self.N = self.M * self.n
        self.m, self.S = config["hypotheses"], config["signals"]
        self.truth = config["truth"]
        self.drop, self.gamma, self.B = (config["drop_prob"],
                                         config["gamma_period"], config["B"])
        self.T = traffic["rounds_per_call"]
        # the program's own builders, as a user of it calls them
        el, rep = hier_edge_list([self.n] * self.M, config["topology"],
                                 rep_choice=config["representative"])
        self.E = int(el.src.shape[0])
        self._rt = social_runtime_from_edge_list(
            el, rep, drop_prob=self.drop, gamma_period=self.gamma, B=self.B)
        self._model = make_confused_model(
            N=self.N, m=self.m, S=self.S, truth=self.truth,
            confusion=config["confusion"], sharpness=config["sharpness"],
            seed=seed)
        self.faults = fault_params(traffic.get("faults"))
        program_faults = None
        if self.faults is not None:
            from repro.core.faults import gilbert_elliott_model
            f = traffic["faults"]
            program_faults = gilbert_elliott_model(
                f["mean_burst"], f["bad_frac"], drop_bad=f["drop_bad"],
                leave_prob=f["leave_prob"], join_prob=f["join_prob"],
                ps_crash_prob=f["ps_crash_prob"])
        self._plan = ExecutionPlan(store="log_ratio", policy=policy,
                                   faults=program_faults)
        self.agent_rounds_per_call = self.N * self.T
        self.rounds_per_call = self.T
        size = {"N": self.N, "K": 1,
                "storage_bytes": 2 if policy == "bf16" else 4}
        self.work = {"pushsum_edge": dict(size, E=self.E, d=self.m),
                     "social_innov": dict(size, m=self.m, S=self.S)}

    def call(self, i: int):
        from repro.core.social import run_social_runtime

        return run_social_runtime(self._model, self._rt, self.M, self.T,
                                  seed=inputs.call_seed(self.seed, i),
                                  plan=self._plan)

    @staticmethod
    def keep(out):
        """What the check reads of a call's answer (the rest is freed)."""
        return out.beliefs, out.final_state.z, out.final_state.m, \
            out.log_ratio

    def free(self):
        """Drop the program's device state before the reference runs."""
        self._rt = self._model = None

    def _reference_inputs(self):
        """The reference's own inputs, made from the seed by
        ``chipbench/inputs.py`` and not taken from the program."""
        import jax.numpy as jnp

        src, dst, rep = inputs.complete_blocks(self.M, self.n)
        tables = inputs.confused_tables(
            self.N, self.m, self.S, self.config["confusion"],
            self.config["sharpness"], self.seed)
        return [jnp.asarray(a) for a in (src, dst, rep, tables)]

    def _reference(self, args, i: int, dtype=None):
        import jax.numpy as jnp
        from chipbench.reference import social as ref

        return ref.run(*args, jnp.uint32(inputs.call_seed(self.seed, i)),
                       jnp.float32(self.drop), jnp.int32(self.gamma),
                       jnp.int32(self.B),
                       None if self.faults is None
                       else jnp.asarray(self.faults),
                       T=self.T, truth=self.truth, M=self.M,
                       dtype=dtype or jnp.float32)

    def reference_control(self, i: int):
        """Call ``i`` answered by the reference in bfloat16 storage, in the
        form ``keep`` gives (``z / m`` with a unit mass): the control where
        the program's own bfloat16 path cannot run."""
        import jax.numpy as jnp

        mu, zm, curve = self._reference(self._reference_inputs(), i,
                                        jnp.bfloat16)
        return mu, zm, jnp.ones((self.N,), jnp.float32), curve

    def check(self, kept: list) -> list:
        """Each kept call's numbers, for the kept calls ``[(i, keep)]``."""
        import jax

        args = self._reference_inputs()
        out = []
        for i, (beliefs, z, mass, curve) in kept:
            beliefs, z, mass, curve = (np.asarray(x, np.float32)
                                       for x in (beliefs, z, mass, curve))
            mu_r, zm_r, curve_r = jax.device_get(self._reference(args, i))
            zm = z / np.maximum(mass, 1e-30)[:, None]
            out.append({
                "belief_gap": float(np.abs(beliefs - mu_r).max()),
                "z_over_m_gap": float(np.abs(zm - zm_r).max()
                                      / np.abs(zm_r).max()),
                "curve_gap": float(np.abs(curve - curve_r).max()),
            })
        return out
