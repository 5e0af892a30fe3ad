"""Bytes the consensus edge step of one round must move through HBM.

The step (robust push-sum delivery and integration) reads the edge index
``src``/``dst`` (int32 each) and the per-edge relay state ``rho`` of
``D = d + 1`` columns (value and mass), writes ``rho`` back, reads the
node-indexed staged send ``sigma`` (N, D) once, and writes each receiver's
(N, D) increment sum in the accumulation dtype (float32).

This is a lower bound that every implementation must pay: the live mask
is left out (it can be drawn where it is used), and so is a per-edge copy
of ``sigma[src]`` (an implementation that keeps ``sigma`` on chip reads it
once). ``storage_bytes`` is the bytes of one stored state element.
"""


def bytes_per_round(N: int, E: int, d: int, K: int = 1,
                    storage_bytes: int = 4) -> int:
    D = d + 1
    per_edge = 2 * 4 + 2 * D * storage_bytes
    per_node = D * storage_bytes + D * 4
    return K * (E * per_edge + N * per_node)
