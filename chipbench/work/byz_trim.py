"""Bytes the Byzantine trim of one gossip round must move through HBM.

Per receiver the trim reads its padded in-neighbour list (``deg`` int32
indices, ``deg`` validity flags, ``deg`` Byzantine-sender flags), the
statistics ``r`` of P = m * m pair coordinates (read once: an
implementation that keeps ``r`` on chip gathers from there), and writes
the survivor sum (P, float32) and the kept count (float32). The attack's
messages are left out: they can be made where they are used. This is the
bytes bound only; the trim's compare-and-select work is not counted.
"""


def bytes_per_round(N: int, deg: int, P: int, K: int = 1,
                    storage_bytes: int = 4) -> int:
    per_receiver = deg * (4 + 1 + 1) + P * storage_bytes + P * 4 + 4
    return K * N * per_receiver
