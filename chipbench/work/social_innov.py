"""Bytes the innovation step of one round must move through HBM.

Per agent the step reads the accumulator ``z`` (m), the mass, the truth
row's inclusive CDF (S, float32) and the log-likelihood table (m, S,
float32), and writes ``z`` back and the belief ``mu`` (m, float32). The
signal uniforms are left out: they can be drawn where they are used.
``storage_bytes`` is the bytes of one stored state element.
"""


def bytes_per_round(N: int, m: int, S: int, K: int = 1,
                    storage_bytes: int = 4) -> int:
    per_agent = (2 * m * storage_bytes + storage_bytes + S * 4 + m * S * 4
                 + m * 4)
    return K * N * per_agent
