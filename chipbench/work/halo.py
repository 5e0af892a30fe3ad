"""Bytes each chip sends over the chip-to-chip interconnect in one round's
halo of the edge-partitioned push-sum.

Each of the ``S`` chips holds its shard's (N, d + 1) float32 receiver
partials (the value columns and the mass), and the halo sums them over the
chips (``psum``). A ring all-reduce, the least any all-reduce can do on a
link, sends ``(S - 1) / S`` of the array in its reduce-scatter half and as
much again in its all-gather half: ``2 (S - 1) / S N (d + 1) 4`` bytes a
chip.
"""


def bytes_per_round(N: int, d: int, S: int) -> int:
    return 2 * (S - 1) * N * (d + 1) * 4 // S
