"""Share of the HBM roofline, in percent, reached by a whole scan round:
the least time the bytes every round must move take at the chip's peak
bandwidth (the sum of the cell's work functions in ``chipbench/work``:
the consensus edge step and the innovation, or the Byzantine trim) over
the device busy time per round. The bytes are a lower bound of the
round's work and the time is all of it, so no implementation reads over
100 %."""
import importlib


def read(ctx):
    if not ctx.rounds or ctx.busy_s <= 0 or not ctx.work:
        return None
    least = sum(importlib.import_module(f"chipbench.work.{name}")
                .bytes_per_round(**args) for name, args in ctx.work.items())
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / (
        ctx.busy_s / ctx.rounds)
