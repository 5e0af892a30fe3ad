"""The halo of the edge-partitioned push-sum in a profiler trace: the ops
under the program's ``jax.named_scope("halo")``, which it opens around the
sum of the shards' receiver partials over the chips (inside the round's
``consensus`` scope, so these ops count there too), and the chip's
interconnect peak.

XLA may run a collective as a pair of ops, ``<op>-start`` and
``<op>-done``, with other work or idle time between them; the collective
takes the whole span from the start op's start to its done op's end, not
the two ops' own time. A done op names its start op among its operands
(``%all-reduce-done.1 = ... all-reduce-done(... %all-reduce-start.1)``);
one that names none closes the earliest open start of its chip. Every
other ``halo`` op takes its own time. Spans that overlap count once.
"""
from __future__ import annotations

import json
import os
import re

from chipbench import trace

SCOPE = "halo"
ICI_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks_ici.json")
_OPERAND = re.compile(r"%([\w.-]+)")


def in_scope(tf_op: str | None) -> bool:
    """True where the ``tf_op`` stack holds the ``halo`` scope."""
    return bool(tf_op) and SCOPE in tf_op.rstrip(":").split("/")


def _opcode(name: str) -> str:
    return trace.describe(name).split(" ")[1] if " = " in name else ""


def intervals(ctx) -> dict[int, list[tuple[float, float]]]:
    """Each chip's disjoint intervals of halo time in the window."""
    ops = sorted((o for o in ctx.ops if in_scope(ctx.tf_ops.get(o.name))),
                 key=lambda o: (o.chip, o.start_ns))
    spans: dict[int, list] = {c: [] for c in ctx.chips}
    open_: dict[int, dict] = {}
    for o in ops:
        code = _opcode(o.name)
        starts = open_.setdefault(o.chip, {})
        if code.endswith("-start"):
            starts[trace.instruction(o.name)] = o.start_ns
        elif code.endswith("-done"):
            args = o.name.partition(" = ")[2]
            named = [a for a in _OPERAND.findall(args) if a in starts]
            key = named[0] if named else min(starts, key=starts.get,
                                             default=None)
            begin = starts.pop(key) if key is not None else o.start_ns
            spans[o.chip].append((begin, o.end_ns))
        else:
            spans[o.chip].append((o.start_ns, o.end_ns))
    return {c: trace.merge(s) for c, s in spans.items()}


def halo_s(ctx) -> float | None:
    """Halo seconds in the window, averaged over the chips; None where no
    op has the scope."""
    per_chip = intervals(ctx)
    if not any(per_chip.values()):
        return None
    return sum(e - s for iv in per_chip.values() for s, e in iv) * 1e-9 / \
        len(ctx.chips)


def ici_bytes_per_s(ctx) -> float | None:
    """The chip's interconnect peak, for the device kind whose table in
    ``peaks.json`` the context carries; None where none matches."""
    from chipbench.work.peaks import PEAKS_FILE

    with open(PEAKS_FILE) as f:
        kinds = [k for k, v in json.load(f).items() if v == ctx.peaks]
    with open(ICI_FILE) as f:
        table = json.load(f)
    return table[kinds[0]]["ici_bytes_per_s"] if kinds and kinds[0] in \
        table else None
