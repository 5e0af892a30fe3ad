"""Reduction of a profiler trace to what the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` (nothing else) into two plain lists:

* ``Op``: one execution of an operation on a chip, from the ``XLA Ops``
  line of each ``/device:TPU:<n>`` plane. On a TPU v5e the event's name is
  the optimised HLO instruction (``%fusion.31 = f32[...] fusion(...)``);
  the events carry no name stack, and a ``while`` (one call's scan) is an
  event of its own that contains the events of its body, round by round.
  A Pallas kernel is a ``custom-call`` named after its ``pallas_call``
  wrapper (``%edge_scatter_pallas.10``), which is how a kernel is found;
* ``Span``: a host span the benchmark opened around its own calls
  (``chipbench.call``: the ``run_*`` entry until it returns;
  ``chipbench.block``: the wait in ``block_until_ready``).

Everything after that is arithmetic on the lists (:class:`Context`), so
the tests check it on hand-made events and on a recorded trace:

* the window: from the first benchmark span's start to the last one's end;
* leaves: the ops that contain no other op of their chip. A container,
  such as the ``while`` of a call's scan, is left out of everything
  below, so the idle time inside it counts as idle;
* busy time: the union of each chip's leaf intervals inside the window,
  averaged over the chips;
* a kernel's time: the summed device time of the custom calls named after
  it, averaged over the chips, or ``None`` when there is none;
* the breakdown: the leaves that took most device time, each with its
  phase, and the longest idle gaps, each named by the innermost host span
  it fell in (:class:`chipbench.phases.Phases`, from the trace's ``tf_op``
  map and the program's spans, which the context keeps).
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

OP_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
_DEVICE = re.compile(r"/device:TPU:(\d+)")
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


class Op(NamedTuple):
    chip: int
    name: str
    start_ns: float
    end_ns: float


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


def instruction(name: str) -> str:
    """``fusion.31`` of ``%fusion.31 = f32[...] fusion(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def describe(name: str) -> str:
    """A short name of an op: its instruction, opcode and result shape."""
    head, _, rest = name.partition(" = ")
    found = _OPCODE.search(" " + rest)
    if not found:
        return head.lstrip("%")
    shape = rest[:found.start()].strip()
    shape = shape if len(shape) <= 48 else shape[:45] + "..."
    return f"{head.lstrip('%')} {found.group(1)} {shape}"


def load(path: str) -> tuple[list[Op], list[Span]]:
    """The device ops and the benchmark's host spans of one trace file."""
    from jax.profiler import ProfileData

    ops, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        dev = _DEVICE.fullmatch(plane.name)
        for line in plane.lines:
            if dev and line.name == OP_LINE:
                ops.extend(Op(int(dev.group(1)), ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                           for ev in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend(Span(ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    return ops, spans


def find_trace(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} trace files in {trace_dir}")
    return paths[0]


def leaves(ops: list[Op]) -> list[Op]:
    """The ops that contain no other op of their chip. Ops are ordered by
    chip, start and longest first, so a container comes just before the
    first op it holds."""
    ops = sorted(ops, key=lambda o: (o.chip, o.start_ns, -o.end_ns))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if not (nxt and nxt.chip == o.chip
                    and nxt.start_ns >= o.start_ns
                    and nxt.end_ns <= o.end_ns)]


def merge(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Context:
    """What a per-layer metric reader gets: the reduced trace of the
    window, and the run's own counts and work (``{work function:
    its arguments}``, see ``chipbench/work``); and, kept as they are for
    :class:`chipbench.phases.Phases`, the trace's ``{op name: tf_op}``
    (:func:`chipbench.xplane.tf_ops`) and the program's host spans
    (:func:`chipbench.phases.program_spans`). Neither changes the window,
    the ops or the busy time."""

    def __init__(self, ops: list[Op], spans: list[Span], *, calls: int,
                 rounds: int, host_call_s: list, work: dict, peaks: dict,
                 tf_ops: dict | None = None,
                 program_spans: list[Span] | None = None):
        if not spans:
            raise ValueError("the trace holds none of the benchmark's spans")
        self.tf_ops = dict(tf_ops or {})
        self.program_spans = list(program_spans or [])
        self.t0 = min(s.start_ns for s in spans)
        self.t1 = max(s.end_ns for s in spans)
        self.spans = sorted(spans, key=lambda s: s.start_ns)
        self.ops = [o._replace(start_ns=max(o.start_ns, self.t0),
                               end_ns=min(o.end_ns, self.t1))
                    for o in leaves(ops)
                    if o.end_ns > self.t0 and o.start_ns < self.t1]
        self.chips = sorted({o.chip for o in self.ops}) or [0]
        self.calls, self.rounds = calls, rounds
        self.host_call_s = list(host_call_s)
        self.work, self.peaks = work, peaks
        self.window_s = (self.t1 - self.t0) * 1e-9
        self._busy = {c: merge((o.start_ns, o.end_ns) for o in self.ops
                               if o.chip == c) for c in self.chips}
        self.busy_s = sum(e - s for b in self._busy.values()
                          for s, e in b) * 1e-9 / len(self.chips)

    @classmethod
    def from_dir(cls, trace_dir: str, *, device_kind: str, **kw):
        from chipbench.work.peaks import peaks

        return cls.from_file(find_trace(trace_dir),
                             peaks=peaks(device_kind), **kw)

    @classmethod
    def from_file(cls, path: str, **kw):
        """The context of one trace file, with its ``tf_op`` map and the
        program's spans; ``kw``: the keyword arguments but those two."""
        from chipbench import phases, xplane

        return cls(*load(path), tf_ops=xplane.tf_ops(path),
                   program_spans=phases.program_spans(path), **kw)

    def kernel_s(self, kernel: str) -> float | None:
        """Device seconds of the custom calls named after ``kernel``,
        averaged over the chips; ``None`` if there is none."""
        pat = re.compile(re.escape(kernel) + r"(\.\d+)?")
        hit = [o for o in self.ops if pat.fullmatch(instruction(o.name))]
        if not hit:
            return None
        return sum(o.end_ns - o.start_ns for o in hit) * 1e-9 / len(
            self.chips)

    def breakdown(self, top: int = 10) -> dict:
        """The ``top`` leaves by device time (per chip), each with its
        phase where the ``tf_op`` map gives one, and the ``top`` longest
        idle gaps of the first chip, each named by the innermost host span,
        the program's or the benchmark's, its middle fell in
        (:meth:`chipbench.phases.Phases.breakdown`)."""
        from chipbench.phases import Phases

        b = Phases.of(self).breakdown(top)
        return {"device_ops": b["device_ops"], "idle_gaps": b["idle_gaps"]}
