"""The program's own measurement points in a profiler trace: the phases of
the scan round on the device, and the ``run_*`` entries' spans on the host.

The program opens one ``jax.named_scope`` per phase of a scan round, with
the same names in both scan cores (``repro.core.social._social_scan_core``,
``repro.core.byzantine._scan_core``), and the scopes do not nest:

* ``links``: the fault plane's draws and the round's edge mask;
* ``consensus``: the social engine's push-sum edge step (the ``sigma[src]``
  gather, the ``pushsum_edge`` kernel, and the finish: a gather at each
  receiver's run end, under the scope ``run_end_gather``, where the
  runtime's edge index is dst-sorted, and the ``segment_sum`` scatter
  elsewhere);
* ``gossip``: the Byzantine engine's trimmed gossip (the neighbour
  gathers, the ``byz_trim`` kernel, the update);
* ``innovation``: the signal draws and the accumulator update;
* ``fusion``: the parameter server's fusion;
* ``readout``: what the round stores (the worst log-ratio, the decisions).

Each device op's ``tf_op`` (:mod:`chipbench.xplane`) is its name stack; an
op whose stack holds exactly one phase name as a whole component has that
phase, any other op is unscoped (the copies XLA inserts, the ops outside the
scan, and an op whose name carries two different stacks).

On the host, ``run_social_runtime`` and ``run_byzantine_grid`` open
``jax.profiler.TraceAnnotation`` spans named ``repro.<entry>.<step>``
(``prepare``, and ``dispatch`` after it; ``repro.byz_grid.prepare`` holds
``fingerprint``, ``build`` on a cache miss, and ``batch``), nested inside
the benchmark's ``chipbench.call`` on one thread and on the device ops'
clock.

:class:`Phases` reads both on top of :class:`chipbench.trace.Context`,
whose window, ops and busy time it leaves as they are, and
:func:`profile` traces a cell's closed loop and keeps the trace file.
"""
from __future__ import annotations

import importlib
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from chipbench import trace  # noqa: E402
from chipbench.trace import Span  # noqa: E402

PHASES = ("links", "consensus", "gossip", "innovation", "fusion", "readout")
PROGRAM_PREFIX = "repro."
# the work function (``chipbench/work``) whose bytes each phase must move
ROOFLINES = {"edge_scatter_roofline": ("pushsum_edge", "consensus"),
             "byz_trim_roofline": ("byz_trim", "gossip")}


def phase_of(tf_op: str | None) -> str | None:
    """The phase named by a ``tf_op`` stack, or None."""
    if not tf_op:
        return None
    parts = set(tf_op.rstrip(":").split("/"))
    hit = [p for p in PHASES if p in parts]
    return hit[0] if len(hit) == 1 else None


def program_spans(path: str) -> list[Span]:
    """The program's host spans (``repro.*``) of one trace file."""
    from jax.profiler import ProfileData

    return [Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PROGRAM_PREFIX)]


class Phases:
    """The phase of each leaf op of a :class:`chipbench.trace.Context`, the
    program's host spans, and what the phase metrics read of them."""

    def __init__(self, ctx: trace.Context, tf_ops: dict,
                 spans: list[Span]):
        self.ctx = ctx
        self.phase = {o.name: phase_of(tf_ops.get(o.name)) for o in ctx.ops}
        self.spans = sorted(spans, key=lambda s: s.start_ns)

    @classmethod
    def of(cls, ctx: trace.Context):
        """The phases of a context that keeps its trace's ``tf_op`` map
        and program spans (:meth:`chipbench.trace.Context.from_file`)."""
        return cls(ctx, ctx.tf_ops, ctx.program_spans)

    @classmethod
    def from_file(cls, path: str, **kw):
        """``kw``: :class:`chipbench.trace.Context`'s keyword arguments."""
        return cls.of(trace.Context.from_file(path, **kw))

    def phase_s(self, phase: str) -> float | None:
        """Leaf device seconds of one phase in the window, averaged over
        the chips; None where no op has the phase."""
        ops = [o for o in self.ctx.ops if self.phase[o.name] == phase]
        if not ops:
            return None
        return sum(o.end_ns - o.start_ns for o in ops) * 1e-9 / len(
            self.ctx.chips)

    def ms_per_round(self, phase: str) -> float | None:
        t = self.phase_s(phase)
        return t / self.ctx.rounds * 1e3 if t and self.ctx.rounds else None

    def scoped_share(self) -> float | None:
        """Share of the busy time, in percent, in which an op with a phase
        ran; None where no op has one (a program without scopes)."""
        ctx = self.ctx
        if ctx.busy_s <= 0 or not any(self.phase.values()):
            return None
        scoped = sum(e - s for c in ctx.chips for s, e in trace.merge(
            (o.start_ns, o.end_ns) for o in ctx.ops
            if o.chip == c and self.phase[o.name]))
        return 100.0 * scoped * 1e-9 / len(ctx.chips) / ctx.busy_s

    def roofline(self, work: str, phase: str) -> float | None:
        """Share of the HBM roofline, in percent: the least time of the
        bytes ``work`` must move a round at the chip's peak bandwidth over
        the phase's device time a round."""
        ctx = self.ctx
        t = self.phase_s(phase)
        if not t or work not in ctx.work or not ctx.rounds \
                or "hbm_bytes_per_s" not in ctx.peaks:
            return None
        least = importlib.import_module(f"chipbench.work.{work}") \
            .bytes_per_round(**ctx.work[work])
        return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / (
            t / ctx.rounds)

    def host_prepare_ms_per_call(self) -> float | None:
        """Median over the window's calls of the ``repro.*.prepare`` span,
        in milliseconds; None where the program opens none."""
        spans = [s for s in self.spans if s.name.endswith(".prepare")
                 and s.start_ns >= self.ctx.t0 and s.end_ns <= self.ctx.t1]
        if not spans:
            return None
        return statistics.median(s.end_ns - s.start_ns for s in spans) * 1e-6

    def span_at(self, t_ns: float) -> str:
        """The innermost span, the program's or the benchmark's, that the
        host was in at ``t_ns``: the latest to start of those covering it."""
        covering = [s for s in self.ctx.spans + self.spans
                    if s.start_ns <= t_ns < s.end_ns]
        if not covering:
            return "between calls"
        return max(covering, key=lambda s: (s.start_ns, -s.end_ns)).name

    def split(self, start_ns: float, end_ns: float) -> dict[str, float]:
        """Seconds of ``[start_ns, end_ns)`` under each innermost span."""
        cuts = sorted({start_ns, end_ns} | {
            t for s in self.ctx.spans + self.spans
            for t in (s.start_ns, s.end_ns) if start_ns < t < end_ns})
        out: dict[str, float] = {}
        for a, b in zip(cuts, cuts[1:]):
            name = self.span_at((a + b) / 2)
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
        return out

    def call_idle(self) -> list[dict[str, float]]:
        """For each call, the device's idle seconds from the end of the
        previous call's wait (the window's start for the first) to the first
        op of the call's scan, split by the innermost span; empty where no
        op has a phase."""
        ctx = self.ctx
        calls = [s for s in ctx.spans if s.name == "chipbench.call"]
        blocks = [s for s in ctx.spans if s.name == "chipbench.block"]
        scan = sorted(o.start_ns for o in ctx.ops if self.phase[o.name])
        gaps = self._gaps()
        out = []
        for k, c in enumerate(calls):
            start = blocks[k - 1].end_ns if k else ctx.t0
            first = next((t for t in scan if t >= c.start_ns), None)
            if first is None:
                break
            idle: dict[str, float] = {}
            for s, e in gaps:
                s, e = max(s, start), min(e, first)
                for name, t in (self.split(s, e) if e > s else {}).items():
                    idle[name] = idle.get(name, 0.0) + t
            out.append(idle)
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The ``top`` leaves by device time (per chip), each with its phase
        after its name (``fusion.31 fusion f32[...] @consensus``), and the
        ``top`` longest idle gaps of the first chip, each named by the
        innermost span at its middle; all the window's idle time split by
        the innermost span over each part (``idle_by_span``), and
        :meth:`call_idle`."""
        ctx = self.ctx
        per_op: dict[str, float] = {}
        for o in ctx.ops:
            phase = self.phase[o.name]
            key = trace.describe(o.name) + (f" @{phase}" if phase else "")
            per_op[key] = per_op.get(key, 0.0) + (o.end_ns - o.start_ns)
        n = len(ctx.chips)
        device_ops = sorted(([k, v * 1e-9 / n] for k, v in per_op.items()),
                            key=lambda kv: -kv[1])[:top]
        gaps = sorted(self._gaps(), key=lambda g: g[0] - g[1])
        idle = [[self.span_at((s + e) / 2), (e - s) * 1e-9]
                for s, e in gaps[:top]]
        by_span: dict[str, float] = {}
        for s, e in gaps:
            for name, t in self.split(s, e).items():
                by_span[name] = by_span.get(name, 0.0) + t
        return {"device_ops": device_ops, "idle_gaps": idle,
                "idle_by_span": by_span, "call_idle": self.call_idle()}

    def _gaps(self) -> list[tuple[float, float]]:
        """The idle intervals of the first chip in the window."""
        ctx = self.ctx
        busy = trace.merge((o.start_ns, o.end_ns) for o in ctx.ops
                           if o.chip == ctx.chips[0])
        edges = [ctx.t0] + [x for iv in busy for x in iv] + [ctx.t1]
        return [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                if edges[k + 1] > edges[k]]

    def metrics(self) -> dict[str, float]:
        """Every phase metric this trace can read, by its name."""
        out = {f"{p}_ms_per_round": self.ms_per_round(p) for p in PHASES}
        out["scoped_share"] = self.scoped_share()
        out.update({name: self.roofline(*wp)
                    for name, wp in ROOFLINES.items()})
        out["host_prepare_ms_per_call"] = self.host_prepare_ms_per_call()
        return {k: v for k, v in out.items() if v is not None}


def profile(workload: str, seed: int, out_dir: str, *, seconds: float = 0.0,
            calls: int = 0, root: str | None = None, require_tpu: bool = True,
            options=None) -> tuple[str, Phases, list, list]:
    """Trace one cell's closed loop as ``chipbench/run.py --trace 1`` does
    (set-up and a warm call first, then back-to-back calls under the
    benchmark's spans) into ``out_dir``, for ``calls`` calls or until
    ``seconds`` have passed; ``options``: the profiler's
    ``jax.profiler.ProfileOptions``, its defaults if None. Returns the
    trace file, its :class:`Phases`, and each call's seconds and host
    seconds before the wait."""
    import jax

    from chipbench import run
    from chipbench.work.peaks import peaks

    root = root or run.ROOT
    found = run.find_cell(run.load_json(os.path.join(root, "BENCHMARK.json")),
                          workload, root)
    devs = run.devices(found["entry"]["chips"], require_tpu)
    run.enable_compile_cache()
    cell = run.load_module(found["engine_path"]).Cell(
        found["config"], found["traffic"], seed, devices=devs)
    jax.block_until_ready(cell.call(0))
    host_s, call_s = [], []
    jax.profiler.start_trace(out_dir, profiler_options=options)
    t0 = time.perf_counter()
    i = 0
    while True:
        i += 1
        c0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(run.SPAN_CALL):
            out = cell.call(i)
        c1 = time.perf_counter()
        with jax.profiler.TraceAnnotation(run.SPAN_BLOCK):
            jax.block_until_ready(out)
        c2 = time.perf_counter()
        host_s.append(c1 - c0)
        call_s.append(c2 - c0)
        del out
        if (calls and i >= calls) or (seconds and c2 - t0 >= seconds):
            break
    jax.profiler.stop_trace()
    path = trace.find_trace(out_dir)
    ph = Phases.from_file(
        path, calls=i, rounds=i * cell.rounds_per_call, host_call_s=host_s,
        work=cell.work,
        peaks=peaks(devs[0].device_kind) if require_tpu else {})
    return path, ph, call_s, host_s


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=(
        "Trace one cell on the chip and print its phase reading (one JSON "
        "line); the .xplane.pb stays in --out."))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    path, ph, call_s, host_s = profile(args.workload, args.seed, args.out,
                                       seconds=args.seconds)
    ctx = ph.ctx
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "trace": path,
        "trace_bytes": os.path.getsize(path), "calls": ctx.calls,
        "rounds": ctx.rounds, "window_s": ctx.window_s,
        "busy_ms_per_round": ctx.busy_s / ctx.rounds * 1e3,
        "metrics": ph.metrics(),
        "call_s": call_s, "host_s": host_s, "breakdown": ph.breakdown()}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
