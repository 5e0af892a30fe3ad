"""Host time of ``run_pushsum_sweep``'s preparation, in milliseconds: the
median over the traced window's calls of the program's own span
``repro.pushsum.prepare`` (the plan, the partition's run-end windows, the
scenario grid, and the copy of the edge arrays from the host to the chips
that hold them), which runs before the dispatch. A program without the
span reads nothing."""
import statistics

SPAN = "repro.pushsum.prepare"


def read(ctx):
    spans = [s for s in ctx.program_spans if s.name == SPAN
             and s.start_ns >= ctx.t0 and s.end_ns <= ctx.t1]
    if not spans:
        return None
    return statistics.median(s.end_ns - s.start_ns for s in spans) * 1e-6
