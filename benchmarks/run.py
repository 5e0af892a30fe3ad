"""Benchmark harness — one module per paper claim (the paper has no
numbered tables; each Theorem/Remark gets a benchmark).

Prints ``name,us_per_call,derived`` CSV rows, plus a §Roofline summary from
the latest dry-run results JSON if present (results/dryrun_single.json).

With ``--json-dir DIR`` each module additionally merge-updates a
machine-readable ``BENCH_<tag>.json`` (name -> {us_per_call, derived})
next to the CSV stream so the perf trajectory is tracked across PRs —
existing keys not re-measured in this invocation (e.g. a ``--smoke`` or
single-module run) are preserved, not clobbered:

    python -m benchmarks.run --json-dir results          # all modules
    python -m benchmarks.run pushsum_sweep               # one module, CSV
    python -m benchmarks.run --smoke --json-dir results  # fast CI subset

``--check PATH`` compares the freshly measured rows against the recorded
baseline (a BENCH_*.json file, or a directory whose BENCH_*.json files are
merged — the CI form) and exits non-zero if any shared name's
``us_per_call`` regressed by more than 25% — the perf gate:

    python -m benchmarks.run pushsum_sweep --smoke \\
        --check results/BENCH_pushsum_sweep.json
    python -m benchmarks.run --smoke --check results --json-dir results
"""
import argparse
import glob
import inspect
import json
import os
import sys

from . import hps_bench, social_learning, byzantine_bench, gamma_sweep
from . import aggregators_bench, pushsum_sweep, compile_cache
from . import merge_bench_json

MODULES = [
    ("hps", hps_bench),
    ("social", social_learning),
    ("byzantine", byzantine_bench),
    ("remark3", gamma_sweep),
    ("aggregators", aggregators_bench),
    ("pushsum_sweep", pushsum_sweep),
    # last: its jax.clear_caches() must not cost the other modules their
    # warm jits mid-run
    ("compile", compile_cache),
]

REGRESSION_FACTOR = 1.25


def _module_rows(mod, smoke: bool):
    """Call mod.rows(), passing smoke= only to modules that support it."""
    if smoke and "smoke" in inspect.signature(mod.rows).parameters:
        return list(mod.rows(smoke=True))
    return list(mod.rows())


def _check_regressions(baseline_path: str, baseline: dict,
                       measured: dict[str, tuple[float, str]],
                       factor: float = REGRESSION_FACTOR) -> int:
    """Compare measured us_per_call against the recorded baseline; return
    the number of >factor regressions (default the 25% gate). Rows present
    in the run but absent from the baseline are announced with a ``# NEW``
    line (so a fault-axis or other freshly-added row is visible in the gate
    output the first time it appears) but never counted as regressions.
    Skipped silently:
    names absent from the measured side,
    NaN rows, explicitly-skipped rows (``derived`` starting ``skipped=``,
    announced with a ``# SKIP`` line so the gate output shows what was not
    measured and why), and rows whose derived tag says ``mode=interpret`` —
    interpreter timings measure the Pallas interpreter, not the kernel,
    and jitter far beyond the gate budget.

    A baseline that shares NO row name with the measured set is a hard
    failure, not a pass: the gate would otherwise compare nothing and
    report success (renamed benchmarks, or --check pointed at the wrong
    artifacts). Keyed on the name intersection — NOT on the checked count,
    which legitimately drops to zero when every overlapping row is
    interpret-mode (the CPU CI lane)."""
    if not (set(baseline) & set(measured)):
        print(f"# perf check vs {baseline_path}: baseline holds "
              f"{len(baseline)} row(s) but NONE match the {len(measured)} "
              "measured name(s) — the gate compared nothing (renamed "
              "benchmarks? wrong --check path?)")
        return 1
    bad = checked = 0
    for name, (us, derived) in measured.items():
        if derived.startswith("skipped="):
            # explicit skip (e.g. sharded bench on a single-device host):
            # say so rather than silently dropping the row from the gate
            print(f"# SKIP {name}: {derived}")
            continue
        if name not in baseline:
            print(f"# NEW {name}: {us:.1f}us (no baseline row)")
            continue
        old = baseline[name].get("us_per_call")
        if old is None or not (old == old) or not (us == us):  # skip NaN
            continue
        if "mode=interpret" in derived:
            continue
        if "gate=off" in derived:
            # compile-time rows: XLA + disk wall, jitters beyond any
            # reasonable gate budget
            continue
        checked += 1
        if us > old * factor:
            print(f"# REGRESSION {name}: {us:.1f}us > "
                  f"{factor:.2f} * baseline {old:.1f}us")
            bad += 1
    if bad == 0:
        print(f"# perf check vs {baseline_path}: {checked} rows checked, "
              f"no >{(factor - 1) * 100:.0f}% regressions")
    return bad


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("only", nargs="?", default=None,
                    help="run a single module tag (hps, social, ..., "
                         "pushsum_sweep)")
    ap.add_argument("--json-dir", default=None,
                    help="merge-update BENCH_<tag>.json per module here")
    ap.add_argument("--smoke", action="store_true",
                    help="fast subset for CI / verify flows (modules that "
                         "support rows(smoke=True); others run as usual)")
    ap.add_argument("--check", default=None, metavar="PATH",
                    help="exit non-zero if any measured us_per_call "
                         "regresses >25%% vs this recorded BENCH json "
                         "(a file, or a directory of BENCH_*.json merged)")
    ap.add_argument("--factor", type=float, default=REGRESSION_FACTOR,
                    help="regression threshold for --check as a ratio "
                         "(default %(default)s = the 25%% gate; CI lanes "
                         "on noisy shared runners pass a looser value)")
    args = ap.parse_args()
    # persistent compile cache: $JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax-cache (see benchmarks/compile_cache.py)
    compile_cache.enable()
    if args.only and args.only not in {t for t, _ in MODULES}:
        # a typo'd tag must fail loudly, not run zero modules and let a
        # --check gate pass green on an empty measurement set
        ap.error(f"unknown module tag {args.only!r}; "
                 f"choose from {[t for t, _ in MODULES]}")

    # snapshot the baseline BEFORE any module runs: --json-dir merge-updates
    # the same BENCH files a --check baseline typically points at
    baseline = None
    if args.check:
        if os.path.isdir(args.check):
            paths = sorted(glob.glob(
                os.path.join(args.check, "BENCH_*.json")))
            if not paths:
                # an empty baseline dir must fail loudly, not let the
                # gate pass green with zero rows checked
                ap.error(f"--check {args.check!r}: no BENCH_*.json found")
            baseline = {}
            for p in paths:
                with open(p) as f:
                    baseline.update(json.load(f))
        else:
            with open(args.check) as f:
                baseline = json.load(f)

    measured: dict[str, tuple[float, str]] = {}
    tag_rows: list[tuple[str, list]] = []
    print("name,us_per_call,derived")
    for tag, mod in MODULES:
        if args.only and tag != args.only:
            continue
        rows = _module_rows(mod, args.smoke)
        tag_rows.append((tag, rows))
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}", flush=True)
            measured[name] = (us, derived)

    # gate BEFORE persisting: a failed check must not ratchet the recorded
    # baseline with the regressed numbers (the retry would then pass)
    if args.check and _check_regressions(args.check, baseline, measured,
                                         args.factor):
        sys.exit(1)

    if args.json_dir:
        os.makedirs(args.json_dir, exist_ok=True)
        for tag, rows in tag_rows:
            merge_bench_json(
                os.path.join(args.json_dir, f"BENCH_{tag}.json"), rows
            )

    path = os.path.join(os.path.dirname(__file__), "..",
                        "results", "dryrun_single.json")
    if os.path.exists(path) and not args.only and not args.smoke:
        with open(path) as f:
            recs = json.load(f)
        ok = [r for r in recs if r.get("ok")]
        print(f"# dry-run roofline summary ({len(ok)} combos):")
        for r in ok:
            t = r["roofline"]
            print(
                f"roofline_{r['arch']}_{r['shape']},"
                f"{t['bound_step_time_s']*1e6:.1f},"
                f"dom={t['dominant']};useful={t['useful_flop_ratio']:.2f}"
            )

if __name__ == "__main__":
    main()
