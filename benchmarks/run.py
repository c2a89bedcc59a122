"""Benchmark harness: one module per paper table/figure.

  python -m benchmarks.run [table3|table4|table5|fig1|fig2|stiff|events|dispatch|serving|training|all] [--json [PATH]]

Prints ``name,value,derived`` CSV rows (value is microseconds for *_time
rows).  ``--json`` additionally writes the rows to a JSON file so CI can
track the perf trajectory across commits; without an explicit PATH each
suite writes its own default (``BENCH_<suite>.json``, e.g. stiff ->
``BENCH_stiff.json``; ``all``/``table3`` keep the historical
``BENCH_solver.json``), so running several suites in one workspace never
silently overwrites another suite's artifact.  ``benchmarks/compare.py``
diffs these files against the committed baselines and gates CI on
regressions.
"""

from __future__ import annotations

import argparse
import json
import time

from repro.launch.compile_cache import enable_compile_cache

_SUITE_CHOICES = ["all", "table3", "table4", "table5", "fig1", "fig2",
                  "stiff", "events", "dispatch", "serving", "training", "step"]

# Suite-named --json defaults; "all" and the historical headline suite keep
# the BENCH_solver.json name CI has tracked since PR 1.
_DEFAULT_JSON = {suite: f"BENCH_{suite}.json" for suite in _SUITE_CHOICES}
_DEFAULT_JSON["all"] = "BENCH_solver.json"
_DEFAULT_JSON["table3"] = "BENCH_solver.json"

_JSON_AUTO = "__suite_default__"


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("suite", nargs="?", default="all", choices=_SUITE_CHOICES)
    parser.add_argument("--json", nargs="?", const=_JSON_AUTO, default=None,
                        metavar="PATH",
                        help="also write rows to a JSON file (default: "
                             "BENCH_<suite>.json)")
    opts = parser.parse_args()
    enable_compile_cache()
    which = opts.suite
    json_path = _DEFAULT_JSON[which] if opts.json == _JSON_AUTO else opts.json

    suites = []
    if which in ("all", "table3"):
        from . import vdp_bench

        suites.append(("table3_vdp", vdp_bench.rows))
    if which in ("all", "fig1"):
        from . import interaction_bench

        suites.append(("fig1_interaction", interaction_bench.rows))
    if which in ("all", "table4"):
        from . import fen_bench

        suites.append(("table4_fen", fen_bench.rows))
    if which in ("all", "table5"):
        from . import cnf_bench

        suites.append(("table5_cnf", cnf_bench.rows))
    if which in ("all", "fig2"):
        from . import pid_bench

        suites.append(("fig2_pid", pid_bench.rows))
    if which in ("all", "events"):
        from . import events_bench

        suites.append(("events", events_bench.rows))
    if which == "dispatch":
        # Not part of "all": the eager-retrace baseline is deliberately slow
        # (it re-traces the whole loop program every call).  CI runs it via
        # ``python -m benchmarks.dispatch_bench --json``.
        from . import dispatch_bench

        suites.append(("dispatch", dispatch_bench.rows))
    if which == "serving":
        # Not part of "all": the per-request eager-jit baseline dispatches
        # hundreds of b=1 solves by design.
        from . import serving_bench

        suites.append(("serving", serving_bench.rows))
    if which == "training":
        # Not part of "all" for the same reason: the per-request jit(grad)
        # baseline dispatches hundreds of b=1 backward solves by design.
        from . import training_bench

        suites.append(("training", training_bench.rows))
    if which == "step":
        # Not part of "all": compares the fused step megakernel against the
        # unfused op-per-op path across backends; the interpret-backend rows
        # are launch-count proxies and take a while.
        from . import step_bench

        suites.append(("step", step_bench.rows))
    if which == "stiff":
        # Not part of "all": the explicit-solver baselines grind at their
        # stability limit by design (200k-step budgets).  Run explicitly, or
        # at reduced size with REPRO_STIFF_SMOKE=1.
        from . import stiff_bench

        suites.append(("stiff", stiff_bench.rows))

    records = []
    print("name,value,derived")
    for tag, fn in suites:
        t0 = time.time()
        for name, v, extra in fn():
            print(f"{tag}/{name},{v},{extra}", flush=True)
            records.append({"suite": tag, "name": name, "value": v, "derived": extra})
        elapsed = time.time() - t0
        print(f"# {tag} took {elapsed:.1f}s", flush=True)
        records.append({"suite": tag, "name": "_suite_wall_s", "value": elapsed,
                        "derived": ""})

    if json_path:
        from .common import calibration_us

        # Machine-speed fingerprint: lets compare.py normalize this payload
        # against a baseline recorded on different hardware (--normalize).
        payload = {"bench": which, "unit": "us for *_time rows",
                   "calibration_us": calibration_us(), "rows": records}
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"# wrote {len(records)} rows to {json_path}", flush=True)


if __name__ == "__main__":
    main()
