"""The readings that the limits of ``correct`` are set from, on the chip,
at a cell's own size: over several seeds in ONE process, a short window
of the cell (its own load; training needs none), the program's numbers
against the reference, and beside them the control's (the reference
computed in fp8, put in the program's place) and, for training, the
faults of half the batch left out and of a state left unchanged, each
with the ``correct`` that a run's own decision and the cell's limits give
it. Benchmark runs never do this.

    python benchmarks/tools/readings.py --workload <name> --seconds 10 \\
        --seeds 11 12 13 ... [--control-seeds 3]

Lines with "phase": "check" carry the program's numbers (the lower
readings), lines with "phase": "control"/"control_fp8"/"fault_half_batch"/
"fault_frozen_state" the upper ones. PERF.md holds the readings each limit was set from.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="the first N seeds also read the control")
    ap.add_argument("--sweep", nargs="+", default=None, metavar="KEY V",
                    help="traffic key and its values, one run for each "
                         "(finding a knee): arrivals.rate_per_s 1.6 2.0")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmarks import run
    from benchmarks.lib import harness, manifest, peaks
    run.configure_cache()
    man = manifest.load_manifest()
    chips = manifest.cell(man, args.workload)["chips"]
    device = run.look_for_chips(chips)
    counter = harness.CompileCounter()
    runs = [(seed, None) for seed in args.seeds]
    if args.sweep:
        runs = [(args.seeds[i % len(args.seeds)], f"{args.sweep[0]}={v}")
                for i, v in enumerate(args.sweep[1:])]
    for i, (seed, override) in enumerate(runs):
        cell = manifest.cell(man, args.workload)
        if override:
            run.apply_overrides(cell["traffic"], [override])
        driver = manifest.load_module("drivers", cell["traffic"]["driver"],
                                      cell["home"])
        env = {"compiles": counter,
               "on_chip": device["platform"] == "tpu",
               "peaks": peaks.peaks_for(device["kind"]),
               "t_start": time.monotonic(),
               "control": "fp8" if i < args.control_seeds else None}
        out = driver.run(cell, seed, args.seconds, False, env)
        harness.say(phase="reading", seed=seed, override=override,
                    correct=out["correct"],
                    checks={k: v[0] for k, v in out["checks"].items()},
                    end_to_end=out["end_to_end"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
