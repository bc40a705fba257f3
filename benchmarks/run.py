"""The benchmark's one command.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. It fails (it never falls back) where JAX finds no
TPU, where the device is not in the peak table, or where fewer chips are
there than the cell asks for. Inputs and weights come from ``--seed``;
every shape the cell uses is warmed up and counted as set-up; then it
measures for ``--seconds``; then it holds what the timed path produced to
the configuration's plain reference. The last line of standard output is
the result (benchmarks/lib/harness.print_result); medians and counters go
on earlier lines.

Adding a cell, a configuration (of a new architecture too: its reference
and benchmarks/models/<reference>.py), a traffic mix or a per-layer metric
is adding files and manifest entries only (benchmarks/lib/manifest.py).
``--set key=value`` overrides a parameter of the traffic mix for a sweep
(finding a knee); the driver's runs never pass it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="override traffic.<K> (dotted) for a sweep")
    ap.add_argument("--dump-trace", default=None,
                    help="write a by-hand summary of the trace here")
    return ap.parse_args(argv)


def apply_overrides(mix: dict, pairs) -> None:
    for pair in pairs:
        key, _, value = pair.partition("=")
        node = mix
        *path, last = key.split(".")
        for p in path:
            node = node[p]
        node[last] = json.loads(value)


def look_for_chips(chips: int) -> dict:
    """The devices JAX found, or an exit: no accelerator, an unknown one,
    or fewer chips than the cell asks for end the run with no result."""
    from benchmarks.lib import harness, peaks
    info = harness.device_info(chips)
    if info["platform"] != "tpu":
        sys.exit(f"benchmarks/run.py: JAX found no accelerator ({info})")
    if info["visible"] < chips:
        sys.exit(f"benchmarks/run.py: the cell needs {chips} chips, JAX "
                 f"found {info['visible']}")
    peaks.peaks_for(info["kind"])       # an unknown device raises
    return info


def configure_cache() -> None:
    """JAX's persistent compilation cache at JAX_COMPILATION_CACHE_DIR or
    a fixed path in the checkout; every program is kept."""
    import jax
    from benchmarks.lib import harness
    jax.config.update("jax_compilation_cache_dir", harness.cache_dir(ROOT))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device: dict, peak_table: dict, t_start: float,
             dump_trace: str = None) -> int:
    """Everything of a run after the look for a chip: drive the cell,
    read its metrics, print the result. Returns the exit code."""
    from benchmarks.lib import harness, manifest
    driver = manifest.load_module("drivers", cell["traffic"]["driver"],
                                  cell["home"])
    env = {"compiles": harness.CompileCounter(),
           "on_chip": device["platform"] == "tpu", "peaks": peak_table,
           "t_start": t_start, "dump_trace": dump_trace}
    out = driver.run(cell, seed, seconds, trace, env)
    e2e = out["end_to_end"]
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": out["memory_peak_bytes"]}
    breakdown = None
    if trace:
        ctx = dict(out["ctx"], end_to_end=e2e)
        tr = ctx["trace"]
        metrics = {}
        for m in cell["per_layer"]:
            value = manifest.load_module("layer_metrics", m["name"],
                                         cell["home"]).read(ctx)
            if value is not None:       # nothing to read: left out
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        dev["busy_s"], dev["window_s"] = tr.busy_s(), tr.window_s
        breakdown = tr.breakdown()
        harness.say(phase="end_to_end_of_traced_run", **e2e)
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    harness.print_result(out["correct"], out["attempted"], out["failed"],
                         metrics, dev, out["checks"], breakdown)
    return 0


def main(argv=None) -> int:
    args = parse(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmarks.lib import manifest, peaks
    cell = manifest.cell(manifest.load_manifest(), args.workload)
    apply_overrides(cell["traffic"], args.set)
    configure_cache()
    device = look_for_chips(cell["chips"])
    return run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                    peaks.peaks_for(device["kind"]), T_START,
                    args.dump_trace)


if __name__ == "__main__":
    sys.exit(main())
