"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its
own, found here by the name in the manifest:

    benchmarks/configs/<config>.json          sizes, source, deployment
    benchmarks/reference/<reference>.py       its plain reference
    benchmarks/traffic/<traffic>.json         a mix's parameters
    benchmarks/limits/<workload>.json         the limits of `correct`
    benchmarks/layer_metrics/<metric>.py      one reader: read(ctx)
    benchmarks/drivers/<driver>.py            one kind of load (a mix names it)
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_manifest(path: str = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(manifest: dict, workload: str) -> dict:
    """One cell with everything it names: its configuration, traffic mix,
    limits and the metrics it has to report."""
    try:
        w = next(w for w in manifest["workloads"] if w["name"] == workload)
    except StopIteration:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"({[w['name'] for w in manifest['workloads']]})")
    conf = next(c for c in manifest["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", w["traffic"] + ".json")
    limits = load_json("limits", w["name"] + ".json")

    def mine(m):
        return "workloads" not in m or w["name"] in m["workloads"]

    e2e = [m for m in manifest["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if mine(m) and m["moves"] in names]
    return {"name": w["name"], "chips": int(w["chips"]), "config": config,
            "traffic": traffic, "limits": limits, "end_to_end": e2e,
            "per_layer": layer}
