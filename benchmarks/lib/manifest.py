"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its
own, found here by the name in the manifest:

    benchmarks/configs/<config>.json          sizes, source, deployment
    benchmarks/reference/<reference>.py       its plain reference
    benchmarks/models/<reference>.py          the program's side of that
                                              architecture (cell["model"])
    benchmarks/traffic/<traffic>.json         a mix's parameters
    benchmarks/limits/<workload>.json         the limits of `correct`
    benchmarks/layer_metrics/<metric>.py      one reader: read(ctx)
    benchmarks/drivers/<driver>.py            one kind of load (a mix names it)

A configuration's ``reference`` key names both its reference and its model
file. A cell's files are looked for first in the benchmark directory that
holds its configuration (``<home>/configs/<config>.json``), then here: a
configuration kept in a directory of its own brings its files with it and
still finds this directory's drivers and readers.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def find(kind: str, filename: str, home: str = None) -> str:
    """<home>/<kind>/<filename>, else benchmarks/<kind>/<filename>."""
    tried = [os.path.join(d, kind, filename)
             for d in dict.fromkeys([home or BENCH_DIR, BENCH_DIR])]
    for path in tried:
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {kind} file {' or '.join(tried)}")


def load_json(kind: str, filename: str, home: str = None) -> dict:
    with open(find(kind, filename, home)) as f:
        return json.load(f)


def load_manifest(path: str = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str, home: str = None):
    """<kind>/<name>.py as a module (names may hold dots)."""
    path = find(kind, name + ".py", home)
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(manifest: dict, workload: str) -> dict:
    """One cell with everything it names: its configuration with its
    reference and model, traffic mix, limits and the metrics it has to
    report."""
    try:
        w = next(w for w in manifest["workloads"] if w["name"] == workload)
    except StopIteration:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"({[w['name'] for w in manifest['workloads']]})")
    conf = next(c for c in manifest["configs"] if c["name"] == w["config"])
    path = os.path.abspath(os.path.join(ROOT, conf["file"]))
    home = os.path.dirname(os.path.dirname(path))
    with open(path) as f:
        config = json.load(f)
    traffic = load_json("traffic", w["traffic"] + ".json", home)
    limits = load_json("limits", w["name"] + ".json", home)

    def mine(m):
        return "workloads" not in m or w["name"] in m["workloads"]

    e2e = [m for m in manifest["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if mine(m) and m["moves"] in names]
    return {"name": w["name"], "chips": int(w["chips"]), "home": home,
            "config": config,
            "reference": load_module("reference", config["reference"], home),
            "model": load_module("models", config["reference"], home),
            "traffic": traffic, "limits": limits, "end_to_end": e2e,
            "per_layer": layer}
