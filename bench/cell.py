"""A cell from data alone: ``load(name)`` reads the ``workloads`` entry of
``BENCHMARK.json`` and the files it names by convention -- the
configuration file the ``configs`` entry gives, ``traffic/<traffic>.json``
and ``limits/<workload>.json`` -- and the metrics that the cell reports."""
from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load(name: str, root: Path = ROOT) -> dict:
    spec = benchmark(root)
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; have {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    bench = root / BENCH.name
    return {
        "name": name,
        "chips": w["chips"],
        "config": config,
        "traffic": json.loads(
            (bench / "traffic" / f"{w['traffic']}.json").read_text()),
        "limits": json.loads((bench / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in spec["per_layer"] if _applies(m, name)],
    }
