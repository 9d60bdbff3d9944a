"""A cell from data alone: ``load(name)`` reads the ``workloads`` entry of
``BENCHMARK.json`` and the files it names by convention -- the
configuration file the ``configs`` entry gives, ``traffic/<traffic>.json``
and ``limits/<workload>.json`` -- the metrics that the cell reports, and
the model family that the configuration's ``model_type`` names,
``families/<model_type>.py``.

A family is the one place that knows a model's layout.  It provides
``arch_config(cfg)``, ``init_weights(cfg, key)``, ``engine_params(w)``,
``logits(w, cfg, tokens, rows, precision=)`` (the plain float32
reference, ``"fp8"`` its control), and the counts ``param_count``,
``prefill_flops``, ``decode_token_flops``, ``decode_attn_flops``,
``decode_attn_bytes`` and ``kv_bytes_per_token``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_file(path: Path, prefix: str):
    """The module in the file ``path``, loaded by path under a name made
    from ``prefix`` and the file's stem."""
    stem = path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"{prefix}_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(model_type: str, root: Path = ROOT):
    """The family module that ``model_type`` names."""
    d = root / BENCH.name / "families"
    have = sorted(p.stem for p in d.glob("*.py"))
    if model_type not in have:
        raise KeyError(f"no model family {model_type!r} in {d}; have {have}")
    return load_file(d / f"{model_type}.py", "bench_family")


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
        "family": family(config["model_type"], root),
        "traffic": json.loads(
            (bench / "traffic" / f"{w['traffic']}.json").read_text()),
        "limits": json.loads((bench / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in spec["per_layer"] if _applies(m, name)],
    }
