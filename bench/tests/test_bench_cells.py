"""A cell is data: every ``workloads`` entry loads from ``BENCHMARK.json``
and the files under ``bench/`` alone, and the file keeps to the shape the
harness reads."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import cell, schedule

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def alone(tmp_path_factory):
    """A directory that holds only BENCHMARK.json and the benchmark's
    paths."""
    d = tmp_path_factory.mktemp("alone")
    shutil.copy(ROOT / "BENCHMARK.json", d)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, d / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return d


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_cells_load(alone, name):
    c = cell.load(name, root=alone)
    assert c["chips"] in (1, 4)
    cfg, mix = c["config"], c["traffic"]
    assert c["family"].arch_config(cfg).n_layers == cfg["num_hidden_layers"]
    assert c["limits"]["max_logit_gap"] > 0
    sched = schedule.build(mix, vocab=cfg["vocab_size"], seed=1,
                           seconds=SPEC["run_seconds"])
    assert len(sched) > 0
    # every request fits the cell's cache
    assert max(len(p) + int(m) - 1 for p, m in
               zip(sched.prompts, sched.max_new)) <= mix["s_max"]
    names = {m["name"] for m in c["end_to_end"] + c["per_layer"]}
    assert "setup_s" in names and len(c["end_to_end"]) >= 2
    assert c["per_layer"]
    for m in names:
        assert (alone / "bench" / "metrics" / f"{m}.py").is_file()


def test_benchmark_file_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    configs = {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
    pairs = set()
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert 0 < len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    layers = {m["name"]: m["layer"] for m in SPEC["per_layer"]}
    assert all(0 < len(v) <= 200 and "\n" not in v for v in layers.values())


# a family's kernel metric, as a model_config change would bring it: one
# reader file and one per_layer entry, nothing else edited
SSD_READER = '''"""Kernels: ssd_scan's share of its roofline."""
import numpy as np

from bench import stats


def read(record):
    work = np.asarray([1e9, 2e9])          # FLOPs per step
    return stats.kernel_roofline(record, "ssd_scan", work, work / 10, [0, 1])
'''
READ_IN_COPY = '''
import json, sys
from bench import cell, harness
c = cell.load(sys.argv[1])
steps = {0: {"kernels": {"ssd_scan": 2e6, "decode_attention": 9e6}},
         1: {"kernels": {"ssd_scan": 2e6}}}
record = {"trace": {"steps": steps},
          "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
mine = [m for m in c["per_layer"] if m["name"] == "ssd_scan_roofline"]
print(harness.METRICS)
print(json.dumps(harness.read_metrics(mine, record)))
'''


def test_family_kernel_metric_is_new_files_only(alone, tmp_path):
    d = tmp_path / "copy"
    shutil.copytree(alone, d)
    spec = json.loads((d / "BENCHMARK.json").read_text())
    names = [w["name"] for w in spec["workloads"]]
    spec["per_layer"].append({
        "name": "ssd_scan_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels (kernels/ssd_scan.py)",
        "moves": "itl_p95_s", "workloads": names})
    (d / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    (d / "bench" / "metrics" / "ssd_scan_roofline.py").write_text(SSD_READER)
    for name in names:
        assert "ssd_scan_roofline" in {
            m["name"] for m in cell.load(name, root=d)["per_layer"]}
    # the copy's own harness finds the reader by the metric's name: 1 ms
    # and 2 ms of work in 4 ms of the kernel
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(d), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", READ_IN_COPY, names[0]],
                       cwd=d, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 0, p.stderr
    where, got = p.stdout.strip().splitlines()[-2:]
    assert Path(where) == d / "bench" / "metrics"
    got = json.loads(got)
    assert got == {"ssd_scan_roofline": {"value": 75.0, "unit": "%"}}
