"""A cell finds its model family by its configuration's ``model_type``:
the Qwen3 family makes the same weights as before it was a family, a
family added as new files only runs a cell end to end, and an unknown
``model_type`` names the families there are."""
import hashlib
import json
import shutil
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import cell, harness, schedule

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
# sha256 over each leaf's path and bytes of the Qwen3 weights of the
# small configuration below from run seed 1, as the harness made them
# before the family existed
QWEN3_TINY_SHA256 = (
    "30b57201b7293dc55b291315525d966863e92e178c908ff7373ee62bfb378c3c")
# what the harness asks of a family; param_count only the tests read
CALLED = ("arch_config", "init_weights", "engine_params", "logits",
          "prefill_flops", "decode_token_flops", "decode_attn_flops",
          "decode_attn_bytes", "kv_bytes_per_token")


def _tiny_config(**kw):
    base = json.loads((ROOT / "bench/configs/qwen3-0.6b.json").read_text())
    return dict(base, name="tiny", hidden_size=256, intermediate_size=768,
                head_dim=32, num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=8, vocab_size=32768, **kw)


def _checksum(w) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree.flatten_with_path(w)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.fixture
def tree(tmp_path):
    """A copy of BENCHMARK.json and the benchmark's paths, to add to."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _add_cell(root: Path, config: dict) -> str:
    """Add a configuration, a traffic mix, a limit and a workload as new
    files and new entries, and the workload to every per-layer metric's
    list; returns the workload's name."""
    b = root / "bench"
    (b / "configs" / "tiny.json").write_text(json.dumps(config))
    (b / "traffic" / "tiny.json").write_text(json.dumps({
        "slots": 4, "s_max": 96,
        "arrivals": {"kind": "poisson", "rate_per_s": 10.0},
        "prompt": {"kind": "lognormal", "median": 24, "sigma": 1.0,
                   "min": 4, "max": 80},
        "output": {"kind": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 2, "max": 16}}))
    (b / "limits" / "tiny.tiny.json").write_text(
        json.dumps({"max_logit_gap": 0.03}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": config["source"],
                            "file": "bench/configs/tiny.json", "reduced": [],
                            "why": "a family added as files"})
    spec["workloads"].append({"name": "tiny.tiny", "config": "tiny",
                              "traffic": "tiny", "chips": 1,
                              "why": "a family added as files"})
    for m in spec["per_layer"]:
        m["workloads"].append("tiny.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return "tiny.tiny"


def test_qwen3_weights_are_those_of_before():
    fam = cell.family("qwen3")
    w = fam.init_weights(_tiny_config(),
                         jax.random.key(schedule.weight_seed(1)))
    assert _checksum(w) == QWEN3_TINY_SHA256


def test_a_family_added_as_files_runs_a_cell(tree, monkeypatch):
    shutil.copy(tree / "bench/families/qwen3.py",
                tree / "bench/families/qwen3copy.py")
    name = _add_cell(tree, _tiny_config(model_type="qwen3copy"))
    c = cell.load(name, root=tree)
    fam = c["family"]
    assert Path(fam.__file__) == tree / "bench/families/qwen3copy.py"
    called = set()
    for f in CALLED:
        def counted(*a, _f=getattr(fam, f), _n=f, **kw):
            called.add(_n)
            return _f(*a, **kw)
        monkeypatch.setattr(fam, f, counted)
    monkeypatch.setattr(harness.peaks, "lookup", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    r = harness.run_cell(c, seed=6, seconds=2.0, trace=True,
                         t_proc=time.perf_counter(), log=lambda m: None)
    assert r["correct"] is True and r["failed"] == 0
    assert "step_mfu" in r["metrics"]
    assert r["window"]["live_kv_bytes_max"] > 0
    assert called == set(CALLED)


def test_an_unknown_model_type_names_the_families(tree):
    name = _add_cell(tree, _tiny_config(model_type="no_such_family"))
    with pytest.raises(KeyError, match=r"no_such_family.*\['qwen3'\]"):
        cell.load(name, root=tree)
