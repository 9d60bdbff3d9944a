"""A whole run on the CPU at a small size, with the harness's look for a
chip skipped: a sound run is correct, and the fp8 control or a token
altered where the engine produces it is not.

The small cell keeps the qwen3 path (bf16, GQA, QK-norm, tied head) at
d 256, 8 layers, vocab 32768.  Its limit of 0.03 lies between what the
program reads here (at most 0.0053 on seeds 1-3) and what the fp8 control
reads (at least 0.093 on the same seeds).
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import cell, correct, harness

ROOT = Path(__file__).resolve().parents[2]
LIMIT = 0.03


def _tiny_cell():
    base = json.loads((ROOT / "bench/configs/qwen3-0.6b.json").read_text())
    cfg = dict(base, name="tiny", hidden_size=256, intermediate_size=768,
               head_dim=32, num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=8, vocab_size=32768)
    mix = {"slots": 4, "s_max": 96,
           "arrivals": {"kind": "poisson", "rate_per_s": 10.0},
           "prompt": {"kind": "lognormal", "median": 24, "sigma": 1.0,
                      "min": 4, "max": 80},
           "output": {"kind": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 16}}
    spec = cell.benchmark()
    return {"name": "tiny", "chips": 1, "config": cfg,
            "family": cell.family("qwen3"), "traffic": mix,
            "limits": {"max_logit_gap": LIMIT},
            "end_to_end": spec["end_to_end"], "per_layer": spec["per_layer"]}


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU has no entry in the table of peaks; these stand in."""
    monkeypatch.setattr(harness.peaks, "lookup", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def _run(trace=False, seed=1):
    return harness.run_cell(_tiny_cell(), seed=seed, seconds=2.0,
                            trace=trace, t_proc=time.perf_counter(),
                            log=lambda m: None)


def test_sound_run_is_correct(cpu_peaks):
    r = _run()
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] == 20
    assert set(r["metrics"]) == {"itl_p95_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert list(r)[-1] == "checks"
    assert 0 <= r["checks"]["max_logit_gap"]["value"] <= LIMIT
    assert r["window"]["compiles_in_window"] == 0
    json.dumps(r)


def test_trace_run_reports_per_layer_metrics(cpu_peaks):
    r = _run(trace=True, seed=2)
    assert r["correct"] is True
    names = {m["name"] for m in cell.benchmark()["per_layer"]}
    assert set(r["metrics"]) <= names
    for m in ("admit_tick_s_p90", "tick_s_p50", "step_mfu",
              "sched_host_s_p50"):
        assert m in r["metrics"]
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_fp8_control_is_not_correct(cpu_peaks):
    c = _tiny_cell()
    record, w = harness.serve(c, seed=3, seconds=2.0, trace=False,
                              t_proc=time.perf_counter())
    finished = [r for r in record["requests"] if r.done]
    seqs = [(r.req.prompt, list(r.req.out))
            for r in correct.sample(finished, 3)]
    size = (c["config"], c["traffic"]["s_max"], 16, seqs)
    program = correct.served_gaps(c["family"], w, *size)
    control = correct.served_gaps(c["family"], w, *size, control=True)
    assert len(program) == len(control) > 100
    assert program.max() <= LIMIT < control.max()


def test_altered_token_is_not_correct(cpu_peaks, monkeypatch):
    from repro.serving import engine as engine_mod
    init = engine_mod.ServingEngine.__init__

    def faulty_init(self, *a, **kw):
        init(self, *a, **kw)
        decode, calls = self._decode_paged, [0]

        def altered(*args):
            toks, state = decode(*args)
            calls[0] += 1
            if calls[0] % 3 == 0:           # every third decode step
                toks = (toks + 1) % self.cfg.vocab
            return toks, state

        self._decode_paged = altered

    monkeypatch.setattr(engine_mod.ServingEngine, "__init__", faulty_init)
    r = _run(seed=4)
    assert r["correct"] is False
    assert r["checks"]["max_logit_gap"]["value"] > LIMIT


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "qwen3-0.6b.burst", "--seed", str(2 ** 33), "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no result" in p.stderr
