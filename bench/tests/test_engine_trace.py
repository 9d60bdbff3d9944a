"""The reduction of the engine's spans and named programs, on hand-made
events and on two traces recorded on one TPU v5e, and the readers that
take it from a run's record.  Both traces hold three decoding steps of
qwen3-0.6b at 32 slots, s_max 320: ``decode_steps`` from before the engine
had spans or named programs and kernels, ``engine_steps`` with them (its
middle step admits a whole 20-token prompt)."""
import gzip
import json
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import cell
from bench import engine_trace as et
from bench import harness, stats
from bench import trace_reduce as tr

FIXTURES = Path(__file__).parent / "fixtures"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"
MS = 1e6          # ns


def _recorded(name):
    return gzip.decompress((FIXTURES / f"{name}.xplane.pb.gz").read_bytes())


def _engine_events(second_step=False):
    """One step of the engine's nested spans: admission (a prefill whose
    first token it waits for), the decode's dispatch, its sync and the
    slot walk; a named prefill and decode program and an unnamed one on
    the device.  ``second_step`` adds a plain decode step after it."""
    tpu = "/device:TPU:0"
    host = [("bench.step", 0, 100, 0), ("engine.step", 2, 98, -1),
            ("engine.admit", 4, 30, -1), ("engine.sync", 20, 28, -1),
            ("engine.dispatch", 30, 40, -1), ("engine.sync", 40, 90, -1),
            ("engine.walk", 90, 96, -1), ("bench.bookkeeping", 100, 110, -1)]
    programs = [("prefill", 10, 22, tpu), ("decode_paged", 42, 80, tpu),
                ("_lambda", 81, 85, tpu)]
    ops = [(12, 18), (19, 21), (43, 60), (62, 78), (82, 84)]
    if second_step:
        host += [("bench.step", 110, 150, 1), ("engine.step", 111, 149, -1),
                 ("engine.dispatch", 112, 114, -1),
                 ("engine.sync", 114, 146, -1), ("engine.walk", 146, 148, -1)]
        programs += [("convert_element_type", 111, 112, tpu),
                     ("decode_paged", 115, 140, tpu)]
        ops += [(116, 139)]
    dev = [(et.label("%fusion.1 = bf16[2,8]{1,0} fusion(bf16[2] %x)"), s, e,
            tpu) for s, e in ops]
    return {"host": host, "device": dev, "programs": programs}


def test_reduce_puts_idle_down_to_innermost_span_or_program():
    out = et.reduce(_engine_events())
    # busy [12,18) [19,21) [43,60) [62,78) [82,84) in the window [0, 100)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(43e-9)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # [18,19) and [60,62) fall between a named program's operations; the
    # unnamed program's [81,82) and [84,85) go to the sync that waits; the
    # admission's nested sync [21,28) is the sync's, not the admission's
    assert gaps == pytest.approx({
        "bench.step": 4e-9, "engine.step": 4e-9, "engine.admit": 10e-9,
        "engine.sync": 20e-9, "engine.dispatch": 10e-9, "engine.walk": 6e-9,
        "device:prefill": 1e-9, "device:decode_paged": 2e-9})
    idle = sum(v for _, v in out["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-9)
    # the keys trace_reduce gives read what they read there
    assert out["steps"][0] == {"span_ns": 100, "busy_ns": 43.0,
                               "kernel_ns": 0.0, "kernels": {}}


def test_reduce_programs_phases_and_sync_lag():
    out = et.reduce(_engine_events(second_step=True))
    # self times: engine.step 96 less its children; the admission less
    # its nested sync
    assert out["phases"] == {
        0: {"engine.step": 4, "engine.admit": 18, "engine.sync": 58,
            "engine.dispatch": 10, "engine.walk": 6},
        1: {"engine.step": 2, "engine.dispatch": 2, "engine.sync": 32,
            "engine.walk": 2}}
    assert out["programs"] == {
        0: {"prefill": 12, "decode_paged": 38, "_lambda": 4},
        1: {"convert_element_type": 1, "decode_paged": 25}}
    # the last sync's end less the decode's last operation's end
    assert out["sync_lag_ns"] == {0: 90 - 78, 1: 146 - 139}
    # a window that stops at step 0 keeps step 0 alone
    first = et.reduce(_engine_events(second_step=True), last_step=0)
    assert set(first["phases"]) == set(first["programs"]) == {0}
    assert first["sync_lag_ns"] == {0: 12}


def test_label_names_a_named_kernel():
    text = ('%decode_attention.3 = bf16[8,8,2,128]{3,2,1,0:T(2,128)} '
            'custom-call(bf16[8,8,2,128] %copy.23), custom_call_target='
            '"tpu_custom_call"')
    assert et.label(text) == "pallas decode_attention bf16[8,8,2,128]"
    # an unnamed kernel and every other operation read as trace_reduce's
    for hlo in ('%closed_call.15 = bf16[32,8,2,128]{3,2,1,0:T(2,128)} '
                'custom-call(bf16[32] %a), custom_call_target='
                '"tpu_custom_call"',
                "%fusion.99 = s32[32]{0:T(128)S(1)} fusion(s32[1,32] %p)"):
        assert et.label(hlo) == tr.label(hlo)
    assert et.kernel_name(et.label(text)) == "decode_attention"
    assert et.kernel_name("pallas ssd_scan (bf16[1,2], f32[3])") == "ssd_scan"
    assert et.kernel_name("pallas bf16[32,8,2,128]") == "pallas"
    assert et.kernel_name("pallas (bf16[1,2], f32[3])") == "pallas"
    assert et.program_name("jit_decode_paged(1234)") == "decode_paged"
    assert et.program_name("jit__lambda(99)") == et.UNNAMED_PROGRAM


@pytest.mark.parametrize("name", ["decode_steps", "engine_steps"])
def test_reduce_keeps_trace_reduce_keys(name):
    # on either recorded trace, the keys the harness's metrics read are
    # trace_reduce's to the last ns
    xspace = _recorded(name)
    old = tr.reduce(tr.events(xspace))
    new = et.reduce(et.events(xspace))
    assert {k: {n: v for n, v in s.items() if n != "kernels"}
            for k, s in new["steps"].items()} == old["steps"]
    assert (new["busy_s"], new["window_s"]) == (old["busy_s"],
                                                old["window_s"])
    assert ([v for _, v in new["breakdown"]["device_ops"]] ==
            [v for _, v in old["breakdown"]["device_ops"]])


@pytest.mark.parametrize("name", ["decode_steps", "engine_steps"])
def test_step_kernels_add_up_to_kernel_ns(name):
    out = et.reduce(et.events(_recorded(name)))
    assert sorted(out["steps"]) == [0, 1, 2]
    for s in out["steps"].values():
        assert s["kernels"] and sum(s["kernels"].values()) == s["kernel_ns"]
    kernels = [set(out["steps"][k]["kernels"]) for k in range(3)]
    if name == "decode_steps":      # its kernels had no names
        assert kernels == [{"pallas"}] * 3
    else:                           # every step decodes; the middle one
        assert kernels == [{"decode_attention"},   # also admits a prompt
                           {"decode_attention", "flash_attention"},
                           {"decode_attention"}]


def test_reduce_recorded_engine_spans():
    """The trace with the engine's spans and named programs."""
    ev = et.events(_recorded("engine_steps"))
    out = et.reduce(ev, top=100)
    assert sorted(out["steps"]) == [0, 1, 2]
    # the phases nest: each lies inside an engine.step inside a bench.step
    steps = [(s, e) for n, s, e, _ in ev["host"] if n == "bench.step"]
    inner = [(s, e) for n, s, e, _ in ev["host"] if n == "engine.step"]
    assert len(steps) == len(inner) == 3
    assert all(any(a <= s and e <= b for a, b in steps) for s, e in inner)
    for n, s, e, _ in ev["host"]:
        if n.startswith("engine.") and n != "engine.step":
            assert any(a <= s and e <= b for a, b in inner), n
    for k, (s, e) in enumerate(sorted(inner)):
        # self times add up to the engine.step span
        assert sum(out["phases"][k].values()) == pytest.approx(e - s)
    # every step ran the named decode program; the middle one the prompt's
    for k in range(3):
        assert "decode_paged" in out["programs"][k]
        assert et.UNNAMED_PROGRAM not in out["programs"][k]
        assert out["sync_lag_ns"][k] > 0
    assert {"prefill", "commit_prefill"} <= set(out["programs"][1])
    ops = [n for n, _ in out["breakdown"]["device_ops"]]
    assert any(n.startswith("pallas decode_attention ") for n in ops)
    # the idle still sums to the window less the busy time, and what the
    # harness's step span held now sits under the engine's names
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    named = sum(v for n, v in gaps.items()
                if n.startswith(("engine.", "device:")))
    assert named >= 0.9 * (named + gaps.get("bench.step", 0.0))


def test_kernel_roofline_arithmetic():
    steps = {0: {"kernels": {"ssd_scan": 4e6, "pallas": 1e6}},
             1: {"kernels": {"ssd_scan": 2e6}},
             2: {"kernels": {"decode_attention": 5e6}},
             3: {"kernels": {"ssd_scan": 1e9}}}
    rec = {"trace": {"steps": steps},
           "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    flops = np.asarray([2e9, 1e9, 0, 0])
    bytes_ = np.asarray([1e8, 3e8, 0, 0])

    def read(kernel="ssd_scan", steps=(0, 1, 2)):
        return stats.kernel_roofline(rec, kernel, flops, bytes_, steps)

    # step 0 needs max(2 ms, 1 ms), step 1 max(1 ms, 3 ms): 5 ms of the
    # kernel's 6 ms; step 2 did not run it, step 3 is not asked for
    assert read() == pytest.approx(100.0 * 5 / 6)
    assert read(steps=[1]) == pytest.approx(100.0 * 3 / 2)
    assert read("decode_attention") is None       # no work counted
    assert read("rglru_scan") is None             # the kernel never ran
    assert read(steps=[]) is None
    rec["trace"] = None
    assert read() is None


def test_decode_attn_roofline_reads_its_own_kernel():
    """On the recorded steps it reads what it read when it took every
    Pallas call in them (``kernel_ns``), since the one step in which another
    kernel ran admitted a prompt and is left out."""
    out = et.reduce(et.events(_recorded("engine_steps")))
    cfg = json.loads((CONFIGS / "qwen3-0.6b.json").read_text())
    # 31 requests admitted before the traced steps decode through them;
    # one more is admitted in step 1 and gets its first token there
    reqs = [SimpleNamespace(admit_step=None, prompt_len=60 + 4 * i,
                            token_steps=[0, 1, 2]) for i in range(31)]
    reqs.append(SimpleNamespace(admit_step=1, prompt_len=20,
                                token_steps=[1, 2]))
    rec = {"trace": out, "config": cfg, "family": cell.family("qwen3"),
           "requests": reqs, "steps": SimpleNamespace(t0=[0.0] * 3),
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = harness._load_reader("decode_attn_roofline")
    named = read(rec)
    assert 0 < named < 100
    for s in out["steps"].values():
        s["kernels"] = {"decode_attention": s["kernel_ns"]}
    assert read(rec) == named
    rec["trace"] = None
    assert read(rec) is None


def _traced(parent=False):
    """Steps 0-2 decode (1 also admits a whole prompt, 2 streams a chunk),
    step 3 only admits; the reduction's phases, programs and lags in ns.
    ``parent``: the trace of a program with no engine spans or names."""
    host = {"engine.step": 0.1 * MS, "engine.grow": 0.1 * MS,
            "engine.dispatch": 0.6 * MS, "engine.walk": 0.2 * MS}
    trace = {
        "phases": {
            k: dict(host, **{"engine.admit": a * MS, "engine.sync": w * MS})
            for k, a, w in ((0, 0.0, 15), (1, 3, 17), (2, 1, 16))},
        "programs": {0: {"decode_paged": 14 * MS, "convert_element_type": 600},
                     1: {"prefill": 2 * MS, "commit_prefill": 0.5 * MS,
                         "decode_paged": 16 * MS},
                     2: {"prefill_chunk": 1 * MS, "commit_chunk": 0.25 * MS,
                         "decode_paged": 15 * MS},
                     3: {"prefill": 4 * MS}},
        "sync_lag_ns": {0: 2 * MS, 1: 3 * MS, 2: 2.5 * MS},
    }
    trace["phases"][3] = {"engine.step": 0.1 * MS, "engine.admit": 2 * MS}
    if parent:
        trace = {"phases": {k: {} for k in range(4)},
                 "programs": {k: {"_lambda": 15 * MS} for k in range(4)},
                 "sync_lag_ns": {}}
    steps = SimpleNamespace(decoded=[k < 3 for k in range(4)])
    return {"steps": steps, "trace": trace}


@pytest.mark.parametrize("name, want", [
    # decoding steps' host time less the sync: 1.0, 4.0, 2.0 ms
    ("sched_host_s_p50", 2.0e-3),
    ("sync_lag_s_p50", 2.5e-3),
    ("decode_program_s_p50", 15e-3),
    # prompt steps: 2.5 (step 1), 1.25 (step 2), 4.0 ms (step 3)
    ("prefill_program_s_p90", 4.0e-3),
])
def test_engine_trace_readers(name, want):
    read = getattr(et, name)
    assert harness._load_reader(name) is read      # the metric's file
    assert read(_traced()) == pytest.approx(want)
    # a trace without the engine's spans and program names, trace_reduce's
    # reduction, or no trace: nothing to read
    assert read(_traced(parent=True)) is None
    rec = _traced()
    rec["trace"] = {"steps": {}, "busy_s": 0.0, "window_s": 0.0}
    assert read(rec) is None
    rec["trace"] = None
    assert read(rec) is None


def test_traced_cpu_run_reads_the_scheduler(monkeypatch):
    """A traced run of a small qwen3 on the CPU, its trace reduced by the
    harness with this module: the engine's spans give the scheduler's host
    time; the CPU has no TPU plane, so no program or kernel time and no
    lag."""
    monkeypatch.setattr(harness.peaks, "lookup", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    base = json.loads((CONFIGS / "qwen3-0.6b.json").read_text())
    cfg = dict(base, hidden_size=256, intermediate_size=768, head_dim=32,
               num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=2, vocab_size=32768)
    mix = {"slots": 4, "s_max": 96,
           "arrivals": {"kind": "poisson", "rate_per_s": 10.0},
           "prompt": {"kind": "lognormal", "median": 24, "sigma": 1.0,
                      "min": 4, "max": 80},
           "output": {"kind": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 16}}
    c = {"config": cfg, "family": cell.family("qwen3"), "traffic": mix}
    record, _ = harness.serve(c, seed=5, seconds=2.0, trace=True,
                              t_proc=time.perf_counter())
    phases = record["trace"]["phases"]
    assert phases and all("engine.step" in ph for ph in phases.values())
    assert any("engine.dispatch" in ph and "engine.sync" in ph
               for ph in phases.values())
    assert all(s["kernels"] == {} for s in record["trace"]["steps"].values())
    assert et.sched_host_s_p50(record) > 0
    assert et.decode_program_s_p50(record) is None
    assert et.sync_lag_s_p50(record) is None
