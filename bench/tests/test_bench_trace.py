"""The trace reduction, on a trace recorded on one TPU v5e (three decode
steps of qwen3-0.6b at 32 slots, s_max 320, no admission) and on
hand-made events."""
import gzip
from pathlib import Path

import pytest

from bench import trace_reduce as tr

FIXTURE = Path(__file__).parent / "fixtures" / "decode_steps.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip_events():
    return tr.events(gzip.decompress(FIXTURE.read_bytes()))


def test_reduce_recorded_chip_trace(chip_events):
    host, dev = chip_events["host"], chip_events["device"]
    assert sum(h[0] == "bench.step" for h in host) == 3
    assert dev and {d[3] for d in dev} == {"/device:TPU:0"}
    out = tr.reduce(chip_events)
    assert sorted(out["steps"]) == [0, 1, 2]
    for s in out["steps"].values():
        # a decode step keeps the chip busy most of its span and runs the
        # decode-attention kernel once per layer
        assert 0.5 * s["span_ns"] < s["busy_ns"] <= s["span_ns"]
        assert 0 < s["kernel_ns"] < s["busy_ns"]
    assert 0 < out["busy_s"] <= out["window_s"]
    span = sum(s["span_ns"] for s in out["steps"].values()) / 1e9
    assert out["window_s"] >= span
    ops = out["breakdown"]["device_ops"]
    assert 0 < len(ops) <= 10 and ops == sorted(ops, key=lambda o: -o[1])
    assert any(name.startswith("pallas ") for name, _ in ops)
    assert not any(name.split(" ")[0] in tr.CONTAINERS for name, _ in ops)
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps and all(n.startswith("bench.") or n == "host.other"
                        for n, _ in gaps)
    idle = sum(v for _, v in gaps)
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)


def test_reduce_last_step_limits_the_window(chip_events):
    whole, first = tr.reduce(chip_events), tr.reduce(chip_events, 0)
    assert sorted(first["steps"]) == [0]
    assert first["window_s"] < whole["window_s"]
    assert first["steps"][0] == whole["steps"][0]


def _events():
    pallas = ('%closed_call.3 = bf16[4,2,2,128]{3,2,1,0} custom-call(bf16[4] '
              '%a), custom_call_target="tpu_custom_call"')
    host = [("bench.step", 0, 100, 0), ("bench.bookkeeping", 100, 130, -1),
            ("bench.step", 130, 230, 1), ("bench.wait", 230, 300, -1)]
    dev = [
        ("while (s32[], bf16[2])", 5, 95, "/device:TPU:0"),
        (tr.label("%fusion.1 = bf16[2,8]{1,0} fusion(bf16[2] %x)"), 5, 40,
         "/device:TPU:0"),
        (tr.label(pallas), 40, 60, "/device:TPU:0"),
        (tr.label(pallas), 150, 170, "/device:TPU:0"),
        (tr.label("%copy.2 = f32[3]{0} copy(f32[3] %y)"), 180, 200,
         "/device:TPU:0"),
    ]
    return {"host": host, "device": dev}


def test_reduce_arithmetic():
    out = tr.reduce(_events())
    assert out["window_s"] == pytest.approx(230e-9)
    # busy: [5, 95) + [150, 170) + [180, 200) inside [0, 230)
    assert out["busy_s"] == pytest.approx(130e-9)
    assert out["steps"][0] == {"span_ns": 100, "busy_ns": 90.0,
                               "kernel_ns": 20.0}
    assert out["steps"][1] == {"span_ns": 100, "busy_ns": 40.0,
                               "kernel_ns": 20.0}
    ops = dict(out["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion bf16[2,8]": 35e-9,
                                 "pallas bf16[4,2,2,128]": 40e-9,
                                 "copy f32[3]": 20e-9})
    gaps = dict(out["breakdown"]["idle_gaps"])
    # [0,5) and [95,100) in step 0; [100,130) bookkeeping; [130,150),
    # [170,180) and [200,230) in step 1
    assert gaps == pytest.approx({"bench.step": 70e-9,
                                  "bench.bookkeeping": 30e-9})
    # a gap no host span covers
    ev = _events()
    ev["host"] = [h for h in ev["host"] if h[0] != "bench.bookkeeping"]
    gaps = dict(tr.reduce(ev)["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"bench.step": 70e-9, "host.other": 30e-9})


def test_cover_and_union():
    merged = tr.union([(5, 10), (0, 3), (2, 4), (8, 12)])
    assert merged == [(0, 4), (5, 12)]
    cover = tr.Cover(merged)
    assert cover(0, 12) == 11
    assert cover(3, 6) == 2
    assert cover(4, 5) == 0
    assert cover(20, 30) == 0


def test_label():
    assert tr.label("%fusion.99 = s32[32]{0:T(128)S(1)} fusion(s32[1,32] %p)"
                    ) == "fusion s32[32]"
    assert tr.label('%closed_call.15 = bf16[32,8,2,128]{3,2,1,0:T(2,128)} '
                    'custom-call(bf16[32] %a), custom_call_target='
                    '"tpu_custom_call"') == "pallas bf16[32,8,2,128]"
