"""Percentiles with missing requests, the FLOP and byte counts of each
configuration's family, and the table of peaks."""
import json
import math
from pathlib import Path

import jax
import pytest

from bench import cell, harness, peaks, stats

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def _family(cfg):
    return cell.family(cfg["model_type"])


def _record(token_steps, done, end_s=10.0):
    """Requests due at 0, 1, 2, ... with their tokens at the steps given;
    step k ends at k + 0.5 s."""
    reqs = []
    for i, (ts, d) in enumerate(zip(token_steps, done)):
        r = harness.Request(i, float(i), 4, len(ts) if d else 8)
        r.token_steps, r.done, r.submit = list(ts), d, float(i)
        reqs.append(r)
    steps = harness.Steps()
    steps.t0 = [k + 0.0 for k in range(40)]
    steps.t1 = [k + 0.5 for k in range(40)]
    return {"requests": reqs, "steps": steps, "end_s": end_s}


def test_tail_nearest_rank():
    assert stats.tail([], 90) is None
    assert stats.tail([3.0], 99) == 3.0
    v = list(range(1, 101))
    assert stats.tail(v, 90) == 90 and stats.tail(v, 99) == 99
    assert stats.tail(v, 50) == 50 and stats.tail(v, 100) == 100


def test_unfinished_requests_count_as_missing():
    # nine requests finish with a TTFT of 0.5 s; the tenth never gets a
    # token: it is missing, infinite in the tail, and sets the p95
    fin = [[i, i + 1] for i in range(9)]
    rec = _record(fin + [[]], [True] * 9 + [False], end_s=30.0)
    ttft = stats.ttft(rec)
    assert ttft[:9] == [0.5] * 9 and ttft[9] == math.inf
    assert stats.tail(ttft, 90) == 0.5
    assert stats.tail(ttft, 95) == math.inf
    # unfinished requests, with and without a token, add one infinite gap
    # each to the ITL
    rec = _record([[0, 1], [1, 2, 3], [2], []], [True, True, False, False],
                  end_s=12.0)
    assert sorted(stats.itl(rec)) == [1.0, 1.0, 1.0, math.inf, math.inf]
    assert stats.tail(stats.itl(rec), 50) == 1.0
    assert stats.tail(stats.itl(rec), 99) == math.inf


def test_step_readers():
    # request 0 streams its prompt over steps 1-3 (first token at 3),
    # request 1 is admitted whole at step 5; steps 1-3 and 5 ran prompt
    # work, and step 6 started after the 6 s window closed
    rec = _record([[3, 4, 5], [5, 6]], [True, True])
    rec["requests"][0].admit_step, rec["requests"][1].admit_step = 1, 5
    st = rec["steps"]
    st.t1 = [k + 0.1 * (k + 1) for k in range(40)]     # step k lasts 0.1(k+1)
    st.decoded = [k != 1 for k in range(40)]
    rec["seconds"] = 6.0
    assert stats.prefill_steps(rec) == {1, 2, 3, 5}
    read = harness._load_reader
    # decoding prompt steps 2, 3, 5 last 0.3, 0.4, 0.6 s
    assert read("admit_tick_s_p90")(rec) == pytest.approx(0.6)
    # decoding steps 0, 2, 3, 4, 5 in the window
    assert read("tick_s_p50")(rec) == pytest.approx(0.4)
    rec["requests"][1].admit_step = None
    rec["requests"][0].token_steps = []
    assert read("admit_tick_s_p90")(rec) is None


def test_live_kv_rows():
    # request 0 (prompt 4) holds a slot from step 1 to its last token at
    # step 5, request 1 (prompt 4) from step 5 to step 6
    rec = _record([[3, 4, 5], [5, 6]], [True, True])
    rec["requests"][0].admit_step, rec["requests"][1].admit_step = 1, 5
    live = stats.live_kv_tokens(rec)
    assert live[:8].tolist() == [0, 4, 4, 5, 6, 7 + 5, 6, 0]


@pytest.mark.parametrize("name, params, kv_kib", [
    ("qwen3-0.6b", 596_049_920, 112), ("qwen3-4b", 4_022_468_096, 144)])
def test_counts_match_the_served_weights(name, params, kv_kib):
    cfg = _cfg(name)
    fam = _family(cfg)
    shapes = jax.eval_shape(
        lambda k: fam.engine_params(fam.init_weights(cfg, k)),
        jax.random.key(0))
    assert fam.param_count(cfg) == params == sum(
        x.size for x in jax.tree.leaves(shapes))
    assert fam.kv_bytes_per_token(cfg) == kv_kib * 1024


@pytest.mark.parametrize("name", ["qwen3-0.6b", "qwen3-4b"])
def test_flops_and_bytes(name):
    cfg = _cfg(name)
    fam = _family(cfg)
    d, v, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    matmul = fam.param_count(cfg) - v * d - n * (2 * d + 2 * hd) - d
    assert fam.layer_matmul_params(cfg) * n == matmul
    # one decoded token: two FLOPs per matmul weight, the tied head, and
    # attention over its context
    assert fam.decode_token_flops(cfg, 100) == (
        2 * matmul + 2 * v * d + n * 4 * h * hd * 100)
    # a one-token prompt is one decode step at context 1
    assert fam.prefill_flops(cfg, 1) == fam.decode_token_flops(cfg, 1)
    # a prompt costs its tokens' matmuls, a causal triangle, one head
    p = 64
    assert fam.prefill_flops(cfg, p) == (
        2 * matmul * p + n * 4 * h * hd * p * (p + 1) // 2 + 2 * v * d)
    assert fam.decode_attn_flops(cfg, 100) == n * 4 * h * hd * 100
    assert fam.decode_attn_bytes(cfg, 100) == n * 2 * (
        2 * h * hd + 2 * 100 * kv * hd)


def test_peaks_refuse_an_unknown_device():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    for kind in ("cpu", "TPU v4", "TPU v5", ""):
        with pytest.raises(KeyError, match="no peaks"):
            peaks.lookup(kind)
