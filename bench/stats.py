"""Arithmetic shared by the metric readers: tails over all requests due in
the window, and the work of each engine step, counted by the run's model
family (``record["family"]``)."""
from __future__ import annotations

import math

import numpy as np


def tail(values, p: float) -> float | None:
    """Nearest-rank ``p``-th percentile; None when there is nothing."""
    v = sorted(values)
    if not v:
        return None
    return float(v[max(0, math.ceil(p / 100.0 * len(v)) - 1)])


def token_times(record, r) -> np.ndarray:
    t1 = np.asarray(record["steps"].t1)
    return t1[np.asarray(r.token_steps, np.int64)]


def ttft(record) -> list[float]:
    """Due to first token, per request; a request with no first token by
    the end of the drain is missing: infinite."""
    return [float(token_times(record, r)[0]) - r.due if r.token_steps
            else math.inf for r in record["requests"]]


def itl(record) -> list[float]:
    """Every gap between consecutive tokens of one request; an unfinished
    request adds one infinite gap, the one it never closed."""
    out = []
    for r in record["requests"]:
        out.extend(np.diff(token_times(record, r)).tolist())
        if not r.done:
            out.append(math.inf)
    return out


def window_steps(record) -> np.ndarray:
    """Indices of the steps that started inside the window."""
    return np.nonzero(np.asarray(record["steps"].t0) < record["seconds"])[0]


def step_flops(record) -> np.ndarray:
    """Model FLOPs of the tokens each step produced: a first token carries
    its whole prompt (a chunked prompt is counted in the step that
    finished it), a later token one decode step over its context."""
    cfg, fam = record["config"], record["family"]
    out = np.zeros(len(record["steps"].t0))
    for r in record["requests"]:
        for j, k in enumerate(r.token_steps):
            out[k] += (fam.prefill_flops(cfg, r.prompt_len) if j == 0 else
                       fam.decode_token_flops(cfg, r.prompt_len + j))
    return out


def decode_attn_work(record) -> tuple[np.ndarray, np.ndarray]:
    """Per step, the FLOPs and bytes decode attention needed for the
    tokens the step decoded."""
    cfg, fam = record["config"], record["family"]
    n = len(record["steps"].t0)
    f, b = np.zeros(n), np.zeros(n)
    for r in record["requests"]:
        for j, k in enumerate(r.token_steps[1:], start=1):
            f[k] += fam.decode_attn_flops(cfg, r.prompt_len + j)
            b[k] += fam.decode_attn_bytes(cfg, r.prompt_len + j)
    return f, b


def kernel_roofline(record, kernel: str, flops, bytes_,
                    steps) -> float | None:
    """The named kernel's share of its roofline, in percent, over the
    traced steps among ``steps`` in which it ran: the least time each step
    needs, the larger of its FLOPs over the peak rate and its bytes over the
    peak bandwidth (``flops`` and ``bytes_``: per step, counted by the
    family), summed, over the kernel's device time in those steps (the
    step's ``kernels[kernel]``).  None where there is no trace, the kernel
    did not run or the steps need no work."""
    tr = record["trace"]
    if tr is None:
        return None
    steps, pk = set(steps), record["peaks"]
    need = kern = 0.0
    for k, s in tr["steps"].items():
        t = s["kernels"].get(kernel, 0.0)
        if k not in steps or t <= 0:
            continue
        need += max(flops[k] / pk["bf16_flops_per_s"],
                    bytes_[k] / pk["hbm_bytes_per_s"])
        kern += t / 1e9
    if kern <= 0 or need <= 0:
        return None
    return 100.0 * need / kern


def admitting_steps(record) -> set[int]:
    return {r.admit_step for r in record["requests"]
            if r.admit_step is not None}


def prefill_steps(record) -> set[int]:
    """Steps that ran prompt work: for each request, every step from the
    one that gave it a slot (a chunked prompt streams from there) to the
    one that produced its first token."""
    out = set()
    for r in record["requests"]:
        if r.admit_step is not None and r.token_steps:
            out.update(range(r.admit_step, r.token_steps[0] + 1))
    return out


def live_kv_tokens(record) -> np.ndarray:
    """Per step, the key/value rows the live requests hold after it: each
    request from the step that gave it a slot to the one of its last
    token, its prompt and the tokens it has so far."""
    out = np.zeros(len(record["steps"].t0), np.int64)
    for r in record["requests"]:
        if r.admit_step is None:
            continue
        end = r.token_steps[-1] if r.token_steps else len(out) - 1
        k = np.arange(r.admit_step, end + 1)
        out[k] += r.prompt_len + np.searchsorted(r.token_steps, k,
                                                 side="right")
    return out
