"""Profiler trace in, numbers out.

``events`` reads an XSpace (the ``.xplane.pb`` that ``jax.profiler``
writes) into two lists on the profiler's one clock, in ns: the host spans
that the harness writes (``bench.*``; a step span carries its step index)
and the operations that ran on the device (the ``XLA Ops`` line of each
TPU plane).  ``reduce`` turns them into what the metrics read:

* ``busy_s`` and ``window_s``: the union of device operations over the
  traced steps (first step start to last step end), averaged over chips,
  and the length of that window;
* per step: its span, the device busy time inside it, and the time of the
  Pallas kernels (``tpu_custom_call``) that started inside it;
* ``breakdown``: the device operations that took most time, by kind and
  output shape, and the idle time inside the window by the host span that
  was open (``host.other`` where none was).

This reduction reads neither the engine's own spans nor the names of its
programs and kernels: ``bench/engine_trace.py``, which the harness calls,
builds on it and adds them.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

HOST_PREFIX = "bench."
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
PALLAS = 'custom_call_target="tpu_custom_call"'
# ops that only hold others; their time is their children's
CONTAINERS = ("while", "conditional", "call")
_OP = re.compile(r"%[\w.\-]+ = (\(.*?\)|\S+?)(?:\{[^}]*\})? ([\w\-]+)\(")


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def label(hlo: str) -> str:
    """'<kind> <output shape>' of an HLO instruction's text."""
    m = _OP.match(hlo)
    if not m:
        return hlo[:60]
    kind = "pallas" if PALLAS in hlo else m.group(2)
    shape = re.sub(r"\{[^}]*\}", "", m.group(1))
    return f"{kind} {shape[:60]}"


def events(xspace) -> dict:
    """Host spans and device operations of one trace: ``xspace`` is the
    path of an ``.xplane.pb`` or its bytes."""
    import jax
    pd = (jax.profiler.ProfileData.from_serialized_xspace(xspace)
          if isinstance(xspace, bytes) else
          jax.profiler.ProfileData.from_file(str(xspace)))
    host, dev = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        step = _stat(ev, "step")
                        host.append((ev.name, ev.start_ns, ev.end_ns,
                                     -1 if step is None else int(step)))
        elif plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.extend((label(ev.name), ev.start_ns, ev.end_ns,
                                plane.name) for ev in line.events)
    return {"host": host, "device": dev}


def find_xplane(log_dir) -> str:
    found = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def union(intervals) -> list[tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


class Cover:
    """Length of any [s, e) that sorted disjoint intervals cover."""

    def __init__(self, merged):
        self.a = np.asarray([m[0] for m in merged], np.float64)
        self.b = np.asarray([m[1] for m in merged], np.float64)
        self.cum = np.concatenate([[0.0], np.cumsum(self.b - self.a)])

    def __call__(self, s, e) -> float:
        i0 = int(np.searchsorted(self.b, s, side="right"))
        i1 = int(np.searchsorted(self.a, e, side="left"))
        if i1 <= i0:
            return 0.0
        tot = self.cum[i1] - self.cum[i0]
        tot -= max(0.0, s - self.a[i0]) + max(0.0, self.b[i1 - 1] - e)
        return float(tot)


def reduce(ev: dict, last_step: int | None = None, top: int = 10) -> dict:
    """The traced steps up to ``last_step`` (all when None) make the
    window; see the module's docstring for what comes out."""
    host, dev = ev["host"], ev["device"]
    chips = max(1, len({d[3] for d in dev}))
    steps = sorted((h for h in host if h[0] == "bench.step" and
                    (last_step is None or h[3] <= last_step)),
                   key=lambda h: h[1])
    if not steps:
        raise ValueError("the trace holds no bench.step span")
    lo, hi = steps[0][1], steps[-1][2]
    merged = union((s, e) for _, s, e, _ in dev)
    cover = Cover(merged)
    starts = np.asarray([s for _, s, _, _ in steps], np.float64)
    per_step = {k: {"span_ns": e - s, "busy_ns": cover(s, e) / chips,
                    "kernel_ns": 0.0} for _, s, e, k in steps}
    by_op: dict[str, float] = {}
    for name, s, e, _ in dev:
        if not lo <= s < hi or name.split(" ", 1)[0] in CONTAINERS:
            continue
        by_op[name] = by_op.get(name, 0.0) + (e - s)
        if name.startswith("pallas "):
            i = int(np.searchsorted(starts, s, side="right")) - 1
            if i >= 0 and s < steps[i][2]:
                per_step[steps[i][3]]["kernel_ns"] += (e - s) / chips
    spans = sorted(host, key=lambda h: h[1])
    span_ends = np.asarray([h[2] for h in spans], np.float64)
    gaps: dict[str, float] = {}

    def idle(a, b):             # split [a, b) over the host spans it meets
        j = int(np.searchsorted(span_ends, a, side="right"))
        while a < b:
            if j < len(spans) and spans[j][1] < b:
                s0, e0 = max(a, spans[j][1]), min(b, spans[j][2])
                if s0 > a:
                    gaps["host.other"] = gaps.get("host.other", 0.0) + s0 - a
                gaps[spans[j][0]] = gaps.get(spans[j][0], 0.0) + e0 - s0
                a, j = e0, j + 1
            else:
                gaps["host.other"] = gaps.get("host.other", 0.0) + b - a
                a = b

    prev = lo
    for a, b in merged + [(hi, hi)]:
        a, b = max(a, lo), min(b, hi)
        if a > prev:
            idle(prev, a)
        prev = max(prev, b)
        if prev >= hi:
            break
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": cover(lo, hi) / chips / 1e9,
        "steps": per_step,
        "breakdown": {
            "device_ops": [[k, v / chips / 1e9] for k, v in
                           sorted(by_op.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": [[k, v / 1e9] for k, v in
                          sorted(gaps.items(), key=lambda x: -x[1])[:top]],
        },
    }


def reduce_dir(log_dir, last_step: int | None = None) -> dict:
    return reduce(events(find_xplane(log_dir)), last_step)
