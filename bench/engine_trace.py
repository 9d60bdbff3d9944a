"""The engine's own spans and named programs in a profiler trace.

``bench/trace_reduce.py`` reads the harness's ``bench.*`` spans and the
device's operations.  This module reads, on the same clock, what the
engine adds: the host spans it enters inside ``engine.step()``
(``engine.*``), and the programs that ran on the device by name (the
``XLA Modules`` line of each TPU plane, named after the jitted function:
``jit_decode_paged(<id>)`` is ``decode_paged``).  ``reduce`` returns what
``trace_reduce.reduce`` returns on the same trace -- the same ``steps``,
``busy_s`` and ``window_s`` -- and adds:

* ``phases``, per step: the self time of each ``engine.*`` span in it (a
  span's length less the part its child spans cover);
* ``programs``, per step: the device time of each program that started in
  it, by name;
* ``kernels``, inside each entry of ``steps``: the device time of the
  Pallas calls that started in the step, by kernel name (``kernel_name``;
  an unnamed kernel is ``pallas``), which adds up to the step's
  ``kernel_ns``;
* ``sync_lag_ns``, per step that ran ``decode_paged``: the end of the
  step's last ``engine.sync`` less the end of that program's last
  operation -- the runtime's round trip from the decode's end to its ids
  on the host.

Its ``breakdown`` labels a Pallas operation by its kernel's name where the
trace carries it, and puts each idle gap down to the named program it
falls inside (``device:<program>``, between the program's first and last
operation), else to the innermost host span open over it, else to
``host.other``.  A program JAX could not name (``jit__lambda``) is kept
under ``programs`` as ``_lambda`` but takes no idle time.

The harness reduces every traced run with ``reduce_dir``.  The four
readers at the end are per-layer metrics of the benchmark: each has its
file under ``bench/metrics/``.  Times per step are in ns and, on several
chips, averaged over them.
"""
from __future__ import annotations

import re

import numpy as np

from bench import stats, trace_reduce
from bench.trace_reduce import PALLAS, find_xplane, union

ENGINE_PREFIX = "engine."
MODULES_LINE = "XLA Modules"
# what XLA names a Pallas call that was given no name, inside a scan (at
# the top level it takes the jitted function's name)
UNNAMED_KERNEL = "closed_call"
UNNAMED_PROGRAM = "_lambda"
DECODE = "decode_paged"
SYNC = "engine.sync"
PREFILL_PROGRAMS = ("prefill", "prefill_chunk", "commit_prefill",
                    "commit_chunk")
_OP = re.compile(r"%([\w.\-]+?)(?:\.\d+)* = (\(.*?\)|\S+?)(?:\{[^}]*\})? "
                 r"([\w\-]+)\(")
_PROGRAM = re.compile(r"(?:jit_)?(.*?)(?:\(\d+\))?$")
_KERNEL = re.compile(r"pallas ([\w.\-]+) ")


def label(hlo: str) -> str:
    """'<kind> <output shape>' of an HLO instruction's text, as
    ``trace_reduce.label``; a Pallas call is 'pallas <name> <shape>', the
    instruction's name being the one its kernel was given
    (``pl.pallas_call(name=...)``), or 'pallas <shape>' for an unnamed
    kernel inside a scan."""
    m = _OP.match(hlo)
    if not m:
        return hlo[:60]
    kind = m.group(3)
    if PALLAS in hlo:
        kind = ("pallas" if m.group(1) == UNNAMED_KERNEL
                else f"pallas {m.group(1)}")
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))
    return f"{kind} {shape[:60]}"


def kernel_name(op: str) -> str:
    """The kernel's name in a Pallas operation's ``label``: 'pallas
    decode_attention bf16[8,8,2,128]' -> 'decode_attention'; 'pallas' for
    an unnamed kernel ('pallas bf16[...]')."""
    m = _KERNEL.match(op)
    return m.group(1) if m else "pallas"


def program_name(module: str) -> str:
    """'jit_decode_paged(123)' -> 'decode_paged'."""
    return _PROGRAM.match(module).group(1)


def events(xspace) -> dict:
    """``trace_reduce.events`` with the engine's spans among the host
    spans, its kernels by name, and the programs (``programs``: name,
    start, end, plane): ``xspace`` is the path of an ``.xplane.pb`` or its
    bytes."""
    import jax
    pd = (jax.profiler.ProfileData.from_serialized_xspace(xspace)
          if isinstance(xspace, bytes) else
          jax.profiler.ProfileData.from_file(str(xspace)))
    host, dev, progs = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((trace_reduce.HOST_PREFIX,
                                           ENGINE_PREFIX)):
                        step = trace_reduce._stat(ev, "step")
                        host.append((ev.name, ev.start_ns, ev.end_ns,
                                     -1 if step is None else int(step)))
        elif plane.name.startswith(trace_reduce.DEVICE_PLANE):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    dev.extend((label(ev.name), ev.start_ns, ev.end_ns,
                                plane.name) for ev in line.events)
                elif line.name == MODULES_LINE:
                    progs.extend((program_name(ev.name), ev.start_ns,
                                  ev.end_ns, plane.name)
                                 for ev in line.events)
    return {"host": host, "device": dev, "programs": progs}


def innermost(spans) -> list[tuple[float, float, str]]:
    """Nested spans ``(name, start, end, ...)`` as sorted disjoint pieces
    ``(start, end, name)``, each named after the innermost span open over
    it; a span that outlives the span it started in is cut at its end."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []       # (end, name), innermost last
    t = -np.inf

    def cut(upto):
        nonlocal t
        if stack and upto > t:
            out.append((t, upto, stack[-1][1]))
        t = max(t, upto)

    for name, s, e, *_ in sorted(spans, key=lambda h: (h[1], -h[2])):
        while stack and stack[-1][0] <= s:
            cut(stack[-1][0])
            stack.pop()
        cut(s)
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        cut(stack[-1][0])
        stack.pop()
    return out


def _spread(a, b, pieces, ends, into, rest):
    """Add each part of [a, b) that a piece of ``pieces`` (sorted, disjoint
    ``(start, end, name)``; ``ends`` their ends) covers to ``into`` under
    the piece's name; hand every other part to ``rest(a, b)``."""
    j = int(np.searchsorted(ends, a, side="right"))
    while a < b:
        if j < len(pieces) and pieces[j][0] < b:
            s0, e0 = max(a, pieces[j][0]), min(b, pieces[j][1])
            if s0 > a:
                rest(a, s0)
            into[pieces[j][2]] = into.get(pieces[j][2], 0.0) + e0 - s0
            a, j = e0, j + 1
        else:
            rest(a, b)
            a = b


def _op_ranges(dev, progs) -> list[tuple[float, float] | None]:
    """Per program: its first operation's start and its last operation's
    end on its plane (None where no operation started inside it)."""
    by_plane: dict[str, tuple[np.ndarray, np.ndarray]] = {}
    for plane in {p[3] for p in progs}:
        ops = sorted((s, e) for _, s, e, pl in dev if pl == plane)
        by_plane[plane] = (np.asarray([o[0] for o in ops], np.float64),
                           np.asarray([o[1] for o in ops], np.float64))
    out = []
    for _, s, e, plane in progs:
        a, b = by_plane[plane]
        i0, i1 = np.searchsorted(a, [s, e], side="left")
        out.append((float(a[i0]), float(b[i0:i1].max())) if i1 > i0
                   else None)
    return out


def reduce(ev: dict, last_step: int | None = None, top: int = 10) -> dict:
    """The traced steps up to ``last_step`` (all when None) make the
    window; see the module's docstring for what comes out."""
    host, dev, progs = ev["host"], ev["device"], ev["programs"]
    bench = [h for h in host if h[0].startswith(trace_reduce.HOST_PREFIX)]
    out = trace_reduce.reduce({"host": bench, "device": dev}, last_step,
                              top)
    chips = max(1, len({d[3] for d in dev}))
    steps = sorted((h for h in bench if h[0] == "bench.step" and
                    (last_step is None or h[3] <= last_step)),
                   key=lambda h: h[1])
    lo, hi = steps[0][1], steps[-1][2]
    starts = np.asarray([s for _, s, _, _ in steps], np.float64)

    def step_of(t):
        """Index of the traced step whose span holds ``t``, or None."""
        i = int(np.searchsorted(starts, t, side="right")) - 1
        return steps[i][3] if i >= 0 and t < steps[i][2] else None

    kernels: dict[int, dict[str, float]] = {k: {} for k in out["steps"]}
    for name, s, e, _ in dev:
        k = step_of(s) if name.startswith("pallas ") else None
        if k is not None:
            n = kernel_name(name)
            kernels[k][n] = kernels[k].get(n, 0.0) + (e - s) / chips
    for k, step in out["steps"].items():
        step["kernels"] = kernels[k]
    pieces = innermost(host)
    phases: dict[int, dict[str, float]] = {k: {} for k in out["steps"]}
    for s, e, name in pieces:
        k = step_of(s) if name.startswith(ENGINE_PREFIX) else None
        if k is not None:
            phases[k][name] = phases[k].get(name, 0.0) + e - s
    programs: dict[int, dict[str, float]] = {k: {} for k in out["steps"]}
    ranges = _op_ranges(dev, progs)
    decode_end: dict[int, float] = {}
    for (name, s, e, _), rng in zip(progs, ranges):
        k = step_of(s)
        if k is None:
            continue
        programs[k][name] = programs[k].get(name, 0.0) + (e - s) / chips
        if name == DECODE:
            decode_end[k] = max(decode_end.get(k, -np.inf),
                                e if rng is None else rng[1])
    sync_end: dict[int, float] = {}
    for name, s, e, _ in host:
        k = step_of(s) if name == SYNC else None
        if k is not None:
            sync_end[k] = max(sync_end.get(k, -np.inf), e)
    sync_lag = {k: sync_end[k] - decode_end[k] for k in decode_end
                if k in sync_end}

    # idle time: inside a named program's operations, else the innermost
    # host span, else nothing the trace names
    on_device = []
    for (name, _, _, _), rng in sorted(zip(progs, ranges),
                                       key=lambda x: x[1] or (0, 0)):
        if rng is None or name == UNNAMED_PROGRAM:
            continue
        a = max(rng[0], on_device[-1][1]) if on_device else rng[0]
        if a < rng[1]:
            on_device.append((a, rng[1], f"device:{name}"))
    dev_ends = np.asarray([p[1] for p in on_device], np.float64)
    host_ends = np.asarray([p[1] for p in pieces], np.float64)
    gaps: dict[str, float] = {}

    def other(a, b):
        gaps["host.other"] = gaps.get("host.other", 0.0) + b - a

    def on_host(a, b):
        _spread(a, b, pieces, host_ends, gaps, other)

    prev = lo
    for a, b in union((s, e) for _, s, e, _ in dev) + [(hi, hi)]:
        a, b = max(a, lo), min(b, hi)
        if a > prev:
            _spread(prev, a, on_device, dev_ends, gaps, on_host)
        prev = max(prev, b)
        if prev >= hi:
            break
    out["phases"], out["programs"], out["sync_lag_ns"] = (phases, programs,
                                                          sync_lag)
    out["breakdown"]["idle_gaps"] = [[k, v / 1e9] for k, v in sorted(
        gaps.items(), key=lambda x: -x[1])[:top]]
    return out


def reduce_dir(log_dir, last_step: int | None = None) -> dict:
    return reduce(events(find_xplane(log_dir)), last_step)


def sched_host_s_p50(record):
    """Engine scheduler on the host: median, over the traced decoding
    steps, of the host time of ``engine.step`` less the time its
    ``engine.sync`` spans waited for the device -- admission, block
    growth, the decode's uploads and enqueue, and the slot walk (the self
    times of every other ``engine.*`` span)."""
    tr = record["trace"]
    if tr is None or "phases" not in tr:
        return None
    decoded = record["steps"].decoded
    host = [sum(ns for name, ns in ph.items() if name != SYNC)
            for k, ph in tr["phases"].items()
            if decoded[k] and "engine.step" in ph]
    if not host:
        return None
    return float(np.median(host)) / 1e9


def sync_lag_s_p50(record):
    """Runtime round trip: median, over the traced steps that ran the
    decode program, of ``sync_lag_ns``."""
    tr = record["trace"]
    if tr is None or not tr.get("sync_lag_ns"):
        return None
    return float(np.median(list(tr["sync_lag_ns"].values()))) / 1e9


def decode_program_s_p50(record):
    """Decode program on the device: median device time of
    ``decode_paged`` over the traced steps that ran it."""
    tr = record["trace"]
    if tr is None or "programs" not in tr:
        return None
    t = [p[DECODE] for p in tr["programs"].values() if DECODE in p]
    if not t:
        return None
    return float(np.median(t)) / 1e9


def prefill_program_s_p90(record):
    """Admission prefill programs on the device: 90th percentile, over the
    traced steps that ran prompt work, of the summed device time of the
    programs that run it (a whole or first-chunk prefill, a later chunk,
    and the commits of either into the KV pool)."""
    tr = record["trace"]
    if tr is None or "programs" not in tr:
        return None
    t = [sum(p.get(n, 0.0) for n in PREFILL_PROGRAMS)
         for p in tr["programs"].values()
         if any(n in p for n in PREFILL_PROGRAMS)]
    if not t:
        return None
    return stats.tail(t, 90) / 1e9
