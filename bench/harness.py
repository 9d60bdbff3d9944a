"""One run of one cell: set-up, the open-loop window, the drain, the check
of what the window served, and the metrics.

The window drives ``ServingEngine.submit`` and ``step`` from one thread:
each request is submitted once it is due, and the engine steps while it
has work.  Every time is on the host's clock, in seconds after the window
opened.  A token counts as delivered when the ``step()`` that produced it
returns.  After the window the engine drains for at most ``DRAIN_S``; a
request still unfinished then is missing: it counts in ``failed`` and as
unbounded in every tail.

With ``trace`` on, the same run also records a profiler trace from
``TRACE_S`` seconds before the window closes to the end of the drain, and
each call into the engine is a named host span in it (``bench.submit``,
``bench.step``, ``bench.bookkeeping``, ``bench.wait``), beside the
engine's own ``engine.*`` spans and named programs and kernels.  After the
drain ``bench/engine_trace.py`` reduces the trace into the record's
``trace``; the device metrics read the traced steps that started inside
the window.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

from bench import correct, engine_trace, peaks, schedule, stats
from bench.cell import BENCH, load_file

DRAIN_S = 60.0
TRACE_S = 5.0
METRICS = BENCH / "metrics"


class Request:
    """What the host saw of one request."""
    __slots__ = ("rid", "due", "submit", "admit_step", "token_steps",
                 "prompt_len", "max_new", "done", "req")

    def __init__(self, rid, due, prompt_len, max_new):
        self.rid, self.due = rid, due
        self.prompt_len, self.max_new = prompt_len, max_new
        self.submit = None
        self.admit_step = None
        self.token_steps: list[int] = []
        self.done = False
        self.req = None


class Steps:
    """Host times of every ``engine.step()`` in the window and the drain."""

    def __init__(self):
        self.t0: list[float] = []
        self.t1: list[float] = []
        self.decoded: list[bool] = []
        self.t_win = 0.0                 # perf_counter when the window opened


def _warm_up(engine, sched, Req):
    """Run every program the window will run, once: one request of each
    distinct prompt length up to the prefill chunk (the bucket and pad
    signatures) and the schedule's longest prompt (chunked prefill), each
    decoding a token."""
    chunk = engine.prefill_chunk or 0
    one_of_each = {len(p): p for p in sched.prompts if len(p) <= chunk}
    prompts = [one_of_each[n] for n in sorted(one_of_each)]
    longest = max(sched.prompts, key=len)
    if len(longest) > chunk:
        prompts.append(longest)
    for i, p in enumerate(prompts):
        engine.submit(Req(rid=-1 - i, prompt=p, max_new=2))
    engine.run_until_idle()


class _CompileCounter:
    """Counts programs traced or compiled while it is open."""

    def __init__(self):
        self.events = []
        self.open = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.open and event in ("/jax/core/compile/jaxpr_trace_duration",
                                   "/jax/core/compile/backend_compile_duration"):
            self.events.append((event, duration))


class _GcPauses:
    """Durations of the garbage collector's full (oldest-generation) runs
    while it is open."""

    def __init__(self):
        self.pauses: list[float] = []
        self.open = False
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if not self.open or info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)


def _span(trace: bool, name: str, **kw):
    if not trace:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, **kw)


def _load_reader(name: str):
    return load_file(METRICS / f"{name}.py", "bench_metric").read


def read_metrics(metrics: list[dict], record: dict) -> dict:
    """Each metric from its reader; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in metrics:
        value = _load_reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _profile_options():
    """Host spans of the harness and the device's operations; no Python
    function tracing, which would slow the host it measures."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def drive(engine, sched, seconds: float, Req, *,
          trace_dir: Path | None = None):
    """The open-loop window and the drain.  Returns the host's view of each
    request, the steps, the time the drain ended (window seconds) and
    whether a trace was recorded into ``trace_dir / "window"``."""
    trace = trace_dir is not None
    reqs = [Request(i, float(d), len(p), int(m)) for i, (d, p, m) in
            enumerate(zip(sched.due, sched.prompts, sched.max_new))]
    steps = Steps()
    n, nxt, open_n = len(reqs), 0, 0
    tracing = False
    steps.t_win = t_win = time.perf_counter()
    clock = lambda: time.perf_counter() - t_win
    while True:
        now = clock()
        if trace and not tracing and seconds - TRACE_S <= now < seconds:
            jax.profiler.start_trace(str(trace_dir / "window"),
                                     profiler_options=_profile_options())
            tracing = True
        if nxt < n and reqs[nxt].due <= now:
            with _span(trace, "bench.submit"):
                while nxt < n and reqs[nxt].due <= now:
                    r = reqs[nxt]
                    r.req = Req(rid=r.rid, prompt=sched.prompts[r.rid],
                                max_new=r.max_new)
                    engine.submit(r.req)
                    r.submit = clock()
                    nxt += 1
                    open_n += 1
        if nxt == n and open_n == 0:
            break
        if now >= seconds + DRAIN_S:
            break
        if engine.queue or any(a is not None for a in engine.active):
            k = len(steps.t0)
            d0 = engine.decode_steps
            t0 = clock()
            with _span(trace, "bench.step", step=k):
                engine.step()
            t1 = clock()
            with _span(trace, "bench.bookkeeping"):
                steps.t0.append(t0)
                steps.t1.append(t1)
                steps.decoded.append(engine.decode_steps > d0)
                done = engine.pop_completed()
                for q in (*engine.active, *done):
                    if q is None or q.rid < 0:
                        continue
                    r = reqs[q.rid]
                    if r.admit_step is None:
                        r.admit_step = k
                    got = len(q.out)
                    if got > len(r.token_steps):
                        r.token_steps += [k] * (got - len(r.token_steps))
                    if q.done and not r.done:
                        r.done = True
                        open_n -= 1
        else:
            with _span(trace, "bench.wait"):
                wake = reqs[nxt].due if nxt < n else now
                time.sleep(max(0.0, min(wake - clock(), 0.005)))
    end = clock()
    if tracing:
        jax.profiler.stop_trace()
    return reqs, steps, end, tracing


def serve(cell: dict, *, seed: int, seconds: float, trace: bool,
          t_proc: float) -> tuple[dict, dict]:
    """Set-up, the window and the drain.  Returns the run's record and the
    weights; the engine is freed.  The cell's family makes the weights and
    the engine's model."""
    from repro.serving.engine import Request as Req
    from repro.serving.engine import ServingEngine

    cfg, mix, fam = cell["config"], cell["traffic"], cell["family"]
    device = jax.devices()[0]
    w = fam.init_weights(cfg, jax.random.key(schedule.weight_seed(seed)))
    engine = ServingEngine(fam.arch_config(cfg), fam.engine_params(w),
                           slots=mix["slots"], s_max=mix["s_max"])
    sched = schedule.build(mix, vocab=cfg["vocab_size"], seed=seed,
                           seconds=seconds)
    _warm_up(engine, sched, Req)
    tmp = None
    if trace:                    # the profiler's own first start is set-up
        tmp = Path(tempfile.mkdtemp(prefix="bench_trace_"))
        jax.profiler.start_trace(str(tmp / "warm"),
                                 profiler_options=_profile_options())
        jax.profiler.stop_trace()
    compiles, pauses = _CompileCounter(), _GcPauses()
    pre0, dec0 = engine.preemptions, engine.decode_steps
    compiles.open = pauses.open = True
    reqs, steps, end, traced = drive(engine, sched, seconds, Req,
                                     trace_dir=tmp)
    compiles.open = pauses.open = False
    mem = device.memory_stats() or {}
    record = {
        "config": cfg, "family": fam, "traffic": mix,
        "seconds": float(seconds),
        "setup_s": steps.t_win - t_proc, "end_s": end,
        "peaks": peaks.lookup(device.device_kind),
        "requests": reqs, "steps": steps,
        "preemptions": engine.preemptions - pre0,
        "decode_dispatches": engine.decode_steps - dec0,
        "compiles": compiles.events, "gc_pauses": pauses.pauses,
        "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
        "trace": None,
    }
    del engine
    gc.collect()
    try:
        if traced:          # the traced steps that started in the window
            last = int(np.searchsorted(steps.t0, seconds)) - 1
            record["trace"] = engine_trace.reduce_dir(tmp / "window", last)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    return record, w


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             t_proc: float, log=print) -> dict:
    """One run: the result line's object."""
    record, w = serve(cell, seed=seed, seconds=seconds, trace=trace,
                      t_proc=t_proc)
    cfg, mix, fam = cell["config"], cell["traffic"], cell["family"]
    reqs, steps = record["requests"], record["steps"]
    n = len(reqs)
    device = jax.devices()[0]
    finished = [r for r in reqs if r.done]
    t_check = time.perf_counter()
    checks = correct.check(fam, w, cfg, mix, cell["limits"], finished,
                           unfinished=sum(not r.done for r in reqs),
                           seed=seed)
    t_check = time.perf_counter() - t_check
    ok = correct.holds(checks)
    if record["compiles"]:
        log(f"compiled inside the window: {len(record['compiles'])} "
            f"programs ({sum(d for _, d in record['compiles']):.3f} s)")
    metrics = read_metrics(cell["per_layer"] if trace else
                           cell["end_to_end"], record)
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": record["memory_peak_bytes"]}
    result = {"correct": bool(ok), "attempted": n,
              "failed": sum(not r.done for r in reqs),
              "metrics": metrics, "device": dev}
    if trace and record["trace"] is not None:
        dev["busy_s"] = record["trace"]["busy_s"]
        dev["window_s"] = record["trace"]["window_s"]
        result["breakdown"] = record["trace"]["breakdown"]
    live = (stats.live_kv_tokens(record)[stats.window_steps(record)]
            * fam.kv_bytes_per_token(cfg))
    result["window"] = {"requests_due": n, "steps": len(steps.t0),
                        "live_kv_bytes_mean": float(live.mean()),
                        "live_kv_bytes_max": int(live.max()),
                        "decode_dispatches": record["decode_dispatches"],
                        "preemptions": record["preemptions"],
                        "compiles_in_window": len(record["compiles"]),
                        "full_gc_in_window": len(record["gc_pauses"]),
                        "full_gc_s": sum(record["gc_pauses"]),
                        "end_s": record["end_s"], "check_s": t_check}
    result["checks"] = checks
    return result
