"""Engine scheduler on the host: median, over the traced decoding steps,
of ``engine.step``'s host time less its waits in ``engine.sync``
(``bench/engine_trace.py``)."""
from bench import engine_trace

read = engine_trace.sched_host_s_p50
