"""Runtime round trip: median, over the traced steps that ran the decode
program, of the end of the step's last ``engine.sync`` less the end of
``decode_paged``'s last operation (``bench/engine_trace.py``)."""
from bench import engine_trace

read = engine_trace.sync_lag_s_p50
