"""Decode program on the device: median device time of ``decode_paged``
over the traced steps that ran it (``bench/engine_trace.py``)."""
from bench import engine_trace

read = engine_trace.decode_program_s_p50
