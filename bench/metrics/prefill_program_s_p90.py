"""Admission prefill programs on the device: 90th percentile, over the
traced steps that ran prompt work, of the summed device time of
``prefill``, ``prefill_chunk``, ``commit_prefill`` and ``commit_chunk``
(``bench/engine_trace.py``)."""
from bench import engine_trace

read = engine_trace.prefill_program_s_p90
