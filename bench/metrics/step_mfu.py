"""Whole step: model FLOPs of the prompt and decode tokens that the
window's engine.step() calls produced, over the time of those calls times
the chip's peak, in percent."""
import numpy as np

from bench import stats


def read(record):
    st = record["steps"]
    k = stats.window_steps(record)
    span = float(np.sum(np.asarray(st.t1)[k] - np.asarray(st.t0)[k]))
    if span <= 0:
        return None
    work = float(np.sum(stats.step_flops(record)[k]))
    if work <= 0:
        return None
    return 100.0 * work / (span * record["peaks"]["bf16_flops_per_s"])
