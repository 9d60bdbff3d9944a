"""Admission prefill: 90th percentile host time of the window's
engine.step() calls that ran prompt work (a whole prompt or one chunk of a
streamed one) beside a decode.  Every other live request waits through
such a step, so these steps set the tail of the token gap."""
import numpy as np

from bench import stats


def read(record):
    st = record["steps"]
    pre = stats.prefill_steps(record)
    k = [i for i in stats.window_steps(record) if st.decoded[i] and i in pre]
    if not k:
        return None
    return stats.tail((np.asarray(st.t1)[k] - np.asarray(st.t0)[k]).tolist(),
                      90)
