"""Model step: median host time of the window's engine.step() calls that
dispatched a decode, host round trip included."""
import numpy as np

from bench import stats


def read(record):
    st = record["steps"]
    k = [i for i in stats.window_steps(record) if st.decoded[i]]
    if not k:
        return None
    return float(np.median(np.asarray(st.t1)[k] - np.asarray(st.t0)[k]))
