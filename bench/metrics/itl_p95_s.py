"""95th percentile of every gap between consecutive tokens of a request,
over all requests due in the window."""
from bench import stats


def read(record):
    return stats.tail(stats.itl(record), 95)
