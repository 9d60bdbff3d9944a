"""Kernels: the decode-attention kernel's share of its roofline, in
percent.  The least time is the larger of the FLOPs over the peak rate and
the bytes over the peak bandwidth, where the bytes are what the algorithm
needs (each decoded token's query and output, and the live key and value
rows of its context), summed over the traced steps that admitted no
request; the time is the device time of the ``decode_attention`` kernel in
those steps."""
from bench import stats


def read(record):
    f, b = stats.decode_attn_work(record)
    keep = set(range(len(f))) - stats.admitting_steps(record)
    return stats.kernel_roofline(record, "decode_attention", f, b, keep)
