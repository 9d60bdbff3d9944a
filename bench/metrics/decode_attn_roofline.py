"""Kernels: the decode-attention kernel's share of its roofline, in
percent.  The least time is the larger of the FLOPs over the peak rate and
the bytes over the peak bandwidth, where the bytes are what the algorithm
needs (each decoded token's query and output, and the live key and value
rows of its context), summed over the traced steps that admitted no
request; the time is the kernel's device time in those steps."""
from bench import stats


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    f, b = stats.decode_attn_work(record)
    skip = stats.admitting_steps(record)
    pk = record["peaks"]
    need = kern = 0.0
    for k, s in tr["steps"].items():
        if k in skip or s["kernel_ns"] <= 0:
            continue
        need += max(f[k] / pk["bf16_flops_per_s"], b[k] / pk["hbm_bytes_per_s"])
        kern += s["kernel_ns"] / 1e9
    if kern <= 0 or need <= 0:
        return None
    return 100.0 * need / kern
