"""The one traffic generator: a mix file in, an open-loop schedule out.

A mix (``traffic/<mix>.json``) names an arrival process and two length
distributions by ``kind``; each kind is the module ``traffic/gen_<kind>.py``.
Everything is drawn from the run's seed: the same seed gives the same
schedule, token for token.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass
class Schedule:
    due: np.ndarray            # (n,) seconds after the window opens, sorted
    prompts: list              # n int32 arrays of token ids
    max_new: np.ndarray        # (n,) tokens each request asks for

    def __len__(self) -> int:
        return len(self.due)


def _kind(spec: dict):
    return importlib.import_module(f"bench.traffic.gen_{spec['kind']}")


# the independent streams one seed gives, by use
STREAMS = ("arrivals", "prompt", "output", "tokens", "weights", "sample")


def stream(seed: int, use: str) -> np.random.SeedSequence:
    """The stream for ``use`` from a seed of any size (numpy takes ints of
    any width, where JAX keys take 32 or 64 bits)."""
    return np.random.SeedSequence(int(seed)).spawn(len(STREAMS))[
        STREAMS.index(use)]


def build(mix: dict, *, vocab: int, seed: int, seconds: float) -> Schedule:
    s_arr, s_prompt, s_out, s_tok = (
        np.random.default_rng(stream(seed, use))
        for use in ("arrivals", "prompt", "output", "tokens"))
    arr = _kind(mix["arrivals"])
    n = int(round(arr.mean_rate(mix["arrivals"]) * seconds))
    due = arr.arrivals(mix["arrivals"], n, seconds, s_arr)
    plen = _kind(mix["prompt"]).lengths(mix["prompt"], n, s_prompt)
    olen = _kind(mix["output"]).lengths(mix["output"], n, s_out)
    prompts = [s_tok.integers(0, vocab, int(k), dtype=np.int32) for k in plen]
    return Schedule(due=np.asarray(due, np.float64), prompts=prompts,
                    max_new=np.asarray(olen, np.int32))


def weight_seed(seed: int) -> int:
    """A 32-bit JAX key seed for the weights."""
    return int(stream(seed, "weights").generate_state(1)[0])
