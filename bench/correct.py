"""What decides ``correct``: the served tokens against the plain float32
reference, once the window has closed.

A sample drawn from the seed of the requests the window finished -- the
longest among them, then others until ``SAMPLE_TOKENS`` served tokens --
is run through the plain reference of the cell's model family (its
``logits``) over each prompt with its served tokens.  For every served
token the number compared is the gap by which the reference's logit of
that token lies below the reference's best logit at that position; the
check holds the widest gap in the sample to the cell's limit
(``limits/<workload>.json``).  Greedy serving would pick the reference's
best everywhere if it computed exactly; bfloat16 serving may pick a
near-tie, never a token far below the best.

Besides, every finished request must carry exactly the tokens it asked
for, and no request may be left unfinished after the drain.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import schedule

SAMPLE_TOKENS = 512


def sample(finished: list, seed: int) -> list:
    """The longest finished request, then others in an order drawn from the
    seed, until the sample holds ``SAMPLE_TOKENS`` served tokens."""
    if not finished:
        return []
    rng = np.random.default_rng(schedule.stream(seed, "sample"))
    longest = max(finished, key=lambda r: (r.prompt_len + r.max_new, r.rid))
    out, served = [longest], len(longest.req.out)
    for i in rng.permutation(len(finished)):
        if served >= SAMPLE_TOKENS:
            break
        r = finished[i]
        if r is not longest:
            out.append(r)
            served += len(r.req.out)
    return out


@jax.jit
def _gap_of(ref, toks):
    """ref (P, V) float32 logits, toks (P,) -> best - ref[tok] per row."""
    picked = jnp.take_along_axis(ref, toks[:, None], axis=1)[:, 0]
    return jnp.max(ref, axis=1) - picked


def served_gaps(family, w, cfg: dict, s_max: int, p_max: int, seqs,
                control: bool = False) -> np.ndarray:
    """Gaps of every served token of ``seqs`` (pairs of prompt and served
    tokens) under ``family``'s reference.  With ``control``, the tokens
    are those that the fp8 reference puts first at each position instead
    of the served ones."""
    gaps = []
    for prompt, out in seqs:
        n, m = len(prompt), len(out)
        tokens = np.zeros((1, s_max), np.int32)
        tokens[0, :n] = prompt
        tokens[0, n:n + m - 1] = out[:-1]
        rows = np.zeros((1, p_max), np.int32)
        rows[0, :m] = n - 1 + np.arange(m)
        ref = family.logits(w, cfg, tokens, rows)[0]
        if control:
            low = family.logits(w, cfg, tokens, rows, precision="fp8")[0]
            toks = jnp.argmax(low, axis=1).astype(jnp.int32)
        else:
            toks = np.zeros(p_max, np.int32)
            toks[:m] = out
            toks = jnp.asarray(toks)
        gaps.append(np.asarray(_gap_of(ref, toks))[:m])
    return np.concatenate(gaps) if gaps else np.zeros(0)


def check(family, w, cfg: dict, mix: dict, limits: dict, finished: list,
          *, unfinished: int, seed: int) -> dict:
    """Each number compared, beside its limit (None: nothing to compare,
    which fails)."""
    short = sum(len(r.req.out) != r.max_new for r in finished)
    picked = sample(finished, seed)
    seqs = [(np.asarray(r.req.prompt), list(r.req.out)) for r in picked]
    gaps = served_gaps(family, w, cfg, mix["s_max"], mix["output"]["max"],
                       seqs)
    return {
        "max_logit_gap": {"value": float(gaps.max()) if len(gaps) else None,
                          "limit": float(limits["max_logit_gap"])},
        "short_outputs": {"value": short, "limit": 0},
        "unfinished": {"value": unfinished, "limit": 0},
    }


def holds(checks: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
