"""How ``correct`` is decided for a served model.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and holding the longest one, is
run through the plain float32 reference over each prompt with its served
tokens. Each served token is greedy, so the reference's logit for it
should be its best up to rounding: the number compared,
``served_logit_gap``, is the widest gap, over every served token of the
sample, by which the served token's reference logit lies below the
reference's best at that position, in units of the standard deviation of
the reference's logits there (so one limit reads alike across models
whose logits differ in scale).

The limits live in ``bench/limits/<workload>.json``, each with the
readings it was set from.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

LIMITS_DIR = Path(__file__).resolve().parent / "limits"


def served_gaps(ref_logits: np.ndarray, served: np.ndarray,
                vocab: int) -> np.ndarray:
    """Per position: reference best minus the reference logit of the
    served token, over the std of the reference's logits of the
    published vocabulary."""
    ref = np.asarray(ref_logits, np.float64)[:, :vocab]
    served = np.asarray(served)
    picked = np.where(served < vocab,
                      ref[np.arange(len(served)), np.minimum(served,
                                                            vocab - 1)],
                      -np.inf)
    return (ref.max(-1) - picked) / ref.std(-1)


def control_gaps(ref_logits: np.ndarray, ctl_logits: np.ndarray,
                 vocab: int) -> np.ndarray:
    """The same gap for the token the control ranks first."""
    return served_gaps(ref_logits, np.argmax(ctl_logits, axis=-1), vocab)


def sample_requests(finished: list, rng: np.random.Generator,
                    min_tokens: int, max_requests: int) -> list:
    """The finished request with the most served tokens, then others drawn
    from ``rng`` until ``min_tokens`` served tokens or ``max_requests``."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.out_tokens), str(r.rid)))
    pick = [order[0]]
    rest = order[1:]
    rng.shuffle(rest)
    for r in rest:
        if (sum(len(p.out_tokens) for p in pick) >= min_tokens
                or len(pick) >= max_requests):
            break
        pick.append(r)
    return pick


def reference_inputs(reqs: list) -> tuple[list, list, list]:
    """Per request: the whole token sequence, the positions whose logits
    chose each served token, and the served tokens."""
    seqs, positions, served = [], [], []
    for r in reqs:
        out = np.asarray(r.out_tokens, np.int32)
        seqs.append(np.concatenate([np.asarray(r.prompt, np.int32), out]))
        positions.append(len(r.prompt) - 1 + np.arange(len(out)))
        served.append(out)
    return seqs, positions, served


def load_limits(workload: str) -> dict:
    return json.loads((LIMITS_DIR / f"{workload}.json").read_text())


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for every number compared.
    A reading that is missing (nothing to compare) fails."""
    out, ok = {}, True
    for name, lim in limits.items():
        v = readings.get(name)
        good = v is not None and np.isfinite(v) and v <= lim["limit"]
        ok = ok and bool(good)
        out[name] = {"value": None if v is None else float(v),
                     "limit": float(lim["limit"])}
    return ok, out
