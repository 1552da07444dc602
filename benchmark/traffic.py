"""The one generator of serving traffic. A mix is a data file of
parameters under ``traffic/``; nothing here knows a mix by name.

The schedule -- arrival times, prompt and answer lengths and which
request has which -- is drawn from ``set_seed``, a constant of the file,
so every ``--seed`` offers the same work at the same moments; the seed
draws the token ids (and, in the runner, the weights). Reordering the
same lengths and gaps by the seed was tried first and measured on the
chip (PR 25): in a 45 s window of 54 requests three seeds moved
``serve_tok_s`` by 7 % and ``ttft_p95_ms`` by 6 %, the sampling noise of
a small window, which no bound of at most 10 % can live with. A system
fast enough for some hundreds of requests a window can give the order
back to the seed.
"""
import dataclasses

import numpy as np


@dataclasses.dataclass
class Req:
    due: float            # seconds after the phase clock's zero
    prompt: list
    max_new_tokens: int
    in_window: bool


def _lengths(rng, spec, n):
    """n lengths from {"dist": "lognormal", "median", "sigma", "min",
    "max"} or {"dist": "uniform", "min", "max"} or {"dist": "fixed",
    "value"}."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif dist == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    elif dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rng, spec, n):
    """n inter-arrival gaps of mean 1 from {"process": "poisson"} or
    {"process": "gamma", "cv": c} (cv 1 is Poisson; above it, bursts)."""
    proc = spec["process"]
    if proc == "poisson":
        return rng.exponential(1.0, n)
    if proc == "gamma":
        k = 1.0 / spec["cv"] ** 2
        return rng.gamma(k, 1.0 / k, n)
    raise ValueError(f"unknown arrival process {proc!r}")


def _phase(spec, vocab, seed, rate, seconds, phase_id, in_window):
    n = int(round(rate * seconds))
    if n <= 0:
        return []
    fixed = np.random.default_rng([int(spec.get("set_seed", 0)), phase_id])
    plens = _lengths(fixed, spec["prompt_len"], n)
    alens = _lengths(fixed, spec["answer_len"], n)
    gaps = _gaps(fixed, spec["arrivals"], n)
    own = np.random.default_rng([int(seed), phase_id])
    # first arrival at the phase's start, the last gap trails the last
    due = (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
    shared = spec.get("shared_prefix")
    docs = None
    if shared:
        docs = np.random.default_rng([int(seed), 7]).integers(
            0, vocab, (shared["documents"], shared["len"]))
    reqs = []
    for i in range(n):
        toks = own.integers(0, vocab, int(plens[i]))
        if docs is not None:
            d = docs[own.integers(0, len(docs))]
            k = min(len(d), len(toks))
            toks[:k] = d[:k]
        reqs.append(Req(float(due[i]), toks.tolist(), int(alens[i]),
                        in_window))
    return reqs


def schedule(spec, vocab, seed, seconds, rate=None):
    """Requests of the warm phase then of the window, ``due`` counted
    from the warm phase's start; the window opens at ``spec["warm_s"]``.
    """
    rate = float(spec["rate_rps"] if rate is None else rate)
    warm_s = float(spec["warm_s"])
    warm = _phase(spec, vocab, seed, rate, warm_s, 0, False)
    win = _phase(spec, vocab, seed, rate, float(seconds), 1, True)
    for r in win:
        r.due += warm_s
    return warm + win


def train_batch(vocab, seed, step, batch, seq):
    """The token ids of one training step, from the seed and the step."""
    rng = np.random.default_rng([int(seed), 11, int(step)])
    return rng.integers(0, vocab, (batch, seq), dtype=np.int64)
