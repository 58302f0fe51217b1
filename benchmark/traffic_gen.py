"""The one general generator: a traffic file's parameters and a seed in,
the inputs out.  Every seed gives the same sizes in the same order; only
the contents, and the place where a serving cycle is entered, change.

Training (``"kind": "training"``): batches of ``batch`` rows of ``seq`` ids,
drawn uniformly below the published vocabulary, all rows different, with
next-token labels and the last position of each row ignored.
"""
from __future__ import annotations

import numpy as np

IGNORE = -100


def rng_of(seed, stream=0):
    return np.random.default_rng([int(seed), int(stream)])


def training_batches(traffic, vocab, seed):
    """An endless stream of ``(tokens, labels)`` int32 arrays."""
    if traffic["kind"] != "training" or traffic["ids"] != "uniform":
        raise ValueError(f"not a training mix this generator knows: "
                         f"{traffic}")
    rng = rng_of(seed)
    batch, seq = traffic["batch"], traffic["seq"]
    tail = np.full((batch, 1), IGNORE, np.int32)
    while True:
        tokens = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
        yield tokens, np.concatenate([tokens[:, 1:], tail], axis=1)


# ---------------------------------------------------------------- serving


def _lognormal_grid(spec, n):
    """``n`` lengths at the mid-quantiles of a clipped log-normal: the same
    set for every seed."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(lengths), spec["min"], spec["max"]).astype(int)


def request_sizes(traffic):
    """One cycle of request sizes, fixed by the traffic file alone, as
    groups of ``group`` requests: ``(prompt length, output length, shared
    prefix or -1)``.

    Every group holds one prompt length and one output length from each
    ``group``-quantile band of its distribution, paired by a fixed shuffle,
    so any run of consecutive groups carries nearly the same work whatever
    the order: a window that catches a different stretch of the stream
    still measures the same mix.  One in ``1 / share`` of the prompts long
    enough takes a shared prefix."""
    n, g = traffic["cycle"], traffic["group"]
    if n != g * g:
        raise ValueError("cycle must be group * group")
    prompts = _lognormal_grid(traffic["prompt"], n)
    outputs = _lognormal_grid(traffic["output"], n)
    fixed = np.random.default_rng(0)
    sp = traffic["shared_prefix"]
    every = round(1 / sp["share"])
    groups, eligible = [], 0
    for j in range(g):
        band_of = fixed.permutation(g)
        group = []
        for k in range(g):
            # up the even bands and down the odd ones: the groups' sums
            # come out alike
            p = int(prompts[g * k + (j if k % 2 == 0 else g - 1 - j)])
            b = int(band_of[k])
            o = int(outputs[g * b + (j if b % 2 else g - 1 - j)])
            prefix = -1
            if p >= sp["min_prompt"]:
                if eligible % every == 0:
                    prefix = (eligible // every) % sp["prefixes"]
                eligible += 1
            group.append((p, o, prefix))
        groups.append(group)
    return groups


def serving_requests(traffic, vocab, seed):
    """An endless stream of ``(prompt ids, output length, shared prefix or
    -1)``: the cycle's requests in one fixed order, over and over, entered
    at a place drawn from the seed; the ids and the shared prefixes are
    drawn from the seed.

    The order is the same for every seed because a closed loop's schedule
    follows from it step by step — which rows share a step, and so how long
    the step takes — and the tail of the gaps between tokens moved by 9%
    from one order to another while two runs of one order agreed within
    0.5% (PERF.md).  A seed moves the window along the cycle and changes
    every token; it does not change the work."""
    if traffic["kind"] != "serving_closed":
        raise ValueError(f"not a serving mix this generator knows: "
                         f"{traffic}")
    rng = rng_of(seed)
    sp = traffic["shared_prefix"]
    prefixes = rng.integers(0, vocab, (sp["prefixes"], sp["length"]))
    groups = request_sizes(traffic)
    fixed = np.random.default_rng(1)
    cycle = [groups[j][i] for j in fixed.permutation(len(groups))
             for i in fixed.permutation(len(groups[j]))]
    at = int(rng.integers(len(cycle)))
    while True:
        p, o, prefix = cycle[at % len(cycle)]
        at += 1
        ids = rng.integers(0, vocab, p)
        if prefix >= 0:
            ids[: sp["length"]] = prefixes[prefix]
        yield ids.tolist(), o, prefix
