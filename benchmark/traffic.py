"""The one general traffic generator: it reads a mix's parameters from
`benchmark/traffic/<name>.json` and draws everything else from `--seed`.

A serve mix fixes a small POOL of (prompt length, answer length) pairs, the
quantiles of its two length distributions, and the ORDER they are sent in
(pass after pass, each pass shuffled), both from the mix's own `pool_seed`.
`--seed` draws the token ids (and the weights).  So every seed deals the
system the same sizes in the same order, and two runs differ by rounding and
the clock alone.  Lengths or order drawn per seed made 45 s windows of this
repo's serve cell differ by up to 20 % in tokens per second and 50 % in the
p95 of time to first token, because the batcher's schedule depends on which
requests finish together (PERF.md, PR 24).  Arrivals are `closed` (N clients, each
sends its next request when its last one completes) or `open` (a schedule of
due times at a fixed rate: Poisson, or gamma-distributed gaps with a
coefficient of variation above 1 for bursts, as BurstGPT fits them).

A train mix fixes the batch and the sequence length; the token ids of step i
are drawn from (seed, i), so no two steps and no two rows repeat.
"""
import json
import os
import statistics

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _lengths(spec, count):
    """`count` lengths at the quantiles (i + 1/2) / count of the spec."""
    if spec["dist"] == "fixed":
        return np.full(count, int(spec["value"]), np.int64)
    if spec["dist"] == "lognormal":
        normal = statistics.NormalDist()
        raw = [spec["median"] * np.exp(spec["sigma"] * normal.inv_cdf(
            (i + 0.5) / count)) for i in range(count)]
        return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def length_pool(mix):
    """[(prompt tokens, answer tokens)] of the mix: the same for every seed."""
    n = int(mix["pool"])
    pairing = np.random.default_rng(int(mix["pool_seed"])).permutation(n)
    return list(zip(_lengths(mix["prompt_tokens"], n).tolist(),
                    _lengths(mix["answer_tokens"], n)[pairing].tolist()))


def serve_requests(mix, seed, vocab_size):
    """Endless iterator of (prompt ids int32, answer tokens): the pool in the
    mix's own order, shuffled anew (by the mix) each time it is used up; the
    ids from the seed.  With `shared_prefix` {"tokens": n, "groups": g} a
    request's first n ids are those of one of g prefixes fixed by the seed
    (prompt length included)."""
    rng = np.random.default_rng([int(seed), 1])
    order = np.random.default_rng([int(mix["pool_seed"]), 1])
    pool = length_pool(mix)
    shared = mix.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = rng.integers(0, vocab_size,
                                (int(shared["groups"]), int(shared["tokens"])))
    while True:
        for i in order.permutation(len(pool)):
            n_prompt, n_answer = pool[i]
            ids = rng.integers(0, vocab_size, n_prompt)
            if prefixes is not None:
                pre = prefixes[rng.integers(len(prefixes))][:n_prompt]
                ids[:len(pre)] = pre
            yield ids.astype(np.int32), int(n_answer)


def open_schedule(mix, seed, horizon_s):
    """Due times (seconds from the window's start) of an open loop, up to
    horizon_s: gaps are exponential (`poisson`) or gamma with the mix's
    coefficient of variation `cv` (`bursty`), mean 1 / rate_per_s."""
    arrivals = mix["arrivals"]
    rate = float(arrivals["rate_per_s"])
    rng = np.random.default_rng([int(seed), 2])
    cv = 1.0 if arrivals["process"] == "poisson" else float(arrivals["cv"])
    shape = 1.0 / (cv * cv)
    due, t = [], 0.0
    while True:
        t += rng.gamma(shape, 1.0 / (rate * shape))
        if t >= horizon_s:
            return due
        due.append(t)


def train_batch(mix, seed, step, vocab_size):
    """Token ids [batch, sequence] of training step `step` (from 0)."""
    rng = np.random.default_rng([int(seed), 3, int(step)])
    return rng.integers(0, vocab_size,
                        (int(mix["batch"]), int(mix["sequence"])),
                        dtype=np.int64).astype(np.int32)
