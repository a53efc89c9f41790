"""The one traffic generator: a traffic mix is a data file of parameters.

``benchmark/traffic/<name>.json`` holds ``{"kind": ..., ...parameters}`` and
this module turns it, with ``--seed`` and ``--seconds``, into the inputs the
driver offers the system. Everything here is a pure function of
(parameters, seed, seconds): same arguments, same inputs, bit for bit.

Every seed of an open-loop mix gets the SAME traffic, starting elsewhere: the
sizes are the quantiles of the stated distribution, not draws from it; which
output length meets which prompt length, the order of the requests and the
order of the gaps between them are functions of the mix alone (``MIX_STREAM``
of seed 0): one cyclic schedule a mix. The seed picks where in that cycle the
window starts (one rotation of requests and gaps together) and draws the token
ids (pixel values). So two seeds offer the same multiset of (prompt length,
output length) pairs and of gaps, each request behind the same neighbours, and
differ in which of them meet the empty engine at the window's start. Seeds
that permuted lengths and gaps independently (before PR 44), or whole requests
and gaps (PR 44's first try), spread a median over the requests by 6-15 % of
itself with nothing else changed, rotations of one cycle by 2-4 % at half the
knee (near the knee nothing is steady): PERF.md section 2.

Kinds:

``open_loop``  requests due on a schedule whatever the system does.
    ``rate_per_s``; ``arrival_cv`` (1 = Poisson, >1 burstier: gamma gaps);
    ``prompt_len`` / ``output_len``: ``{"dist": "lognormal", "median": m,
    "sigma": s, "min": a, "max": b}`` | ``{"dist": "uniform", "min": a,
    "max": b}`` | ``{"dist": "fixed", "value": v}``;
    ``shared_prefix`` (optional): ``{"group_size": g, "tokens": n}`` — each
    run of ``g`` consecutive requests starts with the same ``n`` tokens;
    ``drain_s``, ``trace_s`` (optional, read by the driver and not here):
    how long requests in flight are waited for once the window has closed,
    and how long a traced run's span at the window's end is.
``train_feed`` a rotation of distinct host batches.
    ``batch_per_chip``; ``distinct_batches``.

A ``rehearse`` object in the file overrides parameters for ``--rehearse``.
"""
from __future__ import annotations

import json
import math
import os
import statistics

import numpy as np

SEED_MOD = 2 ** 32
MIX_STREAM = 2       # of seed 0: a mix's pairing and cyclic order, no --seed


def load(path, rehearse=False):
    with open(path) as f:
        params = json.load(f)
    tiny = params.pop("rehearse", None)
    if rehearse and tiny:
        params.update(tiny)
    params["name"] = os.path.splitext(os.path.basename(path))[0]
    return params


def _rng(seed, stream):
    """Independent generator per (seed, stream); seeds beyond 32 bits fold."""
    return np.random.default_rng([int(seed) % SEED_MOD, int(seed) // SEED_MOD,
                                  stream])


def length_quantiles(spec, n):
    """``n`` integer lengths: the (i + 0.5) / n quantiles of ``spec``."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    u = (np.arange(n) + 0.5) / n
    if dist == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        z = np.asarray([statistics.NormalDist().inv_cdf(x) for x in u])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError("unknown length distribution %r" % (dist,))
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def gap_quantiles(n, cv):
    """``n`` inter-arrival gaps of unit mean with coefficient of variation
    ``cv``: exponential quantiles at cv 1; otherwise a gamma's, taken from a
    large fixed draw so that no inverse CDF is needed."""
    u = (np.arange(n) + 0.5) / n
    if cv == 1:
        gaps = -np.log1p(-u)
    else:
        shape = 1.0 / (cv * cv)
        draw = np.sort(np.random.default_rng(0).gamma(shape, 1.0 / shape,
                                                      200_000))
        gaps = draw[(u * draw.size).astype(np.int64)]
    return gaps / gaps.mean()


def open_loop(params, seed, seconds, vocab):
    """The schedule of one open-loop run: a list of dicts ``due_s``,
    ``prompt`` (int32 token ids) and ``max_new``, ordered by ``due_s``, all
    due inside ``[0, seconds)``."""
    n = max(1, int(round(params["rate_per_s"] * seconds)))
    # the mix's own cycle: pairs, their order and the gaps' order
    mix = _rng(0, MIX_STREAM)
    olens = mix.permutation(length_quantiles(params["output_len"], n))
    pick = mix.permutation(n)
    plens = length_quantiles(params["prompt_len"], n)[pick]
    olens = olens[pick]
    gaps = mix.permutation(gap_quantiles(n, params.get("arrival_cv", 1)))
    # the seed: where in the cycle the window starts
    start = int(_rng(seed, 0).integers(n))
    plens, olens, gaps = (np.roll(a, -start) for a in (plens, olens, gaps))
    due = (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
    ids = _rng(seed, 1)
    shared = params.get("shared_prefix")
    prefix = None
    reqs = []
    for i in range(n):
        prompt = ids.integers(1, vocab, int(plens[i]), dtype=np.int32)
        if shared:
            if i % shared["group_size"] == 0:
                prefix = ids.integers(1, vocab, int(shared["tokens"]),
                                      dtype=np.int32)
            k = min(prefix.size, prompt.size - 1)
            prompt[:k] = prefix[:k]
        reqs.append({"due_s": float(due[i]), "prompt": prompt,
                     "max_new": int(olens[i])})
    return reqs


def train_feed(params, seed, chips, image, classes):
    """The rotation of one training run: ``distinct_batches`` host batches
    ``(x float32 (B, 3, H, W), y float32 (B,))`` with ``B = batch_per_chip *
    chips``. All rows differ."""
    batch = int(params["batch_per_chip"]) * chips
    out = []
    for i in range(int(params["distinct_batches"])):
        rng = _rng(seed, 100 + i)
        x = rng.standard_normal((batch, 3, image, image), dtype=np.float32)
        y = rng.integers(0, classes, batch).astype(np.float32)
        out.append((x, y))
    return out


def max_length(spec):
    """The longest length ``spec`` can give."""
    return int(spec["value"] if spec["dist"] == "fixed" else spec["max"])
