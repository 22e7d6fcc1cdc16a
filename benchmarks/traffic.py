"""The one general traffic generator: a traffic file's parameters and a
seed in, a list of requests (or of training rows) out.  numpy only.

Every seed gets the same multiset of sizes and of arrival gaps, in another
order: lengths and gaps are the distribution's quantiles at (i + 0.5) / n,
permuted by the seed, so that the work in a window does not change with
the seed, only its order and the tokens themselves.
"""
from __future__ import annotations

import math
import statistics

import numpy as np


def zipf_tokens(rng, n, vocab):
    """n token ids with p(id) ~ 1 / (id + 1) (copied from chip_smoke.py:
    a unigram law a model can start to learn, entropy ~7.8 nats at
    vocab 50304)."""
    ids = np.exp(rng.random(n) * math.log(vocab)).astype(np.int64) - 1
    return np.clip(ids, 0, vocab - 1).astype(np.int32)


def _norm_ppf(q):
    inv = statistics.NormalDist().inv_cdf
    return np.array([inv(float(x)) for x in q])


def quantile_lengths(dist: dict, n: int):
    """n whole lengths: the quantiles (i + 0.5) / n of `dist`, clipped to
    [min, max].  Kinds: uniform, loguniform, lognormal (median, sigma)."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    kind = dist["dist"]
    if kind == "uniform":
        x = lo + q * (hi - lo)
    elif kind == "loguniform":
        x = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif kind == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * _norm_ppf(q))
    elif kind == "fixed":
        x = np.full(n, dist["value"], float)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(int)


def _shared_choice(shared: dict, n: int):
    """Which of the shared prefixes each of n requests carries: counts in
    proportion to 1 / rank**s, largest remainders first."""
    w = 1.0 / np.arange(1, shared["count"] + 1) ** shared["zipf_s"]
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[:n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.arange(shared["count"]), counts)


def _block(spec, rng, n, vocab, prefixes):
    """n requests with the stratified sizes of `spec`, shuffled."""
    plen = rng.permutation(quantile_lengths(spec["prompt"], n))
    olen = rng.permutation(quantile_lengths(spec["output"], n))
    which = None
    if prefixes is not None:
        which = rng.permutation(_shared_choice(spec["prompt"]["shared"], n))
    out = []
    for i in range(n):
        if which is None:
            prompt = zipf_tokens(rng, plen[i], vocab)
        else:
            pre = prefixes[which[i]]
            prompt = np.concatenate(
                [pre, zipf_tokens(rng, plen[i] - len(pre), vocab)])
        out.append({"prompt": prompt.tolist(), "max_new": int(olen[i])})
    return out


def serve_schedule(spec: dict, seed: int, seconds: float, vocab: int):
    """Requests for a `serve` traffic file, by its `mode`.

    open: round(rate * seconds) requests, all due inside the window
    (`due_s` from its start).  The arrivals are stratified, not Poisson:
    the gaps are the exponential law's quantiles at (i + 0.5) / n, scaled
    to sum to `seconds` and permuted by the seed, so every seed offers the
    same number of requests and the same multiset of gaps.  Bursts come
    from the order of the gaps alone, and the count never varies, which a
    Poisson process's would (by about sqrt(n)).
    closed: a pool of requests in blocks of `block`, each block with the
    same stratified sizes, which `clients` clients take in order.
    """
    rng = np.random.default_rng([int(seed), 0x5EED])
    shared = spec["prompt"].get("shared")
    prefixes = None
    if shared:
        prefixes = [zipf_tokens(rng, shared["tokens"], vocab)
                    for _ in range(shared["count"])]
    if spec["mode"] == "open":
        n = max(1, int(round(spec["rate_per_s"] * seconds)))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q)
        gaps = rng.permutation(gaps * (seconds / gaps.sum()))
        due = np.cumsum(gaps) - gaps[0]
        reqs = _block(spec, rng, n, vocab, prefixes)
        for r, t in zip(reqs, due):
            r["due_s"] = float(t)
    elif spec["mode"] == "closed":
        total = int(math.ceil(spec["pool_per_s"] * seconds))
        reqs = []
        while len(reqs) < total:
            reqs += _block(spec, rng, spec["block"], vocab, prefixes)
    else:
        raise ValueError(f"not a serving mode: {spec['mode']!r}")
    for i, r in enumerate(reqs):
        r["id"] = i
    return reqs


def warmup_requests(spec: dict, seed: int, vocab: int):
    """One request per shape the engine compiled: an unshared prompt that
    fills each bucket, then the same prompt with a new tail, so that the
    prefix path runs at each bucket its suffix can fall into."""
    rng = np.random.default_rng([int(seed), 0xA11])
    page = spec["engine"]["page_size"]
    out = []
    for b in spec["engine"]["prompt_buckets"]:
        base = zipf_tokens(rng, b, vocab)
        out.append({"prompt": base.tolist(), "max_new": 4})
        for sb in spec["engine"]["prompt_buckets"]:
            keep = (b - sb) // page * page
            if keep < page or b - keep > sb:
                continue
            tail = zipf_tokens(rng, b - keep, vocab)
            out.append({"prompt": np.concatenate([base[:keep], tail]).tolist(),
                        "max_new": 4})
    for i, r in enumerate(out):
        r["id"] = i
    return out


def train_rows(seed: int, first_row: int, n_rows: int, seq: int, vocab: int):
    """Rows [n_rows, seq + 1] of a `fit` traffic file, each from its own
    stream so that any row can be made alone: row r of seed s is the same
    whoever asks."""
    out = np.empty((n_rows, seq + 1), np.int32)
    for i in range(n_rows):
        rng = np.random.default_rng([int(seed), 0x7A1, first_row + i])
        out[i] = zipf_tokens(rng, seq + 1, vocab)
    return out
