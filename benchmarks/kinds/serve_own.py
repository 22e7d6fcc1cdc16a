"""Traffic kind `serve_own`: `serve` (its server, load generator, window
and check, imported and not copied) for a mix whose prompts are one of the
shared prefixes followed by an own part with a law of its own:

    "prompt": {"shared": {"count": 4, "tokens": 2048, "zipf_s": 1.0},
               "own": {"dist": "lognormal", "median": 1024, "sigma": 0.6,
                       "min": 256, "max": 3840}}

`traffic.py` draws one law for the whole prompt and lays `length - prefix`
tokens behind the prefix, so no file of parameters gives it a prefix plus a
lognormal.  Here the whole prompt's length is the prefix's plus the own
law's quantiles, and everything else is the generator's own: the stratified
arrivals, the choice of prefix, the outputs, the tokens.  While `serve`
drives the run, `traffic.quantile_lengths` reads a law with an `own` part
so; a law without one is read as before.
"""
from __future__ import annotations

import contextlib

from benchmarks import traffic as gen
from benchmarks.kinds import serve

_plain = gen.quantile_lengths


def prompt_lengths(dist: dict, n: int):
    """`quantile_lengths`, with `shared.tokens` added to the quantiles of
    `own` where the law has an own part."""
    if "own" in dist:
        return dist["shared"]["tokens"] + _plain(dist["own"], n)
    return _plain(dist, n)


@contextlib.contextmanager
def own_part_law():
    """The generator draws prompt lengths by `prompt_lengths` inside."""
    gen.quantile_lengths = prompt_lengths
    try:
        yield
    finally:
        gen.quantile_lengths = _plain


def drive(run):
    with own_part_law():
        serve.drive(run)
