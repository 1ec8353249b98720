"""Fenced timing utilities shared by the benches (and anything else).

JAX dispatch is asynchronous: a wall-clock around ``fn()`` times the
*enqueue* unless the result is fenced with ``block_until_ready``.  Every
bench in this repo needs the same three moves -- fence, best-of repeats,
paired trials with a median ratio -- and before this module each grew its
own copy (``bench_prop``'s phase helpers, ``precision``'s fp32/f64
pairing).  This is the one implementation both import.

Methodology (docs/BENCHMARKS.md): :func:`time_fenced` takes best-of-
``repeats`` after ``warmup`` unmeasured calls (minimum = least-noise
estimator for a deterministic workload); :func:`paired_trials` interleaves
variants A/B/A/B per trial so drift hits both sides equally, and
:func:`median_ratio` reduces the per-trial ratios by median -- robust to a
single noisy trial in a way mean-of-ratios is not.
"""
from __future__ import annotations

import statistics
import time

import jax


def fence(x):
    """Block until ``x`` (any pytree of device arrays) has materialized."""
    return jax.block_until_ready(x)


def time_fenced(fn, repeats: int = 3, warmup: int = 1) -> float:
    """Best-of-``repeats`` wall seconds of ``fn()``, fencing its result.

    ``fn`` needs no fencing of its own -- whatever it returns is passed to
    ``jax.block_until_ready`` inside the timed region, so asynchronous
    dispatch cannot leak work past the clock.
    """
    for _ in range(warmup):
        fence(fn())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fence(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def paired_trials(fns, trials: int = 5, repeats: int = 3, warmup: int = 1):
    """Interleaved timing of variants: ``trials`` rows of per-variant seconds.

    ``fns`` is a sequence of zero-arg callables (each fenced via
    :func:`time_fenced`); each trial times them in order, so slow drift --
    thermal, frequency scaling, a neighbour process -- lands on every
    variant instead of biasing whichever ran last.  Returns a list of
    ``len(fns)``-tuples, one per trial.
    """
    fns = list(fns)
    for fn in fns:  # shared warmup: compiles outside every timed region
        for _ in range(warmup):
            fence(fn())
    return [
        tuple(time_fenced(fn, repeats=repeats, warmup=0) for fn in fns)
        for _ in range(trials)
    ]


def median_of(trials, idx: int) -> float:
    """Median across trials of variant ``idx``'s seconds."""
    return statistics.median(t[idx] for t in trials)


def median_ratio(trials, num: int = 0, den: int = 1) -> float:
    """Median across trials of the per-trial ratio ``t[num] / t[den]``."""
    return statistics.median(t[num] / t[den] for t in trials)


def time_phases(phases, repeats: int = 3, warmup: int = 1) -> dict:
    """Time named zero-arg callables: ``{name: microseconds}``.

    The partitioned bench's phase breakdown (copy/reduce/combine/merge)
    in one call: each phase is fenced and best-of timed independently.
    """
    return {
        name: time_fenced(fn, repeats=repeats, warmup=warmup) * 1e6
        for name, fn in dict(phases).items()
    }
