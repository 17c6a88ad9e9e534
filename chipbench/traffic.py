"""The one traffic generator.  A traffic mix is a data file
``traffic/<name>.json`` of parameters; this module turns it and a seed
into inputs.  The program under test receives only what comes out.

Every seed gets the SAME sizes and arrival gaps in the SAME order, drawn
once from the mix's own ``set_seed``; only the token ids (and the
weights) follow ``--seed``.  So runs with different seeds do the same
work and their spread is the system's, not the draw's.  (An order of
its own per seed was tried first: which requests fall into a 40 s window
then moved the decode cell's tokens per second by 13 % between seeds,
against 0-2 % between two runs of one seed; PERF.md section 6.)

Mix parameters (all in the file):

``kind: "batches"``   training: ``batch`` rows of ``seq`` + 1 token ids
                      per step, fresh every step.
``kind: "requests"``  serving: ``loop`` ``"open"`` (``rate_per_s``,
                      ``arrivals`` ``"poisson"`` or ``"uniform"``,
                      ``burst`` requests per arrival) or ``"closed"``
                      (``clients``, each sends its next request when its
                      last completes; ``stagger`` true cuts the first
                      request of each client to a random share of its
                      length so the window opens on a steady state);
                      ``prompt_len`` / ``output_len``: a distribution
                      ``{"dist": "uniform"|"lognormal"|"fixed", ...}``
                      clipped to ``lo``..``hi``; ``shared_prefix``:
                      tokens of a prompt prefix common to
                      ``prefix_groups`` groups of requests (0 = none);
                      ``pool``: how many distinct sizes a closed loop
                      cycles through."""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def rng_for(seed, stream):
    seed = int(seed)
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def _lengths(spec, n, rng):
    kind = spec["dist"]
    if kind == "fixed":
        x = np.full(n, spec["value"], float)
    elif kind == "uniform":
        x = rng.uniform(spec["lo"], spec["hi"] + 1, n)
    elif kind == "lognormal":
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), spec.get("lo", 1),
                   spec.get("hi", 1 << 30)).astype(int)


def train_batches(mix, seed, vocab):
    """Endless [batch, seq + 1] int32 batches; rows all differ."""
    rng = rng_for(seed, 1)
    shape = (mix["batch"], mix["seq"] + 1)
    while True:
        yield rng.integers(0, vocab, shape, dtype=np.int32)


def serve_plan(mix, seed, seconds, vocab):
    """The requests of one run: a list of dicts with ``prompt`` (int32
    ids), ``max_new`` and, in an open loop, ``due_s`` from the window's
    start (sorted); in a closed loop the list is the order in which
    clients draw their requests, and the first ``clients`` entries carry
    ``first_new``, the staggered length of the fill made during
    set-up."""
    fixed = np.random.default_rng(mix["set_seed"])
    ids = rng_for(seed, 3)
    if mix["loop"] == "open":
        n_arr = max(1, int(np.ceil(mix["rate_per_s"] * seconds
                                   / mix.get("burst", 1))))
        mean_gap = mix.get("burst", 1) / mix["rate_per_s"]
        if mix["arrivals"] == "poisson":
            gaps = fixed.exponential(mean_gap, n_arr)
            # the same offered load in every run: the set of gaps is
            # scaled to span the window exactly
            gaps *= seconds / gaps.sum()
        else:
            gaps = np.full(n_arr, mean_gap)
        due = np.repeat(np.cumsum(gaps) - gaps[0], mix.get("burst", 1))
        n = due.size
    else:
        n = mix["pool"]
        due = None
    plen = _lengths(mix["prompt_len"], n, fixed)
    olen = _lengths(mix["output_len"], n, fixed)
    share = fixed.uniform(0.05, 1.0, n)
    pre = int(mix.get("shared_prefix", 0))
    groups = [ids.integers(0, vocab, pre, dtype=np.int32)
              for _ in range(mix.get("prefix_groups", 1))] if pre else []
    plan = []
    for i in range(n):
        body = ids.integers(0, vocab, int(plen[i]), dtype=np.int32)
        if pre:
            body = np.concatenate([groups[i % len(groups)], body])
        r = {"prompt": body, "max_new": int(olen[i])}
        if due is not None:
            r["due_s"] = float(due[i])
        elif mix.get("stagger") and i < mix["clients"]:
            r["first_new"] = max(1, int(olen[i] * share[i]))
        plan.append(r)
    return plan
