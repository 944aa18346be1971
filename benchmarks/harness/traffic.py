"""ONE general traffic generator, driven by a mix's data file. A mix is
a fixed grid, not a sampler: `--seed` decides the token ids and the
order in which the grid is walked, and nothing else, so every seed
offers the same requests (and, in an open loop, the same gaps) in
another order — the same total work, the same tail population.

Mix files (`benchmarks/traffic/<mix>.json`), by `kind`:

  closed_loop  prompt_len / output_len {min, median, max}; grid [P, O]:
               the P × O pairs of their quantile midpoints; each client
               sends its next request when the last completes, taking
               the next pair of ONE seeded walk over the grid (a fresh
               permutation each lap, balanced block by block: see
               `closed_walk`).
  open_loop    rate_per_s, prompt_len, output_len: N = round(rate ×
               seconds) requests; lengths are the N quantile midpoints
               of each distribution, paired by a fixed stride; gaps are
               the N quantile midpoints of the exponential distribution
               at that rate, scaled to sum to the window. Pairs and
               gaps are each put in a seeded order of balanced blocks
               (`stratified_order`, blocks of `block`, default 8).
  train_steps  batch, seq, loss_every: the seed decides the token ids.

A length distribution {min, median, max} is log-linear in the quantile
on each side of the median (a two-piece log-uniform): short values are
common, the tail reaches `max`.
"""
import json
import math
import os

import numpy as np

EXTENSIONS = (".json", ".jsonl", ".toml", ".txt", ".csv")


def load_mix(traffic_dir, name):
    path = os.path.join(traffic_dir, name + ".json")
    with open(path) as f:
        mix = json.load(f)
    if "kind" not in mix:
        raise ValueError(f"{path}: a mix names its driver under 'kind'")
    return mix


def rng_for(seed, stream):
    """Independent numpy streams of one seed (any whole number)."""
    return np.random.default_rng([int(seed), int(stream)])


def quantile_midpoints(n):
    return (np.arange(n) + 0.5) / n


def length_at(dist, q):
    """The two-piece log-uniform quantile function, rounded to whole
    tokens."""
    lo, med, hi = (float(dist[k]) for k in ("min", "median", "max"))
    q = np.asarray(q, float)
    left = np.exp(np.log(lo) + (np.log(med) - np.log(lo)) * (q / 0.5))
    right = np.exp(np.log(med)
                   + (np.log(hi) - np.log(med)) * ((q - 0.5) / 0.5))
    return np.rint(np.where(q < 0.5, left, right)).astype(int)


def closed_grid(mix):
    """The P × O (prompt, output) pairs, in grid order."""
    n_p, n_o = mix["grid"]
    ps = length_at(mix["prompt_len"], quantile_midpoints(n_p))
    os_ = length_at(mix["output_len"], quantile_midpoints(n_o))
    return [(int(p), int(o)) for p in ps for o in os_]


def closed_walk(mix, seed):
    """An endless seeded walk over the grid, lap after lap, each lap a
    fresh permutation of all its pairs. On a square grid the lap is
    BALANCED: it goes block by block, and every block of P requests
    holds each prompt length and each output length exactly once (a
    row of a Latin square: block b pairs output o with prompt
    (o + shift_b) mod P; the seed draws the shifts — a permutation, so
    a lap covers every pair once — and the order inside each block).
    Whatever the seed, any run of whole blocks — the first wave that
    fills the slots among them — then offers the same lengths, paired
    differently. Yields (prompt_len, output_len)."""
    n_p, n_o = mix["grid"]
    grid = closed_grid(mix)
    rng = rng_for(seed, 1)
    while True:
        if n_p != n_o:
            for i in rng.permutation(len(grid)):
                yield grid[i]
            continue
        for shift in rng.permutation(n_p):
            for o in rng.permutation(n_o):
                yield grid[((o + shift) % n_p) * n_o + o]


def _stride(n):
    """A stride coprime to n near n/φ: pairs the i-th prompt length
    with a far-away output length, the same way for every seed."""
    s = max(1, int(round(n / 1.6180339887)))
    while math.gcd(s, n) != 1:
        s += 1
    return s


def open_count(mix, seconds):
    return max(1, int(round(float(mix["rate_per_s"]) * float(seconds))))


def open_pairs(mix, seconds):
    """The N (prompt, output) pairs of an open-loop window, unshuffled."""
    n = open_count(mix, seconds)
    ps = length_at(mix["prompt_len"], quantile_midpoints(n))
    os_ = length_at(mix["output_len"], quantile_midpoints(n))
    s = _stride(n)
    return [(int(ps[i]), int(os_[(i * s) % n])) for i in range(n)]


def open_gaps(mix, seconds):
    """N inter-arrival gaps: exponential quantile midpoints at the
    mix's rate, scaled so that they sum to the window. Unshuffled."""
    n = open_count(mix, seconds)
    g = -np.log1p(-quantile_midpoints(n)) / float(mix["rate_per_s"])
    return g * (float(seconds) / g.sum())


def stratified_order(rng, n, block):
    """A seeded order of `n` items that are sorted by size: the items
    fall into `block` size classes (contiguous, sizes within one of
    each other); block b of the order takes the b-th item of a seeded
    permutation of each class, and is shuffled inside. Every run of
    `block` consecutive places then holds one item of each class —
    whatever the seed, each stretch of the window offers nearly the
    same work — while which item, and where in its block, is the
    seed's."""
    blocks = [[] for _ in range(-(-n // block))]
    for cls in np.array_split(np.arange(n), min(block, n)):
        for b, i in enumerate(rng.permutation(cls)):
            blocks[b].append(int(i))
    return [int(i) for blk in blocks for i in rng.permutation(blk)]


def open_schedule(mix, seed, seconds):
    """[(due_s, prompt_len, output_len)] for one window: the pairs
    (sorted by prompt length) and the gaps (sorted by length) each put
    in a seeded `stratified_order` of blocks of `mix["block"]`; the
    first request is due at 0 and every one before `seconds`."""
    pairs = sorted(open_pairs(mix, seconds))
    gaps = np.sort(open_gaps(mix, seconds))
    rng = rng_for(seed, 1)
    block = int(mix.get("block", 8))
    pairs = [pairs[i] for i in stratified_order(rng, len(pairs), block)]
    gaps = gaps[stratified_order(rng, len(gaps), block)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [(float(t), p, o) for t, (p, o) in zip(due, pairs)]


def token_ids(seed, stream, shape, vocab_size):
    return rng_for(seed, stream).integers(
        0, int(vocab_size), shape).astype(np.int32)


def train_batches(mix, seed, vocab_size, n):
    """`n` distinct [batch, seq] int32 batches (every row differs)."""
    return token_ids(seed, 2, (n, int(mix["batch"]), int(mix["seq"])),
                     vocab_size)


def summary(mix, seconds=None):
    """The grid in a few numbers, for the run's earlier lines."""
    if mix["kind"] == "closed_loop":
        g = closed_grid(mix)
    elif mix["kind"] == "open_loop":
        g = open_pairs(mix, seconds)
    else:
        return {"kind": mix["kind"], "batch": mix["batch"],
                "seq": mix["seq"]}
    p, o = np.array(g).T
    return {"kind": mix["kind"], "requests": len(g),
            "prompt_min_med_max": [int(p.min()), int(np.median(p)),
                                   int(p.max())],
            "output_min_med_max": [int(o.min()), int(np.median(o)),
                                   int(o.max())],
            "prompt_tokens": int(p.sum()), "output_tokens": int(o.sum())}
