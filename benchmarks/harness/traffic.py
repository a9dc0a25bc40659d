"""One general generator for serving traffic, driven by a traffic file.

A traffic file (``traffic/<name>.json``, ``"kind": "serve"``) gives classes
of requests, each with a share, a distribution of prompt and answer lengths
and, optionally, a shared prefix; a table size; a block size; and for an
open loop the arrival schedule. The generator lays out ONE request table
from the file alone:

- lengths are the fixed quantiles ``(i + 0.5) / n`` of each distribution
  (stratified, heavy tail kept), paired by a permutation drawn from the
  file's ``layout_seed``;
- every block of ``block`` consecutive requests holds the classes in their
  exact ratio;
- due times come from the schedule alone.

So every ``--seed`` offers the same multiset of (class, prompt length,
answer length), the same hits and misses in every block, and the same due
times. The seed decides only token ids, which shared prefix a request
carries, and the order inside a block. ``request_stream`` cycles the table
without end, so a closed loop can ask for as many requests as its engine
answers; ``requests`` is the stream's first ``count`` items.

Distributions: ``{"dist": "lognormal", "min", "max", "median"}`` is a
lognormal with that median whose 99.5th percentile sits at ``max``, cut to
[min, max]; ``{"dist": "uniform", "min", "max"}``; ``{"dist": "fixed",
"value"}``.
"""
from __future__ import annotations

import itertools
import math
import random
from statistics import NormalDist

_Z995 = NormalDist().inv_cdf(0.995)


def quantile(dist: dict, q: float) -> int:
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    lo, hi = int(dist["min"]), int(dist["max"])
    if kind == "uniform":
        x = lo + q * (hi - lo)
    elif kind == "lognormal":
        med = float(dist["median"])
        sigma = math.log(hi / med) / _Z995
        x = med * math.exp(sigma * NormalDist().inv_cdf(q))
    else:
        raise ValueError(f"unknown dist {kind!r}")
    return max(lo, min(hi, int(round(x))))


def stratified(dist: dict, n: int) -> list[int]:
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def request_table(traffic: dict) -> list[dict]:
    """The seed-free table: ``[{"cls", "prompt", "answer", "prefix"}]`` in
    block order. ``prompt`` is the whole prompt length (shared prefix
    included), ``prefix`` the shared part's length (0 for unshared)."""
    n, block = int(traffic["table_size"]), int(traffic["block"])
    classes = traffic["classes"]
    total = sum(c["per_block"] for c in classes)
    if total != block or n % block:
        raise ValueError("classes' per_block must add up to block, and "
                         "table_size be a multiple of block")
    rng = random.Random(int(traffic["layout_seed"]))
    per_class = {}
    for c in classes:
        k = c["per_block"] * (n // block)
        user = stratified(c["prompt"], k)
        ans = stratified(c["answer"], k)
        rng.shuffle(user)
        rng.shuffle(ans)
        pre = int(c.get("shared_prefix", 0))
        per_class[c["name"]] = [
            {"cls": c["name"], "prompt": pre + u, "answer": a, "prefix": pre}
            for u, a in zip(user, ans)]
    table = []
    for b in range(n // block):
        rows = []
        for c in classes:
            rows += per_class[c["name"]][b * c["per_block"]:
                                         (b + 1) * c["per_block"]]
        rng.shuffle(rows)            # a fixed order inside the block
        table += rows
    return table


def due_times(schedule: dict, horizon_s: float) -> list[float]:
    """Open-loop arrival offsets in [0, horizon): one request every
    ``1 / rate_per_s`` seconds, except that in every ``group_every_s`` the
    ``group`` requests nearest its middle are due together (their mean
    time), so the count offered per period does not change."""
    gap = 1.0 / float(schedule["rate_per_s"])
    n = int(horizon_s / gap)
    due = [i * gap for i in range(n)]
    every, group = schedule.get("group_every_s"), int(schedule.get("group", 0))
    if every and group > 1:
        period = int(round(float(every) / gap))
        for start in range(period // 2, n - group + 1, period):
            t = sum(due[start:start + group]) / group
            for j in range(start, start + group):
                due[j] = t
    return due


def request_stream(traffic: dict, seed: int, vocab: int):
    """The requests for ``seed``, without end: the table cycled, each
    block's order shuffled by the seed, every cycle with fresh token ids.
    ``{"index", "cls", "prompt_ids", "answer", "prefix", "prefix_id"}``;
    ``prompt_ids`` is an int32 array. Shared prefixes are ``n_prefixes``
    seeded sequences; each sharing request draws one."""
    import numpy as np
    table = request_table(traffic)
    block = int(traffic["block"])
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    order_rng = random.Random(int(seed))
    n_pre = int(traffic.get("n_prefixes", 0))
    pre_len = max((r["prefix"] for r in table), default=0)
    prefixes = [rng.randint(0, vocab, pre_len).astype(np.int32)
                for _ in range(n_pre)]
    index = 0
    for b in itertools.count():
        at = (b * block) % len(table)
        rows = list(table[at:at + block])
        order_rng.shuffle(rows)
        for r in rows:
            pid = int(rng.randint(0, n_pre)) if r["prefix"] else -1
            user = rng.randint(0, vocab, r["prompt"] - r["prefix"]) \
                .astype(np.int32)
            ids = np.concatenate([prefixes[pid][:r["prefix"]], user]) \
                if r["prefix"] else user
            yield {"index": index, "cls": r["cls"],
                   "prompt_ids": ids, "answer": r["answer"],
                   "prefix": r["prefix"], "prefix_id": pid}
            index += 1


def requests(traffic: dict, seed: int, count: int, vocab: int) -> list[dict]:
    """The first ``count`` items of ``request_stream``."""
    return list(itertools.islice(request_stream(traffic, seed, vocab), count))
