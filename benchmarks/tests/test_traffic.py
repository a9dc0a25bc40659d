"""The generator offers every seed the same work: the same multiset of
(class, prompt length, answer length), the same hits and misses in every
block, the same due times; only ids, the prefix drawn and the order inside a
block differ."""
import hashlib
import itertools
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from harness import traffic as T

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
SERVE = [p.stem for p in TRAFFIC.glob("*.json")
         if json.loads(p.read_text())["kind"] == "serve"]


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", SERVE)
def test_two_seeds_same_work_different_ids(name):
    tr = load(name)
    n = 2 * tr["table_size"] + tr["block"]
    a = T.requests(tr, 7, n, 50257)
    b = T.requests(tr, 3_000_000_019, n, 50257)
    shape = lambda rs: Counter(                       # noqa: E731
        (r["cls"], len(r["prompt_ids"]), r["answer"], r["prefix"]) for r in rs)
    assert shape(a) == shape(b)
    blk = tr["block"]
    for i in range(0, n - blk + 1, blk):
        assert shape(a[i:i + blk]) == shape(b[i:i + blk])
        hits = lambda rs: sum(r["prefix"] > 0 for r in rs)   # noqa: E731
        assert hits(a[i:i + blk]) == hits(b[i:i + blk])
    assert any(not np.array_equal(x["prompt_ids"], y["prompt_ids"])
               for x, y in zip(a, b))
    assert [r["answer"] for r in a] != [r["answer"] for r in b]


@pytest.mark.parametrize("name", SERVE)
def test_due_times_do_not_depend_on_the_seed(name):
    tr = load(name)
    if tr["loop"] != "open":
        pytest.skip("closed loop: no schedule")
    d = T.due_times(tr["schedule"], 45.0)
    assert d == T.due_times(tr["schedule"], 45.0)
    gap = 1.0 / tr["schedule"]["rate_per_s"]
    assert len(d) == int(45.0 / gap)
    # groups: `group` requests due together once per period, none lost
    together = Counter(d)
    assert max(together.values()) == tr["schedule"]["group"]
    assert abs(np.mean(d) - np.mean([i * gap for i in range(len(d))])) < 1e-9


@pytest.mark.parametrize("name", SERVE)
def test_same_seed_same_requests(name):
    tr = load(name)
    a = T.requests(tr, 2 ** 31 + 5, 40, 50257)
    b = T.requests(tr, 2 ** 31 + 5, 40, 50257)
    assert all(np.array_equal(x["prompt_ids"], y["prompt_ids"])
               and x["answer"] == y["answer"] for x, y in zip(a, b))


def test_prefix_sharers_carry_a_whole_prefix():
    tr = load("prefill-open-sysprompt-v2")
    rs = T.requests(tr, 3, 64, 50257)
    hits = [r for r in rs if r["prefix"]]
    assert len(hits) == 48 and {r["prefix"] for r in hits} == {512}
    by_pid = {}
    for r in hits:
        by_pid.setdefault(r["prefix_id"], []).append(r["prompt_ids"][:512])
    assert 1 < len(by_pid) <= tr["n_prefixes"]
    for rows in by_pid.values():
        assert all(np.array_equal(rows[0], x) for x in rows)


def test_stratified_quantiles_keep_range_and_median():
    d = {"dist": "lognormal", "min": 32, "max": 384, "median": 96}
    v = T.stratified(d, 96)
    assert min(v) >= 32 and max(v) <= 384 and v == sorted(v)
    assert abs(np.median(v) - 96) <= 2 and max(v) > 300


def digest(reqs):
    h = hashlib.sha256()
    for r in reqs:
        h.update(np.asarray(r["prompt_ids"], np.int32).tobytes())
        for k in ("answer", "index", "prefix_id"):
            h.update(int(r[k]).to_bytes(4, "little", signed=True))
    return h.hexdigest()[:16]


# what ``requests()`` gave before it became the first items of a stream
# without end (the parent of the PR that took the closed loop's list away):
# 440 was that list at 40 s, 220 the open loop's 45 s of schedule + prefixes
PARENT = {
    ("decode-closed-24-v2", 7, 440): "a8f9eedec67180e9",
    ("decode-closed-24-v2", 3_000_000_019, 440): "0717da1b3363900f",
    ("prefill-open-sysprompt-v2", 7, 220): "2ad15903d1dadeb2",
    ("prefill-open-sysprompt-v2", 3_000_000_019, 220): "38139131c705ce98",
}


@pytest.mark.parametrize("name,seed,n", sorted(PARENT))
def test_the_stream_starts_with_the_requests_the_list_held(name, seed, n):
    tr = load(name)
    first = list(itertools.islice(T.request_stream(tr, seed, 50257), n))
    assert digest(first) == PARENT[name, seed, n]
    assert [r["index"] for r in first] == list(range(n))
    # requests(..., n) is the stream's first n
    assert digest(T.requests(tr, seed, n, 50257)) == PARENT[name, seed, n]
    assert digest(T.requests(tr, seed, 50, 50257)) == digest(first[:50])


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
@pytest.mark.parametrize("name", SERVE)
def test_an_unshared_prompt_never_comes_twice(name, seed):
    """Ten tables' worth of the stream: a repeat would hit the prefix cache
    in a class that says it shares nothing, and the runner joins what a
    client saw to the program's marks by the prompt's bytes."""
    tr = load(name)
    stream = T.request_stream(tr, seed, 50257)
    unshared = [r["prompt_ids"].tobytes()
                for r in itertools.islice(stream, 10 * tr["table_size"])
                if not r["prefix"]]
    assert len(unshared) >= 10 * tr["table_size"] // 4
    assert len(set(unshared)) == len(unshared)
    # and goes on past any list: the table cycled, indices counting on
    assert next(stream)["index"] == 10 * tr["table_size"]
