"""The traffic generator: the same seed gives the same requests, every
seed the same sizes in another order, all within the mix's bounds."""
import numpy as np
import pytest

from chip import traffic


@pytest.mark.parametrize("name", ["chat-closed32", "docqa-closed32"])
def test_same_seed_same_requests(name):
    spec = traffic.load(name)
    a, b = (traffic.Traffic(spec, 2**31 + 11, 32064) for _ in range(2))
    da, db = a.documents(), b.documents()
    assert all(np.array_equal(x, y) for x, y in zip(da, db))
    for i in (0, 1, 777, spec["batch"] + 3):
        ra, rb = a.request(i, da), b.request(i, db)
        assert np.array_equal(ra.prompt, rb.prompt)
        assert (ra.max_new_tokens, ra.greedy, ra.document, ra.sampling_seed) == (
            rb.max_new_tokens, rb.greedy, rb.document, rb.sampling_seed)


@pytest.mark.parametrize("name", ["chat-closed32", "docqa-closed32"])
def test_seeds_share_sizes_and_stay_in_bounds(name):
    spec = traffic.load(name)
    n = spec["batch"]
    sizes = []
    for seed in (1, 2**33 + 5):
        t = traffic.Traffic(spec, seed, 151552)
        docs = t.documents()
        reqs = [t.request(i, docs) for i in range(n)]
        doc_len = spec["documents"]["tokens"] if spec["documents"] else 0
        q = [len(r.prompt) - doc_len for r in reqs]
        out = [r.max_new_tokens for r in reqs]
        assert min(q) >= spec["prompt"]["min"] and max(q) <= spec["prompt"]["max"]
        assert min(out) >= spec["output"]["min"] and max(out) <= spec["output"]["max"]
        assert all(0 <= r.prompt.min() and r.prompt.max() < 151552 for r in reqs)
        assert sum(r.greedy for r in reqs) == round(n * spec["greedy_share"])
        if spec["documents"]:
            assert all(np.array_equal(r.prompt[:doc_len], docs[r.document])
                       for r in reqs)
        sizes.append((sorted(q), sorted(out),
                      sorted(r.document or 0 for r in reqs), [r.prompt[-1] for r in reqs]))
    assert sizes[0][:3] == sizes[1][:3]          # the same work ...
    assert sizes[0][3] != sizes[1][3]            # ... in other tokens


def test_length_quantiles():
    chat = traffic.load("chat-closed32")
    q = traffic.lengths(chat["prompt"], 1001)
    assert q[500] == chat["prompt"]["median"]
    assert list(q) == sorted(q)
    u = traffic.lengths({"dist": "uniform", "min": 64, "max": 256}, 193)
    assert sorted(set(u)) == list(range(64, 257))


def test_zipf_picks_follow_the_law():
    picks = traffic.zipf_picks(32, 1.0, 10_000)
    counts = np.bincount(picks, minlength=32)
    want = 10_000 / np.arange(1, 33) / np.sum(1.0 / np.arange(1, 33))
    assert np.all(np.abs(counts - want) <= 1.0)
