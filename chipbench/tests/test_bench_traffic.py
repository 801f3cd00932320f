"""The generator: one seed, one schedule; every seed, the same work."""

import numpy as np
import pytest

from chipbench import spec
from chipbench.traffic import Traffic, prompt_buckets

CHAT = spec.load_cell("qwen3-1.7b.chat").mix
LONG = spec.load_cell("qwen3-1.7b.long-prompt").mix
SEED = 2**31 + 12345  # past 32 signed bits: seeds may be that large


def _plan(mix, seed, n=None):
    t = Traffic(mix, 151936, seed, rate=2.3, seconds=45) if mix["loop"] == "open" \
        else Traffic(mix, 151936, seed)
    return t, [t[i] for i in range(n or len(t))]


@pytest.mark.parametrize("mix", [CHAT, LONG], ids=["chat", "long-prompt"])
def test_same_seed_same_requests(mix):
    (ta, a), (tb, b) = _plan(mix, SEED, 48), _plan(mix, SEED, 48)
    if mix["loop"] == "open":
        np.testing.assert_array_equal(ta.dues, tb.dues)
    for x, y in zip(a, b):
        assert x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)
    _, c = _plan(mix, SEED + 1, 48)
    assert any(len(x.prompt) != len(y.prompt) or not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))


@pytest.mark.parametrize("mix", [CHAT, LONG], ids=["chat", "long-prompt"])
def test_lengths_within_buckets_and_limits(mix):
    _, reqs = _plan(mix, SEED, 48)
    buckets = set(prompt_buckets(mix))
    assert {len(r.prompt) for r in reqs} <= buckets
    assert all(mix["output"]["min"] <= r.max_new <= mix["output"]["max"] for r in reqs)
    assert all(r.prompt.dtype == np.int32 and 0 <= r.prompt.min() and r.prompt.max() < 151936
               for r in reqs)


def test_bucket_counts():
    assert prompt_buckets(CHAT) == list(range(64, 769, 64))
    assert prompt_buckets(LONG) == [512, 768, 1024, 1280, 1536, 1792]


def test_every_seed_offers_the_same_work():
    """Open loop: the same lengths and gaps in another order, all due in
    the window. Closed loop: each block of the mix holds the same lengths."""
    ta, a = _plan(CHAT, 1)
    tb, b = _plan(CHAT, 99)
    assert len(a) == len(b) == 104  # ceil(2.3 · 45)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert np.allclose(sorted(np.diff(ta.dues, prepend=0)), sorted(np.diff(tb.dues, prepend=0)))
    assert ta.dues[-1] == pytest.approx(tb.dues[-1]) and ta.dues[-1] < 46
    assert (ta.dues < 45).sum() == (tb.dues < 45).sum() >= 103
    assert list(ta.dues) != list(tb.dues)
    _, la = _plan(LONG, 1, 48)
    _, lb = _plan(LONG, 99, 48)
    for blk in (slice(0, 24), slice(24, 48)):
        assert sorted(len(r.prompt) for r in la[blk]) == sorted(len(r.prompt) for r in lb[blk])
        assert sorted(r.max_new for r in la[blk]) == sorted(r.max_new for r in lb[blk])
    assert [len(r.prompt) for r in la] != [len(r.prompt) for r in lb]
