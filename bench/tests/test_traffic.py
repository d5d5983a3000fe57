"""The schedule is fixed by the traffic file; the seed draws only ids."""

import numpy as np
import pytest

from bench import spec, traffic

VOCAB = 151936


@pytest.mark.parametrize("name", ["docqa", "chat"])
def test_schedule_is_independent_of_the_seed(name):
    tr = spec.traffic(name)
    a, b = traffic.schedule(tr), traffic.schedule(tr)
    assert np.array_equal(a.prompt_len, b.prompt_len)
    assert np.array_equal(a.output_len, b.output_len)
    if a.arrival_s is not None:
        assert np.array_equal(a.arrival_s, b.arrival_s)
    for i in range(64):
        assert a.request(i) == b.request(i)
    s1, s2 = 2 ** 31 + 5, 7
    for i in range(8):
        p1 = traffic.prompt(s1, i, a, VOCAB)
        p2 = traffic.prompt(s2, i, a, VOCAB)
        assert len(p1) == len(p2)
        assert not np.array_equal(p1, p2)
        assert np.array_equal(p1, traffic.prompt(s1, i, a, VOCAB))
        assert p1.min() >= 1 and p1.max() < VOCAB


def test_docqa_asks_documents_round_robin():
    tr = spec.traffic("docqa")
    s = traffic.schedule(tr)
    docs = [s.request(i)[0] for i in range(12)]
    assert docs == [i % tr["documents"]["count"] for i in range(12)]
    seed = 3_000_000_001
    p = traffic.prompt(seed, 4, s, VOCAB)
    doc = traffic.document(seed, docs[4], s, VOCAB)
    assert np.array_equal(p[:len(doc)], doc)
    assert len(p) == len(doc) + s.request(4)[1]
    lo, hi = tr["prompt"]["min"], tr["prompt"]["max"]
    assert lo <= s.prompt_len.min() and s.prompt_len.max() <= hi


def test_chat_lengths_and_arrivals():
    tr = spec.traffic("chat")
    s = traffic.schedule(tr)
    assert 128 <= s.prompt_len.min() and s.prompt_len.max() <= 768
    assert 16 <= s.output_len.min() and s.output_len.max() <= 256
    assert 250 <= np.median(s.prompt_len) <= 400
    assert 48 <= np.median(s.output_len) <= 80
    assert s.arrival_s[0] >= -tr["preroll_s"]
    assert np.all(np.diff(s.arrival_s) > 0)
    # the rate the file states is the rate the schedule offers
    n = np.sum((s.arrival_s >= 0) & (s.arrival_s < 200))
    assert abs(n / 200 - tr["rate_per_s"]) < 0.15 * tr["rate_per_s"]


def test_a_bare_share_sends_the_document_alone():
    tr = dict(spec.traffic("docqa"))
    tr["documents"] = dict(tr["documents"], bare_share=0.25)
    s = traffic.schedule(tr)
    bare = s.prompt_len == 0
    assert 0.2 < bare.mean() < 0.3
    # the other lengths are those the file draws without a bare share
    plain = traffic.schedule(spec.traffic("docqa"))
    assert np.array_equal(s.prompt_len[~bare], plain.prompt_len[~bare])
    assert np.array_equal(s.output_len, plain.output_len)
    i = int(np.flatnonzero(bare)[0])
    seed = 2 ** 33 + 1
    doc = traffic.document(seed, s.request(i)[0], s, VOCAB)
    assert np.array_equal(traffic.prompt(seed, i, s, VOCAB), doc)
    with pytest.raises(ValueError):
        traffic.schedule(dict(spec.traffic("chat"),
                              documents={"bare_share": 0.25}))
