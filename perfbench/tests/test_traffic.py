"""perfbench/lib/traffic.py: determinism and the stated clips."""
import json
import os

import numpy as np

from perfbench.lib import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = json.load(open(os.path.join(HERE, "..", "traffic", "chat-over.json")))


def _gen(seed, seconds=30.0):
    return traffic.serve_requests(SPEC, seed, seconds, 50257)


def test_same_seed_same_requests():
    a, b = _gen(2 ** 31 + 11), _gen(2 ** 31 + 11)
    assert len(a) == len(b) == round(SPEC["rate_rps"] * 30)
    for x, y in zip(a, b):
        assert x["arrival_s"] == y["arrival_s"]
        assert x["max_new_tokens"] == y["max_new_tokens"]
        assert np.array_equal(x["prompt"], y["prompt"])


def test_lengths_inside_their_clips():
    p, o = SPEC["prompt_len"], SPEC["output_len"]
    for r in _gen(5):
        assert p["min"] <= len(r["prompt"]) <= p["max"]
        assert 1 <= r["max_new_tokens"] <= o["max"]
        assert len(r["prompt"]) + r["max_new_tokens"] <= SPEC["max_total"]
        assert 0 <= r["arrival_s"] < 30.0
        assert r["prompt"].dtype == np.int32 and r["prompt"].max() < 50257


def test_seeds_offer_the_same_work_in_another_order():
    a, b = _gen(1), _gen(2 ** 31 + 2)
    assert np.allclose([r["arrival_s"] for r in a][::8],
                       [r["arrival_s"] for r in b][::8], rtol=0, atol=1e-9)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert sum(r["shared"] >= 0 for r in a) == sum(r["shared"] >= 0 for r in b)
    assert abs(sum(r["max_new_tokens"] for r in a)
               - sum(r["max_new_tokens"] for r in b)) <= 0.01 * sum(
                   r["max_new_tokens"] for r in a)
    # every eight consecutive requests hold one of each octile: level sums
    for key in (lambda r: r["max_new_tokens"], lambda r: len(r["prompt"])):
        for reqs in (a, b):
            sums = [sum(key(r) for r in reqs[i:i + 8])
                    for i in range(0, len(reqs) - 7, 8)]
            assert max(sums) - min(sums) <= 0.15 * max(sums)


def test_shared_requests_begin_with_a_system_prompt():
    reqs = _gen(7)
    sh = SPEC["shared_prefix"]
    groups = {}
    for r in reqs:
        if r["shared"] >= 0:
            groups.setdefault(r["shared"], []).append(
                r["prompt"][:sh["tokens"]].tolist())
    assert len(groups) == sh["count"]
    assert all(all(p == ps[0] for p in ps) for ps in groups.values())
    counts = sorted((len(v) for v in groups.values()), reverse=True)
    assert counts[0] > counts[-1]            # Zipf, not uniform
    assert sum(counts) == round(sh["share"] * len(reqs))


def test_corpus_windows():
    raw = (np.arange(5000) % 251).astype(np.uint8)
    off = traffic.corpus_offsets(len(raw), 3, 128, 4, 10)
    assert off.shape == (10, 4) and off.max() + 129 <= len(raw)
    assert np.array_equal(off, traffic.corpus_offsets(len(raw), 3, 128, 4, 10))
    b = traffic.corpus_batch(raw, off[0], 128)
    assert b.shape == (4, 129) and b.dtype == np.int32
    assert np.array_equal(b[1], raw[off[0, 1]:off[0, 1] + 129])
