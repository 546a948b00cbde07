"""The traffic generator: seeds, the schedule every seed shares, and the
length distributions and clips."""
import numpy as np

import smoke  # noqa: F401  (puts bench/ on the path)
import gen_traffic
import harness

CHAT = harness.data_file("traffic", "chat-over")
BIG = 2**31 + 12345


def test_same_seed_same_requests_and_large_seeds():
    a = gen_traffic.open_loop(CHAT, BIG, 1000, 20)
    b = gen_traffic.open_loop(CHAT, BIG, 1000, 20)
    c = gen_traffic.open_loop(CHAT, BIG + 1, 1000, 20)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]


def test_every_seed_gets_the_same_schedule():
    """Arrivals and lengths, in their order, are the mix's own; the seed
    draws only the tokens.  Each block holds the same quantiles."""
    runs = [gen_traffic.open_loop(CHAT, s, 1000, 60) for s in (1, 2, BIG)]
    shape = [[(r["due"], len(r["prompt"]), r["max_new"]) for r in reqs]
             for reqs in runs]
    assert shape[0] == shape[1] == shape[2]
    assert runs[0][0]["prompt"] != runs[1][0]["prompt"]
    blk = CHAT["block"]
    blocks = [sorted(len(r["prompt"]) for r in runs[0][i:i + blk])
              for i in (0, blk, 2 * blk)]
    assert blocks[0] == blocks[1] == blocks[2]
    other = gen_traffic.open_loop(dict(CHAT, schedule_seed=1), 1, 1000, 60)
    assert [r["max_new"] for r in other] != [r[2] for r in shape[0]]


def test_lengths_follow_the_lognormal_and_its_clips():
    spec = dict(CHAT, block=2000)
    reqs = gen_traffic.open_loop(spec, 5, 1000, 1)
    p = np.array([len(r["prompt"]) for r in reqs])
    o = np.array([r["max_new"] for r in reqs])
    assert p.min() >= 16 and p.max() == 3584 and o.min() == 8 and o.max() == 512
    assert abs(np.median(p) - 512) <= 2 and abs(np.median(o) - 128) <= 1
    # sigma 1: the 84th percentile is e times the median, before the clip
    assert abs(np.percentile(p, 84.13) / 512 - np.e) < 0.05
    gaps = np.diff([r["due"] for r in reqs])
    assert abs(gaps.mean() - 1 / CHAT["rate_per_s"]) < 0.02
    assert all(1 <= t < 1000 for r in reqs[:50] for t in r["prompt"])
