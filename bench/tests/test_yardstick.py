"""The yardstick's arithmetic against hand counts: operations and bytes,
and the traffic generator's use of the seed."""
import json
import os

import numpy as np
import pytest

from harness.spec import BENCH_DIR, load_family
from harness.traffic import Traffic, lengths


def _config(name):
    """A configuration file's ``hf_config`` and the counts of its family."""
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        config = json.load(f)
    return config["hf_config"], load_family(config["program"]["family"])


# hand counts at one context: the token at position 999 attends 1000 keys
#   phi4-mini: d 3072, 24/8 heads of 128, d_ff 8192, 32 layers, vocab 200064
#     per-layer matmuls 2·(3072·3072 + 2·3072·1024 + 3072·3072 + 3·3072·8192)
#       = 2·100,663,296 = 201,326,592; × 32 = 6,442,450,944
#     attention 32 · 4 · 24 · 128 · 1000 = 393,216,000
#     head 2 · 3072 · 200064 = 1,229,193,216
#     paged kernel bytes 32 · (2·1000·8·128·2 + 2·24·128·2) = 131,465,216
#   phi3-medium (10 layers): d 5120, 40/10 heads of 128, d_ff 17920, vocab 32064
#     per-layer 2·(5120·5120 + 2·5120·1280 + 5120·5120 + 3·5120·17920)
#       = 2·340,787,200 = 681,574,400; × 10 = 6,815,744,000
#     attention 10 · 4 · 40 · 128 · 1000 = 204,800,000
#     head 2 · 5120 · 32064 = 328,335,360
#     paged kernel bytes 10 · (2·1000·10·128·2 + 2·40·128·2) = 51,404,800
HAND = {
    "phi4-mini-3.8b": (6_442_450_944, 393_216_000, 1_229_193_216, 131_465_216),
    "phi3-medium-14b": (6_815_744_000, 204_800_000, 328_335_360, 51_404_800),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_flops_match_hand_count(name):
    hf, flops = _config(name)
    mm, attn, head, kbytes = HAND[name]
    assert flops.layer_matmul_flops(hf) == mm
    assert flops.attention_flops(hf, 1000) == attn
    assert flops.head_flops(hf) == head
    assert flops.token_flops(hf, 999, True) == mm + attn + head
    assert flops.token_flops(hf, 999, False) == mm + attn
    assert flops.paged_attn_flops(hf, 1000) == attn
    assert flops.paged_attn_bytes(hf, 1000) == kbytes
    # a prompt of 3 rows: rows at positions 0, 1, 2 and the head once
    assert flops.prompt_flops(hf, 3) == (
        3 * mm + attn / 1000 * (1 + 2 + 3) + head)


MIX = {"prompt": {"dist": "lognormal", "median": 256, "sigma": 1.0,
                  "min": 32, "max": 1536},
       "output": {"dist": "uniform", "min": 16, "max": 64},
       "loop": "open", "rate_rps": 1.5, "pool": 4096, "order_seed": 3}


def test_traffic_reproducible_from_seed():
    big = 2 ** 40 + 12345            # seeds may pass 32 bits
    a, b = Traffic(MIX, big, 200064), Traffic(MIX, big, 200064)
    for i in (0, 1, 17, 4095, 4096 + 3):
        assert a.size(i) == a.size(i + 4096)            # the pool repeats
        assert a.size(i) == b.size(i)
        assert a.arrival(i) == b.arrival(i)
        np.testing.assert_array_equal(a.prompt(i), b.prompt(i))
    c = Traffic(MIX, big + 1, 200064)
    assert not np.array_equal(a.prompt(0), c.prompt(0))


def test_every_seed_serves_the_same_work():
    """The seed draws content; sizes and arrivals replay from order_seed."""
    a, c = Traffic(MIX, 1, 32064), Traffic(MIX, 2, 32064)
    assert all(a.size(i) == c.size(i) and a.arrival(i) == c.arrival(i)
               for i in range(100))
    assert abs(a.arrivals[-1] / a.pool - 1 / 1.5) < 1e-3   # mean gap
    d = Traffic(dict(MIX, order_seed=4), 1, 32064)
    assert any(a.size(i) != d.size(i) for i in range(20))
    assert sorted(a.prompt_lens) == sorted(d.prompt_lens)


def test_length_quantiles():
    """A lognormal is truncated to [min, max]: its quantiles are those of
    the part of the distribution inside the range."""
    v = lengths(MIX["prompt"], 4096)
    assert v.min() >= 32 and v.max() <= 1536
    # median 256, sigma 1: the range holds the quantiles from
    # Phi(ln(32/256)) = 0.01879 to Phi(ln(1536/256)) = 0.96341, so the
    # truncated median is the full one's quantile 0.49110: 256 e^-0.02231
    assert abs(np.median(v) - 250.35) <= 1
    assert abs(np.mean(v <= 256) - (0.5 - 0.01879) / (0.96341 - 0.01879)
               ) < 2e-3
    u = lengths(MIX["output"], 4096)
    assert u.min() == 16 and u.max() == 64
    assert abs(u.mean() - 40) < 0.1


def test_prompt_tokens_in_vocabulary():
    t = Traffic(MIX, 5, 100)
    p = t.prompt(3)
    assert p.dtype == np.int32 and p.min() >= 0 and p.max() < 100
    assert len(p) == t.size(3)[0]


def test_prompt_tokens_credited_over_their_span():
    """A prompt counts evenly from its send to its first token: a window
    that holds half of that span takes half of the prompt."""
    from harness.client import Rec, Window
    from harness.endtoend import prompt_tok_s
    win = Window(t0=0.0, open_at=10.0, close_at=20.0)

    def rec(sent, first, n):
        r = Rec(i=0, prompt=np.zeros(n, np.int32), max_new=1, due=sent,
                sent=sent)
        r.times = [first] if first is not None else []
        return r
    recs = [rec(8.0, 12.0, 1000),      # half inside: 500
            rec(12.0, 14.0, 300),      # wholly inside: 300
            rec(19.0, 23.0, 400),      # a quarter inside: 100
            rec(2.0, 5.0, 900),        # before the window: 0
            rec(15.0, None, 700)]      # never answered: 0
    assert prompt_tok_s(recs, win) == pytest.approx((500 + 300 + 100) / 10)
