"""Window arithmetic of the benchmark harness."""
import numpy as np
import pytest

import gen_traffic
import window


def test_rate_counts_every_token_over_the_whole_window():
    stamps = {0: [0.5, 1.0, 2.0, 3.0], 1: [1.5, 3.9, 4.5]}
    # tokens in (1, 4]: 2.0, 3.0 (request 0), 1.5, 3.9 (request 1)
    assert window.tokens_in(stamps, 1.0, 4.0) == 4
    assert window.rate(stamps, 1.0, 4.0) == pytest.approx(4 / 3.0)


def test_p90_is_over_all_gaps_not_a_median_of_chunks():
    # one request with many short gaps, one with few long ones
    fast = list(np.arange(0.0, 10.0, 0.1))
    slow = [0.0, 3.0, 6.0, 9.0]
    g = window.gaps({0: fast, 1: slow}, 0.0, 10.0)
    assert len(g) == (len(fast) - 1) + 3
    assert window.pct(g, 90) == pytest.approx(
        float(np.percentile(np.asarray(g), 90)))
    # a median of per-request p90s would read 1.55 s; the tail of all
    # gaps is set by the many short ones
    assert window.pct(g, 90) == pytest.approx(0.1, abs=1e-9)


def test_gaps_end_in_the_window():
    g = window.gaps({0: [0.0, 1.0, 5.0, 11.0]}, 0.5, 10.0)
    assert g == [1.0, 4.0]


def test_ttft_over_requests_submitted_in_the_window():
    submit = {0: 0.0, 1: 2.0, 2: 9.5, 3: 10.0}
    first = {0: 1.0, 1: 3.5, 2: 12.0, 3: 11.0}
    t = window.ttfts(submit, first, 1.0, 10.0)
    # request 0 was sent before the window, request 3 after it; request 2
    # was sent inside and counts although its first token came late
    assert sorted(t) == pytest.approx([1.5, 2.5])


def test_billed_bytes_to_fp16_chunk_bytes():
    chunk = 64 * 2 * 8 * 128 * 2        # one phi4 K+V chunk in fp16
    billed_per_chunk = chunk * 0.28125  # int4 codec ratio with scales
    billed = 7 * billed_per_chunk
    assert window.fp16_bytes(billed, billed_per_chunk, chunk) == \
        pytest.approx(7 * chunk)
    assert window.fp16_bytes(0.0, 0.0, chunk) == 0.0


MIXES = {
    "decode-8k": gen_traffic.load("decode-8k"),
    "zipf": {"prompt": {"law": "zipf_geometric", "a": 1.4, "rank_cap": 64,
                        "lo": 512, "hi": 4088},
             "set_size": 16, "max_new": 8, "max_len": 4096},
    "uniform": {"prompt": {"law": "uniform", "lo": 3000, "hi": 3500},
                "set_size": 8, "max_new": "fill", "max_len": 4096},
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_gets_the_same_lengths_in_another_order(mix):
    m = MIXES[mix]
    n = int(m["set_size"])
    s1, s2 = gen_traffic.stream(m, 1000, 7), gen_traffic.stream(m, 1000,
                                                                   2**40 + 3)
    a = [len(next(s1).prompt) for _ in range(n)]
    b = [len(next(s2).prompt) for _ in range(n)]
    assert sorted(a) == sorted(b) == sorted(
        gen_traffic.length_set(m["prompt"], n))
    assert max(a) < int(m["max_len"])
    s3 = gen_traffic.stream(m, 1000, 7)
    assert [len(next(s3).prompt) for _ in range(n)] == a
