"""The end-to-end arithmetic on hand-made timestamps, with requests cut by
the window's edges."""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from cellbench import clientmath as cm  # noqa: E402

W0, W1 = 100.0, 110.0


def req(due, tokens, done=True, error=None, sent=None, prompt=10, want=None):
    return {
        "due": due, "sent": due + 0.001 if sent is None else sent,
        "tokens": tokens, "done": done, "error": error, "prompt_tokens": prompt,
        "output_tokens": len(tokens) if want is None else want,
    }


def requests():
    return [
        # started in the ramp, ends inside the window: tpot counts, ttft does not
        req(98.0, [99.0, 99.5, 100.5, 101.0]),
        # wholly inside: four tokens at once (one dispatch), then four more
        req(101.0, [101.4, 101.4, 101.4, 101.4, 102.0, 102.0, 102.0, 102.0]),
        # first token inside, cut by the window's end: neither done nor failed
        req(108.0, [108.6, 109.2, 110.4], done=False, want=9),
        # due inside, refused
        req(105.0, [], done=False, error="HTTP 503"),
        # due after the window
        req(111.0, [111.5, 112.0]),
    ]


def test_out_tok_s_counts_every_token_inside_the_window():
    # inside: 100.5, 101.0 | 8 tokens | 108.6, 109.2
    assert cm.out_tok_s(requests(), W0, W1) == pytest.approx(12 / 10.0)


def test_tpot_is_per_request_over_completed_requests():
    got = cm.tpots_ms(requests(), W0, W1)
    assert got == pytest.approx([(101.0 - 99.0) / 3 * 1e3, (102.0 - 101.4) / 7 * 1e3])
    assert cm.percentile(got, 50) == pytest.approx(sum(got) / 2)


def test_ttft_is_timed_from_the_due_time():
    got = cm.ttfts_ms(requests(), W0, W1)
    assert got == pytest.approx([400.0, 600.0])


def test_gaps_pool_every_gap_that_ends_inside():
    got = sorted(cm.gaps_ms(requests(), W0, W1))
    # r0: 1000 (ends 100.5), 500 | r1: 0,0,0,600,0,0,0 | r2: 600 (1200 ends outside)
    assert got == pytest.approx([0, 0, 0, 0, 0, 0, 500, 600, 600, 1000])


@pytest.mark.parametrize("share,want", [(0.05, 1000.0), (0.2, 800.0), (1.0, 270.0)])
def test_gap_tail_is_a_mean_beyond_the_percentile(share, want):
    gaps = cm.gaps_ms(requests(), W0, W1)
    assert cm.tail_mean(gaps, share) == pytest.approx(want)


def test_gap_tail5_moves_smoothly_where_p99_jumps():
    base = [0.0] * 750 + [550.0] * 240 + [900.0] * 10
    more = [0.0] * 750 + [550.0] * 238 + [900.0] * 12
    assert cm.percentile(base, 99) == pytest.approx(553.5, abs=1)
    assert cm.percentile(more, 99) == pytest.approx(900.0, abs=1)
    a, b = cm.tail_mean(base, 0.05), cm.tail_mean(more, 0.05)
    assert abs(b - a) / a < 0.03


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (100, 4.0), (90, 3.7)])
def test_percentile_interpolates(q, want):
    assert cm.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_attempted_and_failed():
    # due inside: 101, 108, 105; one refused; the cut one is not a failure
    assert cm.attempted_failed(requests(), W0, W1) == (3, 1)


def test_lateness_of_requests_due_inside():
    late = cm.lateness_ms(requests(), W0, W1)
    assert late == pytest.approx([1.0, 1.0, 1.0])


def test_summary_leaves_out_what_it_cannot_compute():
    s = cm.summarise([req(111.0, [111.5])], W0, W1)
    assert s["out_tok_s"] == 0.0 and "tpot_p50_ms" not in s and "gap_tail5_ms" not in s
    full = cm.summarise(requests(), W0, W1)
    for key in ("tpot_p50_ms", "ttft_p50_ms", "ttft_p90_ms", "gap_tail5_ms",
                "gap_p99_ms", "gen_lateness_p99_ms"):
        assert key in full
    assert (full["n_tpot"], full["n_ttft"], full["n_gaps"]) == (2, 2, 10)


def test_live_lanes_and_context():
    reqs = [
        req(0.0, [1.0, 2.0, 3.0, 4.0], prompt=100),  # live on [1, 4)
        req(0.0, [2.0, 5.0], prompt=10, want=3, done=False),  # live from 2 on
    ]
    live = cm.live_lanes_context(reqs, 2.0, 4.0, samples=4)
    # samples at 2.25, 2.75, 3.25, 3.75: both live at each
    assert live["lanes"] == pytest.approx(2.0)
    assert live["context"] == pytest.approx((102 + 11 + 102 + 11 + 103 + 11 + 103 + 11) / 8)
    assert cm.live_lanes_context(reqs, 10.0, 11.0) == {"lanes": 1.0, "context": 12.0}
    assert cm.live_lanes_context([], 0.0, 1.0) is None
