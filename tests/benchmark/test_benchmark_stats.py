"""Percentile, gap and tokens/s arithmetic on hand-made timelines."""

import pytest

from benchmark import stats as st


@pytest.mark.parametrize("values,q,want", [
    ([], 95, None),
    ([7.0], 95, 7.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20], 95, 19.5),
    ([5, 1, 3], 100, 5.0),
    (list(range(101)), 95, 95.0),
])
def test_percentile(values, q, want):
    assert st.percentile(values, q) == pytest.approx(want)


def req(due, times, state="finished", lag=0.01):
    return {"due": due, "submit": due + lag, "token_times": times,
            "state": state}


def timeline():
    return [
        # from the lead-in: decodes into the window; gaps and tokens count,
        # its first token does not
        req(2.0, [3.0, 9.0, 11.0, 12.0]),
        # due in the window, finished
        req(10.5, [11.0, 11.5, 12.5]),
        # due in the window, still decoding at its end: neither failed nor
        # finished; its later token is outside
        req(15.0, [16.0, 19.0, 21.0], state="running"),
        # due in the window, never gets a first token
        req(12.0, [], state="queued"),
        # due in the window, shed by the engine after one token
        req(13.0, [13.5], state="cancelled"),
        # due in the grace period at the end: not attempted
        req(18.5, [19.5]),
        # due after the window
        req(20.5, []),
    ]


def test_serve_window_counts():
    w = st.serve_window(timeline(), 10.0, 20.0, grace_s=2.0)
    assert (w["attempted"], w["failed"]) == (4, 2)
    assert w["seconds"] == 10.0
    # tokens in [10, 20]: 11,12 | 11,11.5,12.5 | 16,19 | 13.5 | 19.5
    assert w["tokens"] == 9
    assert sorted(w["ttft_s"]) == pytest.approx([0.5, 0.5, 1.0, 8.0])
    assert w["lag_s"] == pytest.approx([0.01] * 4)


def test_serve_window_gaps_end_in_the_window():
    w = st.serve_window(timeline(), 10.0, 20.0, grace_s=2.0)
    # 9->11 and 11->12 of the lead-in request (3->9 ended before the window),
    # 11->11.5->12.5, 16->19; 19->21 ends after it
    assert sorted(w["gaps_s"]) == pytest.approx([0.5, 1.0, 1.0, 2.0, 3.0])
    assert st.percentile(w["gaps_s"], 95) == pytest.approx(2.8)
    assert w["tokens"] / w["seconds"] == pytest.approx(0.9)


def test_a_request_without_first_token_weighs_on_the_tail():
    w = st.serve_window(timeline(), 10.0, 20.0, grace_s=2.0)
    # it enters at window end - due = 8 s, not left out
    assert max(w["ttft_s"]) == pytest.approx(8.0)
    assert st.percentile(w["ttft_s"], 95) > 6.0


@pytest.mark.parametrize("fence_ms,want", [
    # a steady window; one with a stalled group (PERF.md section 7, 1: one
    # to four groups at 1.1-7 times the step among steady ones); none
    ([830.0, 829.5, 831.0, 830.5], (4, 830.25, 831.0)),
    ([830.0, 829.5, 2400.0, 831.0, 830.5], (5, 830.5, 2400.0)),
    ([], (0, None, None)),
])
def test_fence_groups_line(fence_ms, want):
    line = st.fence_groups(fence_ms)
    assert list(line) == ["groups", "fence_ms_p50", "fence_ms_max"]
    assert (line["groups"], line["fence_ms_p50"], line["fence_ms_max"]) == \
        (want[0], pytest.approx(want[1]) if want[1] else None, want[2])
