"""Window-edge arithmetic on a synthetic event list."""
from harness import window as W

RECORDS = [
    # first token before the window, done inside: a TPOT sample only
    dict(t_due=0.0, t_first_token=0.9, t_done=2.9, n_tokens=11),
    # both inside
    dict(t_due=1.0, t_first_token=1.5, t_done=3.5, n_tokens=5),
    # first token inside, done after the close: a TTFT sample only
    dict(t_due=4.0, t_first_token=4.4, t_done=6.5, n_tokens=9),
    # one token: no time per output token
    dict(t_due=2.0, t_first_token=2.2, t_done=2.2, n_tokens=1),
    # failed inside the window: no TPOT
    dict(t_due=2.0, t_first_token=2.3, t_done=3.0, n_tokens=4, error="x"),
    # never started
    dict(t_due=4.9, t_first_token=None, t_done=None, n_tokens=0),
]


def test_samples_belong_to_the_window_of_their_closing_event():
    ttft = W.ttft_samples(RECORDS, 1.0, 5.0)
    assert sorted(round(x, 6) for x in ttft) == [0.2, 0.3, 0.4, 0.5]
    tpot = W.tpot_samples(RECORDS, 1.0, 5.0)
    assert sorted(round(x, 6) for x in tpot) == [0.2, 0.5]


def test_edges_are_half_open():
    r = [dict(t_due=0.0, t_first_token=1.0, t_done=5.0, n_tokens=3)]
    assert len(W.ttft_samples(r, 1.0, 5.0)) == 1      # at open: inside
    assert len(W.tpot_samples(r, 1.0, 5.0)) == 0      # at close: outside


def test_rate_reads_counters_at_the_edges():
    a = {"engine.tokens": 1000}
    b = {"engine.tokens": 13000, "engine.steps": 7}
    assert W.rate(a, b, "engine.tokens", 10.0, 50.0) == 300.0
    assert W.counter_delta(a, b, "engine.steps") == 7.0


def test_percentile_interpolates():
    assert W.percentile([], 50) is None
    assert W.percentile([3.0], 90) == 3.0
    assert W.percentile([1, 2, 3, 4], 50) == 2.5
    assert abs(W.percentile(list(range(11)), 90) - 9.0) < 1e-12
