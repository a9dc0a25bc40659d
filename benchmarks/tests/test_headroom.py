"""The closed loop has no ceiling: a rehearsal of the decode cell sends more
requests than the list held that the runner once built ahead of the window
(clients + 4 x clients + 8 requests/s x seconds, at the real file's cap),
and nothing runs out."""
import json

import tiny


def test_closed_loop_sends_past_the_old_list(capsys):
    seconds = 1.5
    clients = tiny.TINY["gpt2m-serve-decode"]["traffic"]["clients"]
    old_list = clients + 4 * clients + int(8 * seconds)
    out = tiny.rehearse("gpt2m-serve-decode", seed=23, seconds=seconds)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert not [ln for ln in lines if ln.get("check") == "client"]
    assert out["checks_correct"] is True and out["failed"] == 0
    assert out["attempted"] > old_list


def test_the_steps_line_names_the_longest_step_and_the_longest_gap():
    from harness import spec
    stalls = spec.runner("serve")._stalls
    t = 50.0
    steps = [(t + 0.00, 0.07), (t + 0.07, 0.07), (t + 0.14, 0.50),
             (t + 7.64, 0.07)]                 # 7 s with no step at all
    got = stalls(steps, t)
    assert got["count"] == 4
    assert round(got["longest_ms"]) == 500
    assert round(got["longest_at_s"], 2) == 0.14
    assert round(got["longest_gap_ms"]) == 7000
    assert round(got["longest_gap_at_s"], 2) == 0.64
    assert stalls([], t) == {"note": "steps", "count": 0}
    assert stalls(steps[:1], t)["longest_gap_ms"] == 0.0
