"""The decode step's roofline share over records and steps written by hand:
bytes of weights and of K and V for gpt2-medium counted by hand, live tokens
from one request wholly inside the window, one that straddles its opening
edge and one outside."""
import json
from pathlib import Path

import pytest

from harness import spec

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "gpt2-medium.json").read_text())
R = spec.reader("decode_roofline")
T0 = 100.0                               # a window of 10 s opens here
RECORDS = [
    # wholly inside: decodes for 4 s at a mean of 100 + 50/2 tokens
    {"prompt_len": 100, "n_tokens": 50, "t_first_token": T0 + 1.0,
     "t_done": T0 + 5.0},
    # straddles the opening edge: 2 s of its 6 lie inside; 200 + 100/2
    {"prompt_len": 200, "n_tokens": 100, "t_first_token": T0 - 4.0,
     "t_done": T0 + 2.0},
    # before the window, after it, and one that never made a token
    {"prompt_len": 300, "n_tokens": 10, "t_first_token": T0 - 9.0,
     "t_done": T0 - 1.0},
    {"prompt_len": 300, "n_tokens": 10, "t_first_token": T0 + 10.5,
     "t_done": T0 + 12.0},
    {"prompt_len": 300, "n_tokens": 0, "t_first_token": None, "t_done": None},
]
LIVE = (125 * 4.0 + 250 * 2.0) / 10.0


def obs(**over):
    out = {"config": CFG, "device_kind": "TPU v5 lite", "t_open": T0,
           "t_close": T0 + 10.0, "records": RECORDS,
           "engine_steps": [0.050, 0.069, 0.070, 0.120, 0.068]}
    out.update(over)
    return out


def test_bytes_of_gpt2_medium_by_hand():
    # per block: qkv 3h^2 + 3h, proj h^2 + h, MLP 2 x 4h^2 + 4h + h, two
    # norms 4h; table 50304 rows (padded), positions 1024, final norm 2h
    h = 1024
    per_block = 12 * h * h + 13 * h
    params = 50304 * h + 1024 * h + 24 * per_block + 2 * h
    assert params == 354_871_296
    assert R.weight_bytes(CFG) == 2 * params == 709_742_592      # 0.710 GB
    assert R.kv_bytes_per_token(CFG) == 2 * 24 * 1024 * 2 == 98_304
    f32 = dict(CFG, serve=dict(CFG["serve"], precision="f32"))
    assert R.weight_bytes(f32) == 4 * params
    assert R.kv_bytes_per_token(f32) == 2 * 98_304


def test_live_tokens_inside_straddling_outside():
    assert R.live_tokens(RECORDS, T0, T0 + 10.0) == pytest.approx(LIVE)
    assert R.live_tokens(RECORDS[2:], T0, T0 + 10.0) == 0.0


def test_share_and_what_it_left_for_the_run_to_print():
    o = obs()
    floor_s = (709_742_592 + 98_304 * LIVE) / 819e9
    assert R.read(o) == pytest.approx(100 * floor_s / 0.069)
    note = o["notes"]["decode_roofline"]
    assert note["W_bytes"] == 709_742_592
    assert note["K_bytes"] == pytest.approx(98_304 * LIVE)
    assert note["live_tokens"] == pytest.approx(LIVE)
    assert note["floor_ms"] == pytest.approx(1e3 * floor_s)
    assert note["step_p50_ms"] == pytest.approx(69.0)
    # through the metric's own file, as a run reads it
    m = spec.layer_metric("decode_roofline_share")
    assert spec.reader(m["reader"]).read(obs()) == pytest.approx(R.read(obs()))


def test_a_step_as_fast_as_its_bytes_reads_100():
    floor_s = (709_742_592 + 98_304 * LIVE) / 819e9
    assert R.read(obs(engine_steps=[floor_s] * 3)) == pytest.approx(100.0)


@pytest.mark.parametrize("over", [
    {"engine_steps": []},                       # no step span in the window
    {"records": RECORDS[2:]},                   # nothing decoded inside it
    {"records": []},
    {"device_kind": None},                      # a rehearsal: no peak
])
def test_nothing_to_read_gives_nothing(over):
    assert R.read(obs(**over)) is None


def test_a_chip_without_a_published_rate_is_an_error_not_a_default():
    with pytest.raises(ValueError):
        R.read(obs(device_kind="TPU v9"))
