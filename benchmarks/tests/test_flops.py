"""FLOPs per token and parameter counts against hand counts for the three
published GPT-2 sizes (small and medium from their configuration files;
large, which no cell runs yet, from its published sizes)."""
import json
from pathlib import Path

import pytest

from harness import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
# by hand: per block 12 h^2 matrix parameters (3h^2 qkv, h^2 proj, 8h^2 MLP);
# head 50257 h. gpt2: 12 x 12 x 768^2 + 50257 x 768 = 84,934,656 + 38,597,376
HAND = {
    "gpt2-small": dict(matmul=123_532_032, total=124_439_808,
                       per_token_1024=6 * 123_532_032 + 6 * 12 * 768 * 1024),
    "gpt2-medium": dict(matmul=24 * 12 * 1024 ** 2 + 50257 * 1024,
                        total=354_823_168,
                        per_token_1024=6 * (24 * 12 * 1024 ** 2
                                            + 50257 * 1024)
                        + 6 * 24 * 1024 * 1024),
    "gpt2-large": dict(matmul=36 * 12 * 1280 ** 2 + 50257 * 1280,
                       total=774_030_080,
                       per_token_1024=6 * (36 * 12 * 1280 ** 2
                                           + 50257 * 1280)
                       + 6 * 36 * 1280 * 1024),
}


LARGE = {"n_layer": 36, "n_embd": 1280, "n_head": 20, "n_inner": 5120,
         "vocab_size": 50257, "n_positions": 1024}


@pytest.mark.parametrize("name", sorted(HAND))
def test_counts(name):
    cfg = LARGE if name == "gpt2-large" else \
        json.loads((CONFIGS / f"{name}.json").read_text())
    assert flops.matmul_params(cfg) == HAND[name]["matmul"]
    # the published totals: 124M, 355M, 774M
    assert flops.total_params(cfg) == HAND[name]["total"]
    assert flops.train_flops_per_token(cfg, 1024) == \
        HAND[name]["per_token_1024"]


def test_mfu_cannot_pass_100_at_the_peak():
    """At the chip's peak (197e12) the utilization formula reads 100%."""
    from harness import device
    from readers import mfu
    cfg = json.loads((CONFIGS / "gpt2-small.json").read_text())
    per_token = flops.train_flops_per_token(cfg, 1024)
    obs = {"step_seconds": [0.1, 0.1, 0.3], "chips": 2,
           "tokens_per_step": 2 * 0.1 * 197e12 / per_token,
           "device_kind": "TPU v5 lite", "config": cfg, "seq": 1024}
    assert abs(mfu.read(obs) - 100.0) < 1e-9
    with pytest.raises(ValueError):
        device.peak("TPU v9", "bf16_flops")
