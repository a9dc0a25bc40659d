"""Model FLOP/s utilization of a training cell: the benchmark's own
operations per token (``harness/flops.py``) times the tokens of one step
over the median step time and the chips, over the published bf16 peak of
that chip kind. Taken from the median step so that a traced run, whose
window the profiler stalls, reads the same as an untraced one."""
from harness import device, flops
from harness.window import percentile


def read(obs):
    steps = obs.get("step_seconds") or []
    if not steps or obs.get("device_kind") is None:
        return None
    rate = obs["tokens_per_step"] / percentile(steps, 50) / obs["chips"]
    per_token = flops.train_flops_per_token(obs["config"], obs["seq"])
    return 100.0 * per_token * rate / device.peak(obs["device_kind"],
                                                  "bf16_flops")
