"""One of the run's memory readings in GB (10^9 bytes). ``of`` is ``peak``
(``memory_stats()["peak_bytes_in_use"]`` of the fullest chip at the window's
close: buffers), ``reserved_peak`` (``peak_bytes_reserved``: the most the
runtime set aside for a program's scratch) or ``program_temp`` (what the
compiler says the step program needs while it runs,
``memory_analysis().temp_size_in_bytes``; it counts a donated argument's
space again)."""


def read(obs, of="peak"):
    b = obs.get("program_temp_bytes") if of == "program_temp" \
        else obs["memory"].get(of)
    return None if not b else b / 1e9
