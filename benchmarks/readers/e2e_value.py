"""One of the quantities the runner computed for the end-to-end metrics
(``obs["e2e"]``), reported as a per-layer metric: a steadier or a sharper
statistic beside the one that is judged."""


def read(obs, of):
    return obs["e2e"].get(of)
