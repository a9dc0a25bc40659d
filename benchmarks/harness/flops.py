"""Operations a GPT-2 training step needs, counted from the configuration.

Model FLOP/s utilization counts what the forward and backward passes
require and nothing recomputed: 6 operations per matrix-multiplied parameter
per token (2 forward, 4 backward), plus attention's two batched products
(QK^T and PV). Attention is causal, so each query needs on average half the
sequence: 2 products x 2 ops x (S/2) x H per layer forward, three times that
with the backward pass = 6 x layers x H x S per token.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication: the blocks'
    four linear maps and the tied output head. Position embeddings, biases
    and layer norms are looked up or added, not multiplied."""
    h, i = cfg["n_embd"], cfg["n_inner"]
    per_block = 3 * h * h + h * h + h * i + i * h
    return cfg["n_layer"] * per_block + cfg["vocab_size"] * h


def total_params(cfg: dict, vocab_rows: int | None = None) -> int:
    """Every parameter: embeddings, blocks with biases and norms, final
    norm. ``vocab_rows`` is the embedding table's height where it is padded
    past the published vocabulary."""
    h, i = cfg["n_embd"], cfg["n_inner"]
    v = cfg["vocab_size"] if vocab_rows is None else vocab_rows
    per_block = (3 * h * h + 3 * h) + (h * h + h) + (h * i + i) \
        + (i * h + h) + 4 * h
    return v * h + cfg["n_positions"] * h + cfg["n_layer"] * per_block + 2 * h


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 6.0 * matmul_params(cfg) \
        + 6.0 * cfg["n_layer"] * cfg["n_embd"] * seq_len
