"""Operations and bytes of serving a Llama-shaped model, from shapes.

A product is counted as 2 x multiply-adds. A request's useful work is its
prompt's prefill (every position through every layer, causal attention over
the positions before it, the head at the last prompt position only, where
the first token is chosen) and one decode step per further output token
(attention over everything before it, and the head). Padding of prompts to
a prefill bucket, and slots that ride along in a decode step without a
request, are not useful work and do not count.
"""
from __future__ import annotations

BF16 = 2


def dims(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"], "H": heads,
            "KH": cfg["num_key_value_heads"], "hd": cfg["hidden_size"] // heads,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def token_matmuls(cfg: dict) -> float:
    """Operations of one token through every layer's projections and MLP."""
    n = dims(cfg)
    d, hd = n["d"], n["hd"]
    per_layer = d * n["H"] * hd + 2 * d * n["KH"] * hd + n["H"] * hd * d + 3 * d * n["F"]
    return 2.0 * n["L"] * per_layer


def attention(cfg: dict, pairs: float) -> float:
    """Operations of ``pairs`` (query, key) pairs in every layer: the score
    and the weighted value, per query head."""
    n = dims(cfg)
    return 4.0 * n["L"] * n["H"] * n["hd"] * pairs


def head(cfg: dict) -> float:
    n = dims(cfg)
    return 2.0 * n["d"] * n["V"]


def prefill(cfg: dict, prompt: int) -> float:
    return prompt * token_matmuls(cfg) + attention(cfg, prompt * (prompt + 1) / 2) + head(cfg)


def decode_step(cfg: dict, context: int) -> float:
    """One output token whose attention reads ``context`` positions (itself
    included)."""
    return token_matmuls(cfg) + attention(cfg, context) + head(cfg)


def request(cfg: dict, prompt: int, output: int) -> float:
    """A request with ``output`` served tokens: the first from its prefill,
    each later one from a decode step at context ``prompt + j``."""
    contexts = output - 1
    pairs = contexts * prompt + contexts * (contexts + 1) / 2
    return prefill(cfg, prompt) + contexts * (token_matmuls(cfg) + head(cfg)) + attention(cfg, pairs)


def flash_decode(cfg: dict, contexts: float, rows: int) -> tuple:
    """Operations and bytes of one layer's paged decode attention for one
    step: ``rows`` active slots whose contexts sum to ``contexts``. It must
    read each slot's keys and values (bfloat16) over its context, and read
    the query and write the output of every query head."""
    n = dims(cfg)
    flops = 4.0 * n["H"] * n["hd"] * contexts
    nbytes = BF16 * (2.0 * n["KH"] * n["hd"] * contexts + 2.0 * rows * n["H"] * n["hd"])
    return flops, nbytes


def flash_decode_least_s(cfg: dict, steps, flops_per_s: float, bytes_per_s: float) -> float:
    """Least time of every layer's flash-decode calls over ``steps``, each a
    ``(contexts, rows)`` pair: per call the larger of operations over the
    peak and bytes over the bandwidth."""
    layers = dims(cfg)["L"]
    total = 0.0
    for contexts, rows in steps:
        flops, nbytes = flash_decode(cfg, contexts, rows)
        total += max(flops / flops_per_s, nbytes / bytes_per_s)
    return layers * total
