"""Model FLOPs of a Qwen2 decoder step, from the configuration file.

Matmul operations per token are twice the matmul weights a token passes
through: per layer Wq, Wk, Wv, Wo and the three MLP matrices, and the head
once.  Attention adds 4 operations per head dim, head and attended key.
Training counts 3x the forward (forward, and a backward of twice the
forward's operations); recomputation is not counted."""


def shapes(config: dict):
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    g = config["num_key_value_heads"]
    hd = d // h
    ff = config["intermediate_size"]
    return d, h, g, hd, ff, config["vocab_size"], config["num_hidden_layers"]


def layer_matmuls(config: dict, m: int):
    """(m, k, n) of each matmul of one layer at ``m`` rows."""
    d, h, g, hd, ff, _, _ = shapes(config)
    return [(m, d, h * hd), (m, d, g * hd), (m, d, g * hd), (m, h * hd, d),
            (m, d, ff), (m, d, ff), (m, ff, d)]


def step_matmuls(config: dict, m: int, head_rows: int):
    """Every matmul of a forward step: the layers' at ``m`` rows, the head
    at ``head_rows``."""
    d, _, _, _, _, v, layers = shapes(config)
    return layer_matmuls(config, m) * layers + [(head_rows, d, v)]


def layer_weights(config: dict) -> int:
    return sum(k * n for _, k, n in layer_matmuls(config, 1))


def forward_flops(config: dict, tokens: float, attended_keys: float,
                  head_rows: float = None) -> float:
    """``tokens`` through every layer's matmuls, ``head_rows`` of them (all,
    by default) through the head; ``attended_keys`` summed over the tokens
    (one layer's worth)."""
    d, h, _, hd, _, v, layers = shapes(config)
    head_rows = tokens if head_rows is None else head_rows
    return (2.0 * layers * layer_weights(config) * tokens
            + 2.0 * d * v * head_rows
            + 4.0 * h * hd * attended_keys * layers)


def train_flops(config: dict, batch: int, seq: int) -> float:
    pairs = batch * seq * (seq + 1) / 2.0
    return 3.0 * forward_flops(config, batch * seq, pairs)
