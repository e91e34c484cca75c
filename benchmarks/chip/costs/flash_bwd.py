"""Causal flash attention backward over ``(batch, heads, seq, head_dim)``
operands: the algorithm recomputes the scores (QK^T) and forms dP = dO V^T,
dV = P^T dO, dQ = dS K and dK = dS^T Q: five products of 2 operations per
head, pair and head dim, over the causal half.  q, k, v, dO read once with
the f32 log-sum-exp and delta rows; dq, dk, dv written once."""


def cost(batch: int, heads: int, seq: int, head_dim: int,
         kv_heads: int = 0, io_bytes: int = 2):
    kv_heads = kv_heads or heads
    pairs = batch * heads * seq * (seq + 1) / 2.0
    flops = 10.0 * head_dim * pairs
    nbytes = (batch * seq * head_dim * io_bytes * (4 * heads + 4 * kv_heads)
              + 8.0 * batch * heads * seq)
    return flops, nbytes
