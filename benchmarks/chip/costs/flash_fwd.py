"""Causal flash attention forward over ``(batch, heads, seq, head_dim)``
operands: QK^T and PV over the causal half, ``seq (seq + 1) / 2`` pairs
per head.  q, k, v read once, out written once, the f32 log-sum-exp row
written once."""


def cost(batch: int, heads: int, seq: int, head_dim: int,
         kv_heads: int = 0, io_bytes: int = 2):
    kv_heads = kv_heads or heads
    pairs = batch * heads * seq * (seq + 1) / 2.0
    flops = 4.0 * head_dim * pairs
    nbytes = (batch * seq * head_dim * io_bytes * (2 * heads + 2 * kv_heads)
              + 4.0 * batch * heads * seq)
    return flops, nbytes
