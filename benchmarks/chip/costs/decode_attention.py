"""One layer's ragged paged decode: each row's query (all heads) against
its ``length`` cached tokens (the new one included).  QK^T and PV are 2
operations per head and position each; the cached keys and values of the
kv heads are read once, the queries read and outputs written once."""
import numpy as np


def cost(lengths, heads: int, kv_heads: int, head_dim: int,
         kv_bytes: int = 2, io_bytes: int = 2):
    lengths = np.asarray(lengths, np.float64)
    tok = float(lengths.sum())
    flops = 4.0 * heads * head_dim * tok
    nbytes = (2.0 * kv_heads * head_dim * kv_bytes * tok
              + 2.0 * len(lengths) * heads * head_dim * io_bytes)
    return flops, nbytes
