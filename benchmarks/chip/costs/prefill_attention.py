"""One layer's paged chunked prefill: a chunk of ``real`` prompt tokens at
offset ``start`` attends causally over ``start + i + 1`` keys for its
i-th token.  The slot's history and the chunk's keys and values are read
once per kv head; queries read and outputs written once."""


def cost(chunks, heads: int, kv_heads: int, head_dim: int,
         kv_bytes: int = 2, io_bytes: int = 2):
    flops = nbytes = 0.0
    for start, real in chunks:
        keys = real * start + real * (real + 1) / 2.0
        flops += 4.0 * heads * head_dim * keys
        nbytes += (2.0 * kv_heads * head_dim * kv_bytes * (start + real)
                   + 2.0 * real * heads * head_dim * io_bytes)
    return flops, nbytes
