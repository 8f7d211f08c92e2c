"""Operations and bytes of paged decode attention.

One decode token whose context holds ``n`` valid positions reads, in each
layer, the valid K and V rows of its slot (``n * KV * hd`` each) and its
query, and writes its output (``H * hd`` each).  Scores and the weighted
sum take ``4 * n * H * hd`` operations.  Pages past ``n``, and slots with no
live request, are not work the step needs, so they are not counted.
"""


def paged_decode(ctx_total: int, n_tokens: int, *, heads: int, kv_heads: int,
                 head_dim: int, layers: int, itemsize: int = 2):
    """(flops, bytes) of ``n_tokens`` decode tokens whose valid contexts sum
    to ``ctx_total`` positions, over ``layers`` layers."""
    flops = layers * 4 * ctx_total * heads * head_dim
    kv = layers * ctx_total * kv_heads * head_dim * 2 * itemsize
    qo = layers * n_tokens * heads * head_dim * 2 * itemsize
    return flops, kv + qo
