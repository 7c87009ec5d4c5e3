"""``mxtpu_gqa_decode`` (mxnet_tpu/serve/gqa_decode.py): one query
position a row attends, grouped by key/value head, over that row's
cached keys and values in the paged pool, a window layer's over the last
``window`` positions alone.  What the ALGORITHM needs, from shapes
alone, whatever implements it."""


def cost(positions: float, rows: float, heads: int, kv_heads: int,
         head_dim: int, itemsize: int, layers: int = 1) -> dict:
    """``layers`` calls over ``rows`` rows that see ``positions`` cached
    positions between them in EACH call (a window layer: ``min(length,
    window)`` a row; a global one: the row's length; the new position
    included).

    Bytes: every seen key and value row once (``kv_heads x head_dim``
    values each), plus each row's ``heads`` queries in and outputs out.
    FLOPs: q.k and p.v, 2 each a position, query head and channel.  The
    blocks a window's first and last block carry outside it, and a
    group padded to 8 query rows, are NOT needed, so they are not
    counted: time spent on them lowers the share."""
    kv = 2 * positions * kv_heads * head_dim * itemsize
    qo = 2 * rows * heads * head_dim * itemsize
    return {"bytes": layers * (kv + qo),
            "flops": layers * 4.0 * positions * heads * head_dim}
