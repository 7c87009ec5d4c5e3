"""``mxtpu_flash_decode`` (mxnet_tpu/serve/flash_decode.py): one query
position per row attends over that row's cached keys and values in the
paged pool.  What the algorithm needs, from shapes alone."""


def cost(cached_tokens: int, rows: int, heads: int, head_dim: int,
         kv_itemsize: int, layers: int = 1) -> dict:
    """One decode step over ``rows`` rows holding ``cached_tokens``
    positions between them (the new one included), for ``layers`` calls.

    Bytes: every live key and value once, plus each row's query in and
    output out.  FLOPs: q.k and p.v, 2 each per position, head and
    channel.  The trash blocks a padded table points at and the columns
    past a row's length are NOT needed, so they are not counted: time
    spent on them lowers the share."""
    width = heads * head_dim
    kv = 2 * cached_tokens * width * kv_itemsize
    qo = 2 * rows * width * kv_itemsize
    return {"bytes": layers * (kv + qo),
            "flops": layers * 4 * cached_tokens * width}
