"""``mxtpu_retention_decode`` (mxnet_tpu/serve/retention_decode.py): one
decode position a row updates that row's recurrent state and reads it
with the query heads that share it.  What the ALGORITHM needs, from
shapes alone, whatever implements it."""


def cost(rows: int, heads: int, kv_heads: int, head_dim: int,
         act_itemsize: int, layers: int = 1) -> dict:
    """One decode step over ``rows`` active rows, for ``layers`` calls.

    Bytes: per row and key/value head the state once in and once out:
    ``S`` is ``hd (hd + 1) / 2`` symmetric degree-2 features by ``hd``
    value channels, ``z`` one more per feature, float32; plus q, k, v in
    and y out in the activations' type and one float32 gate a head.  The
    program stores the state padded (8320 x 136 where 8256 x 129 are
    needed, 6.3 % more) and its kernel moves the padding: that is NOT
    counted, so it lowers the share.  FLOPs: decay and rank-one update,
    3 a state element; the read, 2 a state element and query head."""
    feats = head_dim * (head_dim + 1) // 2
    state = feats * (head_dim + 1)
    group = heads // kv_heads
    act = (2 * heads + 2 * kv_heads) * head_dim * act_itemsize + 4 * kv_heads
    return {"bytes": layers * rows * (kv_heads * 2 * state * 4 + act),
            "flops": layers * rows * kv_heads * state * (3 + 2 * group)}
