"""``mxtpu_mla_decode`` (mxnet_tpu/serve/mla_decode.py): one query
position a row attends, in the absorbed form, over that row's cached
latent rows in the paged pool.  What the ALGORITHM needs, from shapes
alone, whatever implements it."""


def cost(cached_tokens: int, rows: int, heads: int, rank: int, rope: int,
         itemsize: int, layers: int = 1) -> dict:
    """One decode step over ``rows`` rows holding ``cached_tokens``
    positions between them (the new one included), for ``layers`` calls.

    Bytes: every live row's ``rank + rope`` values once (the keys of all
    heads AND, its first ``rank`` values, their values), plus each row's
    absorbed queries ``[heads, rank + rope]`` in and ``[heads, rank]``
    out.  The program stores a row on whole 128-lane rows (640 lanes for
    576 values) and its kernel moves the padding: that is NOT counted,
    so it lowers the share.  FLOPs: the scores, 2 a head and value of
    the row, and the output, 2 a head and latent value: ``2 x heads x
    (2 rank + rope)`` a cached position (278,528 at 128 heads, 512 +
    64)."""
    width = rank + rope
    qo = rows * heads * (width + rank) * itemsize
    return {"bytes": layers * (cached_tokens * width * itemsize + qo),
            "flops": layers * 2 * heads * (width + rank) * cached_tokens}
