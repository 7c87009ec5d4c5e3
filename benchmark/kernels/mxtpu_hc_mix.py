"""``mxtpu_hc_mix`` (mxnet_tpu/serve/hc_mix.py): the maps of one
hyper-connection and the sublayer's input, for ``rows`` positions of
``n`` streams of ``d`` values.  What the ALGORITHM needs, from shapes
alone, whatever implements it."""


def cost(rows: int, n: int, d: int, iters: int, itemsize: int,
         calls: int = 1) -> dict:
    """``calls`` connections over ``rows`` positions each.

    Bytes: the streams in (``rows x n x d`` values of ``itemsize``),
    ``u`` out (``rows x d``), Phi in (``(2n + n^2) x n d`` float32) and
    the maps out (``rows x (2n + n^2)`` float32: H_pre, H_post, H_res).
    The lanes and rows a tile pads are NOT counted.  FLOPs a position:
    the projection (2 a weight), the sum of squares (2 n d), ``u``
    (2 n d) and the ``iters`` row and column normalisations (a sum and a
    division an entry, twice: 4 n^2 an iteration); the sigmoids and the
    ``exp`` are not counted."""
    m = 2 * n + n * n
    per_call = (rows * (n * d + d) * itemsize + m * n * d * 4
                + rows * m * 4)
    flops = rows * (2 * m * n * d + 4 * n * d + iters * 4 * n * n)
    return {"bytes": calls * per_call, "flops": calls * flops}
