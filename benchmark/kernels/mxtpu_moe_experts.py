"""``mxtpu_moe_experts`` (mxnet_tpu/serve/moe_experts.py): the held
experts of one routed layer over the assignments they were given.  What
the ALGORITHM needs, from shapes alone, whatever implements it."""


def cost(experts_hit: float, assigned: float, d_model: int, width: int,
         itemsize: int, layers: int = 1) -> dict:
    """``layers`` routed layers in which ``experts_hit`` held experts
    (summed over the layers) had an assignment and ``assigned``
    assignments (summed likewise) went to held experts.

    Bytes: a hit expert's three ``d_model x width`` matrices once
    (47.19 MB at 5120 x 1536 in bf16; an expert nobody chose is not
    needed at all), plus each assignment's hidden state in, its
    ``width`` gated activations out and in again, and its result out.
    FLOPs: 2 a parameter of the expert and assignment.  The padding a
    row tile adds is NOT needed, so it is not counted.  ``layers`` only
    says that the two counts are sums over that many layers: nothing is
    multiplied by it."""
    del layers
    expert = 3 * d_model * width
    rows = assigned * (2 * d_model + 2 * width) * itemsize
    return {"bytes": experts_hit * expert * itemsize + rows,
            "flops": 2.0 * expert * assigned}
