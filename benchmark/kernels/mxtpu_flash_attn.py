"""The training flash-attention kernels (mxnet_tpu/parallel/
flash_attention.py): ``mxtpu_flash_fwd``, ``mxtpu_flash_dq`` and
``mxtpu_flash_dkdv``, causal.  What one CALL of each needs, from its
shapes alone."""

MATMULS = {"mxtpu_flash_fwd": 2,      # q k^T, p v
           "mxtpu_flash_dq": 3,       # q k^T again, do v^T, ds k
           "mxtpu_flash_dkdv": 4}     # q k^T again, do v^T, p^T do, ds^T q
OPERANDS = {"mxtpu_flash_fwd": 4,     # q, k, v in; o out
            "mxtpu_flash_dq": 6,      # q, k, v, o, do in; dq out
            "mxtpu_flash_dkdv": 7}    # q, k, v, o, do in; dk, dv out


def cost(kernel: str, batch: int, heads: int, seq: int, head_dim: int,
         itemsize: int) -> dict:
    """One call over ``[batch, heads, seq, head_dim]`` operands.  A
    causal contraction needs half of ``seq x seq``: 2 FLOPs x
    ``seq^2 / 2`` x ``head_dim`` a matmul, head and sequence."""
    per = batch * heads * seq * head_dim
    return {"flops": MATMULS[kernel] * per * seq,
            "bytes": OPERANDS[kernel] * per * itemsize}
