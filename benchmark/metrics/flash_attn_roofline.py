"""The training flash-attention kernels' share of their roofline:
``mxtpu_flash_fwd`` / ``_dq`` / ``_dkdv``.  What each CALL in the traced
window needs (its FLOPs over the peak, or its bytes over HBM bandwidth,
whichever is the larger; compute-bound at these shapes) times the calls
the trace holds on a chip, against the kernels' device time.  A call's
shapes are the chip's share of the job: batch over ``data``, heads over
``model``.  Calls that recompute the forward pass (``remat``) count:
this is the kernels' efficiency, not the model's (``train_mfu``)."""
from benchmark.harness import readers, spec


def read(facts):
    tr = facts.get("trace")
    train = facts["traffic"].get("train")
    if tr is None or not train or "heads" not in train["symbol"]["kwargs"]:
        return None
    mod = spec.load_module("kernels", "mxtpu_flash_attn")
    k = train["symbol"]["kwargs"]
    mesh = train.get("mesh", {})
    batch = int(k["batch_size"]) // int(mesh.get("data", 1))
    heads = int(k["heads"]) // int(mesh.get("model", 1))
    head_dim = int(k["d_model"]) // int(k["heads"])
    itemsize = 2 if train.get("compute_dtype") == "bfloat16" else 4
    total = {"flops": 0.0, "bytes": 0.0}
    seconds = 0.0
    for name in mod.MATMULS:
        calls = tr.kernel_calls(name) / max(tr.chips, 1)
        c = mod.cost(name, batch, heads, int(k["seq_len"]), head_dim,
                     itemsize)
        total["flops"] += calls * c["flops"]
        total["bytes"] += calls * c["bytes"]
        seconds += tr.kernel_seconds(name)
    if not seconds:
        return None
    return readers.roofline_pct(total, seconds, facts["peaks"])
