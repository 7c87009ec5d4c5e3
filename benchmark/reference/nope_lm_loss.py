"""The ``nope_lm`` family's training reference, for the cell that trains
``nope-lm-2048x24`` (``lm-train-4chip``), through ``reference/nope_lm.py``'s
float32 blocks.

* The loss: mean next-token cross-entropy.  The labels are given (the job
  draws them as the input shifted by one), so the loss is
  ``mean(-log softmax(logits)[label])`` over every position.
* The first update.  The first step's forward loss cannot see the
  gradient's exchange between chips or the optimizer, so where the
  runner hands ``cfg["first_update"]`` (a dict) the same pass goes on
  backwards through every block and leaves there what the trainer's
  first step has to do to the PROBED parameters (every parameter of the
  first, the middle and the last layer, the final LayerNorm and the
  head): ``want`` (the update of the whole batch), ``want_half`` (of the
  batch's first half alone: what a step whose gradients were not summed
  over the ``data`` axis would do) and ``before`` (their initial
  values).  The trainer's objective is the cross-entropy summed over a
  sequence's positions and averaged over the batch's sequences
  (``rescale_grad`` 1 / batch, ``ShardedTrainer``'s default), and the
  update is Adam's first, written out from its equations:
  ``m = (1 - b1) g``, ``v = (1 - b2) g^2``, ``w -= lr sqrt(1 - b2) /
  (1 - b1) m / (sqrt(v) + eps)``, i.e. ``-lr g / (|g| + eps / sqrt(1 -
  b2))``: every element moves by ``lr`` in its gradient's direction.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import spec

nope_lm = spec.load_module("reference", "nope_lm")
ROWS_AT_A_TIME = 2     # [2, 32, 2048, 2048] float32 scores are 1.07 GB
ROWS_BACKWARDS = 1     # a block's backward pass keeps three such tensors
ADAM = {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}   # optimizer.Adam's


def _kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return cfg["train"]["symbol"]["kwargs"]


def _layer(params: Dict[str, Any], i: int) -> Dict[str, Any]:
    """Layer ``i``'s parameters under their suffixes, on the device once
    for all the rows that pass through it."""
    pre = f"layer{i}_"
    return jax.device_put({k[len(pre):]: v for k, v in params.items()
                           if k.startswith(pre)})


_HEAD = ("final_ln_gamma", "final_ln_beta", "lm_head_weight", "lm_head_bias")


@jax.jit
def _nll_sum(h, head, labels):
    logits = nope_lm._head(h, *(head[k] for k in _HEAD))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


_nll_back = jax.jit(jax.value_and_grad(_nll_sum, argnums=(0, 1)))


@functools.partial(jax.jit, static_argnames=("heads",))
def _block_back(h, layer, dh, heads):
    """The cotangents of one block's input and of its parameters, the
    block recomputed from its input.  One program for every layer (a
    second one without the parameters' part would save a tenth of the
    arithmetic and cost another half minute of compilation)."""
    _, vjp = jax.vjp(lambda x, p: nope_lm._block(x, p, heads), h, layer)
    return vjp(dh)


def probed_layers(layers: int) -> Tuple[int, ...]:
    return tuple(sorted({0, layers // 2, layers - 1}))


def _chunks(n: int, rows: int):
    return [slice(r, min(r + rows, n)) for r in range(0, n, rows)]


def _add(acc, new):
    return new if acc is None else jax.tree_util.tree_map(jnp.add, acc, new)


def adam_first_update(g, lr: float):
    """What Adam's first step adds to a parameter whose gradient is ``g``."""
    b1, b2, eps = ADAM["beta1"], ADAM["beta2"], ADAM["epsilon"]
    m, v = (1.0 - b1) * g, (1.0 - b2) * g * g
    return -lr * np.sqrt(1.0 - b2) / (1.0 - b1) * m / (jnp.sqrt(v) + eps)


def reference_loss(params: Dict[str, Any], batch: Dict[str, Any],
                   cfg: Dict[str, Any]) -> float:
    """Mean loss of the whole first batch, layer by layer and a few
    sequences at a time (the trainer's state fills most of the chip this
    runs beside); fills ``cfg["first_update"]`` where the runner put one
    (see the module's docstring)."""
    heads = int(_kwargs(cfg)["heads"])
    tokens = np.asarray(batch["data"]).astype(np.int32)
    labels = np.asarray(batch[cfg["train"]["label_name"]]).astype(np.int32)
    out = cfg.get("first_update")
    n, rows = nope_lm._layers(params), len(tokens)
    h = np.asarray(nope_lm._embed(tokens, params["embed_weight"]))
    inputs: List[np.ndarray] = []        # every layer's input, on the host
    for i in range(n):
        layer = _layer(params, i)
        if out is not None:
            inputs.append(h)
        h = np.concatenate([np.asarray(nope_lm._block(h[c], layer, heads))
                            for c in _chunks(rows, ROWS_AT_A_TIME)])
    head = jax.device_put({k: params[k] for k in _HEAD})
    if out is None:
        return sum(float(_nll_sum(h[c], head, labels[c]))
                   for c in _chunks(rows, ROWS_AT_A_TIME)) / labels.size

    # backwards, the batch's two halves summed apart
    halves = [slice(0, rows // 2), slice(rows // 2, rows)]
    total, dh = 0.0, np.empty_like(h)
    grads = [{}, {}]                     # name -> gradient, one per half
    for half, rng in enumerate(halves):
        acc = None
        for c in _chunks(rng.stop - rng.start, ROWS_AT_A_TIME):
            c = slice(rng.start + c.start, rng.start + c.stop)
            nll, (gh, gp) = _nll_back(h[c], head, labels[c])
            total += float(nll)
            dh[c] = np.asarray(gh)
            acc = _add(acc, gp)
        grads[half].update(acc)
    for i in reversed(range(n)):
        layer = _layer(params, i)
        keep = i in probed_layers(n)
        for half, rng in enumerate(halves):
            acc = None
            for c in _chunks(rng.stop - rng.start, ROWS_BACKWARDS):
                c = slice(rng.start + c.start, rng.start + c.stop)
                gh, gp = _block_back(inputs[i][c], layer, dh[c], heads)
                dh[c] = np.asarray(gh)
                acc = _add(acc, gp) if keep else None
            if keep:
                grads[half].update({f"layer{i}_{k}": v
                                    for k, v in acc.items()})
        inputs.pop()
    lr = float(cfg["train"]["optimizer_params"]["learning_rate"])
    out["before"] = {k: np.asarray(params[k], np.float32) for k in grads[0]}
    out["want"] = {k: np.asarray(adam_first_update(
        (grads[0][k] + grads[1][k]) / rows, lr)) for k in grads[0]}
    out["want_half"] = {k: np.asarray(adam_first_update(
        grads[0][k] / (rows // 2), lr)) for k in grads[0]}
    return total / labels.size


def program_loss(head, batch: Dict[str, Any], cfg: Dict[str, Any]) -> float:
    """The program's ``loss_head`` output is the per-token cross-entropy
    [batch * seq] in float32: its loss is the mean."""
    return float(np.mean(np.asarray(head, np.float64)))


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward + backward FLOPs a token requires: 6 per parameter of the
    matmuls (q, k, v, proj, the two FFN matrices, the head; the
    embedding is a lookup) + 12 x layers x seq x d for the attention's
    two contractions over the whole sequence.  No recomputation, no
    optimizer."""
    k = _kwargs(cfg)
    d, n, v = int(k["d_model"]), int(k["num_layers"]), int(k["vocab_size"])
    matmul_params = n * (4 * d * d + 2 * d * 4 * d) + v * d
    return 6.0 * matmul_params + 12.0 * n * seq * d


def train_flops_per_step(cfg: Dict[str, Any], shapes: Dict[str, Any]) -> float:
    batch, seq = shapes["data"]
    return batch * seq * train_flops_per_token(cfg, int(seq))
