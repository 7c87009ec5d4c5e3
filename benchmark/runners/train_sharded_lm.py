"""``runners/train_sharded.py``'s loop for a language model on a mesh
with a ``model`` axis: the same window, fetch discipline and checks
(that module's ``run`` is called, not copied), with the three things it
cannot be told through its files laid over it for the call:

* the trainer's settings come from the JOB file's ``train`` section (the
  configuration ``nope-lm-2048x24`` has none, and a ``model_config`` PR
  may not edit it), and the loss reference is the job's ``reference``;
* ``ShardedTrainer`` gets sharding ``rules`` (``train.rules`` names a
  function of ``mxnet_tpu.parallel.trainer``: ``megatron_rules``);
* the inputs are ``next_token`` batches: ``high``-sided ids, the label
  the same sequence shifted by one;
* ``correct`` takes a second number, because the first step's forward
  loss (that module's one comparison) sees neither the gradients'
  exchange between the chips nor the optimizer: the trainer's FIRST
  UPDATE of the probed parameters (``reference/nope_lm_loss.py`` says
  which) against the float32 reference's, as the norm of the difference
  over the norm of the reference's update.  A state left unchanged
  reads 1; ``train.update_tolerance`` is the limit.

A later ``benchmark`` PR folds these into ``train_sharded.py`` (a
``rules`` key, a ``next_token`` input kind) and drops this file
(ROADMAP W0).
"""
from __future__ import annotations

import copy
import math
from typing import Any, Dict

import numpy as np

from benchmark.harness import spec, traffic
from benchmark.harness.runtime import Result, Run, say

train_sharded = spec.load_module("runners", "train_sharded")


def build_trainer(run: Run, batch_shapes):
    """``train_sharded.build_trainer`` with ``rules``."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import initializer as init_mod
    from mxnet_tpu import models
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh
    from mxnet_tpu.parallel import trainer as trainer_mod

    t = run.config["train"]
    mx.random.seed(int(run.seed) & 0x7FFFFFFF)
    sym = models.get_symbol(t["symbol"]["name"], **t["symbol"]["kwargs"])
    mesh = make_mesh({k: int(v) for k, v in t["mesh"].items()},
                     jax.local_devices()[:run.chips])
    init = getattr(init_mod, t["initializer"]["name"])(
        **t["initializer"].get("kwargs", {}))
    tr = ShardedTrainer(
        sym, mesh=mesh, rules=getattr(trainer_mod, t["rules"])(),
        optimizer=t["optimizer"], optimizer_params=dict(t["optimizer_params"]),
        initializer=init, matmul_precision=t.get("matmul_precision"),
        compute_dtype=t.get("compute_dtype"))
    label = t["label_name"]
    tr.bind(data_shapes={k: v for k, v in batch_shapes.items() if k != label},
            label_shapes={label: batch_shapes[label]})
    _read_after_first_step(tr, run.config["first_update"])
    return tr


def _read_after_first_step(tr, out: Dict[str, Any]) -> None:
    """The trainer's first ``step`` also leaves the probed parameters as
    it made them in ``out["after"]`` (the reference has said which by
    then: ``train_sharded.run`` asks it for its loss first)."""
    step = tr.step

    def first_step(batch):
        heads = step(batch)
        tr.step = step
        after = tr.get_params()[0]
        out["after"] = {k: after[k].asnumpy() for k in out["want"]}
        return heads

    tr.step = first_step


def update_error(moved: Dict[str, np.ndarray],
                 want: Dict[str, np.ndarray]) -> float:
    """``|moved - want| / |want|`` over all the probed parameters as one
    vector: 0 for the reference's own update, 1 for none at all."""
    diff = sum(float(np.sum(np.square(moved[k].astype(np.float64) - want[k])))
               for k in want)
    return math.sqrt(diff / sum(float(np.sum(np.square(
        want[k].astype(np.float64)))) for k in want))


class NextTokenTraffic:
    """``harness.traffic`` with one more input kind: ``next_token`` draws
    ``count`` batches of ``[batch, seq + 1]`` ids in ``[0, high)`` from
    the seed; the input of that kind gets ``[:, :-1]`` and the
    ``next_token_label`` input ``[:, 1:]``, both float32 (the program's
    input dtype)."""

    @staticmethod
    def batch_arrays(inputs: Dict[str, Dict[str, Any]], count: int,
                     seed: int) -> Dict[str, np.ndarray]:
        (data, dspec), = [(k, v) for k, v in inputs.items()
                          if v["kind"] == "next_token"]
        (label, _), = [(k, v) for k, v in inputs.items()
                       if v["kind"] == "next_token_label"]
        batch, seq = (int(s) for s in dspec["shape"])
        rng = np.random.default_rng([int(seed), 0x7EA1])
        ids = rng.integers(0, int(dspec["high"]),
                           (count * batch, seq + 1)).astype(np.float32)
        return {data: np.ascontiguousarray(ids[:, :-1]),
                label: np.ascontiguousarray(ids[:, 1:])}

    def __getattr__(self, name):
        return getattr(traffic, name)


def run(run: Run) -> Result:
    job = run.traffic
    first: Dict[str, Any] = {}           # the reference and the trainer fill it
    run.config = dict(copy.deepcopy(run.config), family=job["reference"],
                      train=copy.deepcopy(job["train"]), first_update=first)
    kept = train_sharded.build_trainer, train_sharded.traffic
    train_sharded.build_trainer = build_trainer
    train_sharded.traffic = NextTokenTraffic()
    try:
        result = train_sharded.run(run)
    finally:
        train_sharded.build_trainer, train_sharded.traffic = kept

    moved = {k: first["after"][k] - first["before"][k] for k in first["want"]}
    got = update_error(moved, first["want"])
    half = update_error(moved, first["want_half"])
    tol = float(job["train"]["update_tolerance"])
    elements = sum(v.size for v in moved.values())
    flipped = sum(int(np.sum(moved[k] * first["want"][k] < 0))
                  for k in moved) / elements
    say(f"[correct] first update of {len(moved)} probed parameters "
        f"({elements:,} elements): |trainer's - reference's| / "
        f"|reference's| = {got:.4f} (tolerance {tol}; no update at all "
        f"reads 1; {flipped:.3%} of the elements moved the other way); "
        f"against the reference's update from the batch's first half "
        f"alone, as gradients not summed over the data axis would "
        f"leave it: {half:.4f}")
    run.compared["first_update_error"] = (got, tol)
    if not got <= tol:
        result.notes.append(
            f"the trainer's first update differs from the reference's by "
            f"{got:.4f} of its norm, tolerance {tol}")
        result.correct = False
    return result
