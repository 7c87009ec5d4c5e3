"""``runners/serve_engine_closed.py``'s loop (that module's ``run`` is
called, not copied) for a model whose layers keep a RECURRENT STATE,
with one more number in ``correct``.

The probe that loop makes (``harness.serving.probe``) compares greedy
tokens, and on the chip the weights and activations are bfloat16: a
state kept in bfloat16 too strays no further than they already do
(``benchmark/precision_reading.py`` reads both), so the probe cannot
hold the float32 state the configuration states (``kv_dtype``), the
6.5 GB a decode step moves.  This number can: the program's decode
update, ``mxnet_tpu.serve.retention_decode.retention_decode``, run for
``STEPS`` positions over a pool of the engine's own type and layout,
from seeded float32 queries, keys, values and gates at the published
head sizes, against the reference's attention form
(``reference/<family>.py::retention``, float32, ``highest``): the norm
of ``y - reference`` over the norm of the reference, all rows, heads and
positions from ``SETTLED`` on as one vector (before that a state holds
so few keys that a query can be nearly orthogonal to all of them, and
the normaliser's own cancellation, not the state's type, sets the
error).  No weights and no bfloat16 activation are involved, so what is
left is the state's own rounding: 6e-3 for one rounded to bfloat16
after every step, against 3e-7 (CPU) or 1.5e-5 (the chip, whose ``exp``
is less exact) for a float32 state.  ``serve.state_tolerance`` is the
limit.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from benchmark.harness import spec
from benchmark.harness.runtime import Result, Run, say

closed = spec.load_module("runners", "serve_engine_closed")
STEPS = 96
SETTLED = 16
ROWS = 2


def state_error(pool_like, cfg: Dict[str, Any], seed: int,
                round_to: Optional[Any] = None) -> float:
    """``pool_like``: the engine's state pool (its type and one slot's
    shape are taken).  ``round_to`` rounds the pool to that type after
    every step: the control reading, not a run's."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serve.retention_decode import retention_decode

    ref = spec.load_module("reference", cfg["family"])
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    eps = float(cfg["serve"]["engine"]["model"]["retention_eps"])
    rng = np.random.default_rng([int(seed), 0x57A7E])
    # what the layer hands the update: q and k of unit mean square (the
    # per-head norm), v of the projections' scale, gates as init_params
    # draws them (sigmoid 0.93-0.995)
    q = rng.standard_normal((STEPS, ROWS, heads, hd), np.float32)
    k = rng.standard_normal((STEPS, ROWS, kv, hd), np.float32)
    v = rng.standard_normal((STEPS, ROWS, kv, hd), np.float32)
    logit = ref.GATE_BIAS + 0.72 * rng.standard_normal((STEPS, ROWS, kv))
    g = -np.log1p(np.exp(-logit)).astype(np.float32)

    slots = jnp.arange(1, ROWS + 1, dtype=jnp.int32)
    interpret = jax.default_backend() != "tpu"

    @jax.jit
    def step(pool, q, k, v, g):
        y, pool = retention_decode(pool, 0, slots, q, k, v, g, eps,
                                   interpret=interpret)
        if round_to is not None:
            # not ``astype`` there and back: XLA drops such a pair on the TPU
            kind = jnp.finfo(round_to)
            pool = jax.lax.reduce_precision(pool, kind.nexp, kind.nmant)
        return y, pool

    pool = jnp.zeros((1, ROWS + 1) + tuple(pool_like.shape[2:]),
                     pool_like.dtype)
    got = []
    for t in range(STEPS):
        y, pool = step(pool, q[t], k[t], v[t], g[t])
        got.append(y)
    got = np.asarray(jnp.stack(got, 1))                  # [ROWS, STEPS, H, hd]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.retention(*(jnp.asarray(a.swapaxes(0, 1))
                                          for a in (q, k, v, g))))
    got, want = got[:, SETTLED:], want[:, SETTLED:]
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def run(run: Run) -> Result:
    seen = {}
    build = closed.serving.build_engine

    def build_and_read(r: Run):
        eng, params, ref = build(r)
        seen["error"] = state_error(eng.state, r.config, r.seed)
        return eng, params, ref

    closed.serving.build_engine = build_and_read
    try:
        result = closed.run(run)
    finally:
        closed.serving.build_engine = build
    tol = float(run.config["serve"]["state_tolerance"])
    say(f"[correct] {STEPS} decode updates of {ROWS} rows over a pool like "
        f"the engine's: |y - attention form| / |attention form| = "
        f"{seen['error']:.3g} (tolerance {tol})")
    run.compared["state_error"] = (seen["error"], tol)
    if not seen["error"] <= tol:
        result.notes.append(
            f"the decode update strays {seen['error']:.3g} from the "
            f"attention form over {STEPS} steps, tolerance {tol}: the "
            "state is not kept in the precision the configuration states")
        result.correct = False
    return result
