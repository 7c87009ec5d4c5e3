"""``runners/serve_engine_closed.py``'s loop (that module's ``run`` is
called, not copied) for a model whose layers cache a LATENT row, with
one more number in ``correct``.

The probe that loop makes (``harness.serving.probe``) compares greedy
tokens, and with seeded weights a position attends over thousands of
cached rows with weights that are far from one-hot: a row's rounding
error is averaged away, so latent rows kept in 8 bits stray little
further than the bfloat16 activations already do, and the probe cannot
hold the bfloat16 rows the configuration states (``kv_dtype``), the
1.4 GB a decode step reads.  This number can: the program's own writer
and decode kernel (``kvcache.write_prefill``,
``mla_decode.mla_decode_attention``) over a pool of the engine's type
and layout, from seeded float32 rows and absorbed queries at the
published head sizes, the queries scaled so that a head's attention is
PEAKED (scores of std ~2.8: a few dozen rows carry the weight, so their
rounding shows), against ``softmax(q . rows * scale) rows[:rank]`` of
the unrounded rows in float64 numpy: the norm of ``y - reference`` over
the norm of the reference, all rows and heads as one vector.  No
weights and no model activations are involved, so what is left is the
rows' (and the kernel's bfloat16 operands') own rounding: ~1.5e-3 for
bfloat16 rows against ~3e-2 for rows rounded to float8 e4m3.
``serve.latent_tolerance`` is the limit.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from benchmark.harness import spec
from benchmark.harness.runtime import Result, Run, say

closed = spec.load_module("runners", "serve_engine_closed")
#: cached positions of the rows read, as shares of what a request may
#: hold (``max_seq_len``, at most 2,304: one row crosses the kernel's
#: double buffer many times, one ends inside its first block)
SHARES = (0.02, 0.3, 0.65, 0.9)


def latent_error(pool_like, cfg: Dict[str, Any], seed: int,
                 round_to: Optional[Any] = None) -> float:
    """``pool_like``: the engine's latent pool (its type, block size and
    lanes are taken).  ``round_to`` rounds the rows to that type before
    they are written: the control reading, not a run's."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serve import kvcache
    from mxnet_tpu.serve.mla_decode import mla_decode_attention

    ref = spec.load_module("reference", cfg["family"])
    heads = int(cfg["num_attention_heads"])
    rank, rope = int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"])
    width = rank + rope
    scale = ref.score_scale(int(cfg["qk_nope_head_dim"]), rope,
                            cfg.get("rope_scaling"))
    bs, lanes = pool_like.shape[2], pool_like.shape[3]
    most = min(int(cfg["serve"]["engine"]["max_seq_len"]), 2304)
    lengths = [max(1, int(s * most)) for s in SHARES]
    nblk = -(-max(lengths) // bs)
    rng = np.random.default_rng([int(seed), 0x1A7E27])
    rows = [rng.standard_normal((n, width)).astype(np.float32)
            for n in lengths]
    # scores of std ~2.8: |q| such that q . row * scale has that spread
    q = (rng.standard_normal((len(lengths), heads, width))
         * (2.8 / (scale * np.sqrt(width)))).astype(np.float32)

    pool = jnp.zeros((1, 1 + len(lengths) * nblk, bs, lanes),
                     pool_like.dtype)
    tables = np.zeros((len(lengths), nblk), np.int32)
    for i, r in enumerate(rows):
        tables[i] = 1 + i * nblk + np.arange(nblk)
        r = jnp.asarray(r)
        if round_to is not None:
            # not ``astype`` there and back: XLA drops such a pair on the TPU
            kind = jnp.finfo(round_to)
            r = jax.lax.reduce_precision(r, kind.nexp, kind.nmant)
        pool = kvcache.write_prefill(
            pool, 0, kvcache.latent_rows(pool, r), jnp.asarray(tables[i]),
            len(rows[i]))
    got = np.asarray(mla_decode_attention(
        jnp.asarray(q).astype(pool.dtype), pool, 0, jnp.asarray(tables),
        jnp.asarray(lengths, jnp.int32), rank=rank, scale=scale,
        interpret=jax.default_backend() != "tpu").astype(jnp.float32))
    want = np.zeros_like(got, dtype=np.float64)
    for i, r in enumerate(rows):
        s = q[i].astype(np.float64) @ r.astype(np.float64).T * scale
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        want[i] = (p / p.sum(axis=-1, keepdims=True)) @ r[:, :rank]
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def run(run: Run) -> Result:
    seen = {}
    build = closed.serving.build_engine

    def build_and_read(r: Run):
        eng, params, ref = build(r)
        seen["error"] = latent_error(eng.latents, r.config, r.seed)
        return eng, params, ref

    closed.serving.build_engine = build_and_read
    try:
        result = closed.run(run)
    finally:
        closed.serving.build_engine = build
    tol = float(run.config["serve"]["latent_tolerance"])
    say(f"[correct] the decode kernel over {len(SHARES)} rows of a pool like "
        f"the engine's: |y - float64 attention of the unrounded rows| / "
        f"|that| = {seen['error']:.3g} (tolerance {tol})")
    run.compared["latent_error"] = (seen["error"], tol)
    if not seen["error"] <= tol:
        result.notes.append(
            f"the decode kernel strays {seen['error']:.3g} from the "
            f"attention of the unrounded rows, tolerance {tol}: the latent "
            "rows are not kept in the precision the configuration states")
        result.correct = False
    return result
