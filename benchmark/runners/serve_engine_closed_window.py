"""``runners/serve_engine_closed.py``'s loop (that module's ``run`` is
called, not copied) for a model whose softmax layers keep their K/V in
two kinds of table, window and global, with one more number in
``correct`` and the two kinds' block counts in the facts.

The probe that loop makes (``harness.serving.probe``) compares greedy
tokens over a few thousand positions, and a key's rounding error is
averaged away over the thousands of keys a query weighs: K/V rows kept
in 8 bits stray little further than the bfloat16 activations do, and a
window one block too long adds 128 of 4,096 keys a query, a few percent
of the weight.  Neither moves a token.  This number can hold both: the
program's own writer and decode kernel (``kvcache.write_prefill``
through the ring, ``gqa_decode.gqa_decode``) over a pool of the
engine's type, block size and ring, from seeded float32 K/V rows and
queries at the published head sizes (48 query heads over 8 of 128), the
queries scaled so that a head's attention is PEAKED (scores of std ~2.8:
a few dozen keys carry the weight, so their rounding shows), on rows
that end before the window, past it, and past the ring's wrap, against
``softmax(q . k / sqrt(hd)) v`` over EXACTLY the window's keys in
float64 numpy: the norm of ``y - reference`` over the norm of the
reference, all rows and heads as one vector.  ``serve.window_tolerance``
is the limit.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from benchmark.harness import spec
from benchmark.harness.runtime import Result, Run, say

closed = spec.load_module("runners", "serve_engine_closed")
#: cached positions of the rows read, as shares of ``max_seq_len``: one
#: inside the window, one just past it, one past the ring's wrap, one
#: at the longest a request may be
SHARES = (0.2, 0.3, 0.45, 1.0)


def window_error(pool_like, ring: int, cfg: Dict[str, Any], seed: int,
                 round_to: Optional[Any] = None, widen: int = 0) -> float:
    """``pool_like``: one of the engine's window pools (its type, block
    size and lanes are taken); ``ring``: the engine's window table
    width.  ``round_to`` rounds the rows to that type before they are
    written, ``widen`` lets the kernel see that many more positions than
    the window: the control readings, not a run's."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serve import kvcache
    from mxnet_tpu.serve.gqa_decode import gqa_decode

    heads, hd = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    kv = int(cfg["num_key_value_heads"])
    window = int(cfg["sliding_window"])
    engine = cfg["serve"]["engine"]
    chunk = int(engine["prefill_chunk"])
    bs = pool_like.shape[2]
    lengths = [max(1, int(s * int(engine["max_seq_len"]))) for s in SHARES]
    rng = np.random.default_rng([int(seed), 0x3A11D0])
    k_rows = [rng.standard_normal((n, kv * hd)).astype(np.float32)
              for n in lengths]
    v_rows = [rng.standard_normal((n, kv * hd)).astype(np.float32)
              for n in lengths]
    scale = 1.0 / np.sqrt(hd)
    q = (rng.standard_normal((len(lengths), heads, hd))
         * (2.8 / (scale * np.sqrt(hd)))).astype(np.float32)

    pools = [jnp.zeros((1, 1 + len(lengths) * ring, bs, kv * hd),
                       pool_like.dtype) for _ in range(2)]
    tables = 1 + np.arange(len(lengths) * ring, dtype=np.int32).reshape(
        len(lengths), ring)
    for i, n in enumerate(lengths):
        for pool_i, rows in enumerate((k_rows[i], v_rows[i])):
            r = jnp.asarray(rows)
            if round_to is not None:
                # not ``astype`` there and back: XLA drops such a pair
                kind = jnp.finfo(round_to)
                r = jax.lax.reduce_precision(r, kind.nexp, kind.nmant)
            r = r.astype(pool_like.dtype)
            # through the ring a chunk at a time, as the engine writes
            for start in range(0, n, chunk):
                pools[pool_i] = kvcache.write_prefill(
                    pools[pool_i], 0,
                    r[start:start + chunk].reshape(-1, kv, hd),
                    jnp.asarray(tables[i]), n, start=start, ring=ring)
    got = np.asarray(gqa_decode(
        jnp.asarray(q).astype(pool_like.dtype), pools[0], pools[1], 0,
        jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), scale=scale,
        window=window + widen, ring=ring,
        interpret=jax.default_backend() != "tpu").astype(jnp.float32))
    want = np.zeros(got.shape, np.float64)
    for i, n in enumerate(lengths):
        lo = max(0, n - window)
        k = k_rows[i][lo:n].astype(np.float64).reshape(-1, kv, hd)
        v = v_rows[i][lo:n].astype(np.float64).reshape(-1, kv, hd)
        for h in range(heads):
            s = k[:, h // (heads // kv)] @ q[i, h].astype(np.float64) * scale
            p = np.exp(s - s.max())
            want[i, h] = (p / p.sum()) @ v[:, h // (heads // kv)]
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


class _KindLog(closed.serving.StepLog):
    """The harness's step record, and after each step the blocks in use
    of each kind (``serve.kv.window_blocks_used`` /
    ``global_blocks_used``, the engine's gauges)."""

    def __init__(self, eng):
        super().__init__(eng)
        self.kinds = {}

    def record(self, t0: float, t1: float, traced: bool) -> None:
        from mxnet_tpu import telemetry
        super().record(t0, t1, traced)
        self.kinds[t1] = tuple(
            telemetry.gauge(f"serve.kv.{k}_blocks_used").value()
            for k in ("window", "global"))


def run(run: Run) -> Result:
    seen = {}
    build, step_log = closed.serving.build_engine, closed.serving.StepLog

    def build_and_read(r: Run):
        eng, params, ref = build(r)
        seen["error"] = window_error(eng.kpool, eng.alloc.ring, r.config,
                                     r.seed)
        seen["engine"] = eng
        return eng, params, ref

    def kind_log(eng):
        seen["log"] = _KindLog(eng)
        return seen["log"]

    closed.serving.build_engine = build_and_read
    closed.serving.StepLog = kind_log
    try:
        result = closed.run(run)
    finally:
        closed.serving.build_engine = build
        closed.serving.StepLog = step_log
    eng, log = seen["engine"], seen["log"]
    in_window = [log.kinds[s["t1"]] for s in result.facts.get("steps", ())]
    if in_window:
        result.facts["blocks"] = {
            "window_peak": max(w for w, _ in in_window),
            "window_usable": eng.alloc.window.num_blocks - 1,
            "global_peak": max(g for _, g in in_window),
            "global_usable": eng.alloc.num_blocks - 1}
    tol = float(run.config["serve"]["window_tolerance"])
    say(f"[correct] the decode kernel over {len(SHARES)} rows of a window "
        f"pool like the engine's: |y - float64 attention of exactly the "
        f"window's keys| / |that| = {seen['error']:.3g} (tolerance {tol})")
    run.compared["window_error"] = (seen["error"], tol)
    if not seen["error"] <= tol:
        result.notes.append(
            f"the decode kernel strays {seen['error']:.3g} from attention "
            f"over exactly the window's keys, tolerance {tol}: the K/V rows "
            "are not kept in the precision the configuration states, or "
            "the walk does not see exactly the window")
        result.correct = False
    return result
