#!/usr/bin/env python3
"""The other side of a LATENT serving configuration's limits: what the
cell's own comparisons read when the program computes in a precision
BELOW the one the configuration states.

    python3 benchmark/precision_reading_latent.py --config deepseek-v2-ep4-5of60 --seed 7

The ENGINE itself is built four times, each time handed to
``harness.serving.probe`` against the float32 reference of the weights
as stated (``benchmark/precision_reading.py`` does the same for a
recurrent-state model with a stand-in; here the program's own paths are
lowered, one at a time):

1. as stated (bfloat16 weights and latent rows, a float32 router);
2. the latent rows rounded to float8 e4m3 as they are written
   (``kvcache.latent_rows``), and the cell's second number,
   ``runners/serve_engine_closed_latent.py::latent_error``, over the
   engine's pool as stated and over 8-bit rows;
3. the router's logits rounded to bfloat16 before its softmax;
4. every weight matrix rounded to float8 e4m3 (the nearest precision
   under the bfloat16 the file states).

A reading in a lower precision has to FAIL one of the cell's limits, and
the cell's own runs (their ``[correct]`` lines) have to pass both with
room.  Each engine gets a fingerprint of its own: the compile cache keys
programs by fingerprint and shapes, not by what was traced.  Needs the
chip for the published widths (``--rehearsal``: tiny, CPU)."""
from __future__ import annotations

import argparse
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _round_to(x, dtype):
    """``x`` rounded to ``dtype``'s exponent and mantissa, in its own
    type (not ``astype`` there and back: XLA drops such a pair)."""
    import jax.numpy as jnp
    kind = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, kind.nexp, kind.nmant)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from benchmark.harness import spec
    from benchmark.run import merged
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, args.config)
    if cfg.get("serve", {}).get("engine", {}).get("model", {}).get(
            "attention") != "latent":
        print(f"{args.config}: not a latent model; see precision_reading.py",
              file=sys.stderr)
        return 2
    if args.rehearsal:
        cfg = merged(cfg, cfg.get("rehearsal", {}))

    import jax.numpy as jnp

    from benchmark.harness import device as dev
    from benchmark.harness import serving
    from benchmark.harness.runtime import say
    from mxnet_tpu import compile_cache as cc
    from mxnet_tpu.models import experts
    from mxnet_tpu.serve import Engine, EngineConfig, kvcache

    dev.require(1, args.rehearsal)
    if not args.rehearsal:
        cc.enable_persistent_cache(os.path.join(REPO, ".jax_cache"))
    ref = spec.load_module("reference", cfg["family"])
    latent = spec.load_module("runners", "serve_engine_closed_latent")
    serve = cfg["serve"]
    wdtype = jnp.dtype(serve["weights_dtype"])
    fp8 = jnp.float8_e4m3fn
    tol = float(serve["logit_tolerance"])
    ltol = float(serve["latent_tolerance"])

    def engine(params, tag):
        eng = Engine(params, EngineConfig(
            heads=int(cfg["num_attention_heads"]),
            dtype=jnp.dtype(serve["kv_dtype"]), **serve["engine"]))
        eng._fingerprint += ":reading-" + tag
        eng.warmup()
        return eng

    def probe(eng, params, reference=ref):
        run = types.SimpleNamespace(config=cfg, seed=args.seed, compared={})
        ok, _ = serving.probe(run, eng, params, reference)
        return ok, run.compared["logit_deficit"][0]

    def free(eng):
        for pool in eng._caches:
            pool.delete()

    params = ref.init_params(args.seed, cfg, wdtype)

    eng = engine(params, "stated")
    ok, worst = probe(eng, params)
    say(f"[reading] as stated: probe {ok} (worst {worst:.4f}, limit {tol})")
    stated_ok = ok
    err = latent.latent_error(eng.latents, cfg, args.seed)
    err8 = latent.latent_error(eng.latents, cfg, args.seed, round_to=fp8)
    say(f"[reading] latent_error: rows as stated {err:.3g}: "
        f"{err <= ltol}; rows rounded to float8 e4m3 {err8:.3g}: "
        f"{err8 <= ltol} (limit {ltol})")
    free(eng)

    rows_as_stated = kvcache.latent_rows
    kvcache.latent_rows = lambda pool, rows: rows_as_stated(
        pool, _round_to(rows.astype(jnp.float32), fp8))
    try:
        eng = engine(params, "latent8")
        ok8, worst = probe(eng, params)
    finally:
        kvcache.latent_rows = rows_as_stated
    say(f"[reading] latent rows rounded to float8 e4m3 as written: probe "
        f"{ok8} (worst {worst:.4f})")
    free(eng)

    logits_as_stated = experts.router_logits
    experts.router_logits = lambda x, w: _round_to(logits_as_stated(x, w),
                                                   jnp.bfloat16)
    try:
        eng = engine(params, "router16")
        okr, worst = probe(eng, params)
    finally:
        experts.router_logits = logits_as_stated
    say(f"[reading] router logits rounded to bfloat16: probe {okr} "
        f"(worst {worst:.4f})")
    free(eng)

    # float8 weights: rounded leaf by leaf IN the weights' place (two
    # sets do not fit the chip), the reference regenerates the stated
    # ones from the seed once the engine has spoken
    low = {}
    for name in list(params):
        leaf = params.pop(name)
        if leaf.ndim >= 2 and jnp.issubdtype(leaf.dtype, jnp.floating):
            low[name] = _round_to(leaf.astype(jnp.float32), fp8).astype(
                leaf.dtype)
            leaf.delete()
        else:
            low[name] = leaf
    eng = engine(low, "weights8")

    class StatedReference:
        """``ref`` whose ``forward`` drops the lowered weights and the
        engine's pool, and computes with the weights as stated."""
        @staticmethod
        def forward(_params, toks, heads):
            free(eng)
            for leaf in low.values():
                if leaf.ndim >= 2:
                    leaf.delete()
            return ref.forward(ref.init_params(args.seed, cfg, wdtype), toks,
                               heads)

    ok_w, worst = probe(eng, low, StatedReference)
    say(f"[reading] weights rounded to float8 e4m3: probe {ok_w} "
        f"(worst {worst:.4f})")
    failed_somewhere = (not ok_w) and (not ok8 or err8 > ltol)
    say(f"[reading] as stated passes: {stated_ok and err <= ltol}; float8 "
        f"weights fail the probe: {not ok_w}; 8-bit latent rows fail a "
        f"limit: {not ok8 or err8 > ltol}; a bfloat16 router "
        f"{'passes' if okr else 'fails'} the probe")
    return 0 if stated_ok and err <= ltol and failed_somewhere else 1


if __name__ == "__main__":
    sys.exit(main())
