#!/usr/bin/env python3
"""The other side of a WINDOW serving configuration's limits: what the
cell's own comparisons read when the program computes in a precision
BELOW the one the configuration states, or over a window one block too
long.

    python3 benchmark/precision_reading_window.py --config trinity-large-ep8-5of60 --seed 7

The ENGINE itself is built four times, each handed to
``harness.serving.probe`` against the float32 reference of the weights
as stated (``precision_reading_latent.py`` does the same for a latent
model):

1. as stated (bfloat16 weights and K/V rows, a float32 router), and the
   cell's second number, ``runners/serve_engine_closed_window.py::
   window_error``, over the engine's window pool as stated, over rows
   rounded to float8 e4m3, and with the kernel seeing one block more
   than the window;
2. the K/V rows rounded to float8 e4m3 as they are written;
3. the window layers told a window one block longer than published;
4. every weight matrix rounded to float8 e4m3 (the nearest precision
   under the bfloat16 the file states).

A reading in a lower precision, or of a longer window, has to FAIL one
of the cell's limits, and the cell's own runs have to pass both with
room.  Each engine gets a fingerprint of its own.  Needs the chip for
the published widths (``--rehearsal``: tiny, CPU)."""
from __future__ import annotations

import argparse
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _round_to(x, dtype):
    """``x`` rounded to ``dtype``'s exponent and mantissa, in its own
    type (not ``astype`` there and back: XLA drops such a pair)."""
    import jax
    import jax.numpy as jnp
    kind = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, kind.nexp, kind.nmant)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--more-seeds", type=int, default=0,
                    help="the stated engine's probe and window_error at "
                    "this many further seeds (weights drawn anew, no "
                    "recompilation): the spread over seeds of the readings "
                    "the limits sit above")
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
    if "sliding" not in cfg.get("serve", {}).get("engine", {}).get(
            "model", {}).get("attention", ()):
        print(f"{args.config}: no window layers; see precision_reading.py",
              file=sys.stderr)
        return 2
    if args.rehearsal:
        cfg = merged(cfg, cfg.get("rehearsal", {}))

    import jax.numpy as jnp

    from benchmark.harness import device as dev
    from benchmark.harness import serving
    from benchmark.harness.runtime import say
    from mxnet_tpu import compile_cache as cc
    from mxnet_tpu.serve import Engine, EngineConfig, kvcache

    dev.require(1, args.rehearsal)
    if not args.rehearsal:
        cc.enable_persistent_cache(os.path.join(REPO, ".jax_cache"))
    ref = spec.load_module("reference", cfg["family"])
    window = spec.load_module("runners", "serve_engine_closed_window")
    serve = cfg["serve"]
    wdtype = jnp.dtype(serve["weights_dtype"])
    fp8 = jnp.float8_e4m3fn
    tol = float(serve["logit_tolerance"])
    wtol = float(serve["window_tolerance"])
    bs = int(serve["engine"]["block_size"])

    def engine(params, tag, **model):
        ecfg = dict(serve["engine"])
        ecfg["model"] = dict(ecfg["model"], **model)
        eng = Engine(params, EngineConfig(
            heads=int(cfg["num_attention_heads"]),
            dtype=jnp.dtype(serve["kv_dtype"]), **ecfg))
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
    ring = eng.alloc.ring
    err = window.window_error(eng.kpool, ring, cfg, args.seed)
    err8 = window.window_error(eng.kpool, ring, cfg, args.seed, round_to=fp8)
    errw = window.window_error(eng.kpool, ring, cfg, args.seed, widen=bs)
    say(f"[reading] window_error: rows as stated {err:.3g}: {err <= wtol}; "
        f"rows rounded to float8 e4m3 {err8:.3g}: {err8 <= wtol}; a window "
        f"one block ({bs}) too long {errw:.3g}: {errw <= wtol} (limit "
        f"{wtol})")
    for seed in range(args.seed + 1, args.seed + 1 + args.more_seeds):
        for leaf in params.values():
            leaf.delete()
        params = ref.init_params(seed, cfg, wdtype)
        eng.swap_weights(params)          # operands: no recompilation
        run = types.SimpleNamespace(config=cfg, seed=seed, compared={})
        ok_s, _ = serving.probe(run, eng, params, ref)
        stated_ok = stated_ok and ok_s
        e_s = window.window_error(eng.kpool, ring, cfg, seed)
        err = max(err, e_s)
        say(f"[reading] seed {seed} as stated: probe {ok_s} (worst "
            f"{run.compared['logit_deficit'][0]:.4f}), window_error {e_s:.3g}")
    if args.more_seeds:
        for leaf in params.values():
            leaf.delete()
        params = ref.init_params(args.seed, cfg, wdtype)
    free(eng)

    write_prefill, write_decode = kvcache.write_prefill, kvcache.write_decode
    kvcache.write_prefill = lambda pool, layer, states, *a, **k: write_prefill(
        pool, layer, _round_to(states, fp8), *a, **k)
    kvcache.write_decode = lambda pool, layer, states, *a, **k: write_decode(
        pool, layer, _round_to(states, fp8), *a, **k)
    try:
        eng = engine(params, "rows8")
        ok8, worst = probe(eng, params)
    finally:
        kvcache.write_prefill, kvcache.write_decode = (write_prefill,
                                                       write_decode)
    say(f"[reading] K/V rows rounded to float8 e4m3 as written: probe {ok8} "
        f"(worst {worst:.4f})")
    free(eng)

    wide = int(serve["engine"]["model"]["sliding_window"]) + bs
    eng = engine(params, "window+1", sliding_window=wide)
    okw, worst = probe(eng, params)
    say(f"[reading] a window of {wide}, one block too long: probe {okw} "
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
        engine's pools, and computes with the weights as stated."""
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
    say(f"[reading] as stated passes: {stated_ok and err <= wtol}; float8 "
        f"weights fail the probe: {not ok_w}; 8-bit K/V rows fail a limit: "
        f"{not ok8 or err8 > wtol}; a window one block too long fails a "
        f"limit: {not okw or errw > wtol}")
    return 0 if (stated_ok and err <= wtol and not ok_w
                 and (not ok8 or err8 > wtol)
                 and (not okw or errw > wtol)) else 1


if __name__ == "__main__":
    sys.exit(main())
