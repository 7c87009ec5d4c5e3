#!/usr/bin/env python3
"""The readings behind a hyper-connection configuration's limits, and the
evidence for its connection kernel, in one process on the chip.

    python3 benchmark/precision_reading_hc.py --config xing4-29b-a4b-6of40 --seed 7 --more-seeds 4

The ENGINE is built once as the configuration states and warmed; then:

1. at ``--seed`` and each further seed (the weights swapped in, the
   programs kept: ``swap_weights``), the cell's probe
   (``harness.serving.probe``), its ``hc_error`` with the kernel and with
   the bfloat16 control (``runners/serve_engine_closed_hc.py``), and
   ``latent_error`` with the rows as stated (and once rounded to float8);
2. the maps' spread on the seeded streams (H_pre, H_post, H_res);
3. the decode step and the chunk with the connections composed by XLA
   (``models/decoder.py::hc_maps``) against the kernel, both programs
   compiled from the engine's own makers and run on the engine's pool and
   weights; and one connection alone, kernel against XLA;
4. last, every weight matrix rounded to float8 e4m3 (the nearest precision
   under the bfloat16 the file states), swapped in, probed against the
   float32 reference of the weights as stated.

Needs the chip for the published widths (``--rehearsal``: tiny, CPU)."""
from __future__ import annotations

import argparse
import os
import statistics
import sys
import time
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _round_to(x, dtype):
    """``x`` rounded to ``dtype``'s exponent and mantissa, in its own type."""
    import jax
    import jax.numpy as jnp
    kind = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, kind.nexp, kind.nmant)


def _median_ms(call, reps: int) -> float:
    import jax
    jax.block_until_ready(call())
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(call())
        times.append((time.perf_counter() - t) * 1e3)
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--more-seeds", type=int, default=0)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from benchmark.harness import spec
    from benchmark.run import merged
    cfg = spec.load_config(spec.load_benchmark(), args.config)
    if args.rehearsal:
        cfg = merged(cfg, cfg.get("rehearsal", {}))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import device as dev
    from benchmark.harness import serving
    from benchmark.harness.runtime import say
    from mxnet_tpu import compile_cache as cc
    from mxnet_tpu.models.decoder import hc_maps
    from mxnet_tpu.serve import Engine, EngineConfig
    from mxnet_tpu.serve.hc_mix import hc_mix

    dev.require(1, args.rehearsal)
    if not args.rehearsal:
        cc.enable_persistent_cache(os.path.join(REPO, ".jax_cache"))
    ref = spec.load_module("reference", cfg["family"])
    hc = spec.load_module("runners", "serve_engine_closed_hc")
    latent = hc.latent
    serve = cfg["serve"]
    wdtype = jnp.dtype(serve["weights_dtype"])
    fp8 = jnp.float8_e4m3fn
    tol, htol = float(serve["logit_tolerance"]), float(serve["hc_tolerance"])
    ltol = float(serve["latent_tolerance"])
    on_chip = jax.default_backend() == "tpu"

    params = ref.init_params(args.seed, cfg, wdtype)
    eng = Engine(params, EngineConfig(
        heads=int(cfg["num_attention_heads"]),
        dtype=jnp.dtype(serve["kv_dtype"]), **serve["engine"]))
    t = time.monotonic()
    eng.warmup()
    say(f"[reading] engine warmed in {time.monotonic() - t:.1f} s; "
        f"attn_impl={eng.attn_impl}, connections by "
        f"{'mxtpu_hc_mix' if eng._mix() else 'hc_maps (XLA)'}")

    def readings(seed):
        run = types.SimpleNamespace(config=cfg, seed=seed, compared={})
        ok, _ = serving.probe(run, eng, params, ref)
        err = hc.hc_error(params, cfg, seed)
        ctl = hc.hc_error(params, cfg, seed, control=True)
        lat = latent.latent_error(eng.latents, cfg, seed)
        say(f"[reading] seed {seed}: probe {ok} (worst "
            f"{run.compared['logit_deficit'][0]:.4f}, limit {tol}); hc_error "
            f"kernel {err:.3g}, bfloat16 maps {ctl:.3g} (limit {htol}); "
            f"latent_error {lat:.3g} (limit {ltol})")
        return ok, err, ctl, lat

    seeds = [args.seed + i for i in range(1 + args.more_seeds)]
    got = [readings(seeds[0])]
    lat8 = latent.latent_error(eng.latents, cfg, seeds[0], round_to=fp8)
    say(f"[reading] latent_error with the rows rounded to float8 e4m3: "
        f"{lat8:.3g}")

    # the maps' spread on the seeded streams of hc_error, every connection
    model = eng.model
    n, d = int(model.hc_mult), int(cfg["hidden_size"])
    rng = np.random.default_rng([int(seeds[0]), 0x4C5D])
    x = jnp.asarray((rng.standard_normal((eng.config.max_batch, n, d))
                     * rng.uniform(0.5, 2.0, (1, n, 1))).astype(np.float32),
                    wdtype)
    pres, posts, ress = [], [], []
    for i in range(eng.num_layers):
        for which in ("attn", "ffn"):
            w, b, a = (params[f"layer{i}_hc_{which}_{k}"]
                       for k in ("weight", "bias", "scale"))
            _, pre, post, res = (np.asarray(m) for m in hc_maps(
                model, x, w, b, a))
            pres.append(pre)
            posts.append(post)
            ress.append(res)
    pre, post, res = (np.stack(v) for v in (pres, posts, ress))
    eye = np.eye(n)
    say(f"[reading] maps over {len(pres)} connections x {x.shape[0]} rows: "
        f"H_pre {np.percentile(pre, 1):.3f}..{np.percentile(pre, 99):.3f} "
        f"(1st..99th pct), H_post {np.percentile(post, 1):.3f}.."
        f"{np.percentile(post, 99):.3f}; H_res entries "
        f"{np.percentile(res, 1):.4f}..{np.percentile(res, 99):.4f}, largest "
        f"{res.max():.4f}, mean |H_res - 1/n| {np.abs(res - 1 / n).mean():.4f},"
        f" mean |H_res - I| {np.abs(res - eye).mean():.4f}, diagonal mean "
        f"{np.mean(np.diagonal(res, axis1=-2, axis2=-1)):.4f}")

    # the decode step and the chunk: the kernel against XLA's composition
    def program(kind, bucket, composed):
        make = {"decode": eng._make_decode_fn,
                "prefill_chunk": eng._make_chunk_prefill_fn}[kind]
        mix = eng._mix
        if composed:
            eng._mix = lambda: None
        try:
            return jax.jit(make(bucket), donate_argnums=(0,)).lower(
                *eng._avals(kind, bucket)).compile()
        finally:
            eng._mix = mix

    bb, cb = eng.config.max_batch, eng.prefill_chunk
    bs = eng.config.block_size
    ctx = min(1536, eng.config.max_seq_len - 2 * bs)
    per = -(-(ctx + 1) // bs)
    tables = np.zeros((bb, eng.max_blocks), np.int32)
    for r in range(bb):
        tables[r, :per] = 1 + (r * per + np.arange(per)) % (
            eng.config.num_blocks - 1)
    lengths = np.full((bb,), ctx, np.int32)
    decode_args = (
        eng._no_toks, np.full((bb,), -1, np.int32), np.ones((bb,), np.int32),
        tables, lengths, tables[:, ctx // bs].copy(),
        np.full((bb,), ctx % bs, np.int32), np.ones((bb,), np.bool_),
        np.zeros((bb, 2), np.uint32), np.zeros((bb,), np.float32),
        np.zeros((bb,), np.int32))
    chunk_args = (np.ones((1, cb), np.int32), np.int32(cb), np.int32(2 * cb),
                  tables[0], np.zeros((2,), np.uint32), np.float32(0.0),
                  np.int32(0))
    times = {}
    for kind, bucket, extra in (("decode", bb, decode_args),
                                ("prefill_chunk", cb, chunk_args)):
        for composed in (False, True):
            t = time.monotonic()
            prog = program(kind, bucket, composed)
            built = time.monotonic() - t
            state = {"pool": eng._caches[0]}

            def call():
                out = prog(state["pool"], params, *extra)
                state["pool"] = out[0]
                return out[1]

            times[(kind, composed)] = _median_ms(call, args.reps)
            eng._caches = (state["pool"],)
            say(f"[reading] {kind}@{bucket} with the connections "
                f"{'composed by XLA' if composed else 'in mxtpu_hc_mix'}: "
                f"{times[(kind, composed)]:.3f} ms a run (median of "
                f"{args.reps}, host clock around block_until_ready; "
                f"compiled in {built:.1f} s)")
    w, b, a = (params[f"layer1_hc_ffn_{k}"] for k in ("weight", "bias",
                                                       "scale"))
    kernel_f = jax.jit(lambda z: hc_mix(model, z, w, b, a,
                                        interpret=not on_chip))
    xla_f = jax.jit(lambda z: hc_maps(model, z, w, b, a))
    for rows in (bb, cb):
        xs = jnp.asarray(rng.standard_normal((rows, n, d)), wdtype)
        k_ms = _median_ms(lambda: kernel_f(xs), args.reps)
        x_ms = _median_ms(lambda: xla_f(xs), args.reps)
        say(f"[reading] one connection on {rows} rows: mxtpu_hc_mix "
            f"{k_ms:.4f} ms, composed by XLA {x_ms:.4f} ms (host clock, "
            "dispatch included)")

    for seed in seeds[1:]:
        for leaf in params.values():
            leaf.delete()
        params = ref.init_params(seed, cfg, wdtype)
        eng.swap_weights(params)          # operands: no recompilation
        got.append(readings(seed))

    # float8 weights: rounded leaf by leaf IN the weights' place (two sets
    # do not fit the chip), swapped in; the reference regenerates the
    # stated ones from the seed once the engine has spoken
    last = seeds[-1]
    low = {}
    for name in list(params):
        leaf = params.pop(name)
        if leaf.ndim >= 2 and jnp.issubdtype(leaf.dtype, jnp.floating):
            low[name] = _round_to(leaf.astype(jnp.float32), fp8).astype(
                leaf.dtype)
            leaf.delete()
        else:
            low[name] = leaf
    eng.swap_weights(low)

    class StatedReference:
        """``ref`` whose ``forward`` drops the lowered weights and the
        engine's pool, and computes with the weights as stated."""
        @staticmethod
        def forward(_params, toks, heads):
            for pool in eng._caches:
                pool.delete()
            for leaf in low.values():
                if leaf.ndim >= 2:
                    leaf.delete()
            return ref.forward(ref.init_params(last, cfg, wdtype), toks,
                               heads)

    run = types.SimpleNamespace(config=cfg, seed=last, compared={})
    ok_w, _ = serving.probe(run, eng, low, StatedReference)
    worst8 = run.compared["logit_deficit"][0]
    say(f"[reading] seed {last}, weights rounded to float8 e4m3: probe "
        f"{ok_w} (worst {worst8:.4f}, limit {tol})")
    worst = [r[0] for r in got]
    say(f"[reading] summary: probes as stated pass {sum(worst)}/{len(got)}; "
        f"hc_error kernel max {max(r[1] for r in got):.3g}, bfloat16 maps min "
        f"{min(r[2] for r in got):.3g}; latent_error max "
        f"{max(r[3] for r in got):.3g}, float8 rows {lat8:.3g}; float8 "
        f"weights fail the probe: {not ok_w}; decode "
        f"{times[('decode', False)]:.3f} ms kernel / "
        f"{times[('decode', True)]:.3f} ms XLA, chunk "
        f"{times[('prefill_chunk', False)]:.3f} / "
        f"{times[('prefill_chunk', True)]:.3f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
