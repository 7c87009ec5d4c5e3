#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that mxnet_tpu still starts on the chip.

Drives the LM train -> serve path ONCE, in ONE process, through the entry
points a user calls, at the full width of the default suite's LM row
(``transformer-lm`` 6 layers x d_model 512 x 8 heads, vocab 32,000):

1. **device** — fails unless ``jax.default_backend() == "tpu"``; prints what
   jax found, the versions, and where the compile cache lives;
2. **kernels** — each Pallas kernel against the repo's own reference, on
   the chip, at bench shapes: flash attention fwd + grads (seq 2048, d 64,
   bf16), the fused optimizer update (adam, sgd_momentum; a length that is
   not a tile multiple), flash-decode over a filled paged pool (f32, fp8);
3. **train** — ``models.get_symbol`` -> ``ShardedTrainer(adam, bf16 AMP)``
   -> ``bind`` (seq 2048, batch 8) -> ``compile`` -> ``place_batch`` -> a
   few ``step()``s on one repeated batch: loss finite, starts near ln(V),
   ends lower; one trace, no AOT fallback, fused update on, and the
   compiled program holds the flash fwd/dq/dkdv and fused-update kernels;
4. **serve** — ``trainer.get_params()`` -> ``serve.Engine`` (``attn_impl``
   left at ``auto`` -> flash) -> ``warmup`` -> a dozen mixed
   greedy/sampled requests through ``run()``, then each one again alone
   through ``stream()``: every budget met, streams token-for-token equal,
   zero traces after warmup, no KV block leaked, and the compiled decode
   program holds the flash-decode kernel.

``--chips 4`` (a four-chip host) instead repeats the train phase on
``data=4`` and ``data=2 x model=2`` (``megatron_rules``) and checks shard
placement and the loss trajectory against the one-chip run.

Any failed check exits non-zero; no phase is caught and continued.  Phase
wall times are printed as information about this run, not as a benchmark.
The last line of stdout is one JSON object, ``{"ok": true, "device":
{...}}``, printed only when every phase passed.

``--rehearsal`` is the sandbox dry run the on-chip guide asks for: tiny
sizes on the CPU with the Pallas kernels in interpret mode.  It exists to
debug this script's control flow, says so in its output, and is reachable
only through the flag — never by finding no chip.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# The LM row of bench.py's default suite.  Widths are fixed; the
# rehearsal shrinks everything because the CPU only checks control flow.
FULL = dict(
    vocab=32000, layers=6, d_model=512, heads=8, seq=2048, batch=8,
    steps=4, lr=1e-3,
    block_size=16, num_blocks=2048, max_batch=16, max_seq=2048, chunk=256,
    requests=12, prompt=(64, 512), new_tokens=(32, 64),
    attn=dict(b=2, h=8, l=2048, d=64), fused_n=1_000_003,
    paged=dict(b=16, nblk=128))
TINY = dict(
    vocab=96, layers=2, d_model=32, heads=4, seq=32, batch=4,
    steps=4, lr=1e-2,
    block_size=4, num_blocks=64, max_batch=4, max_seq=48, chunk=8,
    requests=4, prompt=(3, 16), new_tokens=(4, 8),
    attn=dict(b=1, h=2, l=64, d=32), fused_n=1231,
    paged=dict(b=3, nblk=6))

# |first loss - ln(vocab)|: Uniform(0.07) init puts ~N(0, 0.9) logits on
# the head, which costs about var/2 = 0.4 nats over the uniform guess
LOSS_BAND = 1.0
# normalized max error (max|x - ref| / max|ref|) against an f32
# highest-precision reference.  bf16 keeps 8 mantissa bits (2^-8 = 4e-3)
# and the kernels round p and ds to bf16 before their second matmul.
TOL_FLASH_FWD = 2e-2
TOL_FLASH_GRAD = 4e-2
# flash-decode is f32 VPU math end to end; only summation order differs
TOL_DECODE = 1e-4
# fused update vs the XLA-compiled jnp reference: same f32 formula, two
# compilers (Mosaic / XLA) free to contract multiply-adds differently
TOL_FUSED_ULP = 16
# multi-chip loss vs the one-chip trajectory, per step (bf16 compute, a
# different reduction order, adam amplifying it over a few steps)
TOL_MESH_LOSS = 0.05


class SmokeFailure(Exception):
    """A check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


class Clock:
    """Per-phase wall time, labelled for what it is."""

    def __init__(self, device_label: str):
        self.device_label = device_label
        self.t0 = time.perf_counter()

    def phase(self, name: str) -> None:
        now = time.perf_counter()
        say(f"[time] {name}: {now - self.t0:.1f} s wall on "
            f"{self.device_label}, compilation included — information, "
            "not a benchmark")
        self.t0 = now


def pallas_kernels(compiled) -> collections.Counter:
    """Kernel name -> number of Mosaic custom calls in a compiled
    program.  ``pallas_call(name=...)`` becomes the HLO instruction name
    (``%mxtpu_flash_fwd.3 = ... custom_call_target="tpu_custom_call"``)."""
    found = collections.Counter()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([A-Za-z_]\w*?)(?:\.\d+)*\s*=", line)
        found[m.group(1) if m else "?"] += 1
    return found


def nerr(x, ref) -> float:
    """max|x - ref| / max|ref| in f32 — one number per comparison."""
    import numpy as np
    x = np.asarray(x, np.float32)
    ref = np.asarray(ref, np.float32)
    check(np.isfinite(x).all(), "non-finite values in a kernel output")
    return float(np.max(np.abs(x - ref)) / (np.max(np.abs(ref)) + 1e-30))


def ulp_diff(a, b) -> int:
    """Largest units-in-the-last-place distance between two f32 arrays."""
    import numpy as np

    def key(x):
        i = np.asarray(x, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, np.int64(-2**31) - i - 1, i)
    return int(np.abs(key(a) - key(b)).max())


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------

def phase_device(args):
    import importlib.metadata as md

    import jax

    try:
        backend = jax.default_backend()
        devices = jax.devices()
    except RuntimeError as e:
        say_err(f"JAX found no usable backend: {e}")
        return None
    if args.rehearsal:
        say("*** REHEARSAL: tiny sizes on the CPU, Pallas kernels in "
            "interpret mode.  This checks the script, NOT the chip; "
            "nothing below is a device result. ***")
    elif backend != "tpu":
        say_err(f"no TPU: jax.default_backend() is {backend!r} "
                f"({len(devices)} x {devices[0].device_kind}).  This "
                "script proves the system on the chip and never falls "
                "back; --rehearsal is the explicit CPU dry run.")
        return None
    check(len(jax.local_devices()) >= args.chips,
          f"--chips {args.chips} but jax sees "
          f"{len(jax.local_devices())} local device(s)")

    def version(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "not installed"

    from mxnet_tpu import compile_cache as cc
    cache_dir = cc.enable_persistent_cache(os.path.join(REPO, ".jax_cache"))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"[device] platform={device['platform']} "
        f"device_kind={device['kind']!r} count={device['count']}")
    say(f"[device] jax={version('jax')} jaxlib={version('jaxlib')} "
        f"libtpu={version('libtpu')} python={sys.version.split()[0]}")
    src = ("JAX_COMPILATION_CACHE_DIR" if os.environ.get(cc.ENV_JAX_CACHE_DIR)
           else "default, inside the checkout")
    say(f"[device] compile cache: {cache_dir} ({src})")
    return device


def say_err(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


class CacheCounter:
    """Hits and misses of jax's persistent compilation cache."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ---------------------------------------------------------------------------
# phase: kernel numerics
# ---------------------------------------------------------------------------

def phase_kernels(cfg, interpret: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import quant
    from mxnet_tpu.ops import fused_update as fu
    from mxnet_tpu.parallel.flash_attention import flash_attention
    from mxnet_tpu.parallel.ring_attention import (blockwise_attention,
                                                   local_attention)
    from mxnet_tpu.serve import kvcache

    rng = np.random.RandomState(0)
    hi = jax.default_matmul_precision("highest")

    # -- flash attention: fwd, dq, dk/dv ---------------------------------
    a = cfg["attn"]
    shape = (a["b"], a["h"], a["l"], a["d"])
    q, k, v, w = (jnp.asarray(rng.randn(*shape) * 0.5, jnp.bfloat16)
                  for _ in range(4))

    def run(attend, *qkv):
        # w is a fixed random cotangent: grads of sum(out * w)
        def loss(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32)
                           * w.astype(jnp.float32))
        return jax.jit(lambda *t: (attend(*t),) + jax.grad(
            loss, (0, 1, 2))(*t))(*qkv)

    kern = run(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=interpret), q, k, v)
    scan = run(lambda q, k, v: blockwise_attention(
        q, k, v, min(1024, a["l"]), causal=True), q, k, v)
    with hi:
        ref = run(lambda q, k, v: local_attention(q, k, v, causal=True),
                  *(t.astype(jnp.float32) for t in (q, k, v)))
    for name, x, s, r, tol in zip(
            ("out", "dq", "dk", "dv"), kern, scan, ref,
            (TOL_FLASH_FWD,) + (TOL_FLASH_GRAD,) * 3):
        e_k, e_s = nerr(x, r), nerr(s, r)
        say(f"[kernels] flash_attention {name}: kernel err {e_k:.2e}, "
            f"jnp blockwise path err {e_s:.2e} (vs f32 dense, tol {tol})")
        check(e_k <= tol, f"flash_attention {name} err {e_k} > {tol}")

    # -- fused optimizer update -----------------------------------------
    n = cfg["fused_n"]                  # not a multiple of 8 * 128
    g, wt = (jnp.asarray(rng.randn(n), jnp.float32) for _ in range(2))
    s1 = jnp.asarray(rng.randn(n) * 1e-2, jnp.float32)
    s2 = jnp.asarray(np.abs(rng.randn(n)) * 1e-3, jnp.float32)
    cases = [
        ("adam", (s1, s2), dict(beta1=0.9, beta2=0.999, epsilon=1e-8,
                                rescale_grad=0.125)),
        ("sgd_momentum", (s1,), dict(momentum=0.9, wd=1e-4,
                                     rescale_grad=0.125)),
    ]
    for kind, state, hyper in cases:
        scalars = (np.float32(1e-3),)
        ref = jax.jit(lambda g, w, s: fu.reference_update(
            g, w, s, scalars, kind=kind, **hyper))(g, wt, state)
        pal = jax.jit(lambda g, w, s: fu.pallas_update(
            g, w, s, scalars, kind=kind, interpret=interpret,
            **hyper))(g, wt, state)
        ulps = [ulp_diff(r, p) for r, p in zip(ref, pal)]
        say(f"[kernels] fused_update {kind} n={n}: max ulp distance to "
            f"reference_update {ulps} (w, *state; tol {TOL_FUSED_ULP})")
        check(max(ulps) <= TOL_FUSED_ULP,
              f"fused_update {kind}: {ulps} ulp > {TOL_FUSED_ULP}")

    # -- flash-decode over a filled paged pool ---------------------------
    p = cfg["paged"]
    nb, bs, h = cfg["num_blocks"], cfg["block_size"], cfg["heads"]
    hd = cfg["d_model"] // h
    states = [jnp.asarray(rng.randn(nb * bs, h, hd), jnp.float32)
              for _ in range(2)]
    qd = jnp.asarray(rng.randn(p["b"], h, hd), jnp.float32)
    tables = jnp.asarray(np.stack([
        rng.permutation(np.arange(1, nb))[:p["nblk"]]
        for _ in range(p["b"])]), jnp.int32)
    lengths = jnp.asarray(rng.randint(1, p["nblk"] * bs + 1, p["b"]),
                          jnp.int32).at[0].set(p["nblk"] * bs)
    # the pools' stored form, two layers: layer 1 holds the states,
    # layer 0 other values in the same slots (a reader that ignored the
    # layer would read those)
    def stored(x, tail=(h * hd,)):
        x = x.reshape((nb, bs) + tail)
        return jnp.stack([jnp.flip(x, 0), x])

    pools = {"f32": [stored(s) for s in states]}
    fp8 = []
    for s in states:
        payload, scale = quant.rowwise_quantize(s, kvcache.KV_FP8_FORMAT)
        fp8.append(kvcache.QuantPool(stored(payload), stored(scale, ())))
    pools["fp8"] = fp8
    flash_impl = "flash_interpret" if interpret else "flash"
    for name, (kp, vp) in pools.items():
        out = jax.jit(lambda q, kp, vp, *t: kvcache.paged_attention(
            q, kp, vp, 1, *t, impl=flash_impl))(qd, kp, vp, tables, lengths)
        with hi:
            ref = jax.jit(lambda q, kp, vp, *t: kvcache.paged_attention(
                q, kp, vp, 1, *t, impl="dense"))(qd, kp, vp, tables, lengths)
        e = nerr(out, ref)
        say(f"[kernels] paged_attention impl=flash vs dense, {name} pool "
            f"{nb} blocks x {bs}: err {e:.2e} (tol {TOL_DECODE})")
        check(e <= TOL_DECODE, f"flash-decode {name} err {e} > {TOL_DECODE}")


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

def train_lm(cfg, mesh, rules=None, on_chip=True):
    """The train half through the normal entry points.  Returns
    ``(trainer, losses)``; every trainer starts from the same seed."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.parallel import ShardedTrainer

    b, l, v = cfg["batch"], cfg["seq"], cfg["vocab"]
    mx.random.seed(7)
    sym = models.get_symbol(
        "transformer-lm", vocab_size=v, num_layers=cfg["layers"],
        d_model=cfg["d_model"], heads=cfg["heads"], batch_size=b,
        seq_len=l, loss_head=True)
    tr = ShardedTrainer(
        sym, mesh=mesh, rules=rules, optimizer="adam",
        optimizer_params={"learning_rate": cfg["lr"]},
        matmul_precision="bfloat16", compute_dtype="bfloat16")
    tr.bind(data_shapes={"data": (b, l)},
            label_shapes={"softmax_label": (b, l)})
    info = tr.compile()
    say(f"[train] mesh {dict(mesh.shape)}: compiled in "
        f"{info[0]['seconds']:.1f} s (source: {info[0]['source']})")

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, v, (b, l + 1))
    batch = tr.place_batch({
        "data": tokens[:, :-1].astype(np.float32),
        "softmax_label": tokens[:, 1:].astype(np.float32)})
    losses = []
    for _ in range(cfg["steps"]):
        (tok_loss,) = tr.step(batch)
        check(tok_loss.shape == (b * l,),
              f"loss head shape {tok_loss.shape} != {(b * l,)}")
        losses.append(float(np.mean(np.asarray(tok_loss, np.float32))))
    say(f"[train] mesh {dict(mesh.shape)}: loss per step "
        + " ".join(f"{x:.4f}" for x in losses)
        + f"  (ln V = {math.log(v):.4f})")

    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(abs(losses[0] - math.log(v)) <= LOSS_BAND,
          f"first loss {losses[0]:.3f} not within {LOSS_BAND} of "
          f"ln({v}) = {math.log(v):.3f}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    check(tr.trace_counts["train"] == 1,
          f"train program traced {tr.trace_counts['train']} times")
    check(tr.aot_stats["fallbacks"] == 0 and
          tr.aot_stats["hits"] == cfg["steps"],
          f"steps did not all run the AOT program: {tr.aot_stats}")

    tp = rules is not None
    check(tr._fused is not tp,
          f"fused update is {tr._fused} with tensor parallelism {tp}")
    if on_chip:
        got = pallas_kernels(tr._aot["train"])
        say(f"[train] mesh {dict(mesh.shape)}: Mosaic kernels in the "
            f"compiled step: {dict(got)}")
        want = {"mxtpu_flash_fwd": cfg["layers"],
                "mxtpu_flash_dq": cfg["layers"],
                "mxtpu_flash_dkdv": cfg["layers"]}
        if tr._fused:
            want["mxtpu_fused_update"] = 1
        for name, least in want.items():
            check(got[name] >= least,
                  f"compiled train step holds {got[name]} x {name}, "
                  f"expected >= {least}: the jnp branch ran instead")
    return tr, losses


def phase_train(cfg, on_chip):
    import jax
    from mxnet_tpu.parallel import make_mesh
    mesh = make_mesh({"data": 1}, jax.local_devices()[:1])
    return train_lm(cfg, mesh, on_chip=on_chip)


def phase_train_mesh(cfg, base_losses, on_chip) -> None:
    """``--chips 4``: the same steps on data=4 and data=2 x model=2."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.flash_attention import AUTO_SWITCH_LEN
    from mxnet_tpu.parallel.trainer import megatron_rules

    devs = jax.local_devices()[:4]
    layouts = [({"data": 4}, None),
               ({"data": 2, "model": 2}, megatron_rules())]
    for axes, rules in layouts:
        mesh = make_mesh(axes, devs)
        tr, losses = train_lm(cfg, mesh, rules=rules, on_chip=on_chip)
        worst = max(abs(a - b) for a, b in zip(losses, base_losses))
        say(f"[train] mesh {axes}: max |loss - one-chip loss| = "
            f"{worst:.4f} (tol {TOL_MESH_LOSS})")
        check(worst <= TOL_MESH_LOSS,
              f"mesh {axes} loss {losses} strays {worst} from the "
              f"one-chip run {base_losses}")

        # every leaf lives on every chip of the mesh — nothing parked on
        # device 0 — and sharded params hold 1/model of the global array
        want_devs = set(devs)
        leaves = dict(tr._params)
        for key, st in tr._opt_state.items():
            for i, leaf in enumerate(jax.tree.leaves(st)):
                leaves[f"opt:{key}:{i}"] = leaf
        sharded = 0
        for name, arr in leaves.items():
            got = {s.device for s in arr.addressable_shards}
            check(got == want_devs,
                  f"{name} lives on {len(got)} of {len(want_devs)} chips")
            pname = name.split(":")[1] if name.startswith("opt:") else name
            spec = (rules.spec_for(pname) if rules is not None
                    and pname in tr._params else None)
            if spec is not None and any(ax is not None for ax in spec):
                want = NamedSharding(mesh, spec)
                check(arr.sharding.is_equivalent_to(want, arr.ndim),
                      f"{name} sharding {arr.sharding} != {want}")
                check(int(np.prod(arr.addressable_shards[0].data.shape))
                      * mesh.shape["model"] == int(np.prod(arr.shape)),
                      f"{name} shard is not 1/model of the array")
                sharded += 1
        say(f"[train] mesh {axes}: {len(leaves)} param/optimizer leaves "
            f"on all {len(want_devs)} chips, {sharded} tensor-sharded")
        check((sharded > 0) == (rules is not None),
              f"{sharded} tensor-sharded leaves under rules={rules}")

        # the flash kernel must run per shard, not replicated behind
        # all-gathers: _wrap_for_mesh puts it inside a shard_map
        # (sequences below AUTO_SWITCH_LEN take the dense path instead)
        if cfg["seq"] >= AUTO_SWITCH_LEN:
            traced, _ = tr.trace_program("train")
            check("shard_map" in str(traced.jaxpr),
                  f"mesh {axes}: flash attention not wrapped in shard_map")


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def phase_serve(cfg, params, on_chip) -> None:
    import numpy as np

    from mxnet_tpu.serve import Engine, EngineConfig

    ecfg = EngineConfig(
        heads=cfg["heads"], block_size=cfg["block_size"],
        num_blocks=cfg["num_blocks"], max_batch=cfg["max_batch"],
        max_prompt_len=cfg["prompt"][1], max_seq_len=cfg["max_seq"],
        prefill_chunk=cfg["chunk"],
        # auto resolves by platform; the CPU rehearsal has to name the
        # interpreted twin of the kernel the chip picks by itself
        attn_impl="auto" if on_chip else "flash_interpret")
    if on_chip:
        check(ecfg.resolved_attn_impl() == "flash",
              f"attn_impl auto resolved to {ecfg.resolved_attn_impl()!r}")
    eng = Engine(params, ecfg)
    infos = eng.warmup()
    say("[serve] warmup: " + ", ".join(
        f"{i['kind']}@{i['bucket']} {i['seconds']:.1f} s ({i['source']})"
        for i in infos))
    warm_traces = dict(eng.trace_counts)
    if on_chip:
        got = pallas_kernels(
            eng._programs[("decode", cfg["max_batch"])].compiled)
        say(f"[serve] Mosaic kernels in the compiled decode step: "
            f"{dict(got)}")
        check(got["mxtpu_flash_decode"] >= cfg["layers"],
              f"compiled decode step holds {got['mxtpu_flash_decode']} x "
              "mxtpu_flash_decode: the kernel is not in the program")

    rng = np.random.RandomState(1)
    reqs = []
    for i in range(cfg["requests"]):
        plen = int(rng.randint(cfg["prompt"][0], cfg["prompt"][1] + 1))
        reqs.append(dict(
            prompt=[int(t) for t in rng.randint(1, cfg["vocab"], plen)],
            max_new_tokens=int(rng.randint(cfg["new_tokens"][0],
                                           cfg["new_tokens"][1] + 1)),
            temperature=0.8 * (i % 2), top_k=40 * (i % 2), seed=100 + i))

    def submit(r):
        return eng.submit(r["prompt"], max_new_tokens=r["max_new_tokens"],
                          temperature=r["temperature"], top_k=r["top_k"],
                          seed=r["seed"])

    # continuous batching: everything in flight at once
    ids = [submit(r) for r in reqs]
    eng.run()
    batched = []
    for rid, r in zip(ids, reqs):
        req = eng.request(rid)
        check(req.state == "finished" and
              len(req.tokens) == r["max_new_tokens"],
              f"request {rid} ended {req.state!r} "
              f"({req.finish_reason!r}) with {len(req.tokens)}/"
              f"{r['max_new_tokens']} tokens")
        check(all(0 <= t < cfg["vocab"] for t in req.tokens),
              f"request {rid} produced an out-of-vocabulary token")
        batched.append(list(req.tokens))
    check(eng.alloc.num_used == 0,
          f"{eng.alloc.num_used} KV blocks still held after the batch")

    # the gold check: the same request served alone, token for token
    for r, want in zip(reqs, batched):
        alone = list(eng.stream(submit(r)))
        kind = "greedy" if r["temperature"] == 0 else "sampled"
        check(alone == want,
              f"{kind} request (seed {r['seed']}) differs alone vs "
              f"batched:\n  alone   {alone}\n  batched {want}")
    check(dict(eng.trace_counts) == warm_traces,
          f"traces after warmup: {dict(eng.trace_counts)} != {warm_traces}")
    check(eng.aot_stats["fallbacks"] == 0,
          f"serve programs fell back to jit: {dict(eng.aot_stats)}")
    check(eng.alloc.num_used == 0,
          f"{eng.alloc.num_used} KV blocks leaked after drain")
    eng.check_tables()
    n_tok = sum(map(len, batched))
    say(f"[serve] {len(reqs)} requests ({len(reqs) // 2} greedy, "
        f"{len(reqs) - len(reqs) // 2} sampled), prompts "
        f"{min(len(r['prompt']) for r in reqs)}-"
        f"{max(len(r['prompt']) for r in reqs)} tokens, {n_tok} tokens "
        f"out; alone == batched for all; {eng.step_idx} engine steps, "
        "0 traces after warmup, 0 blocks leaked")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: on a four-chip host, repeat the train phase "
                    "on data=4 and data=2 x model=2 instead of serving")
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU dry run of this script at tiny sizes "
                    "(Pallas in interpret mode); proves nothing about "
                    "the chip")
    args = ap.parse_args(argv)
    if args.rehearsal:
        # the one way onto the CPU: asked for by name, before jax loads
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.chips}")
    sys.path.insert(0, REPO)

    t_start = time.perf_counter()
    device = phase_device(args)
    if device is None:
        return 2
    cfg = TINY if args.rehearsal else FULL
    on_chip = not args.rehearsal
    cache = CacheCounter()
    clock = Clock(f"{device['count']} x {device['kind']}")

    if args.chips == 1:
        phase_kernels(cfg, interpret=args.rehearsal)
        clock.phase("kernels")
    trainer, losses = phase_train(cfg, on_chip)
    clock.phase("train")
    if args.chips == 1:
        params, _ = trainer.get_params()
        del trainer
        phase_serve(cfg, params, on_chip)
        clock.phase("serve")
    else:
        del trainer
        phase_train_mesh(cfg, losses, on_chip)
        clock.phase("train on 4 chips")

    say(f"[cache] jax persistent cache: {cache.hits} hits, "
        f"{cache.misses} misses")
    say(f"[time] total: {time.perf_counter() - t_start:.1f} s wall — "
        "information, not a benchmark")
    result = {"ok": True, "device": device}
    if args.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        say_err(f"FAILED: {e}")
        sys.exit(1)
