"""Per-shape conv fwd/dgrad/wgrad probe on the real chip.

Times every distinct ResNet-50 conv shape (batch 256, bf16) three ways:

* ``fwd``    — ``lax.conv_general_dilated`` as the framework runs it;
* ``dgrad``  — input gradient, XLA's own VJP lowering;
* ``wgrad``  — weight gradient, XLA's own VJP lowering;

plus candidate replacements where the XLA lowering is suspected weak
(reference analog: the hand-tuned backward paths the 2016 framework got
from cuDNN, src/operator/cudnn_convolution-inl.h):

* ``dgrad_phase`` — stride-2 input gradient decomposed into 4 phase
  convolutions (no lhs_dilation: XLA's transposed-conv lowering inserts
  zeros, wasting 3/4 of the MXU MACs at stride 2);
* ``wgrad_mm``    — 1x1 wgrad as a plain dot_general over N*H*W.

Timing: chained ``fori_loop`` with a NON-FACTORABLE per-iteration input
transform (``abs(x + i)``) and a NONLINEAR whole-output accumulator
(``sum(abs(out))``) — conv is linear in its input, so scalar scales
hoist and plain sums collapse through it (see make_timer).  One
device->host scalar fetch at the end, two-point slope over loop counts
sized so the delta is ~120 ms of device time, well above the noise of
the closing fetch (see iters_for).

Usage: python tools/conv_probe.py [--filter 3x3_s2] [--iters 64 400]
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# (name, cin, hw_in, cout, k, stride, pad, count_in_resnet50)
RESNET50_SHAPES = [
    ("stem_7x7_s2", 3, 224, 64, 7, 2, 3, 1),
    ("s1_1x1_64_64", 64, 56, 64, 1, 1, 0, 1),
    ("s1_3x3_64", 64, 56, 64, 3, 1, 1, 3),
    ("s1_1x1_64_256", 64, 56, 256, 1, 1, 0, 4),
    ("s1_1x1_256_64", 256, 56, 64, 1, 1, 0, 2),
    ("s2_1x1_256_128", 256, 56, 128, 1, 1, 0, 1),
    ("s2_3x3_128_s2", 128, 56, 128, 3, 2, 1, 1),
    ("s2_1x1_sc_s2", 256, 56, 512, 1, 2, 0, 1),
    ("s2_1x1_128_512", 128, 28, 512, 1, 1, 0, 4),
    ("s2_1x1_512_128", 512, 28, 128, 1, 1, 0, 3),
    ("s2_3x3_128", 128, 28, 128, 3, 1, 1, 3),
    ("s3_1x1_512_256", 512, 28, 256, 1, 1, 0, 1),
    ("s3_3x3_256_s2", 256, 28, 256, 3, 2, 1, 1),
    ("s3_1x1_sc_s2", 512, 28, 1024, 1, 2, 0, 1),
    ("s3_1x1_256_1024", 256, 14, 1024, 1, 1, 0, 6),
    ("s3_1x1_1024_256", 1024, 14, 256, 1, 1, 0, 5),
    ("s3_3x3_256", 256, 14, 256, 3, 1, 1, 5),
    ("s4_1x1_1024_512", 1024, 14, 512, 1, 1, 0, 1),
    ("s4_3x3_512_s2", 512, 14, 512, 3, 2, 1, 1),
    ("s4_1x1_sc_s2", 1024, 14, 2048, 1, 2, 0, 1),
    ("s4_1x1_512_2048", 512, 7, 2048, 1, 1, 0, 3),
    ("s4_1x1_2048_512", 2048, 7, 512, 1, 1, 0, 2),
    ("s4_3x3_512", 512, 7, 512, 3, 1, 1, 2),
]


def make_timer(op, primary, rest):
    """jitted t(n): run op n times chained through an iteration-dependent
    scale on the primary operand; returns a scalar."""
    import jax
    import jax.numpy as jnp

    def chain(n, primary, *rest):
        def body(i, acc):
            # The per-iteration transform must make the op input a
            # DIFFERENT tensor each step in a way XLA cannot factor out.
            # A scalar multiply is NOT enough: conv/dot are linear in the
            # primary operand, so conv(x*s_i) = s_i*conv(x) and the
            # simplifier hoists the conv (observed: rows at 385-2155
            # "TFLOP/s", far above the chip's 197 peak).  abs(x + i) is
            # not scalar-related across iterations, so the op must run.
            # The accumulator must consume the WHOLE output NONLINEARLY:
            # a plain sum lets the simplifier push the reduction through
            # the (linear) conv — sum(conv(x, w)) collapses to an
            # elementwise dot with precomputed kernel sums (observed:
            # 5,515 "TFLOP/s") — and reducing a single element pushes a
            # slice through the same way.  abs blocks the rewrite; it
            # still fuses into the conv epilogue.
            shift = (1 + i % 8).astype(primary.dtype)
            out = op(jnp.abs(primary + shift), *rest)
            return acc + jnp.sum(jnp.abs(out.astype(jnp.float32)))
        return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))

    fn = jax.jit(chain)
    def t_of_n(n):
        t0 = time.perf_counter()
        v = fn(n, primary, *rest)
        np.asarray(v)  # forced fetch = true sync
        return time.perf_counter() - t0
    return t_of_n


def slope(t_of_n, n1, n2, reps=5):
    """Median two-point slope in seconds per op."""
    t_of_n(n1)  # compile+warm
    out = []
    for _ in range(reps):
        t1 = t_of_n(n1)
        t2 = t_of_n(n2)
        out.append((t2 - t1) / (n2 - n1))
    ok = sorted(s for s in out if s > 0)
    return ok[(len(ok) - 1) // 2] if ok else float("nan")


def iters_for(flops, target_s=0.12, rate=150e12, floor_s=15e-6):
    """Iteration counts sized so the SLOPE SIGNAL dominates the noise
    of the closing fetch: the n2-n1 delta must represent >= ~120 ms of
    device time.  A fixed small count made every sub-0.3 ms row pure
    noise (observed: 'ops' at 963 TF on a 197 TF chip, negative slopes,
    5x run-to-run flips)."""
    per_op = max(flops / rate, floor_s)
    delta = int(np.ceil(target_s / per_op))
    n1 = max(8, delta // 4)
    return n1, n1 + delta


def conv_fwd(s, p):
    import jax
    def op(x, w):
        return jax.lax.conv_general_dilated(
            x, w, window_strides=(s, s), padding=[(p, p), (p, p)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return op


def variants_for(name, cin, hw, cout, k, s, p, batch, rng, check=False):
    """Yield (variant_name, op, primary, rest, flops_per_call).

    ``check=True`` additionally asserts each replacement variant matches
    the XLA-VJP reference on the live data before it is timed."""
    import jax
    import jax.numpy as jnp
    ho = (hw + 2 * p - k) // s + 1
    x = jnp.asarray(rng.standard_normal((batch, cin, hw, hw)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((cout, cin, k, k)), jnp.bfloat16)
    dy = jnp.asarray(rng.standard_normal((batch, cout, ho, ho)), jnp.bfloat16)
    fwd = conv_fwd(s, p)
    macs = batch * ho * ho * cout * cin * k * k
    fl = 2.0 * macs

    def _assert_close(vname, got, ref):
        got = np.asarray(got, np.float32)
        ref = np.asarray(ref, np.float32)
        err = float(np.max(np.abs(got - ref)))
        tol = 1e-2 * max(1.0, float(np.max(np.abs(ref))))
        print(json.dumps({"shape": name, "variant": vname,
                          "check_max_err": round(err, 6),
                          "check_ok": err <= tol}), flush=True)
        if err > tol:
            raise AssertionError(f"{name}/{vname} mismatch: {err}")

    yield "fwd", fwd, x, (w,), fl

    # all arrays are explicit args — a closure-captured operand becomes a
    # baked-in constant at trace time (hundreds of MB inside the program)
    def dgrad(dy_, w_, x_):
        _, vjp = jax.vjp(lambda xx: fwd(xx, w_), x_)
        return vjp(dy_)[0]
    yield "dgrad", dgrad, dy, (w, x), fl

    def wgrad(x_, dy_, w_):
        _, vjp = jax.vjp(lambda ww: fwd(x_, ww), w_)
        return vjp(dy_)[0]
    yield "wgrad", wgrad, x, (dy, w), fl

    # candidate replacements are the PRODUCTION implementations
    # (mxnet_tpu/ops/conv_backward.py) — the probe must time exactly
    # what ships, so there is one copy of the math
    from mxnet_tpu.ops.conv_backward import (_dgrad_mm, _phase_dgrad,
                                             _wgrad_mm)

    if s == 2:
        # phase-decomposed dgrad: dx split by output parity, 4 stride-1
        # convs over the kernel-tap parity classes, interleaved back.
        def dgrad_phase(dy_, w_):
            return _phase_dgrad(dy_, w_, (batch, cin, hw, hw), k, s, p)
        if check:
            _assert_close("dgrad_phase", dgrad_phase(dy, w),
                          dgrad(dy, w, x))
        yield "dgrad_phase", dgrad_phase, dy, (w,), fl

    if k == 1 and s == 1 and p == 0:
        def wgrad_mm(x_, dy_):
            return _wgrad_mm(x_, dy_, (cout, cin, 1, 1))
        if check:
            _assert_close("wgrad_mm", wgrad_mm(x, dy), wgrad(x, dy, w))
        yield "wgrad_mm", wgrad_mm, x, (dy,), fl

        # 1x1 dgrad as a plain matmul: dx[n,c,h,w] = sum_o dy[n,o,h,w]
        # * w[o,c] — XLA's transposed-conv lowering leaves several of
        # these slow; a dot_general should run near peak
        def dgrad_mm(dy_, w_):
            return _dgrad_mm(dy_, w_, (batch, cin, hw, hw))
        if check:
            _assert_close("dgrad_mm", dgrad_mm(dy, w), dgrad(dy, w, x))
        yield "dgrad_mm", dgrad_mm, dy, (w,), fl


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--filter", default="")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, nargs=2, default=None,
                    help="fixed (n1, n2); default: auto-sized per shape "
                    "so the slope signal is ~120 ms of device time")
    ap.add_argument("--check", action="store_true",
                    help="numerically check variants vs XLA on CPU-size data")
    args = ap.parse_args()
    import jax

    rng = np.random.default_rng(0)
    rows = []
    total = {"fwd": 0.0, "dgrad": 0.0, "wgrad": 0.0, "best_bwd": 0.0}
    for (name, cin, hw, cout, k, s, p, count) in RESNET50_SHAPES:
        if args.filter and args.filter not in name:
            continue
        best = {}
        for vname, op, primary, rest, fl in variants_for(
                name, cin, hw, cout, k, s, p, args.batch, rng,
                check=args.check):
            n1, n2 = args.iters if args.iters else iters_for(fl)
            t = slope(make_timer(op, primary, rest), n1, n2)
            eff = fl / t / 1e12
            rows.append({"shape": name, "variant": vname,
                         "ms": round(t * 1e3, 3),
                         "tflops": round(eff, 1), "count": count})
            suspect = eff > 210  # v5e bf16 peak is 197: reading is bogus
            if suspect:
                rows[-1]["suspect_hoisted"] = True
            print(json.dumps(rows[-1]), flush=True)
            if not suspect:  # hoisted timings must not win best/totals
                best.setdefault(vname.split("_")[0], []).append((t, vname))
        for base in ("fwd", "dgrad", "wgrad"):
            if base in best:
                total[base] += count * min(best[base])[0]
        bwd = sum(count * min(best[b])[0] for b in ("dgrad", "wgrad")
                  if b in best)
        total["best_bwd"] += bwd
    print(json.dumps({"totals_ms": {k: round(v * 1e3, 2)
                                    for k, v in total.items()}}))


if __name__ == "__main__":
    main()
