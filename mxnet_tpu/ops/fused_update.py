"""Single-pass fused optimizer update over flat gradient buckets.

The unfused train step re-reads each flat grad bucket many times: loss-
scale unscale, global-norm clip, the non-finite guard's ``where`` gating
and the optimizer math are all separate jnp ops over the same HBM bytes
(sgd+momentum 5 reads/5 writes per bucket, adam 12, the full guardrail
stack 18 — BENCH_r07.json).  This module collapses the whole update into
ONE primitive per bucket, ``mxtpu_fused_update``:

    (g, w, *state[, wd_vec], *kind_scalars[, mult][, ok])
        -> (new_w, *new_state)

``wd_vec`` (optional, same flat length as ``g``) carries a per-element
effective weight decay — the per-bucket segment vector the trainer
builds when ``wd_mult`` differs across params (gamma/beta/bias
exclusion), which used to force the unfused fallback.  When present it
replaces the scalar ``wd`` hyperparameter elementwise (and for adamw
the kernel forms ``lrwd = lr_eff * wd_vec`` in place of the caller's
pre-multiplied scalar).

The scalar chain (loss-scale unscale x clip coefficient -> ``mult``,
bias-corrected ``lr_t`` for adam, the guard verdict ``ok``) is computed
once OUTSIDE the primitive; everything elementwise rides inside it, so
each bucket streams through VMEM exactly once.

Why a primitive and not a ``platform_dependent`` cpu/tpu branch:
``jax.lax.platform_dependent`` traces EVERY branch into the jaxpr (a
``cond`` over ``platform_index``) and prunes only at lowering, so the
jnp reference's elementwise chain would sit in the jaxpr on every
platform and the static HBM-pass auditor (``analysis/program.py``),
which reads the jaxpr, could no longer see the fusion boundary.  A
primitive keeps one opaque eqn in the jaxpr on every platform and picks
the lowering per backend:

- default (cpu/gpu): ``mlir.lower_fun`` of the jnp reference — XLA fuses
  the elementwise chain itself, and the reference IS the bitwise spec;
- tpu: a Pallas kernel streaming ``(block_rows, 128)`` f32 tiles through
  VMEM with the weight/state operands aliased to the outputs
  (``input_output_aliases``), so the update is literally 1R/1W per
  operand.  ``interpret=True`` runs the same kernel on CPU for tests.

The reference replicates ``optimizer._functional_step`` op-for-op
(including ``_prep_grad``'s rescale/clip order and the guard's
``jnp.where`` no-op gating), which is what makes the fused path
bitwise-identical to the unfused one.

Opt-out knob: ``MXNET_TPU_FUSED_UPDATE=0`` (docs/env_vars.md).
"""
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from jax.extend import core as _jex_core
from jax.interpreters import mlir as _mlir

__all__ = ["fused_update", "fused_update_p", "reference_update",
           "pallas_update", "FusedPlan", "build_plan", "fused_kind",
           "fused_enabled", "SUPPORTED_KINDS"]

SUPPORTED_KINDS = ("sgd", "sgd_momentum", "adam", "adamw")

# number of state operands / extra scalar operands per optimizer kind
_N_STATE = {"sgd": 0, "sgd_momentum": 1, "adam": 2, "adamw": 2}
_N_SCALARS = {"sgd": 1, "sgd_momentum": 1, "adam": 1, "adamw": 2}

_LANES = 128          # f32 TPU tile is (8, 128); lane dim is fixed
_SUBLANES = 8
_MAX_BLOCK_ROWS = 512  # 512x128 f32 = 256 KiB per operand block in VMEM


def fused_enabled() -> bool:
    """The MXNET_TPU_FUSED_UPDATE opt-out knob (default: on)."""
    return os.environ.get("MXNET_TPU_FUSED_UPDATE", "1") != "0"


# ----------------------------------------------------------------------
# operand packing
# ----------------------------------------------------------------------

def _split_operands(args, *, kind, n_state, has_mult, has_ok,
                    has_wdvec=False):
    g, w = args[0], args[1]
    i = 2
    state = tuple(args[i:i + n_state])
    i += n_state
    wdvec = None
    if has_wdvec:
        wdvec = args[i]
        i += 1
    nsc = _N_SCALARS[kind]
    scalars = tuple(args[i:i + nsc])
    i += nsc
    mult = None
    if has_mult:
        mult = args[i]
        i += 1
    ok = args[i] if has_ok else None
    return g, w, state, scalars, mult, ok, wdvec


# ----------------------------------------------------------------------
# jnp reference: the bitwise spec (mirrors optimizer._functional_step)
# ----------------------------------------------------------------------

def _reference(*args, kind, momentum, beta1, beta2, epsilon, wd,
               rescale_grad, clip_gradient, has_mult, has_ok, n_state,
               has_wdvec=False):
    g, w, state, scalars, mult, ok, wdvec = _split_operands(
        args, kind=kind, n_state=n_state, has_mult=has_mult, has_ok=has_ok,
        has_wdvec=has_wdvec)
    # the scalar wd hyperparameter, or the per-element segment vector —
    # elementwise either way, so the op chain below is unchanged
    wdv = wdvec if has_wdvec else wd
    if has_mult:
        g = g * mult
    # _prep_grad, verbatim
    g = g * rescale_grad
    if clip_gradient is not None:
        g = jnp.clip(g, -clip_gradient, clip_gradient)

    if kind == "sgd":
        lr_eff = scalars[0]
        new_w = w - lr_eff * (g + wdv * w)
        new_state = ()
    elif kind == "sgd_momentum":
        lr_eff = scalars[0]
        mom = momentum * state[0] - lr_eff * (g + wdv * w)
        new_w = w + mom
        new_state = (mom,)
    elif kind == "adam":
        lr_t = scalars[0]
        mean, variance = state
        g = g + wdv * w
        m = beta1 * mean + (1.0 - beta1) * g
        v = beta2 * variance + (1.0 - beta2) * g * g
        new_w = w - lr_t * m / (jnp.sqrt(v) + epsilon)
        new_state = (m, v)
    elif kind == "adamw":
        # scalar form: scalars[1] is the pre-multiplied lr*wd; vector
        # form: scalars[1] is lr_eff and lrwd forms elementwise here
        lr_t, lrwd = scalars
        if has_wdvec:
            lrwd = lrwd * wdvec
        mean, variance = state
        m = beta1 * mean + (1.0 - beta1) * g
        v = beta2 * variance + (1.0 - beta2) * g * g
        update = lr_t * m / (jnp.sqrt(v) + epsilon)
        new_w = w - update - lrwd * w
        new_state = (m, v)
    else:  # pragma: no cover - bind() validates
        raise ValueError(f"unsupported fused kind {kind!r}")

    if has_ok:
        new_w = jnp.where(ok, new_w, w)
        new_state = tuple(jnp.where(ok, ns, s)
                          for ns, s in zip(new_state, state))
    return [new_w, *new_state]


def _materialized_reference(*args, **params):
    """Default-platform lowering: ``_reference`` inside a one-trip
    ``while_loop``.

    The loop is not an implementation detail — it is a bitwise-parity
    fix.  Lowering ``_reference`` inline lets XLA fuse the update math
    with the ``concatenate`` that forms the flat bucket; on CPU that
    fusion compiles to a branchy scalar loop, and LLVM's backend FMA
    contraction (chosen per basic block) then fuses a *different*
    multiply into the update's subtract than in the unfused per-tensor
    loops — a 1-ulp divergence that compounds over steps.  A while-loop
    body is a separate XLA computation: fusion cannot pull the
    concatenate in, the operand buckets materialize (which is also the
    advertised memory contract — form the bucket once, stream it once),
    and the update compiles to the same straight-line vectorized loop,
    with the same contraction, as the unfused path.  The trip count is
    always one, but it is derived from a traced value (``lr == lr`` is
    unfoldable under NaN semantics) so WhileLoopSimplifier cannot
    inline the body back into the caller.
    """
    g, w, state, scalars, mult, ok, wdvec = _split_operands(
        args, kind=params["kind"], n_state=params["n_state"],
        has_mult=params["has_mult"], has_ok=params["has_ok"],
        has_wdvec=params.get("has_wdvec", False))
    trip = jnp.where(scalars[0] == scalars[0], jnp.int32(1), jnp.int32(2))

    def cond(carry):
        return carry[0] < trip

    def body(carry):
        # wdvec is input-only (never rewritten) so it is captured, not
        # carried — but it must sit between state and scalars to match
        # the operand protocol _reference re-splits
        outs = _reference(g, carry[1], *carry[2:],
                          *(() if wdvec is None else (wdvec,)),
                          *scalars,
                          *(() if mult is None else (mult,)),
                          *(() if ok is None else (ok,)), **params)
        return (carry[0] + jnp.int32(1), *outs)

    res = jax.lax.while_loop(cond, body, (jnp.int32(0), w, *state))
    return list(res[1:])


# ----------------------------------------------------------------------
# Pallas TPU kernel: one VMEM pass per bucket
# ----------------------------------------------------------------------

def _make_kernel(*, kind, momentum, beta1, beta2, epsilon, wd,
                 rescale_grad, clip_gradient, has_mult, has_ok, n_state,
                 has_wdvec=False):
    nsc = _N_SCALARS[kind]
    n_out = 1 + n_state
    # pre-cast the trace-time python-float hyperparameters to numpy-f32
    # LITERALS: the kernel body may be traced outside our
    # enable_x64(False) scope (interpret mode lowers lazily), where a
    # bare python float would widen to f64 and break Mosaic/MLIR
    # verification; jnp constants would be captured tracers, which
    # pallas kernels reject.  Bitwise-neutral either way: a weak
    # python-float constant is cast to f32 at the op anyway.
    momentum_c = np.float32(momentum)
    rescale_c = np.float32(rescale_grad)
    eps_c = np.float32(epsilon)
    wd_c = np.float32(wd)
    b1_c, b2_c = np.float32(beta1), np.float32(beta2)
    omb1_c, omb2_c = np.float32(1.0 - beta1), np.float32(1.0 - beta2)
    clip_lo = clip_hi = None
    if clip_gradient is not None:
        clip_lo = np.float32(-clip_gradient)
        clip_hi = np.float32(clip_gradient)

    def kernel(*refs):
        g_ref, w_ref = refs[0], refs[1]
        i = 2
        state_refs = refs[i:i + n_state]
        i += n_state
        wdv_ref = None
        if has_wdvec:
            wdv_ref = refs[i]
            i += 1
        sc_refs = refs[i:i + nsc]
        i += nsc
        mult_ref = None
        if has_mult:
            mult_ref = refs[i]
            i += 1
        ok_ref = refs[i] if has_ok else None
        out_refs = refs[-n_out:]

        g = g_ref[...]
        w = w_ref[...]
        wdv = wdv_ref[...] if has_wdvec else wd_c
        if has_mult:
            g = g * mult_ref[0, 0]
        g = g * rescale_c
        if clip_gradient is not None:
            g = jnp.clip(g, clip_lo, clip_hi)

        if kind == "sgd":
            new_w = w - sc_refs[0][0, 0] * (g + wdv * w)
            new_state = ()
        elif kind == "sgd_momentum":
            st = state_refs[0][...]
            mom = momentum_c * st - sc_refs[0][0, 0] * (g + wdv * w)
            new_w = w + mom
            new_state = (mom,)
        else:  # adam / adamw
            lr_t = sc_refs[0][0, 0]
            mean = state_refs[0][...]
            variance = state_refs[1][...]
            if kind == "adam":
                g = g + wdv * w
            m = b1_c * mean + omb1_c * g
            v = b2_c * variance + omb2_c * g * g
            update = lr_t * m / (jnp.sqrt(v) + eps_c)
            if kind == "adam":
                new_w = w - update
            else:
                lrwd = (sc_refs[1][0, 0] * wdv if has_wdvec
                        else sc_refs[1][0, 0])
                new_w = w - update - lrwd * w
            new_state = (m, v)

        if has_ok:
            okv = ok_ref[0, 0] != 0
            new_w = jnp.where(okv, new_w, w)
            new_state = tuple(jnp.where(okv, ns, sr[...])
                              for ns, sr in zip(new_state, state_refs))
        out_refs[0][...] = new_w
        for k, ns in enumerate(new_state):
            out_refs[1 + k][...] = ns

    return kernel


def _pallas_apply(args, params, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kind = params["kind"]
    n_state = params["n_state"]
    has_mult, has_ok = params["has_mult"], params["has_ok"]
    has_wdvec = params.get("has_wdvec", False)
    g, w, state, scalars, mult, ok, wdvec = _split_operands(
        args, kind=kind, n_state=n_state, has_mult=has_mult, has_ok=has_ok,
        has_wdvec=has_wdvec)
    n = g.shape[0]
    n_out = 1 + n_state

    # pad the flat bucket to a whole number of (8, 128) f32 tiles; the
    # tail lanes compute harmless junk that is sliced off below (adam's
    # sqrt(0)+eps divisor keeps even the tail finite)
    rows = -(-n // _LANES)
    rows = -(-rows // _SUBLANES) * _SUBLANES
    brows = min(rows, _MAX_BLOCK_ROWS)
    if rows % brows:
        rows = -(-rows // brows) * brows
    padded = rows * _LANES

    def as_tiles(a):
        if padded != n:
            a = jnp.pad(a, (0, padded - n))
        return a.reshape(rows, _LANES)

    arrays = [as_tiles(g), as_tiles(w)] + [as_tiles(s) for s in state]
    if has_wdvec:
        # input-only tile operand (never aliased to an output; the
        # {1+k: k} aliasing below only covers w and the state operands,
        # whose indices precede it)
        arrays.append(as_tiles(wdvec))
    smalls = [jnp.asarray(s, jnp.float32).reshape(1, 1)
              for s in scalars]
    if has_mult:
        smalls.append(jnp.asarray(mult, jnp.float32).reshape(1, 1))
    if has_ok:
        smalls.append(ok.astype(jnp.int32).reshape(1, 1))

    arr_spec = pl.BlockSpec((brows, _LANES), lambda i: (i, 0))
    sc_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))

    kernel = _make_kernel(**params)
    with jax.enable_x64(False):  # Mosaic rejects i64 index types
        outs = pl.pallas_call(
            kernel,
            name="mxtpu_fused_update",
            out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)
                       ] * n_out,
            grid=(rows // brows,),
            in_specs=[arr_spec] * len(arrays) + [sc_spec] * len(smalls),
            out_specs=[arr_spec] * n_out,
            # w and each state operand are consumed exactly once -> alias
            # them onto the outputs so the update is in-place in HBM
            input_output_aliases={1 + k: k for k in range(n_out)},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(*arrays, *smalls)
    return [o.reshape(-1)[:n] for o in outs]


# ----------------------------------------------------------------------
# the primitive
# ----------------------------------------------------------------------

fused_update_p = _jex_core.Primitive("mxtpu_fused_update")
fused_update_p.multiple_results = True


def _abstract_eval(*avals, n_state, **_):
    return [avals[1]] + [avals[2 + k] for k in range(n_state)]


def _pallas_lowering(*args, mesh, **params):
    """TPU lowering.  A Mosaic kernel is an opaque custom call GSPMD
    cannot partition (jax refuses to lower one outside a fully manual
    region), so under a multi-device ``mesh`` the kernel runs inside a
    ``shard_map``: the fused path only ever sees replicated buckets, and
    every device applies the same update to its own copy."""
    def apply(*a):
        return _pallas_apply(a, params, interpret=False)
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        apply = jax.shard_map(apply, mesh=mesh, in_specs=P(), out_specs=P(),
                              check_vma=False)
    return apply(*args)


# ``mesh`` only matters to the TPU lowering; it is a primitive param (not
# a trace-time wrapper) so the jaxpr keeps ONE opaque eqn on every mesh
fused_update_p.def_abstract_eval(_abstract_eval)
fused_update_p.def_impl(
    lambda *args, mesh, **params: _reference(*args, **params))

_mlir.register_lowering(
    fused_update_p,
    _mlir.lower_fun(lambda *args, mesh, **params: _materialized_reference(
        *args, **params), multiple_results=True))
_mlir.register_lowering(
    fused_update_p,
    _mlir.lower_fun(_pallas_lowering, multiple_results=True),
    platform="tpu")


def fused_update(g, w, state=(), scalars=(), *, kind, mult=None, ok=None,
                 wd_vec=None, momentum=0.0, beta1=0.0, beta2=0.0,
                 epsilon=0.0, wd=0.0, rescale_grad=1.0, clip_gradient=None,
                 mesh=None):
    """Bind one fused update over a flat f32 bucket.

    Returns ``(new_w, *new_state)``.  ``scalars`` is the kind's combined
    learning-rate chain, already computed by the caller:
    ``(lr_eff,)`` for sgd/sgd_momentum, ``(lr_t,)`` for adam,
    ``(lr_t, lr*wd)`` for adamw.  ``mult`` (optional f32 scalar) is the
    combined loss-scale-unscale x clip coefficient; ``ok`` (optional
    bool scalar) gates the whole update to a bitwise no-op.  ``wd_vec``
    (optional flat f32, same length as ``g``) is the per-element
    effective weight decay (``wd * wd_mult`` per param segment); when
    present it replaces the scalar ``wd``, and for adamw ``scalars``
    must be ``(lr_t, lr_eff)`` — the kernel forms ``lr_eff * wd_vec``.
    ``mesh``: the mesh the enclosing jit partitions over, when it spans
    more than one device (operands must be replicated on it).
    """
    if kind not in SUPPORTED_KINDS:
        raise ValueError(f"unsupported fused kind {kind!r}")
    if len(state) != _N_STATE[kind]:
        raise ValueError(f"{kind} expects {_N_STATE[kind]} state operands, "
                         f"got {len(state)}")
    if len(scalars) != _N_SCALARS[kind]:
        raise ValueError(f"{kind} expects {_N_SCALARS[kind]} scalar "
                         f"operands, got {len(scalars)}")
    if wd_vec is not None and wd_vec.shape != g.shape:
        raise ValueError(f"wd_vec shape {wd_vec.shape} != bucket shape "
                         f"{g.shape}")
    operands = [g, w, *state]
    if wd_vec is not None:
        operands.append(wd_vec)
    operands.extend(jnp.asarray(s, jnp.float32) for s in scalars)
    if mult is not None:
        operands.append(jnp.asarray(mult, jnp.float32))
    if ok is not None:
        operands.append(ok)
    return tuple(fused_update_p.bind(
        *operands, kind=kind, momentum=float(momentum), beta1=float(beta1),
        beta2=float(beta2), epsilon=float(epsilon), wd=float(wd),
        rescale_grad=float(rescale_grad),
        clip_gradient=(None if clip_gradient is None
                       else float(clip_gradient)),
        has_mult=mult is not None, has_ok=ok is not None,
        has_wdvec=wd_vec is not None, n_state=len(state), mesh=mesh))


def reference_update(g, w, state=(), scalars=(), *, kind, mult=None,
                     ok=None, wd_vec=None, **hyper):
    """The jnp reference, callable directly (tests)."""
    kw = _norm_hyper(kind, len(state), mult, ok, wd_vec, hyper)
    operands = _pack(g, w, state, scalars, mult, ok, wd_vec)
    return tuple(_reference(*operands, **kw))


def pallas_update(g, w, state=(), scalars=(), *, kind, mult=None, ok=None,
                  wd_vec=None, interpret=True, **hyper):
    """The Pallas kernel, callable directly; ``interpret=True`` runs it
    on CPU (tests pin it bitwise against :func:`reference_update`)."""
    kw = _norm_hyper(kind, len(state), mult, ok, wd_vec, hyper)
    operands = _pack(g, w, state, scalars, mult, ok, wd_vec)
    return tuple(_pallas_apply(operands, kw, interpret=interpret))


def _pack(g, w, state, scalars, mult, ok, wd_vec=None):
    operands = [g, w, *state]
    if wd_vec is not None:
        operands.append(wd_vec)
    operands.extend(jnp.asarray(s, jnp.float32) for s in scalars)
    if mult is not None:
        operands.append(jnp.asarray(mult, jnp.float32))
    if ok is not None:
        operands.append(jnp.asarray(ok))
    return operands


def _norm_hyper(kind, n_state, mult, ok, wd_vec, hyper):
    kw = dict(kind=kind, momentum=0.0, beta1=0.0, beta2=0.0, epsilon=0.0,
              wd=0.0, rescale_grad=1.0, clip_gradient=None)
    kw.update(hyper)
    kw.update(has_mult=mult is not None, has_ok=ok is not None,
              has_wdvec=wd_vec is not None, n_state=n_state)
    return kw


# ----------------------------------------------------------------------
# optimizer-kind detection
# ----------------------------------------------------------------------

def fused_kind(opt) -> Optional[str]:
    """Map an optimizer INSTANCE to a fused kind, or None if its update
    rule has no fused twin.  Detection is by the identity of the class's
    ``_functional_step`` so subclasses that override the step (NAG, user
    optimizers) safely fall back to the unfused path."""
    from ..optimizer import SGD, Adam, AdamW
    if type(opt)._needs_rng:
        return None
    step = type(opt)._functional_step
    if step is SGD._functional_step:       # SGD and alias subclasses (ccSGD)
        return "sgd_momentum" if getattr(opt, "momentum", 0.0) else "sgd"
    if step is AdamW._functional_step:
        return "adamw"
    if step is Adam._functional_step:
        return "adam"
    return None


# ----------------------------------------------------------------------
# flat bucket plan: the optimizer-state layout contract
# ----------------------------------------------------------------------

@dataclass(frozen=True)
class FusedPlan:
    """Bucket-aligned layout for params/grads/opt-state, mirroring
    ``reduce_grads`` in parallel/trainer.py exactly (same reversed
    priority order, same greedy ``plan_buckets`` fill) so the explicit-
    comm path can hand its reduced flat buckets straight to the kernel
    with zero re-packing."""
    order: Tuple[str, ...]                       # reversed param order
    shapes: Dict[str, Tuple[int, ...]] = field(hash=False)
    # per bucket: ((name, start_elem, stop_elem), ...)
    buckets: Tuple[Tuple[Tuple[str, int, int], ...], ...] = ()

    @property
    def bucket_sizes(self) -> Tuple[int, ...]:
        return tuple(sum(s1 - s0 for _, s0, s1 in b) for b in self.buckets)

    def gather(self, tree, i):
        """Flat f32 bucket ``i`` from a {name: array} tree."""
        segs = [tree[n].reshape(-1)[s0:s1] for n, s0, s1 in self.buckets[i]]
        return segs[0] if len(segs) == 1 else jnp.concatenate(segs)

    def scatter(self, bucket_vals):
        """Inverse of gather over all buckets: {name: original-shape
        array} from the per-bucket flat outputs."""
        pieces: Dict[str, list] = {n: [] for n in self.order}
        for i, segs in enumerate(self.buckets):
            off = 0
            for n, s0, s1 in segs:
                ln = s1 - s0
                pieces[n].append(bucket_vals[i][off:off + ln])
                off += ln
        out = {}
        for n in self.order:
            ps = pieces[n]
            flat = ps[0] if len(ps) == 1 else jnp.concatenate(ps)
            out[n] = flat.reshape(self.shapes[n])
        return out


def build_plan(param_names: Sequence[str],
               shapes: Dict[str, Tuple[int, ...]],
               bucket_bytes: int) -> FusedPlan:
    """Mirror of ``reduce_grads``'s bucket layout (reversed priority
    order, greedy byte-budget fill; all params f32 — the trainer gates
    fused mode on that)."""
    from ..parallel.collectives import plan_buckets
    order = [n for n in reversed(list(param_names))
             if int(np.prod(shapes[n])) > 0]
    counts = [int(np.prod(shapes[n])) for n in order]
    raw = plan_buckets(counts, 4, bucket_bytes)
    buckets = tuple(
        tuple((order[idx], s0, s1) for idx, s0, s1 in bucket)
        for bucket in raw)
    return FusedPlan(order=tuple(order),
                     shapes={n: tuple(shapes[n]) for n in order},
                     buckets=buckets)
