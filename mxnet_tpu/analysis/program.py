"""Static auditor for lowered programs: walk the closed jaxpr + compiled
HLO of anything the framework can lower (ShardedTrainer step programs,
Module/FeedForward executors, optimizer update steps) and report typed
findings *before a single step runs*.

Rules (catalogue + worked examples in docs/static_analysis.md):

- ``program.widen``          64-bit values introduced from 32-bit inputs
- ``program.carry-widen``    carried state leaves with a different dtype
                             than it entered (the PR 2 retrace bug class)
- ``program.captured-const`` large trace-time constants baked in
- ``program.host-transfer``  callback/infeed/outfeed eqns inside the step
- ``program.donation-miss``  donated buffers XLA could not alias
- ``program.donation-alias`` donation contract violations (weights on the
                             legacy optimizer path must never be donated)
- ``program.carry-sharding`` carried state changing sharding / a scalar
                             carry that is not fully replicated

plus the **HBM-pass metric**: gradients are tagged in the trainer's step
with the identity primitive ``mxtpu_tag`` (zero HLO footprint), and the
auditor counts how many program eqns traverse each gradient buffer on the
update path, aggregated onto the flat comm buckets (how many times the
update chain sweeps each gradient before XLA fuses it).

And the **HBM-bytes metric** (``program.hbm-bytes``): every reduce
collective (``psum``/``psum_invariant``) gets a dtype-width-weighted wire-bytes
row.  A quantized all-reduce accumulates on wide lanes for exactness
(int8 payload sums on int32, fp8 on f32 — see ``psum_compressed``), so
the collective's own operand dtype overstates the wire: the auditor
walks the operand's backward cone for the narrowest same-shape value
(the ``convert_element_type`` into int8/fp8 that formed the payload)
and charges THAT element width.  An fp8/int8 bucket is therefore ¼ the
bytes of its f32 twin in the metric, and auditing with
``expect_wire_itemsize`` turns silent re-widening (a refactor dropping
the quantize) into a finding.

The same rule covers serving **decode programs** (round 12): a paged
KV-cache read is a ``gather`` whose operand is pool-shaped (rank >= 4 —
``[blocks, block_size, heads, head_dim]`` or the full per-layer pool),
and its element width is the KV bytes-per-token the decode step streams.
An fp8 pool reads 1-byte payloads (the f32 per-block scales are rank-2/3
gathers, excluded by shape); auditing with ``expect_kv_itemsize=1``
turns a silently re-widened pool (a refactor reading a pre-dequantized
f32 copy) into the same ``program.hbm-bytes`` finding.
"""

from __future__ import annotations

import contextlib
import re
import time
import warnings
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler
from .findings import Finding, Report

from jax.extend import core as _jex_core
from jax.interpreters import mlir as _mlir

__all__ = [
    "AuditConfig", "tag", "mark_grads", "audit_traced", "audit_trainer",
    "audit_executor", "audit_module", "audit_optimizer",
    "audit_on_compile", "assert_program_clean", "update_passes",
    "collective_wire_rows", "kv_read_rows",
]


# ----------------------------------------------------------------------
# The grad tag primitive: identity at runtime (lowers to nothing), but a
# visible `mxtpu_tag[label=...]` eqn in the jaxpr the auditor can anchor
# buffer-traffic analysis on.  Does not change HLO, executables, or
# compile-cache keys (those hash graph fingerprint + avals, not jaxprs).
# ----------------------------------------------------------------------

tag_p = _jex_core.Primitive("mxtpu_tag")
tag_p.def_impl(lambda x, **_: x)
tag_p.def_abstract_eval(lambda aval, **_: aval)
_mlir.register_lowering(tag_p, lambda ctx, x, **_: [x])


def tag(x, label: str):
    """Identity-tag a traced value so the auditor can find it."""
    return tag_p.bind(x, label=label)


def mark_grads(grads: Dict[str, Any]) -> Dict[str, Any]:
    """Tag each gradient leaf ``grad:<name>`` (used by ShardedTrainer)."""
    return {n: tag(g, label=f"grad:{n}") for n, g in grads.items()}


# ----------------------------------------------------------------------
# Config
# ----------------------------------------------------------------------

#: eqn primitives that round-trip through the host inside a program
HOST_TRANSFER_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed", "host_callback_call",
    "device_put",
})

#: layout-only primitives that do not move bucket bytes through HBM
FREE_PASS_PRIMS = frozenset({
    "reshape", "squeeze", "expand_dims", "bitcast_convert_type", "copy",
    "mxtpu_tag",
})

#: opaque fused calls that read each operand from HBM once (a Pallas
#: kernel): counted as exactly 1 read + 1 write for every tagged operand
#: they consume, with NO propagation to their outputs (the outputs are
#: new buffers, not more traffic over the gradient).  This is how the
#: counter sees through ``pallas_call`` instead of miscounting it as an
#: ordinary eqn.
STREAM_ONCE_PRIMS = frozenset({
    "pallas_call",
})

#: reduce collectives whose operands cross the interconnect (``psum`` at
#: the jax API level and under ``check_vma=False``; a vma-checked
#: shard_map jaxpr spells the same reduction ``psum_invariant``)
REDUCE_COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "all_reduce", "reduce_scatter",
})

_64BIT_KINDS = ("f", "i", "u", "c")


@dataclass
class AuditConfig:
    """Knobs for one audit run (defaults match the CI gate)."""
    const_bytes_threshold: int = 1024      # captured-const floor
    widen_bytes_threshold: int = 65536     # large 64-bit intermediate floor
    compile: bool = True                   # compile for sharding checks
    count_hbm: bool = True
    # reduce collectives whose f32-width payload is below this many bytes
    # are exempt from the hbm-bytes rule (loss/grad-norm scalars ride
    # plain psum by design; only bucket-scale payloads must quantize)
    collective_bytes_floor: int = 1024
    host_transfer_prims: frozenset = HOST_TRANSFER_PRIMS
    free_pass_prims: frozenset = FREE_PASS_PRIMS
    stream_once_prims: frozenset = STREAM_ONCE_PRIMS
    reduce_collective_prims: frozenset = REDUCE_COLLECTIVE_PRIMS


def _is64(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    # extended dtypes (typed PRNG keys) have no kind/itemsize — never 64-bit
    return (getattr(dt, "itemsize", 0) == 8
            and getattr(dt, "kind", "") in _64BIT_KINDS)


def _src_of(eqn) -> Tuple[str, int]:
    """Best-effort (file, line) of the user code that emitted an eqn."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return frame.file_name, frame.start_line
    except Exception:
        pass
    return "", 0


def _sub_closed(obj, out: List):
    """Collect every (Closed)Jaxpr reachable from an eqn params value."""
    if isinstance(obj, _jex_core.ClosedJaxpr):
        out.append(obj)
    elif isinstance(obj, _jex_core.Jaxpr):
        out.append(_jex_core.ClosedJaxpr(obj, ()))
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            _sub_closed(v, out)


def _eqn_subjaxprs(eqn) -> List:
    subs: List = []
    for v in eqn.params.values():
        _sub_closed(v, subs)
    return subs


def iter_eqns(closed, depth: int = 0):
    """Yield ``(eqn, depth)`` over a closed jaxpr and all sub-jaxprs."""
    for eqn in closed.jaxpr.eqns:
        yield eqn, depth
        for sub in _eqn_subjaxprs(eqn):
            yield from iter_eqns(sub, depth + 1)


def _all_consts(closed) -> List:
    consts = list(closed.consts)
    for eqn, _ in iter_eqns(closed):
        for sub in _eqn_subjaxprs(eqn):
            consts.extend(sub.consts)
    return consts


# ----------------------------------------------------------------------
# jaxpr-level rules
# ----------------------------------------------------------------------

def _all_jaxpr_levels(closed) -> List:
    levels = [closed]
    for eqn, _ in iter_eqns(closed):
        levels.extend(_eqn_subjaxprs(eqn))
    return levels


def _check_widen(closed, program: str, report: Report,
                 config: AuditConfig) -> None:
    """Flag eqns that *introduce* 64-bit values from non-64-bit inputs.

    The package enables x64 globally, so benign narrow-immediately
    intermediates exist in most programs (argmax index dtype, bool-sum
    promotion); those stay silent below ``widen_bytes_threshold``.  An
    introduction whose 64-bit result *escapes* to a program output is
    always an error — that is the retrace/memory bug class PR 2 hit."""
    for level in _all_jaxpr_levels(closed):
        jaxpr = level.jaxpr
        src: Dict[Any, Set[int]] = {}
        intros: List[Any] = []
        for eqn in jaxpr.eqns:
            outs64 = [v for v in eqn.outvars if _is64(v.aval)]
            ins = [v for v in eqn.invars
                   if not isinstance(v, _jex_core.Literal)]
            if outs64 and not any(_is64(v.aval) for v in ins):
                key = len(intros)
                intros.append(eqn)
                for v in outs64:
                    src.setdefault(v, set()).add(key)
            else:
                flow: Set[int] = set()
                for v in ins:
                    flow |= src.get(v, set())
                if flow:
                    for v in outs64:
                        src.setdefault(v, set()).update(flow)
        escaped: Set[int] = set()
        for v in jaxpr.outvars:
            if not isinstance(v, _jex_core.Literal) and _is64(v.aval):
                escaped |= src.get(v, set())
        for key, eqn in enumerate(intros):
            outs64 = [v for v in eqn.outvars if _is64(v.aval)]
            nbytes = sum(
                int(np.prod(v.aval.shape, dtype=np.int64)) * 8
                for v in outs64)
            does_escape = key in escaped
            if not does_escape and nbytes < config.widen_bytes_threshold:
                continue
            path, line = _src_of(eqn)
            in_dts = sorted({str(getattr(v.aval, "dtype", "?"))
                             for v in eqn.invars})
            what = ("escapes to a program output"
                    if does_escape else
                    f"is a {nbytes}-byte 64-bit intermediate")
            report.add(Finding(
                "program.widen",
                f"eqn `{eqn.primitive.name}` produces "
                f"{'/'.join(str(v.aval.dtype) for v in outs64)} from "
                f"{'/'.join(in_dts) or 'no'} inputs and {what}",
                path=path, line=line, program=program,
                severity="error" if does_escape else "warn",
                details={"primitive": eqn.primitive.name,
                         "out_dtypes": [str(v.aval.dtype)
                                        for v in outs64],
                         "in_dtypes": in_dts, "bytes": nbytes,
                         "escapes": does_escape}))


def _check_host_transfers(closed, program: str, report: Report,
                          config: AuditConfig) -> None:
    for eqn, _ in iter_eqns(closed):
        name = eqn.primitive.name
        if name not in config.host_transfer_prims:
            continue
        path, line = _src_of(eqn)
        report.add(Finding(
            "program.host-transfer",
            f"eqn `{name}` inside the program is a host round-trip per "
            "dispatch",
            path=path, line=line, program=program,
            details={"primitive": name}))


def _check_captured_consts(closed, program: str, report: Report,
                           config: AuditConfig) -> int:
    total = 0
    for c in _all_consts(closed):
        shape = getattr(c, "shape", ())
        dtype = getattr(c, "dtype", None)
        if dtype is None:
            continue
        nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        total += nbytes
        if nbytes >= config.const_bytes_threshold:
            report.add(Finding(
                "program.captured-const",
                f"trace-time constant {dtype}{list(shape)} "
                f"({nbytes} bytes) baked into the program — a different "
                "value at the next call means a full retrace",
                program=program,
                details={"shape": list(shape), "dtype": str(dtype),
                         "bytes": nbytes}))
    return total


# ----------------------------------------------------------------------
# Carry checks (dtype + sharding fixed points)
# ----------------------------------------------------------------------

def _check_carry_dtypes(closed, pairs, program: str,
                        report: Report) -> None:
    in_avals, out_avals = closed.in_avals, closed.out_avals
    for in_idx, out_idx, name in pairs:
        a, b = in_avals[in_idx], out_avals[out_idx]
        if a.dtype != b.dtype or tuple(a.shape) != tuple(b.shape):
            report.add(Finding(
                "program.carry-widen",
                f"carried value `{name}` enters as "
                f"{a.dtype}{list(a.shape)} but leaves as "
                f"{b.dtype}{list(b.shape)} — the next call re-traces the "
                "whole program",
                program=program,
                details={"carry": name, "in": f"{a.dtype}{list(a.shape)}",
                         "out": f"{b.dtype}{list(b.shape)}"}))


def _shardings_equiv(s_in, s_out, ndim: int) -> bool:
    try:
        return s_in.is_equivalent_to(s_out, ndim)
    except Exception:
        return str(s_in) == str(s_out)


def _check_carry_shardings(compiled, closed, pairs, replicated_idx,
                           program: str, report: Report) -> None:
    try:
        ins = jax.tree_util.tree_leaves(compiled.input_shardings)
        outs = jax.tree_util.tree_leaves(compiled.output_shardings)
    except Exception:
        return
    if len(ins) != len(closed.in_avals) or \
            len(outs) != len(closed.out_avals):
        return  # flattening mismatch (tokens etc.) — skip, don't guess
    for in_idx, out_idx, name in pairs:
        ndim = len(closed.in_avals[in_idx].shape)
        if not _shardings_equiv(ins[in_idx], outs[out_idx], ndim):
            report.add(Finding(
                "program.carry-sharding",
                f"carried value `{name}` changes sharding across the "
                f"step ({ins[in_idx]} -> {outs[out_idx]}) — every call "
                "resharding/regathers",
                program=program, details={"carry": name}))
    for out_idx, name in replicated_idx:
        s = outs[out_idx]
        try:
            repl = s.is_fully_replicated
        except Exception:
            continue
        if not repl:
            report.add(Finding(
                "program.carry-sharding",
                f"scalar carry `{name}` is not fully replicated ({s}) — "
                "per-device divergence accumulates silently",
                program=program, details={"carry": name}))


# ----------------------------------------------------------------------
# Donation checks
# ----------------------------------------------------------------------

_ALIAS_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")
_ARG_SPLIT_RE = re.compile(r"%arg(\d+):")


def lower_recording_warnings(traced):
    """``traced.lower()`` capturing jax's donated-buffer warnings (on
    this jax version an unaliasable donated input produces a UserWarning
    at lowering and *no* MLIR attribute)."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        lowered = traced.lower()
    msgs = [str(w.message) for w in caught
            if "donated" in str(w.message).lower()]
    return lowered, msgs


def _mlir_alias_map(lowered) -> Optional[Dict[int, int]]:
    """``{flat arg index: flat output index}`` for donation-aliased args,
    parsed from the lowered MLIR main signature; None when the signature
    cannot be matched to flat args one-to-one."""
    try:
        text = lowered.as_text()
    except Exception:
        return None
    m = re.search(r"@main\s*\((.*?)\)\s*->", text, re.DOTALL)
    if not m:
        return None
    sig = m.group(1)
    # chunk the signature on %argN tokens: attribute dicts nest braces
    # inside quoted sharding strings, so a regex over the dict is fragile
    marks = list(_ARG_SPLIT_RE.finditer(sig))
    out: Dict[int, int] = {}
    for i, am in enumerate(marks):
        idx = int(am.group(1))
        end = marks[i + 1].start() if i + 1 < len(marks) else len(sig)
        al = _ALIAS_RE.search(sig[am.end():end])
        if al:
            out[idx] = int(al.group(1))
    return out


def _check_donation(donate_flat: Set[int],
                    never_donate: Dict[int, str], warn_msgs: List[str],
                    lowered, program: str, report: Report) -> Dict[str, Any]:
    alias_map = _mlir_alias_map(lowered)
    info: Dict[str, Any] = {
        "donated_leaves": len(donate_flat),
        "aliased_outputs": (len(alias_map) if alias_map is not None
                            else None),
    }
    for msg in warn_msgs:
        report.add(Finding(
            "program.donation-miss",
            "XLA could not alias some donated buffers — they are freed "
            f"and reallocated every step ({msg.splitlines()[0][:200]})",
            program=program, details={"warning": msg[:500]}))
    if alias_map is not None:
        if not warn_msgs and len(alias_map) < len(donate_flat):
            report.add(Finding(
                "program.donation-miss",
                f"{len(donate_flat) - len(alias_map)} of "
                f"{len(donate_flat)} donated buffers have no "
                "tf.aliasing_output in the lowered program",
                program=program, details=dict(info)))
        for idx, why in never_donate.items():
            if idx in alias_map:
                report.add(Finding(
                    "program.donation-alias",
                    f"buffer at flat arg {idx} is donation-aliased but "
                    f"must never be donated: {why}",
                    program=program, details={"arg": idx, "why": why}))
    return info


# ----------------------------------------------------------------------
# HBM-pass counter
# ----------------------------------------------------------------------

def update_passes(closed, config: Optional[AuditConfig] = None
                  ) -> Dict[str, Dict[str, int]]:
    """Count how many eqns traverse each ``mxtpu_tag``-marked gradient
    on the update path: ``{label: {reads, writes}}``.

    ``reads`` counts non-layout eqns consuming the gradient or a
    same-shape value derived from it (the clip multiply, the optimizer
    step, the non-finite gate...); ``writes`` counts the same-shape
    buffers those eqns produce.  One streaming pass reads 1 / writes 1;
    every extra count is one more sweep of the jaxpr over the gradient
    (XLA may fuse them into one pass).
    """
    config = config or AuditConfig()
    free = config.free_pass_prims
    stream_once = config.stream_once_prims
    roots: Dict[str, Tuple[int, ...]] = {}          # label -> shape
    derived: Dict[Any, Set[str]] = {}               # var -> labels
    reads: Dict[str, int] = {}
    writes: Dict[str, int] = {}
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "mxtpu_tag":
            label = str(eqn.params.get("label", "grad"))
            shape = tuple(eqn.outvars[0].aval.shape)
            roots[label] = shape
            derived.setdefault(eqn.outvars[0], set()).add(label)
            reads.setdefault(label, 0)
            writes.setdefault(label, 0)
            continue
        hit: Set[str] = set()
        for v in eqn.invars:
            if isinstance(v, _jex_core.Literal):
                continue
            labels = derived.get(v)
            if labels:
                hit |= labels
        if not hit:
            continue
        if eqn.primitive.name in free:
            for ov in eqn.outvars:
                derived.setdefault(ov, set()).update(hit)
            continue
        if eqn.primitive.name in stream_once:
            # fused kernel: one streaming pass over every bucket operand;
            # outputs are new weight/state buffers, not derived grads
            for label in hit:
                reads[label] = reads.get(label, 0) + 1
                writes[label] = writes.get(label, 0) + 1
            continue
        for label in hit:
            reads[label] = reads.get(label, 0) + 1
        for ov in eqn.outvars:
            prop = {l for l in hit
                    if tuple(getattr(ov.aval, "shape", ())) == roots[l]}
            if prop:
                derived.setdefault(ov, set()).update(prop)
                for label in prop:
                    writes[label] = writes.get(label, 0) + 1
    return {label: {"reads": reads[label], "writes": writes[label]}
            for label in roots}


def bucket_passes(per_param: Dict[str, Dict[str, int]],
                  param_avals: Dict[str, Any],
                  param_order: Sequence[str],
                  bucket_bytes: int) -> List[Dict[str, Any]]:
    """Aggregate per-gradient pass counts onto the flat comm buckets
    (mirrors the trainer's bucket plan: last-declared-first, grouped by
    dtype, split at ``grad_bucket_bytes``)."""
    from ..parallel.collectives import plan_buckets
    out: List[Dict[str, Any]] = []
    order = [n for n in reversed(list(param_order))
             if f"grad:{n}" in per_param]
    by_dtype: Dict[Any, List[str]] = {}
    for n in order:
        by_dtype.setdefault(jnp.dtype(param_avals[n].dtype), []).append(n)
    for dtype, names in by_dtype.items():
        counts = [int(np.prod(param_avals[n].shape, dtype=np.int64))
                  for n in names]
        plan = plan_buckets(counts, dtype.itemsize, bucket_bytes)
        for bucket in plan:
            members = sorted({names[pi] for pi, _, _ in bucket})
            nbytes = sum((s1 - s0) * dtype.itemsize
                         for _, s0, s1 in bucket)
            rds = [per_param[f"grad:{n}"]["reads"] for n in members]
            wrs = [per_param[f"grad:{n}"]["writes"] for n in members]
            out.append({
                "index": len(out),
                "dtype": str(dtype),
                "bytes": nbytes,
                "params": members,
                "reads": max(rds) if rds else 0,
                "writes": max(wrs) if wrs else 0,
            })
    return out


# ----------------------------------------------------------------------
# HBM-bytes: dtype-width-weighted wire traffic of reduce collectives
# ----------------------------------------------------------------------

_WIRE_CONE_DEPTH = 8


def collective_wire_rows(closed, config: Optional[AuditConfig] = None
                         ) -> List[Dict[str, Any]]:
    """One row per reduce-collective operand: ``{primitive, shape, dtype,
    elems, wire_itemsize, wire_bytes, f32_bytes, float_payload}``.

    ``wire_itemsize`` is the narrowest element width found in the
    operand's backward cone among SAME-SHAPE values (depth-bounded walk
    through the producing eqns).  A quantized payload accumulates on
    wide lanes — int8 sums on int32, fp8 on f32 — so the collective's
    operand dtype is the LANE width; the narrow ``convert_element_type``
    that formed the payload is what crosses the wire, and the same-shape
    restriction is what keeps unrelated narrow values (bool masks,
    scalar flags) out of the cone.  ``float_payload`` marks rows whose
    cone carries floating data (gradient buckets), which is what the
    ``program.hbm-bytes`` rule quantifies; ``f32_bytes`` is the
    unquantized twin's traffic (elems x 4) for ratio math.
    """
    config = config or AuditConfig()
    rows: List[Dict[str, Any]] = []
    for level in _all_jaxpr_levels(closed):
        jaxpr = level.jaxpr
        producer: Dict[Any, Any] = {}
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                producer[ov] = eqn
        for eqn in jaxpr.eqns:
            if eqn.primitive.name not in config.reduce_collective_prims:
                continue
            for v in eqn.invars:
                if isinstance(v, _jex_core.Literal):
                    continue
                aval = v.aval
                dt = getattr(aval, "dtype", None)
                if dt is None:
                    continue
                shape = tuple(aval.shape)
                elems = int(np.prod(shape, dtype=np.int64))
                wire = dt.itemsize
                floaty = jnp.issubdtype(dt, jnp.floating)
                seen = {v}
                frontier = [v]
                for _ in range(_WIRE_CONE_DEPTH):
                    nxt = []
                    for fv in frontier:
                        pe = producer.get(fv)
                        if pe is None:
                            continue
                        for iv in pe.invars:
                            if isinstance(iv, _jex_core.Literal) \
                                    or iv in seen:
                                continue
                            seen.add(iv)
                            idt = getattr(iv.aval, "dtype", None)
                            if idt is None or \
                                    tuple(iv.aval.shape) != shape:
                                continue
                            wire = min(wire, idt.itemsize)
                            floaty = floaty or jnp.issubdtype(
                                idt, jnp.floating)
                            nxt.append(iv)
                    frontier = nxt
                    if not frontier:
                        break
                rows.append({
                    "primitive": eqn.primitive.name,
                    "shape": list(shape),
                    "dtype": str(dt),
                    "elems": elems,
                    "wire_itemsize": int(wire),
                    "wire_bytes": elems * int(wire),
                    "f32_bytes": elems * 4,
                    "float_payload": bool(floaty),
                })
    return rows


def _check_hbm_bytes(rows: List[Dict[str, Any]], expect_itemsize: int,
                     program: str, report: Report,
                     config: AuditConfig) -> None:
    """The ``program.hbm-bytes`` rule: with ``expect_wire_itemsize`` set
    (the trainer runs a quantized ``grad_compression``), every bucket-
    scale floating reduce collective must put a payload at most that
    wide on the wire — a wider payload means the quantize was silently
    dropped and the program re-widened to f32."""
    big = [r for r in rows if r["float_payload"]
           and r["f32_bytes"] >= config.collective_bytes_floor]
    if not big:
        report.add(Finding(
            "program.hbm-bytes",
            "expect_wire_itemsize was set but the program has no bucket-"
            "scale floating reduce collective — the quantized grad "
            "reduction is not in the trace",
            program=program,
            details={"expect_wire_itemsize": expect_itemsize}))
        return
    for r in big:
        if r["wire_itemsize"] > expect_itemsize:
            report.add(Finding(
                "program.hbm-bytes",
                f"reduce collective `{r['primitive']}` over "
                f"{r['dtype']}{r['shape']} puts {r['wire_itemsize']} "
                f"bytes/elem on the wire — expected <= {expect_itemsize} "
                "(quantized); the bucket silently widened back to full "
                "precision",
                program=program,
                details={**{k: r[k] for k in
                            ("primitive", "dtype", "wire_itemsize",
                             "wire_bytes", "f32_bytes")},
                         "expect_wire_itemsize": expect_itemsize}))


def kv_read_rows(closed, config: Optional[AuditConfig] = None
                 ) -> List[Dict[str, Any]]:
    """One row per paged KV-pool read: ``{shape, dtype, itemsize, elems,
    bytes, f32_bytes}``.

    A pool read is a ``gather`` whose operand is pool-shaped — rank >= 4
    (``[blocks, block_size, heads, head_dim]`` layer view, or the full
    ``[layers, ...]`` pool).  That shape filter keeps embedding lookups
    (rank 2) and the fp8 per-block scale gathers (rank 2/3) out, so the
    rows measure exactly the K/V payload traffic a decode step streams;
    ``bytes`` charges the operand's element width over the gathered
    output elements, ``f32_bytes`` is the unquantized twin (elems x 4)
    for ratio math."""
    rows: List[Dict[str, Any]] = []
    for level in _all_jaxpr_levels(closed):
        for eqn in level.jaxpr.eqns:
            if eqn.primitive.name != "gather":
                continue
            src = eqn.invars[0]
            if isinstance(src, _jex_core.Literal):
                continue
            aval = getattr(src, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is None or len(aval.shape) < 4:
                continue
            out = eqn.outvars[0].aval
            elems = int(np.prod(out.shape, dtype=np.int64))
            rows.append({
                "shape": list(aval.shape),
                "dtype": str(dt),
                "itemsize": int(dt.itemsize),
                "elems": elems,
                "bytes": elems * int(dt.itemsize),
                "f32_bytes": elems * 4,
            })
    return rows


def _check_kv_bytes(rows: List[Dict[str, Any]], expect_itemsize: int,
                    program: str, report: Report) -> None:
    """The ``program.hbm-bytes`` rule over paged KV-cache reads: with
    ``expect_kv_itemsize`` set (the engine runs a quantized pool), every
    pool-shaped gather must read elements at most that wide — a wider
    read means the program streams a silently re-widened pool and the
    decode step's HBM bytes/token snapped back to full precision."""
    if not rows:
        report.add(Finding(
            "program.hbm-bytes",
            "expect_kv_itemsize was set but the program has no "
            "pool-shaped KV gather — the paged cache read is not in "
            "the trace",
            program=program,
            details={"expect_kv_itemsize": expect_itemsize}))
        return
    for r in rows:
        if r["itemsize"] > expect_itemsize:
            report.add(Finding(
                "program.hbm-bytes",
                f"paged KV gather over {r['dtype']}{r['shape']} reads "
                f"{r['itemsize']} bytes/elem — expected <= "
                f"{expect_itemsize} (quantized pool); the cache "
                "silently widened back to full precision",
                program=program,
                details={**{k: r[k] for k in
                            ("dtype", "itemsize", "bytes", "f32_bytes")},
                         "expect_kv_itemsize": expect_itemsize}))


# ----------------------------------------------------------------------
# Generic entry: audit one traced program
# ----------------------------------------------------------------------

def audit_traced(traced, program: str,
                 donate_flat: Optional[Set[int]] = None,
                 never_donate: Optional[Dict[int, str]] = None,
                 carry_pairs: Optional[Sequence[Tuple[int, int, str]]] = None,
                 replicated_out: Optional[Sequence[Tuple[int, str]]] = None,
                 expect_wire_itemsize: Optional[int] = None,
                 expect_kv_itemsize: Optional[int] = None,
                 config: Optional[AuditConfig] = None,
                 report: Optional[Report] = None) -> Report:
    """Run every program rule over one ``jax.stages.Traced``.

    ``donate_flat``: flat input-leaf indices the caller donates.
    ``never_donate``: ``{flat index: reason}`` buffers that must not be
    donation-aliased (the `_owned_state` contract cross-check).
    ``carry_pairs``: ``(in_flat_idx, out_flat_idx, name)`` carried state.
    ``replicated_out``: ``(out_flat_idx, name)`` scalar carries that must
    be fully replicated.
    ``expect_wire_itemsize``: assert the quantized-collective contract —
    every bucket-scale floating reduce collective must put at most this
    many bytes/elem on the wire (``program.hbm-bytes`` findings
    otherwise; the wire-bytes rows land in the metrics either way).
    ``expect_kv_itemsize``: assert the quantized paged-KV contract —
    every pool-shaped gather must read elements at most this wide
    (``program.hbm-bytes`` findings otherwise; the kv-read rows land in
    the metrics either way).
    """
    config = config or AuditConfig()
    report = report if report is not None else Report(mode="audit")
    t0 = time.perf_counter()
    n0 = len(report.findings)
    closed = traced.jaxpr
    _check_widen(closed, program, report, config)
    _check_host_transfers(closed, program, report, config)
    consts_bytes = _check_captured_consts(closed, program, report, config)
    if carry_pairs:
        _check_carry_dtypes(closed, carry_pairs, program, report)
    metrics: Dict[str, Any] = {
        "eqns": sum(1 for _ in iter_eqns(closed)),
        "consts_bytes": consts_bytes,
    }
    lowered = None
    if donate_flat is not None:
        lowered, warn_msgs = lower_recording_warnings(traced)
        metrics["donation"] = _check_donation(
            donate_flat, never_donate or {}, warn_msgs,
            lowered, program, report)
    if config.compile:
        try:
            if lowered is None:
                lowered = traced.lower()
            compiled = lowered.compile()
        except Exception as e:  # audit must not die on a backend quirk
            metrics["compile_error"] = str(e)
            compiled = None
        if compiled is not None and (carry_pairs or replicated_out):
            _check_carry_shardings(
                compiled, closed, carry_pairs or [],
                replicated_out or [], program, report)
    if config.count_hbm:
        per = update_passes(closed, config)
        if per:
            metrics["hbm_passes"] = {"per_grad": per}
        rows = collective_wire_rows(closed, config)
        if rows:
            frows = [r for r in rows if r["float_payload"]]
            wire = sum(r["wire_bytes"] for r in frows)
            full = sum(r["f32_bytes"] for r in frows)
            metrics["hbm_bytes"] = {
                "collectives": rows,
                "wire_bytes": wire,
                "f32_bytes": full,
                "ratio": (full / wire) if wire else None,
            }
        if expect_wire_itemsize is not None:
            _check_hbm_bytes(rows, expect_wire_itemsize, program,
                             report, config)
        krows = kv_read_rows(closed, config)
        if krows:
            metrics["kv_reads"] = {
                "reads": krows,
                "read_bytes": sum(r["bytes"] for r in krows),
                "f32_bytes": sum(r["f32_bytes"] for r in krows),
            }
        if expect_kv_itemsize is not None:
            _check_kv_bytes(krows, expect_kv_itemsize, program, report)
    report.metrics[program] = metrics
    profiler.record_audit(program, len(report.findings) - n0,
                          time.perf_counter() - t0)
    return report


# ----------------------------------------------------------------------
# ShardedTrainer audit
# ----------------------------------------------------------------------

def _leaf_names(prefix: str, tree) -> List[str]:
    names = []
    for path, _ in jax.tree_util.tree_leaves_with_path(tree):
        names.append(prefix + jax.tree_util.keystr(path))
    return names


def audit_trainer(trainer, programs: Sequence[str] = ("train", "train_acc"),
                  batch_spec=None, config: Optional[AuditConfig] = None,
                  report: Optional[Report] = None) -> Report:
    """Audit a bound :class:`~mxnet_tpu.parallel.trainer.ShardedTrainer`'s
    step programs.  Carried state (params/aux/opt/metric carry/guard
    state) is checked as a dtype+sharding fixed point, donation is
    cross-checked, and the HBM-pass metric is aggregated onto the flat
    grad buckets."""
    config = config or AuditConfig()
    report = report if report is not None else Report(mode="audit")
    for kind in programs:
        label = f"trainer.{kind}"
        traced, in_args = trainer.trace_program(kind, batch_spec=batch_spec)
        sizes = [len(jax.tree_util.tree_leaves(a)) for a in in_args]
        offs = list(np.cumsum([0] + sizes))
        closed = traced.jaxpr
        n_out = len(closed.out_avals)

        carry_pairs: List[Tuple[int, int, str]] = []
        replicated_out: List[Tuple[int, str]] = []
        donate_flat: Optional[Set[int]] = None
        if kind in ("train", "train_acc"):
            p_n, a_n, o_n = sizes[0], sizes[1], sizes[2]
            donate_flat = set(range(offs[0], offs[3]))
            # outputs: (params, aux, opt, heads, [acc], [gstate])
            has_gs = trainer._guard_state is not None
            has_acc = kind == "train_acc"
            g_n = (len(jax.tree_util.tree_leaves(in_args[-1]))
                   if has_gs else 0)
            heads_n = n_out - p_n - a_n - o_n - (1 if has_acc else 0) - g_n
            names = (_leaf_names("param", in_args[0])
                     + _leaf_names("aux", in_args[1])
                     + _leaf_names("opt", in_args[2]))
            for j in range(p_n + a_n + o_n):
                carry_pairs.append((offs[0] + j, j, names[j]))
            out_after_heads = p_n + a_n + o_n + heads_n
            if has_acc:
                carry_idx = offs[6]  # (p,a,o,b,lr,t,carry,...)
                carry_pairs.append(
                    (carry_idx, out_after_heads, "metric carry"))
                replicated_out.append((out_after_heads, "metric carry"))
                out_after_heads += 1
            if has_gs:
                gs_in = offs[len(in_args) - 1]
                gnames = _leaf_names("gstate", in_args[-1])
                for j in range(g_n):
                    carry_pairs.append(
                        (gs_in + j, out_after_heads + j, gnames[j]))
                    replicated_out.append((out_after_heads + j, gnames[j]))
        expect_wire = None
        if kind in ("train", "train_acc") and \
                getattr(trainer, "grad_compression", None) is not None:
            from .. import quant
            expect_wire = quant.wire_itemsize(trainer.grad_compression)
        audit_traced(
            traced, label, donate_flat=donate_flat,
            carry_pairs=carry_pairs, replicated_out=replicated_out,
            expect_wire_itemsize=expect_wire,
            config=config, report=report)
        if config.count_hbm and kind in ("train", "train_acc"):
            per = report.metrics[label].get(
                "hbm_passes", {}).get("per_grad")
            if per:
                buckets = bucket_passes(
                    per, trainer._params, trainer._param_names,
                    trainer.grad_bucket_bytes)
                hbm = report.metrics[label]["hbm_passes"]
                hbm["buckets"] = buckets
                hbm["max_reads"] = max(
                    (b["reads"] for b in buckets), default=0)
                hbm["max_writes"] = max(
                    (b["writes"] for b in buckets), default=0)
    return report


# ----------------------------------------------------------------------
# Executor / Module audit (legacy layer)
# ----------------------------------------------------------------------

def _jit_of(prog):
    return getattr(prog, "_jit_fn", prog)


def audit_executor(exc, train: Optional[bool] = None,
                   config: Optional[AuditConfig] = None,
                   report: Optional[Report] = None,
                   label: str = "executor") -> Report:
    """Audit an :class:`~mxnet_tpu.executor.Executor`'s compiled programs
    (the inference forward and, when gradients are bound, the train
    forward + fused forward/backward).  Aux running stats are checked as
    a dtype fixed point: an aux update that widens re-traces the program
    on the next batch exactly like a trainer carry."""
    config = config or AuditConfig()
    report = report if report is not None else Report(mode="audit")
    if exc._placement is not None:
        return report  # eagerly-placed executors have no programs
    sds = jax.ShapeDtypeStruct
    arg_avals = {n: sds(a.shape, jnp.dtype(a.dtype))
                 for n, a in exc._arg_dict.items()}
    aux_avals = {n: sds(a.shape, jnp.dtype(a.dtype))
                 for n, a in exc._aux_dict.items()}
    rng = exc._next_rng()
    rng_aval = sds(rng.shape, rng.dtype)
    work = [("fwd_False", _jit_of(exc._get_fwd(False)),
             (arg_avals, aux_avals, rng_aval))]
    if train or (train is None and exc._grad_names):
        work.append(("fwd_True", _jit_of(exc._get_fwd(True)),
                     (arg_avals, aux_avals, rng_aval)))
        out_grads = tuple(sds(s, jnp.float32)
                          for s in exc._infer_head_shapes())
        work.append(("fb", _jit_of(exc._get_fb()),
                     (arg_avals, aux_avals, rng_aval, out_grads)))
    for kind, jit_fn, in_args in work:
        traced = jit_fn.trace(*in_args)
        carry_pairs = _executor_aux_pairs(traced, in_args, kind)
        audit_traced(traced, f"{label}.{kind}", carry_pairs=carry_pairs,
                     config=config, report=report)
    return report


def _executor_aux_pairs(traced, in_args, kind: str):
    """(heads, auxu[, grads]) outputs: pair each auxu entry with its
    input aux slot by name via the traced output pytree."""
    try:
        out_info = traced.out_info
    except Exception:
        return []
    aux_avals = in_args[1]
    n_args0 = len(jax.tree_util.tree_leaves(in_args[0]))
    aux_keys = sorted(aux_avals)
    flat_out = jax.tree_util.tree_leaves_with_path(out_info)
    pairs = []
    for out_idx, (path, _) in enumerate(flat_out):
        ks = jax.tree_util.keystr(path)
        m = re.match(r"^\[1\]\['([^']+)'\]$", ks)
        if m and m.group(1) in aux_avals:
            in_idx = n_args0 + aux_keys.index(m.group(1))
            pairs.append((in_idx, out_idx, f"aux:{m.group(1)}"))
    return pairs


def audit_module(mod, config: Optional[AuditConfig] = None,
                 report: Optional[Report] = None) -> Report:
    """Audit every executor in a bound Module's executor group."""
    report = report if report is not None else Report(mode="audit")
    group = getattr(mod, "_exec_group", None)
    execs = getattr(group, "execs", None) or []
    for i, exc in enumerate(execs):
        audit_executor(exc, config=config, report=report,
                       label=f"module.exec{i}")
    return report


# ----------------------------------------------------------------------
# Legacy optimizer update audit (the `_owned_state` cross-check)
# ----------------------------------------------------------------------

def audit_optimizer(opt, weight_shape: Tuple[int, ...] = (16,),
                    dtype=jnp.float32,
                    config: Optional[AuditConfig] = None,
                    report: Optional[Report] = None) -> Report:
    """Audit one legacy ``Optimizer._functional_step`` update program in
    its donating (steady-state) form.  The donation contract from PR 2's
    `_owned_state` audit is checked statically: optimizer STATE must be
    donated and aliased; the WEIGHT must never be (same-device
    copyto/get_params share weight buffers with user-held dicts)."""
    config = config or AuditConfig()
    report = report if report is not None else Report(mode="audit")
    sds = jax.ShapeDtypeStruct
    w = sds(weight_shape, jnp.dtype(dtype))
    g = sds(weight_shape, jnp.dtype(dtype))
    state = jax.tree_util.tree_map(
        lambda l: sds(l.shape, l.dtype),
        jax.eval_shape(opt.state_zeros_like, w))
    hyper = opt._hyper()
    rng = (jax.eval_shape(lambda: jax.random.key_data(
        jax.random.PRNGKey(0)))
        if opt._needs_rng else None)
    jit_fn = type(opt)._jitted_step(donate=True)
    in_args = (hyper, w, g, state, 0.1, 0.0, 1, rng)
    traced = jit_fn.trace(*in_args)
    sizes = [len(jax.tree_util.tree_leaves(a)) for a in in_args]
    offs = list(np.cumsum([0] + sizes))
    donate_flat = set(range(offs[3], offs[4]))
    never = {offs[1]: "legacy weight buffers are shared with user-held "
                      "param dicts (copyto/get_params); donating one "
                      "deletes storage the caller still owns"}
    label = f"optimizer.{type(opt).__name__}"
    audit_traced(traced, label, donate_flat=donate_flat,
                 never_donate=never, config=config, report=report)
    return report


# ----------------------------------------------------------------------
# pytest helper
# ----------------------------------------------------------------------

def assert_program_clean(target, programs: Sequence[str] = ("train",),
                         batch_spec=None,
                         config: Optional[AuditConfig] = None) -> Report:
    """Audit ``target`` (a ShardedTrainer, Module, Executor, Optimizer,
    or an already-built Report) and raise ``AssertionError`` listing
    every unsuppressed finding if the program is not hazard-free.
    Returns the report so tests can additionally pin metrics (e.g. the
    HBM pass count)."""
    if isinstance(target, Report):
        report = target
    else:
        from ..parallel.trainer import ShardedTrainer
        from ..optimizer import Optimizer
        if isinstance(target, ShardedTrainer):
            report = audit_trainer(target, programs=programs,
                                   batch_spec=batch_spec, config=config)
        elif isinstance(target, Optimizer):
            report = audit_optimizer(target, config=config)
        elif hasattr(target, "_exec_group"):
            report = audit_module(target, config=config)
        elif hasattr(target, "_get_fwd"):
            report = audit_executor(target, config=config)
        else:
            raise TypeError(f"cannot audit {type(target).__name__}")
    bad = report.unsuppressed("error")
    if bad:
        lines = "\n".join(f.format() for f in bad)
        raise AssertionError(
            f"program audit found {len(bad)} hazard(s):\n{lines}")
    return report


# ----------------------------------------------------------------------
# Live audit of the compile path
# ----------------------------------------------------------------------

@contextlib.contextmanager
def audit_on_compile(report: Optional[Report] = None,
                     config: Optional[AuditConfig] = None):
    """Audit every program the framework traces for compilation while
    the context is active, via the compile-cache lowering observers —
    the audited trace IS the one that gets compiled, so there is no
    drift between analysis and execution.

    Only cache *misses* are seen (a cache hit dispatches a stored
    executable without a fresh lowering).  The shared program rules run
    per program; the trainer-specific carry/donation cross-checks need
    the trainer's index maps and remain :func:`audit_trainer`'s job.

        with analysis.audit_on_compile() as report:
            trainer.compile(programs=("train",))
        assert report.clean, report.format_text()
    """
    from .. import compile_cache as cc
    report = report if report is not None else Report(mode="audit")
    cfg = config or AuditConfig(compile=False)

    def observer(label, traced):
        audit_traced(traced, label, config=cfg, report=report)

    cc.add_lowering_observer(observer)
    try:
        yield report
    finally:
        cc.remove_lowering_observer(observer)
