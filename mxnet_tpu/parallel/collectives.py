"""Device-resident collectives over per-device arrays.

The reference reduces multi-device gradients by copying every shard into
pinned CPU memory and summing with OpenMP (``src/kvstore/
kvstore_local.h:148-236``) or into GPU merge buffers (``kvstore_device.h:
37-70``).  The TPU-native replacement: form a global array whose shards ARE
the per-device values (zero-copy via
``jax.make_array_from_single_device_arrays``) and run one compiled
``shard_map``/``psum`` — XLA lowers it to an ICI all-reduce, no host
round-trips.  This backs the KVStore ``device``/``local`` tiers when the
pushed values live on distinct devices.

Gradient fusion (this module's perf layer): issuing one collective per
tensor makes every BN scale / bias pay full dispatch + latency cost, the
failure mode the reference paper's dependency engine avoids by overlapping
push with backward.  :func:`allreduce_sum`/:func:`allreduce_mean` therefore
accept a *list of gradient groups* and fuse them into size-targeted
**buckets** (DDP-style flat buffers, default ~4 MiB): tensors are
flattened, laid end-to-end in priority order (higher ``priority`` →
earlier bucket, the contract ``KVStore.push(priority=...)`` advertises),
and each bucket is reduced as ONE fused program.  A tensor that straddles
a bucket boundary is split, so exactly ``ceil(total_bytes/bucket_bytes)``
programs are dispatched per dtype class.  Dispatch is async (JAX returns
futures), so early buckets reduce while later ones are still being
assembled — compute/comm overlap without an engine thread.

Optional quantized reduction (``compression='int8' | 'bf16' | 'fp8'``)
implements EQuARX-style quantize → all-reduce → dequantize inside the
same fused program, with one f32 scale per 128-element *block* (not per
buffer) so a single outlier only poisons its own block, and optional
**error feedback**: callers that carry a persistent f32 residual get
the per-step quantization error accumulated into the next step's input,
so compression bias vanishes across steps instead of biasing SGD; see
:func:`psum_compressed`.
"""
from __future__ import annotations

import contextlib
import functools
import re
import threading
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from .. import quant

__all__ = ["allreduce_sum", "allreduce_mean", "distinct_devices",
           "psum_compressed", "count_collectives", "CollectiveStats",
           "DEFAULT_BUCKET_BYTES", "COMPRESSIONS", "plan_buckets",
           "collective_inventory", "collectives_by_axes", "replica_groups"]

DEFAULT_BUCKET_BYTES = 4 << 20  # ~4 MiB, the classic DDP default
COMPRESSIONS = (None, "int8", "bf16", "fp8")


def check_compression(compression: Optional[str]) -> Optional[str]:
    if compression not in COMPRESSIONS:
        raise MXNetError(f"unknown compression {compression!r}; "
                         f"expected one of {COMPRESSIONS}")
    return compression


def distinct_devices(arrays: Sequence[jax.Array]) -> bool:
    """True when each array is committed to its own single device."""
    seen = set()
    for a in arrays:
        devs = getattr(a, "devices", None)
        if devs is None:
            return False
        ds = devs() if callable(devs) else devs
        if len(ds) != 1:
            return False
        d = next(iter(ds))
        if d in seen:
            return False
        seen.add(d)
    return True


# ---------------------------------------------------------------------------
# counting hook — lets tests assert how many fused programs a reduction
# dispatched (and how big they were) without reaching into XLA.

_dispatch_hooks: List[Callable[[dict], None]] = []
_hook_lock = threading.Lock()


class CollectiveStats:
    """Record of collective dispatches seen inside a
    :func:`count_collectives` scope."""

    def __init__(self):
        self.records: List[dict] = []

    def _record(self, rec: dict) -> None:
        self.records.append(rec)

    @property
    def count(self) -> int:
        return len(self.records)

    @property
    def total_bytes(self) -> int:
        return sum(r["nbytes"] for r in self.records)

    @property
    def total_wire_bytes(self) -> int:
        """Bytes actually crossing the interconnect (compressed width)."""
        return sum(r.get("wire_nbytes", r["nbytes"]) for r in self.records)

    def __repr__(self):
        return f"CollectiveStats(count={self.count}, bytes={self.total_bytes})"


@contextlib.contextmanager
def count_collectives():
    """``with count_collectives() as stats: ...`` — counts every fused
    all-reduce program dispatched by this module (one per bucket)."""
    stats = CollectiveStats()
    with _hook_lock:
        _dispatch_hooks.append(stats._record)
    try:
        yield stats
    finally:
        with _hook_lock:
            _dispatch_hooks.remove(stats._record)


def _emit(rec: dict) -> None:
    # unified-telemetry mirror: the same per-dispatch record that feeds
    # CollectiveStats lands in the process-wide registry, so wire-byte
    # totals are scrape()-able without opening a count_collectives scope
    from .. import telemetry
    telemetry.counter("collectives.dispatches").inc()
    nbytes = rec.get("nbytes", 0)
    telemetry.counter("collectives.bytes").inc(nbytes)
    telemetry.counter("collectives.wire_bytes").inc(
        rec.get("wire_nbytes", nbytes))
    if _dispatch_hooks:
        with _hook_lock:
            hooks = list(_dispatch_hooks)
        for h in hooks:
            h(rec)


# ---------------------------------------------------------------------------
# quantized psum — usable standalone inside any shard_map body (the
# ShardedTrainer grad path imports it) and by the bucket programs below.

def _block_view(flat: jax.Array, block: int) -> jax.Array:
    """Pad a flat f32 vector to a whole number of scale blocks and view
    it as ``[nblocks, block]``."""
    n = flat.size
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(nb, block)


def psum_compressed(x: jax.Array, axis_name: str,
                    compression: Optional[str] = None, *,
                    block: Optional[int] = None,
                    residual: Optional[jax.Array] = None):
    """All-reduce-sum ``x`` over ``axis_name``, optionally through a
    quantized wire format.

    Lossy formats quantize with one f32 scale per ``block`` contiguous
    elements (default ``quant.default_block_size()``, 128); every shard
    shares the same per-block scale (``pmax`` of the per-shard block
    absmax) so the reduction stays a plain sum on the quantized lanes:

    ``'int8'``: symmetric round-to-nearest onto [-127, 127]; the reduce
    runs on int32 lanes (exact for any realistic device count), then one
    dequantize multiply.  4x (f32) less wire traffic.

    ``'fp8'``: cast onto the e4m3 grid with the block absmax pinned to
    the format max (448), psum on f32 lanes — the 1-byte payload is what
    an EQuARX-style in-XLA reduce puts on the ICI links; accumulation is
    exact, matching int8's int32 lanes.

    ``'bf16'``: cast → psum → cast back; exact for values already bf16.

    **Error feedback**: pass ``residual`` (flat f32, ``x.size`` elems,
    per-shard) to compress ``x + residual`` instead of ``x`` and get
    ``(sum, new_residual)`` back, where ``new_residual`` is exactly the
    quantization error this shard just committed.  Carried across steps
    it cancels compression bias instead of letting it accumulate in the
    weights (Seide et al. 1-bit SGD; EQuARX).

    Non-float inputs ignore ``compression`` (quantizing indices or bool
    masks is never right) and take the plain psum.
    """
    check_compression(compression)
    if compression is None or not jnp.issubdtype(x.dtype, jnp.floating):
        red = jax.lax.psum(x, axis_name)
        return red if residual is None else (red, residual)
    if compression == "bf16" and residual is None:
        return jax.lax.psum(x.astype(jnp.bfloat16), axis_name).astype(x.dtype)

    xf = x.astype(jnp.float32).ravel()
    y = xf if residual is None else xf + residual.reshape(xf.shape)

    if compression == "bf16":
        q = y.astype(jnp.bfloat16)
        deq = q.astype(jnp.float32)
        red = jax.lax.psum(q, axis_name).astype(jnp.float32)
    else:
        if block is None:
            block = quant.default_block_size()
        yb = _block_view(y, block)
        absmax = jax.lax.pmax(
            jnp.max(jnp.abs(yb), axis=1, keepdims=True), axis_name)
        if compression == "int8":
            scale = jnp.maximum(absmax, jnp.float32(1e-30)) / jnp.float32(127.0)
            q = jnp.clip(jnp.round(yb / scale), -127.0, 127.0).astype(jnp.int8)
            s = jax.lax.psum(q.astype(jnp.int32), axis_name)
        else:  # fp8: e4m3 payload, exact f32 accumulation lanes
            scale = (jnp.maximum(absmax, jnp.float32(1e-30))
                     / jnp.float32(quant.FP8_MAX["e4m3"]))
            q = (yb / scale).astype(jnp.float8_e4m3fn)
            s = jax.lax.psum(q.astype(jnp.float32), axis_name)
        deq = (q.astype(jnp.float32) * scale).reshape(-1)[:y.size]
        red = (s.astype(jnp.float32) * scale).reshape(-1)[:y.size]

    out = red.reshape(x.shape).astype(x.dtype)
    if residual is None:
        return out
    return out, (y - deq).reshape(residual.shape)


# ---------------------------------------------------------------------------
# fused bucket programs

@functools.lru_cache(maxsize=None)
def _allreduce_prog(devices, mean: bool, compression: Optional[str],
                    block: int):
    mesh = Mesh(np.array(devices), ("dev",))
    n = len(devices)

    def body(x):
        s = psum_compressed(x, "dev", compression, block=block)
        return s / n if mean else s

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("dev"),
                             out_specs=P("dev"))), mesh


def _reduce_stacked(arrays: List[jax.Array], devices, mean: bool,
                    compression: Optional[str]) -> List[jax.Array]:
    """One fused all-reduce over N per-device arrays of identical shape.
    Returns the reduced value per device, input order."""
    shape = tuple(arrays[0].shape)
    # the block size is part of the cached program's identity: an env
    # override between calls must not be served a stale trace
    prog, mesh = _allreduce_prog(devices, mean, compression,
                                 quant.default_block_size())
    shards = [a[None] for a in arrays]  # (1, *shape), stays on its device
    global_arr = jax.make_array_from_single_device_arrays(
        (len(arrays),) + shape, NamedSharding(mesh, P("dev")), shards)
    out = prog(global_arr)
    by_dev = {s.device: s.data for s in out.addressable_shards}
    return [by_dev[d][0] for d in devices]


# ---------------------------------------------------------------------------
# bucket planning

def plan_buckets(elem_counts: Sequence[int], itemsize: int,
                 bucket_bytes: int) -> List[List[Tuple[int, int, int]]]:
    """Slice tensors (given in dispatch order) into flat buckets.

    Returns a list of buckets; each bucket is a list of
    ``(tensor_index, start_elem, stop_elem)`` pieces.  Tensors straddling
    a bucket boundary are split, so the plan always has exactly
    ``ceil(total_elems / elems_per_bucket)`` buckets.
    """
    elems_per_bucket = max(1, int(bucket_bytes) // max(1, itemsize))
    buckets: List[List[Tuple[int, int, int]]] = []
    cur: List[Tuple[int, int, int]] = []
    cur_elems = 0
    for idx, n in enumerate(elem_counts):
        start = 0
        while start < n:
            take = min(n - start, elems_per_bucket - cur_elems)
            cur.append((idx, start, start + take))
            cur_elems += take
            start += take
            if cur_elems == elems_per_bucket:
                buckets.append(cur)
                cur, cur_elems = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def _group_devices(group: List[jax.Array]):
    return tuple(next(iter(a.devices())) for a in group)


def _allreduce_bucketed(groups: List[List[jax.Array]], mean: bool,
                        priorities: Optional[Sequence[int]],
                        bucket_bytes: int,
                        compression: Optional[str]) -> List[List[jax.Array]]:
    """Reduce many gradient groups (each: one value per device) through
    fused flat buckets.  Returns reduced groups in the input order."""
    ngroups = len(groups)
    if priorities is not None and len(priorities) != ngroups:
        raise MXNetError("allreduce: priorities length mismatch")
    devices = _group_devices(groups[0])
    for g in groups[1:]:
        if _group_devices(g) != devices:
            raise MXNetError("allreduce: bucketed groups must share one "
                             "device set in one order")
    for g in groups:
        shape, dtype = g[0].shape, g[0].dtype
        for a in g[1:]:
            if a.shape != shape or a.dtype != dtype:
                raise MXNetError("allreduce: mismatched shapes/dtypes")

    # dispatch order: higher priority first (the contract KVStore.push
    # advertises); stable for ties so same-priority grads keep push order
    order = sorted(range(ngroups),
                   key=(lambda i: -priorities[i]) if priorities is not None
                   else (lambda i: 0))

    # dtype classes can't share a flat buffer; plan each independently
    by_dtype: dict = {}
    for i in order:
        by_dtype.setdefault(jnp.dtype(groups[i][0].dtype), []).append(i)

    results: List[Optional[List[jax.Array]]] = [None] * ngroups
    pieces_out: dict = {i: [] for i in range(ngroups)}  # idx -> [(per-dev flat piece list)]

    for dtype, idxs in by_dtype.items():
        counts = [int(np.prod(groups[i][0].shape, dtype=np.int64))
                  for i in idxs]
        # zero-size tensors contribute nothing; pass them through
        sized = [(i, c) for i, c in zip(idxs, counts) if c > 0]
        for i, c in zip(idxs, counts):
            if c == 0:
                results[i] = list(groups[i])
        if not sized:
            continue
        plan = plan_buckets([c for _, c in sized], dtype.itemsize,
                            bucket_bytes)
        flats = {i: [a.ravel() for a in groups[i]] for i, _ in sized}
        for bucket in plan:
            # assemble the flat buffer per device, then dispatch at once —
            # JAX async dispatch returns immediately, so this bucket's
            # reduce overlaps with assembling the next
            per_dev: List[jax.Array] = []
            for d_i in range(len(devices)):
                segs = []
                for piece_i, (start, stop) in ((sized[pi][0], (s0, s1))
                                               for pi, s0, s1 in bucket):
                    flat = flats[piece_i][d_i]
                    segs.append(flat if (start == 0 and stop == flat.size)
                                else flat[start:stop])
                per_dev.append(segs[0] if len(segs) == 1
                               else jnp.concatenate(segs))
            reduced = _reduce_stacked(per_dev, devices, mean, compression)
            wire_item = quant.wire_itemsize(
                compression if jnp.issubdtype(dtype, jnp.floating) else None,
                dtype.itemsize)
            _emit({"nbytes": int(per_dev[0].size) * dtype.itemsize,
                   "wire_nbytes": int(per_dev[0].size) * wire_item,
                   "num_pieces": len(bucket),
                   "tensor_indices": [sized[pi][0] for pi, _, _ in bucket],
                   "dtype": str(dtype), "compression": compression,
                   "mean": mean, "kind": "bucket"})
            # carve the reduced flat buffer back into tensor pieces
            off = 0
            for pi, start, stop in bucket:
                idx = sized[pi][0]
                ln = stop - start
                pieces_out[idx].append(
                    [r[off:off + ln] for r in reduced])
                off += ln

    for idx in range(ngroups):
        if results[idx] is not None:
            continue
        shape = tuple(groups[idx][0].shape)
        outs = []
        for d_i in range(len(devices)):
            parts = [p[d_i] for p in pieces_out[idx]]
            flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            outs.append(flat.reshape(shape))
        results[idx] = outs
    return results  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# public API

def _allreduce(arrays, mean: bool, priorities=None,
               bucket_bytes: Optional[int] = None,
               compression: Optional[str] = None):
    check_compression(compression)
    if bucket_bytes is None:
        bucket_bytes = DEFAULT_BUCKET_BYTES
    arrays = list(arrays)
    if not arrays:
        return []
    grouped = isinstance(arrays[0], (list, tuple))
    groups = [list(g) for g in arrays] if grouped else [arrays]

    # groups whose members are NOT on distinct devices take the degenerate
    # co-resident path (plain tree sum) — the single-device tier the
    # reference also special-cases
    flat_out: List[List[jax.Array]] = [None] * len(groups)  # type: ignore
    bucketable: List[int] = []
    for gi, g in enumerate(groups):
        if len(g) == 1:
            flat_out[gi] = list(g)
        elif not distinct_devices(g):
            acc = g[0]
            for a in g[1:]:
                acc = acc + jax.device_put(a, next(iter(g[0].devices())))
            if mean:
                acc = acc / len(g)
            _emit({"nbytes": int(acc.size) * acc.dtype.itemsize,
                   "wire_nbytes": int(acc.size) * acc.dtype.itemsize,
                   "num_pieces": 1, "tensor_indices": [gi],
                   "dtype": str(acc.dtype), "compression": None,
                   "mean": mean, "kind": "tree"})
            flat_out[gi] = [acc] * len(g)
        else:
            bucketable.append(gi)

    if bucketable:
        sub_prior = ([priorities[gi] for gi in bucketable]
                     if priorities is not None else None)
        reduced = _allreduce_bucketed([groups[gi] for gi in bucketable],
                                      mean, sub_prior, bucket_bytes,
                                      compression)
        for gi, r in zip(bucketable, reduced):
            flat_out[gi] = r

    return flat_out if grouped else flat_out[0]


def allreduce_sum(arrays, *, priorities=None,
                  bucket_bytes: Optional[int] = None,
                  compression: Optional[str] = None):
    """All-reduce-sum per-device arrays; each device gets the total.

    ``arrays`` is either one group (a flat list of same-shaped arrays,
    one per device — the classic single-tensor call) or a list of groups
    (one group per gradient).  Groups are fused into ~``bucket_bytes``
    flat buckets dispatched in descending ``priorities`` order; each
    bucket is ONE compiled all-reduce over ICI.  ``compression`` selects
    the quantized wire format (see :func:`psum_compressed`)."""
    return _allreduce(arrays, mean=False, priorities=priorities,
                      bucket_bytes=bucket_bytes, compression=compression)


def allreduce_mean(arrays, *, priorities=None,
                   bucket_bytes: Optional[int] = None,
                   compression: Optional[str] = None):
    return _allreduce(arrays, mean=True, priorities=priorities,
                      bucket_bytes=bucket_bytes, compression=compression)


# ---------------------------------------------------------------------------
# Collectives of a compiled program, by mesh axis
# ---------------------------------------------------------------------------

_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute", "collective-broadcast")
# ``%name = <shape> <opcode>(``: the opcode is the last word before the
# operand list's parenthesis
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s"
                        r"([a-z][a-z0-9\-]*)\(")
_HLO_ARRAY = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9]+m[0-9]+[a-z]*)?)"
                        r"\[([0-9,]*)\]")
_HLO_BITS = {"pred": 8, "s4": 4, "u4": 4, "s8": 8, "u8": 8, "s16": 16,
             "u16": 16, "f16": 16, "bf16": 16, "s32": 32, "u32": 32,
             "f32": 32, "s64": 64, "u64": 64, "f64": 64, "c64": 64,
             "c128": 128}
_IOTA_GROUPS = re.compile(r"^\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def _hlo_bytes(shape_text: str) -> int:
    """Bytes of an HLO result type, a tuple's elements summed (the
    float8 kinds ``f8e4m3fn`` ... are a byte an element)."""
    total = 0
    for dtype, dims in _HLO_ARRAY.findall(shape_text):
        bits = _HLO_BITS.get(dtype, 8 if dtype.startswith("f8") else 0)
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * bits // 8
    return total


def replica_groups(attr: str, num_devices: int) -> List[List[int]]:
    """The groups of an HLO ``replica_groups=`` value, in either form:
    the list ``{{0,1},{2,3}}`` or XLA's iota form ``[G,S]<=[dims]`` /
    ``[G,S]<=[dims]T(perm)`` (``arange(prod(dims))`` laid out as
    ``dims``, transposed by ``perm``, read as G groups of S).  ``{}``
    is one group of every device."""
    attr = attr.strip()
    m = _IOTA_GROUPS.match(attr)
    if m:
        g, size = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(p) for p in m.group(4).split(",")])
        return ids.reshape(g, size).tolist()
    groups = [[int(i) for i in grp.split(",") if i.strip()]
              for grp in re.findall(r"\{([\d,\s]*)\}", attr)]
    groups = [grp for grp in groups if grp]
    return groups or [list(range(num_devices))]


def _axes_spanned(groups, coords, axis_names) -> Tuple[str, ...]:
    """The mesh axes along which the devices of some group differ."""
    varied = set()
    for grp in groups:
        pos = [coords[i] for i in grp if i in coords]
        for a in range(len(axis_names)):
            if len({p[a] for p in pos}) > 1:
                varied.add(a)
    return tuple(axis_names[a] for a in sorted(varied))


def collective_inventory(hlo_text: str, mesh: Mesh,
                         device_order: Optional[Sequence[int]] = None):
    """Every collective instruction of a compiled program's HLO text:
    ``{instruction: {"kind", "bytes", "axes", "op_name"}}``.

    ``axes`` are the mesh axes its replica groups (a permute's source ->
    target pairs) span.  The groups' ids are the program's logical
    device ids, mapped to devices through ``device_order`` (the
    executable's device assignment; default the mesh's own order) and
    to mesh coordinates through ``mesh``.  Both halves of an
    asynchronous form (``all-reduce-start`` / ``-done``) are listed, the
    ``-done`` with its ``-start``'s axes; ``bytes`` is the collective's
    result (a ``-start`` the bytes of its ``-done``), a tuple's
    elements summed.  A device trace names each executed instruction
    the same way, so a collective's device time can be read by axis.
    """
    devs = list(mesh.devices.flat)
    order = (list(device_order) if device_order is not None
             else [d.id for d in devs])
    at = {d.id: np.unravel_index(i, mesh.devices.shape)
          for i, d in enumerate(devs)}
    coords = {logical: at[dev] for logical, dev in enumerate(order)
              if dev in at}
    axis_names = tuple(mesh.axis_names)
    out = {}
    starts = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        name, shape_text, opcode = m.groups()
        base = re.sub(r"-(start|done)$", "", opcode)
        if base not in _COLLECTIVE_OPS:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        rec = {"kind": opcode, "bytes": _hlo_bytes(shape_text), "axes": (),
               "op_name": op_name.group(1) if op_name else ""}
        if opcode.endswith("-done"):
            src = re.search(r"%([\w.\-]+)", line[m.end():])
            start = starts.get(src.group(1)) if src else None
            if start is not None:
                rec["axes"] = start["axes"]
                start["bytes"] = rec["bytes"]
            out[name] = rec
            continue
        groups = re.search(r"replica_groups=(\[[^=]*?\]<=\[[\d,]+\]"
                           r"(?:T\([\d,]+\))?|\{[\d,{}\s]*\})", line)
        pairs = re.search(r"source_target_pairs=(\{[\d,{}\s]*\})", line)
        if groups:
            spanned = replica_groups(groups.group(1), len(order))
        elif pairs:
            spanned = replica_groups(pairs.group(1), len(order))
        else:
            spanned = [list(range(len(order)))]
        rec["axes"] = _axes_spanned(spanned, coords, axis_names)
        out[name] = rec
        if opcode.endswith("-start"):
            starts[name] = rec
    return out


def collectives_by_axes(inventory) -> dict:
    """``{"model": {instruction: bytes}, "data": {...}}`` from a
    :func:`collective_inventory`: each instruction under the axes it
    spans, joined by ``+`` where it spans several (``""``: none)."""
    out: dict = {}
    for name, rec in inventory.items():
        out.setdefault("+".join(rec["axes"]), {})[name] = rec["bytes"]
    return out
