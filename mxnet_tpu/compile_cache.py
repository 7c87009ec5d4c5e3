"""Compile-management: persistent program cache + bucket canonicalization.

Kill the cold start.  XLA takes tens of seconds to compile a train
program whose steady-state step takes tens of milliseconds (an earlier
round recorded 41-61 s against ~24-110 ms); on a preemptible fleet
(PR 3's auto-resume restarts often) compilation is the dominant
wall-clock tax, and ``BucketingModule`` multiplies it by one
shape-specialized program per bucket.  Three levers live here:

* :class:`ProgramCache` — an in-process LRU over compiled XLA
  executables with an opt-in on-disk layer
  (``jax.experimental.serialize_executable``), keyed by
  :func:`program_key` (graph fingerprint, avals, shardings, donation
  set, mesh, backend, jax/jaxlib version).  A restarted trainer
  re-attaches to yesterday's programs in milliseconds.
* :func:`enable_persistent_cache` — turns on jax's own HLO-keyed XLA
  cache, so even programs that bypass our keyed store (tracing through
  plain ``jax.jit``) skip the XLA backend compile on re-run.  It lives
  where ``JAX_COMPILATION_CACHE_DIR`` says when that is set, else under
  the same root (``<dir>/xla``); the chip entry points (``chip_smoke.py``,
  ``bench.py``) default it to ``<checkout>/.jax_cache``.
* :class:`BucketPolicy` / :func:`plan_shape_buckets` — geometric
  shape-bucket canonicalization: dozens of dynamic sequence lengths
  round up into ~4-8 padded buckets, collapsing per-length programs.
  ``BucketingModule`` consumes the policy at ``switch_bucket`` time;
  the io pipeline pads batches into the chosen bucket
  (:func:`mxnet_tpu.io.pad_batch_to_bucket`).

Env knobs (see docs/env_vars.md):

* ``MXNET_TPU_CACHE_DIR`` — enables the on-disk layer (and jax's
  persistent cache under ``<dir>/xla``, unless
  ``JAX_COMPILATION_CACHE_DIR`` already places it) at first use.
* ``MXNET_TPU_CACHE=0`` — disables all program caching (memory too).
* ``MXNET_TPU_CACHE_MAX_ENTRIES`` — in-process LRU capacity (default 64).
* ``MXNET_TPU_BUCKET_POLICY`` — default bucket ladder as
  ``min:factor:round`` (e.g. ``16:2.0:16``).
* ``MXNET_TPU_MAX_BUCKETS`` — runaway-recompilation warning threshold.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

from .base import MXNetError

__all__ = ["ProgramCache", "CacheKey", "AotProgram", "program_key",
           "describe_avals", "mesh_fingerprint", "get_cache", "configure",
           "enable_persistent_cache", "BucketPolicy", "plan_shape_buckets",
           "bucket_for", "pad_to_bucket"]

_log = logging.getLogger(__name__)

ENV_CACHE_DIR = "MXNET_TPU_CACHE_DIR"
ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
ENV_CACHE = "MXNET_TPU_CACHE"
ENV_CACHE_MAX_ENTRIES = "MXNET_TPU_CACHE_MAX_ENTRIES"
ENV_BUCKET_POLICY = "MXNET_TPU_BUCKET_POLICY"
ENV_MAX_BUCKETS = "MXNET_TPU_MAX_BUCKETS"


# ---------------------------------------------------------------------------
# Cache keys
# ---------------------------------------------------------------------------

def _versions() -> str:
    try:
        import jaxlib
        jl = getattr(jaxlib, "__version__", "?")
    except Exception:
        jl = "?"
    return f"jax={jax.__version__};jaxlib={jl}"


def describe_avals(tree) -> str:
    """Canonical string for a pytree of array-likes: per leaf
    ``(path, shape, dtype, sharding)``.  Shardings matter — the same
    jaxpr partitioned differently is a different executable."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    parts = []
    for i, leaf in enumerate(leaves):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        sh = getattr(leaf, "sharding", None)
        parts.append(f"{i}:{shape}:{dtype}:{sh}")
    return f"{treedef}|" + ";".join(parts)


def mesh_fingerprint(mesh) -> str:
    """Mesh identity for the key: axis names/sizes + device kinds + ids.
    Two meshes with the same shape over different chips compile to
    different (and non-interchangeable) executables."""
    if mesh is None:
        return "mesh=None"
    devs = list(np.asarray(mesh.devices).flat)
    kinds = sorted({getattr(d, "device_kind", "?") for d in devs})
    ids = tuple(getattr(d, "id", -1) for d in devs)
    return (f"axes={tuple(mesh.axis_names)};shape={tuple(mesh.devices.shape)};"
            f"kinds={kinds};ids={ids}")


class CacheKey:
    """Hashable identity of one compiled program.  ``digest`` is the
    sha256 over every field; ``fields`` stay readable so the inspect
    tool can show what a key was made of."""

    def __init__(self, fields: Dict[str, str]):
        self.fields = dict(fields)
        h = hashlib.sha256()
        for k in sorted(self.fields):
            h.update(k.encode())
            h.update(b"\x00")
            h.update(str(self.fields[k]).encode())
            h.update(b"\x01")
        self.digest = h.hexdigest()

    def __hash__(self):
        return hash(self.digest)

    def __eq__(self, other):
        return isinstance(other, CacheKey) and other.digest == self.digest

    def __repr__(self):
        return f"CacheKey({self.digest[:12]})"

    def describe(self) -> Dict[str, str]:
        return dict(self.fields)


def program_key(fingerprint: str, avals=None, donate: Sequence[int] = (),
                mesh=None, backend: Optional[str] = None,
                extra: Optional[Dict[str, Any]] = None) -> CacheKey:
    """Build the :class:`CacheKey` for one program.

    ``fingerprint`` is the graph identity (use
    :func:`mxnet_tpu.graph_eval.graph_fingerprint` for symbols);
    ``avals`` a pytree of the call arguments (arrays or
    ``ShapeDtypeStruct``; shardings are read off the leaves); ``donate``
    the donated argnums.  Backend defaults to jax's default backend.
    """
    fields = {
        "fingerprint": str(fingerprint),
        "avals": describe_avals(avals) if avals is not None else "",
        "donate": str(tuple(donate)),
        "mesh": mesh_fingerprint(mesh),
        "backend": backend or jax.default_backend(),
        "versions": _versions(),
    }
    for k, v in (extra or {}).items():
        fields[f"x:{k}"] = str(v)
    return CacheKey(fields)


# ---------------------------------------------------------------------------
# Program cache
# ---------------------------------------------------------------------------

def _atomic_write(path: str, payload: bytes) -> None:
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    with open(tmp, "wb") as f:
        f.write(payload)
    os.replace(tmp, path)


class ProgramCache:
    """LRU of compiled executables with an optional on-disk layer.

    Memory entries hold live ``jax.stages.Compiled`` objects; disk
    entries hold ``serialize_executable`` payloads written atomically
    (tmp + ``os.replace``) next to a JSON sidecar with the key fields —
    the unit the inspect tool lists/evicts.  Lookup order: memory ->
    disk -> compile.  Every resolution is recorded in ``stats`` and as a
    profiler compile event (:func:`mxnet_tpu.profiler.record_compile`).
    """

    def __init__(self, cache_dir: Optional[str] = None,
                 max_entries: int = 64, enabled: bool = True):
        self.cache_dir = cache_dir
        self.max_entries = max(1, int(max_entries))
        self.enabled = enabled
        self._mem: "OrderedDict[str, Any]" = OrderedDict()
        self._lock = threading.RLock()
        self._disk_broken = False
        self.stats = {"memory_hits": 0, "disk_hits": 0, "misses": 0,
                      "puts": 0, "disk_errors": 0}

    # -- paths ----------------------------------------------------------

    def _progdir(self) -> Optional[str]:
        if self.cache_dir is None or self._disk_broken:
            return None
        d = os.path.join(self.cache_dir, "programs")
        try:
            os.makedirs(d, exist_ok=True)
        except OSError as e:
            _log.warning("program cache dir %s unusable (%s); disk layer off",
                         d, e)
            self._disk_broken = True
            return None
        return d

    def _paths(self, digest: str) -> Tuple[Optional[str], Optional[str]]:
        d = self._progdir()
        if d is None:
            return None, None
        return os.path.join(d, f"{digest}.bin"), os.path.join(d, f"{digest}.json")

    # -- core -----------------------------------------------------------

    def lookup(self, key: CacheKey):
        """Memory then disk; returns a callable Compiled or None.
        Remembers which layer answered in ``_last_source``."""
        if not self.enabled:
            return None
        with self._lock:
            ent = self._mem.get(key.digest)
            if ent is not None:
                self._mem.move_to_end(key.digest)
                self._bump_stat("memory_hits")
                self._last_source = "memory"
                return ent
        compiled = self._disk_load(key)
        if compiled is not None:
            self._mem_put(key.digest, compiled)
            self._bump_stat("disk_hits")
        return compiled

    def put(self, key: CacheKey, compiled, label: str = "",
            compile_seconds: float = 0.0) -> None:
        if not self.enabled:
            return
        self._mem_put(key.digest, compiled)
        self._bump_stat("puts")
        self._disk_store(key, compiled, label, compile_seconds)

    def get_or_compile(self, key: CacheKey, compile_fn: Callable[[], Any],
                       label: str = "") -> Tuple[Any, Dict[str, Any]]:
        """Resolve ``key`` -> compiled program.  ``compile_fn`` runs only
        on a full miss.  Returns ``(compiled, info)`` with
        ``info["source"]`` in memory/disk/compile and ``info["seconds"]``
        the time that resolution took."""
        t0 = time.perf_counter()
        compiled = self.lookup(key)
        if compiled is not None:
            info = {"source": self._last_source, "seconds":
                    time.perf_counter() - t0, "digest": key.digest}
            self._record(label, info)
            return compiled, info
        from . import telemetry
        with telemetry.span("compile.build", label=label or "program",
                            digest=key.digest[:12]):
            compiled = compile_fn()
        seconds = time.perf_counter() - t0
        self._bump_stat("misses")
        self.put(key, compiled, label=label, compile_seconds=seconds)
        info = {"source": "compile", "seconds": seconds,
                "digest": key.digest}
        self._record(label, info)
        return compiled, info

    def _bump_stat(self, key: str) -> None:
        """Increment a cache stat and its unified-telemetry mirror
        (``compile_cache.<stat>`` counters, docs/observability.md)."""
        self.stats[key] += 1
        from . import telemetry
        telemetry.counter(f"compile_cache.{key}").inc()

    def _record(self, label: str, info: Dict[str, Any]) -> None:
        from . import profiler
        profiler.record_compile(label or "program", info["seconds"],
                                source=info["source"],
                                digest=info["digest"])

    def _mem_put(self, digest: str, compiled) -> None:
        with self._lock:
            self._mem[digest] = compiled
            self._mem.move_to_end(digest)
            while len(self._mem) > self.max_entries:
                self._mem.popitem(last=False)

    # -- disk layer ------------------------------------------------------

    def _disk_load(self, key: CacheKey):
        self._last_source = "disk"
        binp, _ = self._paths(key.digest)
        if binp is None or not os.path.exists(binp):
            return None
        try:
            from jax.experimental import serialize_executable
            with open(binp, "rb") as f:
                payload, in_tree, out_tree, device_ids = pickle.load(f)
            # load onto the devices the program was compiled for — the
            # default is EVERY local device, which mis-shards a program
            # built for one chip (or a sub-mesh) of a multi-chip host
            by_id = {d.id: d for d in jax.devices()}
            return serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except Exception as e:
            self._bump_stat("disk_errors")
            _log.warning("program cache: failed to load %s (%s) — treating "
                         "as a miss", key.digest[:12], e)
            return None

    def _disk_store(self, key: CacheKey, compiled, label: str,
                    compile_seconds: float) -> None:
        binp, metap = self._paths(key.digest)
        if binp is None:
            return
        try:
            from jax.experimental import serialize_executable
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            _atomic_write(binp, pickle.dumps(
                (payload, in_tree, out_tree, device_ids)))
            import json
            meta = {"digest": key.digest, "label": label,
                    "compile_seconds": round(compile_seconds, 4),
                    "created": time.time(),
                    "payload_bytes": os.path.getsize(binp),
                    "fields": key.describe()}
            _atomic_write(metap, json.dumps(meta, indent=1).encode())
        except Exception as e:
            self._bump_stat("disk_errors")
            _log.debug("program cache: could not persist %s (%s)",
                       key.digest[:12], e)

    # overwritten per lookup so get_or_compile can report memory vs disk
    _last_source = "disk"

    # -- maintenance -----------------------------------------------------

    def clear_memory(self) -> None:
        """Drop the in-process LRU (disk entries survive — the warm
        restart simulation bench --compile uses)."""
        with self._lock:
            self._mem.clear()

    def clear(self) -> None:
        self.clear_memory()
        d = self._progdir()
        if d is None:
            return
        for name in os.listdir(d):
            if name.endswith((".bin", ".json")):
                try:
                    os.remove(os.path.join(d, name))
                except OSError:
                    pass

    def entries(self) -> List[Dict[str, Any]]:
        """Disk-entry metadata (one dict per persisted program)."""
        d = self._progdir()
        out = []
        if d is None:
            return out
        import json
        for name in sorted(os.listdir(d)):
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(d, name)) as f:
                    out.append(json.load(f))
            except Exception:
                continue
        return out

    def evict(self, digest: str) -> bool:
        """Remove one disk entry (and its memory copy) by digest prefix."""
        removed = False
        with self._lock:
            for full in [k for k in self._mem if k.startswith(digest)]:
                del self._mem[full]
                removed = True
        d = self._progdir()
        if d is not None:
            for name in os.listdir(d):
                if name.startswith(digest) and name.endswith((".bin", ".json")):
                    try:
                        os.remove(os.path.join(d, name))
                        removed = True
                    except OSError:
                        pass
        return removed


class AotProgram:
    """An AOT-compiled executable with a jit fallback, shared by
    :meth:`Executor.warmup` and the serving engine.

    An aval mismatch (the call's shapes/dtypes differ from what the
    program was lowered for) raises BEFORE the executable consumes
    donated buffers, so re-dispatching through ``jit_fn`` is safe — but
    it retraces and recompiles, so every fallback is logged and counted
    (``compile_cache.aot_fallbacks`` plus ``stats["fallbacks"]`` when the
    owner passes its counter): a warm path expects zero.
    """

    __slots__ = ("_compiled", "_jit_fn", "label", "_stats")

    def __init__(self, compiled, jit_fn, label: str = "program",
                 stats: Optional[Dict[str, int]] = None):
        self._compiled = compiled
        self._jit_fn = jit_fn
        self.label = label
        self._stats = stats

    @property
    def compiled(self):
        """The ``jax.stages.Compiled`` behind this program."""
        return self._compiled

    def __call__(self, *args):
        try:
            return self._compiled(*args)
        except (TypeError, ValueError) as e:
            if self._stats is not None:
                self._stats["fallbacks"] += 1
            from . import telemetry
            telemetry.counter("compile_cache.aot_fallbacks").inc()
            _log.warning("AOT program %r does not match this call (%s); "
                         "falling back to jit", self.label, e)
            return self._jit_fn(*args)


# ---------------------------------------------------------------------------
# Global cache singleton + jax persistent-cache wiring
# ---------------------------------------------------------------------------

_global: Dict[str, Any] = {"cache": None}
_glock = threading.Lock()


# ---------------------------------------------------------------------------
# Lowering observers
# ---------------------------------------------------------------------------
#
# The static auditor (mxnet_tpu.analysis) taps the compile path here:
# every program the framework traces on its way INTO the cache is
# offered to registered observers as a ``jax.stages.Traced``, so
# ``analysis.audit_on_compile()`` inspects exactly what gets compiled —
# no second trace, no drift between the audited and the shipped
# program.  Observers fire on cache misses only (a hit dispatches a
# stored executable; there is no fresh lowering to look at).

_lowering_observers: List[Callable[[str, Any], None]] = []


def add_lowering_observer(fn: Callable[[str, Any], None]) -> None:
    """Register ``fn(label, traced)`` to be called for every program
    traced for compilation while registered."""
    with _glock:
        if fn not in _lowering_observers:
            _lowering_observers.append(fn)


def remove_lowering_observer(fn: Callable[[str, Any], None]) -> None:
    with _glock:
        if fn in _lowering_observers:
            _lowering_observers.remove(fn)


def notify_lowering(label: str, traced: Any) -> None:
    """Offer a freshly traced program to observers.  Observer errors are
    logged, never raised — an analysis bug must not break compilation."""
    with _glock:
        observers = list(_lowering_observers)
    if not observers:
        return
    from . import telemetry
    with telemetry.span("compile.lowering", label=label,
                        observers=len(observers)):
        for fn in observers:
            try:
                fn(label, traced)
            except Exception:
                _log.exception("lowering observer %r failed on %r",
                               fn, label)


def enable_persistent_cache(default_dir: str) -> str:
    """Turn on jax's own HLO-keyed compilation cache and return the
    directory it uses — the ONE place this codebase decides where that
    cache lives.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself
    and nothing here (or anywhere else) assigns another directory.
    Otherwise the cache goes to ``default_dir``, which callers must
    derive from something stable (the cache root, the checkout) and never
    from a temp name, pid or time — the path is part of what a later run
    has to find again.  Size/time thresholds are dropped so every
    program persists (a restart still pays the small ones without this).
    """
    cache_dir = os.environ.get(ENV_JAX_CACHE_DIR)
    if not cache_dir:
        cache_dir = default_dir
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def _wire_jax_cache(cache_root: str) -> None:
    try:
        enable_persistent_cache(os.path.join(cache_root, "xla"))
    except OSError as e:
        _log.warning("could not enable jax persistent cache: %s", e)


def configure(cache_dir: Optional[str] = None,
              max_entries: Optional[int] = None,
              enabled: Optional[bool] = None,
              wire_jax_cache: bool = True) -> ProgramCache:
    """(Re)build the global :class:`ProgramCache`.  With ``cache_dir``
    the disk layer turns on and (unless ``wire_jax_cache=False``) jax's
    persistent cache is enabled too — under the same root, or wherever
    ``JAX_COMPILATION_CACHE_DIR`` already places it."""
    with _glock:
        cur = _global["cache"]
        cache = ProgramCache(
            cache_dir=cache_dir,
            max_entries=(max_entries if max_entries is not None
                         else (cur.max_entries if cur else 64)),
            enabled=(enabled if enabled is not None else True))
        if cache_dir and wire_jax_cache and cache.enabled:
            _wire_jax_cache(cache_dir)
        _global["cache"] = cache
        return cache


def get_cache() -> ProgramCache:
    """Global cache, auto-configured from the environment on first use."""
    with _glock:
        if _global["cache"] is None:
            enabled = os.environ.get(ENV_CACHE, "1") != "0"
            cache_dir = os.environ.get(ENV_CACHE_DIR) or None
            max_entries = int(os.environ.get(ENV_CACHE_MAX_ENTRIES, "64"))
            cache = ProgramCache(cache_dir=cache_dir if enabled else None,
                                 max_entries=max_entries, enabled=enabled)
            if enabled and cache_dir:
                _wire_jax_cache(cache_dir)
            _global["cache"] = cache
        return _global["cache"]


# ---------------------------------------------------------------------------
# Bucket-shape canonicalization
# ---------------------------------------------------------------------------

def _round_up(x: int, to: int) -> int:
    return -(-int(x) // int(to)) * int(to)


class BucketPolicy:
    """Geometric padded-bucket ladder for dynamic shapes.

    ``bucket_of(length)`` is CLOSED FORM and data-independent: the
    smallest ladder value ``>= length`` where the ladder starts at
    ``min_bucket`` and multiplies by ``factor`` (each rung rounded up to
    a multiple of ``round_to``).  Deterministic canonicalization means a
    stream of lengths never re-plans (and never re-compiles) as new
    lengths show up.  Pass ``buckets=[...]`` to pin an explicit set
    instead (e.g. the output of :func:`plan_shape_buckets`).

    ``round_to`` should match the attention block size when bitwise
    padded-loss parity matters: blockwise attention with a fixed block
    processes padded tail blocks as exact no-ops (see docs/perf.md r7).

    ``axis`` is the padded dimension of the batch arrays (1 for
    ``[batch, seq]`` token ids); ``pad_value``/``label_pad`` fill data /
    label padding (point ``label_pad`` at the loss head's
    ``ignore_label`` so padded positions drop out of loss and metrics).
    """

    def __init__(self, min_bucket: int = 16, factor: float = 2.0,
                 max_buckets: int = 8, round_to: int = 16, axis: int = 1,
                 pad_value=0, label_pad=None,
                 buckets: Optional[Sequence[int]] = None):
        if factor <= 1.0:
            raise MXNetError(f"BucketPolicy factor must be > 1, got {factor}")
        if min_bucket < 1 or round_to < 1:
            raise MXNetError("BucketPolicy min_bucket/round_to must be >= 1")
        self.min_bucket = int(min_bucket)
        self.factor = float(factor)
        self.max_buckets = int(max_buckets)
        self.round_to = int(round_to)
        self.axis = int(axis)
        self.pad_value = pad_value
        self.label_pad = label_pad if label_pad is not None else pad_value
        self.buckets = sorted(int(b) for b in buckets) if buckets else None

    @classmethod
    def fixed(cls, size: int) -> "BucketPolicy":
        """A single-rung policy: every length pads to ``size`` (longer
        lengths raise).  The chunked-prefill serve path uses this to
        collapse the geometric prompt ladder to one chunk shape — one
        warm program instead of one per rung."""
        if size < 1:
            raise MXNetError(f"BucketPolicy.fixed: size must be >= 1, "
                             f"got {size}")
        return cls(min_bucket=int(size), round_to=1, buckets=[int(size)])

    @classmethod
    def from_env(cls, **kwargs) -> "BucketPolicy":
        """Build from ``MXNET_TPU_BUCKET_POLICY=min:factor:round`` (+
        ``MXNET_TPU_MAX_BUCKETS``); explicit kwargs win."""
        spec = os.environ.get(ENV_BUCKET_POLICY, "")
        if spec:
            parts = spec.split(":")
            try:
                if len(parts) >= 1 and parts[0]:
                    kwargs.setdefault("min_bucket", int(parts[0]))
                if len(parts) >= 2 and parts[1]:
                    kwargs.setdefault("factor", float(parts[1]))
                if len(parts) >= 3 and parts[2]:
                    kwargs.setdefault("round_to", int(parts[2]))
            except ValueError:
                raise MXNetError(
                    f"bad {ENV_BUCKET_POLICY}={spec!r} (want min:factor:round)")
        mb = os.environ.get(ENV_MAX_BUCKETS)
        if mb:
            kwargs.setdefault("max_buckets", int(mb))
        return cls(**kwargs)

    def _ladder(self, upto: int) -> List[int]:
        rungs = [_round_up(self.min_bucket, self.round_to)]
        while rungs[-1] < upto:
            nxt = _round_up(max(rungs[-1] + 1,
                                int(rungs[-1] * self.factor)), self.round_to)
            rungs.append(nxt)
        return rungs

    def bucket_of(self, length: int) -> int:
        length = int(length)
        if length < 1:
            raise MXNetError(f"bucket_of: length must be >= 1, got {length}")
        if self.buckets is not None:
            return bucket_for(length, self.buckets)
        return self._ladder(length)[-1]

    def __repr__(self):
        if self.buckets is not None:
            return f"BucketPolicy(buckets={self.buckets})"
        return (f"BucketPolicy(min={self.min_bucket}, factor={self.factor}, "
                f"round_to={self.round_to}, max_buckets={self.max_buckets})")


def bucket_for(length: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= length from an explicit sorted set."""
    for b in sorted(buckets):
        if b >= length:
            return int(b)
    raise MXNetError(
        f"length {length} exceeds the largest bucket {max(buckets)}")


def plan_shape_buckets(lengths: Sequence[int],
                       policy: Optional[BucketPolicy] = None) -> List[int]:
    """Round ``lengths`` onto the policy's geometric ladder and return
    the sorted bucket set actually used.  If the set exceeds
    ``policy.max_buckets`` the factor widens geometrically until it
    fits, so dozens of distinct lengths always collapse into a small
    program set (pad waste grows instead — the documented trade)."""
    if policy is None:
        policy = BucketPolicy.from_env()
    if not lengths:
        return []
    pol = policy
    for _ in range(32):
        buckets = sorted({pol.bucket_of(l) for l in lengths})
        if len(buckets) <= pol.max_buckets:
            if pol is not policy:
                _log.warning(
                    "plan_shape_buckets: widened factor %.2f -> %.2f to fit "
                    "%d lengths into %d buckets", policy.factor, pol.factor,
                    len(set(lengths)), pol.max_buckets)
            return buckets
        pol = BucketPolicy(min_bucket=pol.min_bucket,
                           factor=pol.factor * 1.5,
                           max_buckets=pol.max_buckets,
                           round_to=pol.round_to, axis=pol.axis,
                           pad_value=pol.pad_value,
                           label_pad=pol.label_pad)
    return buckets  # pragma: no cover — factor growth always terminates


def pad_to_bucket(arr, bucket: int, axis: int = 1, pad_value=0):
    """Pad one array along ``axis`` up to ``bucket`` (host numpy in,
    host numpy out; no-op when already at the bucket size)."""
    a = np.asarray(arr)
    if axis >= a.ndim:
        raise MXNetError(
            f"pad_to_bucket: axis {axis} out of range for shape {a.shape}")
    cur = a.shape[axis]
    if cur > bucket:
        raise MXNetError(
            f"pad_to_bucket: length {cur} exceeds bucket {bucket}")
    if cur == bucket:
        return a
    cfg = [(0, 0)] * a.ndim
    cfg[axis] = (0, bucket - cur)
    return np.pad(a, cfg, constant_values=pad_value)
