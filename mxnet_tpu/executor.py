"""Executor: compiled graph execution.

TPU-native rebuild of the reference GraphExecutor
(``src/symbol/graph_executor.{h,cc}``, ``python/mxnet/executor.py``).
Design mapping (SURVEY.md §7):

* ``Bind`` in the reference builds a StaticGraph, plans pooled memory
  (``graph_memory_allocator.h``), creates per-node engine ops and pushes them
  per batch (``RunOps``, ``graph_executor.cc:833-862``).  Here ``bind``
  traces the whole symbol into ONE jitted function — XLA buffer assignment
  replaces the memory planner, XLA fusion replaces bulk-exec, and async
  dispatch replaces the dependency engine.
* ``grad_req`` write/add/null semantics (``OpReqType``, ``operator.h:23-36``)
  are applied when writing gradients back into the bound ``args_grad``
  arrays.
* Auxiliary states (BatchNorm moving stats) are extra inputs/outputs of the
  compiled function; after a training forward the executor writes the
  updates back into the bound aux NDArrays — preserving the reference's
  mutate-in-forward semantics (``operator.h`` aux TBlobs).
* The monitor hook (``graph_executor.cc:890-905``) is realized by a second
  compiled function that also returns every internal node output.
* Gradient mirroring (``MXNET_BACKWARD_DO_MIRROR``, ``static_graph.cc:404``)
  maps to ``jax.checkpoint`` wrapped around nodes carrying the
  ``__force_mirroring__`` attr.

The train-step call pattern ``forward(is_train=True); backward()`` costs one
compiled execution: a training ``forward`` only snapshots inputs; outputs
are computed by the fused forward+backward when ``backward()`` runs (or by
the forward-only program if outputs are read first).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError
from .context import Context
from .ndarray import NDArray
from .ops.registry import OpContext

__all__ = ["Executor"]


def _as_req_dict(grad_req, arg_names: List[str]) -> Dict[str, str]:
    if isinstance(grad_req, str):
        return {n: grad_req for n in arg_names}
    if isinstance(grad_req, (list, tuple)):
        return dict(zip(arg_names, grad_req))
    if isinstance(grad_req, dict):
        return {n: grad_req.get(n, "null") for n in arg_names}
    raise MXNetError(f"invalid grad_req {grad_req!r}")


class Executor:
    """Compiled executor for one Symbol on one context."""

    def __init__(self, symbol, ctx: Context, args, args_grad=None,
                 grad_req="write", aux_states=None, group2ctx=None,
                 shared_exec: Optional["Executor"] = None):
        self._symbol = symbol
        self._ctx = ctx
        self._group2ctx = dict(group2ctx or {})
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()

        # --- bind argument arrays (list or dict, reference executor.py) ---
        if isinstance(args, dict):
            missing = [n for n in arg_names if n not in args]
            if missing:
                raise MXNetError(f"bind: missing arguments {missing}")
            self._arg_dict = {n: args[n] for n in arg_names}
        else:
            if len(args) != len(arg_names):
                raise MXNetError(
                    f"bind: expected {len(arg_names)} args, got {len(args)}")
            self._arg_dict = dict(zip(arg_names, args))

        if args_grad is None:
            self._grad_dict: Dict[str, NDArray] = {}
        elif isinstance(args_grad, dict):
            self._grad_dict = dict(args_grad)
        else:
            self._grad_dict = {n: g for n, g in zip(arg_names, args_grad)
                               if g is not None}

        self._req = _as_req_dict(grad_req, arg_names)
        for n in arg_names:
            if self._req.get(n, "null") != "null" and n not in self._grad_dict:
                self._req[n] = "null"
        self._grad_names = [n for n in arg_names
                            if self._req.get(n, "null") != "null"]

        if aux_states is None:
            aux_states = {}
        if isinstance(aux_states, dict):
            self._aux_dict = {n: aux_states[n] for n in aux_names} \
                if aux_names else {}
            missing = [n for n in aux_names if n not in aux_states]
        else:
            self._aux_dict = dict(zip(aux_names, aux_states))
            missing = aux_names[len(aux_states):]
        if missing:
            raise MXNetError(f"bind: missing aux states {missing}")

        self._arg_names = arg_names
        self._aux_names = aux_names
        self._outputs: Optional[List[NDArray]] = None
        self._pending_train = False
        self._monitor_cb: Optional[Callable[[str, NDArray], None]] = None

        # compiled programs, built lazily (shared_exec shares the cache —
        # the analog of bucketing executors sharing memory,
        # executor_manager.py:288, module/executor_group.py:307)
        if shared_exec is not None:
            self._cache = shared_exec._cache
        else:
            self._cache: Dict[str, Any] = {}

        self._topo = symbol._topo()
        self._node_index = {id(n): i for i, n in enumerate(self._topo)}

        # --- model parallelism: ctx_group -> device placement -------------
        # (reference AssignContext, graph_executor.cc:390+; dead-kwarg no
        # more).  Ops carrying a __ctx_group__ attr run on group2ctx[group];
        # variables are placed with their first consumer; execution goes
        # eager (per-op async dispatch ≈ the reference engine) with
        # transfers at group boundaries.
        self._placement: Optional[Dict[str, jax.Device]] = None
        if self._group2ctx:
            placement: Dict[str, jax.Device] = {}
            default_dev = ctx.jax_device
            for node in self._topo:
                if node.is_variable:
                    continue
                group = node.anno_attrs().get("ctx_group")
                gctx = self._group2ctx.get(group) if group else None
                placement[node.name] = (Context(gctx).jax_device if gctx
                                        else default_dev)
            # variables adopt the first consumer's device
            var_dev: Dict[str, jax.Device] = {}
            for node in self._topo:
                if node.is_variable:
                    continue
                for src, _ in node.inputs:
                    if src.is_variable and src.name not in var_dev:
                        var_dev[src.name] = placement[node.name]
            self._placement = placement
            for name_, arr in self._arg_dict.items():
                dev = var_dev.get(name_)
                if dev is not None:
                    arr._migrate(dev)
            for name_, arr in self._grad_dict.items():
                dev = var_dev.get(name_)
                if dev is not None:
                    arr._migrate(dev)

    # ------------------------------------------------------------------
    # Graph evaluation (traced under jit)
    # ------------------------------------------------------------------

    def _eval(self, arg_vals: Dict[str, jax.Array], aux_vals: Dict[str, jax.Array],
              rng, is_train: bool, want_internals: bool = False):
        from .graph_eval import eval_symbol
        return eval_symbol(self._symbol, arg_vals, aux_vals, rng, is_train,
                           want_internals=want_internals, topo=self._topo,
                           placement=self._placement)

    # compiled program builders ----------------------------------------

    def _prog(self, key: str, build):
        """Fetch/compile a cached program.  The cache may be shared across
        executors (bucketing), so entries are keyed by symbol identity and
        pin the symbol — a shared bind over a *different* symbol compiles
        its own program instead of silently reusing the wrong graph."""
        full_key = (id(self._symbol), key)
        ent = self._cache.get(full_key)
        if ent is None or ent[0] is not self._symbol:
            fn = build()
            # group-placed graphs run eagerly: per-op async dispatch with
            # cross-device transfers, like the reference engine schedule
            ent = (self._symbol, fn if self._placement else jax.jit(fn))
            self._cache[full_key] = ent
        return ent[1]

    def _get_fwd(self, is_train: bool):
        def build():
            def run(arg_vals, aux_vals, rng):
                return self._eval(arg_vals, aux_vals, rng, is_train)
            return run
        return self._prog(f"fwd_{is_train}", build)

    def _get_fwd_internals(self, is_train: bool):
        def build():
            def run(arg_vals, aux_vals, rng):
                return self._eval(arg_vals, aux_vals, rng, is_train,
                                  want_internals=True)
            return run
        return self._prog(f"fwd_int_{is_train}", build)

    def _get_fb(self):
        def build():
            grad_names = list(self._grad_names)

            def run(arg_vals, aux_vals, rng, out_grads):
                wrt = {n: arg_vals[n] for n in grad_names}
                rest = {n: v for n, v in arg_vals.items() if n not in wrt}

                def f(wrt_vals):
                    merged = dict(rest)
                    merged.update(wrt_vals)
                    heads, auxu = self._eval(merged, aux_vals, rng, True)
                    return heads, auxu

                heads, vjp_fn, auxu = jax.vjp(f, wrt, has_aux=True)
                cot = tuple(
                    g.astype(h.dtype) if g.dtype != h.dtype else g
                    for g, h in zip(out_grads, heads))
                (grads,) = vjp_fn(cot)
                return heads, grads, auxu

            return run
        return self._prog("fb_" + ",".join(self._grad_names), build)

    # ------------------------------------------------------------------
    # AOT warmup (compile_cache integration)
    # ------------------------------------------------------------------

    def program_cache_size(self) -> int:
        """Number of compiled programs in this executor's (possibly
        shared) cache — the bucketing reuse gauge."""
        return len(self._cache)

    def _fingerprint(self) -> str:
        if getattr(self, "_graph_fp", None) is None:
            from .graph_eval import graph_fingerprint
            self._graph_fp = graph_fingerprint(self._symbol, topo=self._topo)
        return self._graph_fp

    def warmup(self, fb: Optional[bool] = None) -> List[Dict[str, Any]]:
        """Eagerly compile this executor's programs through the global
        :class:`~mxnet_tpu.compile_cache.ProgramCache` instead of waiting
        for the first batch: the inference forward, and (when gradients
        are bound, or ``fb=True``) the fused forward+backward.

        Resolved executables are installed into the program cache wrapped
        in :class:`~mxnet_tpu.compile_cache.AotProgram` — subsequent
        ``forward``/``backward`` calls dispatch them directly, with a
        logged and counted jit fallback on a shape change.  Returns the
        per-program resolution info (``source``: memory/disk/compile,
        ``seconds``).  Eagerly-placed executors (``group2ctx``) have no
        compiled programs and return ``[]``.
        """
        if self._placement is not None:
            return []
        from . import compile_cache as cc
        sds = jax.ShapeDtypeStruct
        arg_avals = {n: sds(a.shape, jnp.dtype(a.dtype))
                     for n, a in self._arg_dict.items()}
        aux_avals = {n: sds(a.shape, jnp.dtype(a.dtype))
                     for n, a in self._aux_dict.items()}
        rng = self._next_rng()
        rng_aval = sds(rng.shape, rng.dtype)
        dev = str(self._ctx.jax_device)
        infos: List[Dict[str, Any]] = []
        cache = cc.get_cache()

        def warm(prog_key: str, jit_fn, in_args, extra):
            ckey = cc.program_key(self._fingerprint(), in_args,
                                  extra=dict(extra, device=dev))
            compiled, info = cache.get_or_compile(
                ckey, lambda: jit_fn.lower(*in_args).compile(),
                label=f"executor.{prog_key}")
            self._cache[(id(self._symbol), prog_key)] = (
                self._symbol, cc.AotProgram(compiled, jit_fn,
                                            f"executor.{prog_key}"))
            infos.append(dict(info, kind=prog_key))

        warm("fwd_False", self._get_fwd(False),
             (arg_avals, aux_avals, rng_aval), {"kind": "fwd_False"})
        if fb or (fb is None and self._grad_names):
            if not self._grad_names:
                raise MXNetError("warmup(fb=True) on an executor bound "
                                 "without gradient arrays")
            # training forwards dispatch the is_train=True program
            # (train-mode ops: dropout live, BN batch stats)
            warm("fwd_True", self._get_fwd(True),
                 (arg_avals, aux_avals, rng_aval), {"kind": "fwd_True"})
            out_grads = tuple(sds(s, jnp.float32)
                              for s in self._infer_head_shapes())
            warm("fb_" + ",".join(self._grad_names), self._get_fb(),
                 (arg_avals, aux_avals, rng_aval, out_grads),
                 {"kind": "fb", "grads": ",".join(self._grad_names)})
        return infos

    # ------------------------------------------------------------------
    # Public API (reference executor.py)
    # ------------------------------------------------------------------

    def _arg_values(self) -> Dict[str, jax.Array]:
        return {n: a.data for n, a in self._arg_dict.items()}

    def _aux_values(self) -> Dict[str, jax.Array]:
        return {n: a.data for n, a in self._aux_dict.items()}

    def _next_rng(self):
        from . import random as _random
        return _random._next_key()

    def forward(self, is_train: bool = False, **kwargs) -> List[NDArray]:
        for k, v in kwargs.items():
            if k not in self._arg_dict:
                raise MXNetError(f"forward: no argument named {k}")
            if isinstance(v, NDArray):
                self._arg_dict[k]._write(v.data)
            else:
                self._arg_dict[k]._write(jnp.asarray(v))
        self._frozen_args = self._arg_values()
        self._frozen_aux = self._aux_values()
        self._frozen_rng = self._next_rng()
        self._frozen_train = is_train
        self._outputs = None
        self._pending_train = bool(is_train)
        if self._monitor_cb is not None:
            heads, auxu, internals = self._get_fwd_internals(is_train)(
                self._frozen_args, self._frozen_aux, self._frozen_rng)
            self._set_outputs(heads, auxu if is_train else None)
            for name_, arr in internals.items():
                self._monitor_cb(name_, NDArray(arr, ctx=self._ctx))
        elif not is_train:
            heads, auxu = self._get_fwd(False)(
                self._frozen_args, self._frozen_aux, self._frozen_rng)
            self._set_outputs(heads, None)
        return self.outputs

    def _set_outputs(self, heads, aux_updates):
        self._outputs = [NDArray(h, ctx=self._ctx) for h in heads]
        self._pending_train = False
        if aux_updates:
            for name_, val in aux_updates.items():
                self._aux_dict[name_]._write(val)

    @property
    def outputs(self) -> List[NDArray]:
        if self._outputs is None:
            if not hasattr(self, "_frozen_args"):
                raise MXNetError("call forward() before reading outputs")
            heads, auxu = self._get_fwd(self._frozen_train)(
                self._frozen_args, self._frozen_aux, self._frozen_rng)
            self._set_outputs(heads, auxu if self._frozen_train else None)
        return self._outputs

    def backward(self, out_grads=None) -> None:
        """Run the fused forward+backward compiled program and write
        gradients into ``args_grad`` honoring grad_req write/add/null."""
        if not hasattr(self, "_frozen_args"):
            raise MXNetError("call forward(is_train=True) before backward()")
        if not self._grad_names:
            raise MXNetError("backward called on an executor bound without "
                             "gradient arrays (grad_req=null)")
        n_out = len(self._symbol._heads)
        if out_grads is None:
            # default head gradient of ones — loss heads ignore it anyway
            if self._outputs is not None:
                out_grads = [jnp.ones(o.shape, dtype=o.dtype) for o in self._outputs]
            else:
                out_shapes = self._infer_head_shapes()
                out_grads = [jnp.ones(s, dtype=jnp.float32) for s in out_shapes]
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            out_grads = [g.data if isinstance(g, NDArray) else jnp.asarray(g)
                         for g in out_grads]
        if len(out_grads) != n_out:
            raise MXNetError(f"backward: need {n_out} head grads, got {len(out_grads)}")
        heads, grads, auxu = self._get_fb()(
            self._frozen_args, self._frozen_aux, self._frozen_rng,
            tuple(out_grads))
        self._set_outputs(heads, auxu)
        for name_ in self._grad_names:
            req = self._req[name_]
            g = grads[name_]
            dst = self._grad_dict[name_]
            if req == "add":
                dst._write(dst.data + g.astype(dst.dtype))
            else:  # write
                dst._write(g.astype(dst.dtype))

    def _infer_head_shapes(self):
        # cached per arg-shape signature: default head grads must not pay
        # full graph shape inference every backward() in the hot loop
        sig = tuple(tuple(a.shape) for a in self._arg_dict.values())
        if getattr(self, "_head_shape_sig", None) != sig:
            shapes = {n: tuple(a.shape) for n, a in self._arg_dict.items()}
            _, out_shapes, _ = self._symbol.infer_shape(**shapes)
            self._head_shape_sig = sig
            self._head_shapes = out_shapes
        return self._head_shapes

    # dict/array accessors (reference executor.py properties) -----------

    @property
    def arg_dict(self) -> Dict[str, NDArray]:
        return self._arg_dict

    @property
    def grad_dict(self) -> Dict[str, NDArray]:
        return self._grad_dict

    @property
    def aux_dict(self) -> Dict[str, NDArray]:
        return self._aux_dict

    @property
    def arg_arrays(self) -> List[NDArray]:
        return [self._arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self) -> List[Optional[NDArray]]:
        return [self._grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self) -> List[NDArray]:
        return [self._aux_dict[n] for n in self._aux_names]

    def copy_params_from(self, arg_params: Dict[str, NDArray],
                         aux_params: Optional[Dict[str, NDArray]] = None,
                         allow_extra_params: bool = False) -> None:
        """Copy parameters into the bound arrays (reference
        ``executor.py:204``)."""
        for name_, arr in arg_params.items():
            if name_ in self._arg_dict:
                self._arg_dict[name_]._write(
                    arr.data if isinstance(arr, NDArray) else jnp.asarray(arr))
            elif not allow_extra_params:
                raise MXNetError(f"copy_params_from: no argument {name_}")
        for name_, arr in (aux_params or {}).items():
            if name_ in self._aux_dict:
                self._aux_dict[name_]._write(
                    arr.data if isinstance(arr, NDArray) else jnp.asarray(arr))
            elif not allow_extra_params:
                raise MXNetError(f"copy_params_from: no aux state {name_}")

    def set_monitor_callback(self, callback) -> None:
        """Install a per-node-output hook (reference
        ``MXExecutorSetMonitorCallback`` → ``graph_executor.cc:890-905``)."""
        self._monitor_cb = callback

    def debug_str(self) -> str:
        """Analog of ``Executor::Print`` — the compiled HLO summary."""
        lines = [f"Symbol outputs: {self._symbol.list_outputs()}"]
        for n in self._topo:
            kind = "var" if n.is_variable else n.op.name
            lines.append(f"  {kind:20s} {n.name}")
        return "\n".join(lines)
