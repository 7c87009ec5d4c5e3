"""Compiled (single-program) 1F1B pipeline schedule.

:class:`SpmdPipelineTrainer` runs the SAME stage partitioning as
:class:`PipelineTrainer` but compiles the ENTIRE training step — every
microbatch forward, every rematerialized backward, gradient
accumulation, and the optimizer update — into ONE ``jit`` program:
``step()`` makes exactly one compiled dispatch however many stages or
microbatches there are.

Reference analog: the 2016 framework's answer to per-node dispatch
overhead was bulk execution — the whole graph fused into ONE engine op
(``/root/reference/src/symbol/graph_executor.cc:833-862``).  The
TPU-native analog is one XLA program for the whole 1F1B step:

* the device grid is a ``(data, pipe)`` :class:`~jax.sharding.Mesh`;
  the program is a ``shard_map`` over BOTH axes;
* per-stage parameters are **flattened into padded f32 buffers** and
  stacked ``[S, n_max]``, sharded ``P('pipe')`` — each device holds
  exactly its stage's parameters.  Flattening is what makes
  *heterogeneous* stages (different shapes per stage — the thing the
  host-driven path supports) stackable into one SPMD program: every
  ``lax.switch`` branch has the same padded signature and unflattens
  its own stage's layout statically;
* the 1F1B order is a **static timetable** computed on the host at
  bind time — ``F(s, j)``/``B(s, j)`` tick indices satisfying the
  classic constraints (activations arrive one tick after the producer,
  cotangents one tick after the consumer, at most ``S - s`` microbatches
  in flight per stage) — and burned into the program as scanned
  ``[T, S]`` lookup tables; a ``lax.scan`` over ticks runs one
  forward slot and one backward slot per device per tick;
* boundary activations ride a ``lax.ppermute`` ring (+1 over ``pipe``),
  cotangents the reverse ring (-1); both move once per tick,
  unconditionally, so collectives stay schedule-independent;
* the backward slot re-runs the stage forward inside ``jax.vjp`` from
  the saved stage *input* (the same GPipe remat recipe as the host
  path), reading it from an in-program ring buffer of ``S`` slots —
  the 1F1B in-flight cap is what bounds that buffer;
* stage gradients accumulate across microbatches in the scan carry,
  are ``psum``'d over ``data``, and the per-stage optimizer update runs
  in the same program.

Semantics notes vs the host-driven path (``tests/test_pipeline_spmd.py``
pins step-equivalence):

* with ``data_parallel > 1``, batch-statistics ops (BatchNorm) compute
  moments over the LOCAL data shard (non-synced BN) — the host path's
  per-stage GSPMD programs reduce over the full microbatch.  Aux states
  are ``pmean``'d over ``data`` after the step.  Stochastic ops
  (Dropout) fold the ``data`` axis index into their key so masks
  decorrelate across shards (the host path draws one global mask and
  shards it — same distribution, different stream).  dp=1 is
  bit-equivalent on both counts;
* boundary tensors travel as f32 on the wire (bf16 values round-trip
  exactly; under AMP this is one widening per hop, never a narrowing).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from .pipeline_trainer import PipelineTrainer

__all__ = ["SpmdPipelineTrainer", "schedule_1f1b"]


def schedule_1f1b(num_stages: int, num_microbatches: int):
    """Static 1F1B timetable.

    Returns ``(fwd_tbl, bwd_tbl)`` of shape ``[T, S]`` int32: entry
    ``[t, s]`` is the microbatch whose forward (resp. backward) stage
    ``s`` runs at tick ``t``, or ``-1``.  Each tick has one forward and
    one backward slot per stage.  Constraints encoded:

    * ``F(s, j) > F(s-1, j)`` — activations arrive next tick (ppermute);
    * ``B(s, j) > B(s+1, j)`` — cotangents likewise;
    * ``B(s, j) >= F(s, j)`` — the last stage turns around same-tick
      (its forward slot runs before its backward slot);
    * ``F(s, j) > B(s, j - (S - s))`` — the 1F1B in-flight cap: stage
      ``s`` holds at most ``S - s`` live microbatches;
    * one forward / one backward per stage per tick.
    """
    S, M = num_stages, num_microbatches
    F = np.zeros((S, M), np.int64)
    B = np.zeros((S, M), np.int64)
    for j in range(M):
        for s in range(S):
            c = [0]
            if s > 0:
                c.append(F[s - 1, j] + 1)
            if j > 0:
                c.append(F[s, j - 1] + 1)
            k = j - (S - s)
            if k >= 0:
                c.append(B[s, k] + 1)
            F[s, j] = max(c)
        for s in range(S - 1, -1, -1):
            c = [F[s, j]]
            if s < S - 1:
                c.append(B[s + 1, j] + 1)
            if j > 0:
                c.append(B[s, j - 1] + 1)
            B[s, j] = max(c)
    T = int(B[0, M - 1]) + 1
    fwd_tbl = -np.ones((T, S), np.int32)
    bwd_tbl = -np.ones((T, S), np.int32)
    for s in range(S):
        for j in range(M):
            fwd_tbl[F[s, j], s] = j
            bwd_tbl[B[s, j], s] = j
    return fwd_tbl, bwd_tbl


def _shard_map(fn, mesh, in_specs, out_specs):
    """shard_map with replication checking off (the program mixes
    per-axis psum/pmean with out-specs that drop axes; correctness is
    pinned by the equivalence tests, not the vma checker)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class _FlatSpec:
    """Layout of a list of named arrays inside one padded f32 buffer."""

    def __init__(self, items: List[Tuple[str, tuple, Any]]):
        # items: (name, shape, dtype)
        self.items = items
        self.offsets = []
        off = 0
        for _, shape, _ in items:
            self.offsets.append(off)
            off += int(np.prod(shape))
        self.size = off

    def flatten(self, values: Dict[str, Any], pad_to: int,
                np_mod=jnp) -> Any:
        parts = [np_mod.ravel(np_mod.asarray(values[n]).astype(jnp.float32))
                 for n, _, _ in self.items]
        pad = pad_to - self.size
        if pad:
            parts.append(np_mod.zeros((pad,), jnp.float32))
        if not parts:
            return np_mod.zeros((max(pad_to, 1),), jnp.float32)
        return np_mod.concatenate(parts) if len(parts) > 1 else parts[0]

    def unflatten(self, buf) -> Dict[str, Any]:
        out = {}
        for (n, shape, dtype), off in zip(self.items, self.offsets):
            size = int(np.prod(shape))
            out[n] = jax.lax.dynamic_slice_in_dim(
                buf, off, size).reshape(shape).astype(dtype)
        return out


class _StackedStateGuard:
    """Data descriptor guarding ``_params``/``_aux``/``_opt_state`` on
    :class:`SpmdPipelineTrainer`: after ``_compile`` the per-stage dicts
    live only in the stacked pipe-sharded buffers (``_pflat``/``_sflat``/
    ``_auxflat``) and the originals are dropped to free memory.  An
    inherited :class:`PipelineTrainer` code path that still reaches for
    them gets a clear ``RuntimeError`` naming the supported surface
    instead of a cryptic ``'NoneType' object is not subscriptable``."""

    def __init__(self, name: str):
        self.name = name
        self.slot = "_guarded" + name

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        if self.slot not in obj.__dict__:
            raise AttributeError(self.name)
        val = obj.__dict__[self.slot]
        if val is None:
            raise RuntimeError(
                f"SpmdPipelineTrainer.{self.name} is dropped after "
                "compile: per-stage params/aux/optimizer state live only "
                "in the stacked pipe-sharded buffers.  Use get_params() "
                "for host copies, or step()/forward(), which read the "
                "stacked buffers directly.")
        return val

    def __set__(self, obj, value):
        obj.__dict__[self.slot] = value


class SpmdPipelineTrainer(PipelineTrainer):
    """:class:`PipelineTrainer` with the whole 1F1B step in ONE program.

    Same constructor and :meth:`bind` signature; ``step()`` makes
    exactly one compiled dispatch (``self.dispatch_count`` counts them).
    """

    _params = _StackedStateGuard("_params")
    _aux = _StackedStateGuard("_aux")
    _opt_state = _StackedStateGuard("_opt_state")

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.dispatch_count = 0

    # -- bind hook: PipelineTrainer.bind calls self._compile() last ----

    def _compile(self):
        S, M, dp = self.num_stages, self.num_microbatches, self.dp
        grid = np.empty((dp, S), dtype=object)
        for s in range(S):
            col = self._stage_meshes[s].devices.reshape(-1)
            grid[:, s] = col
        self.mesh = Mesh(grid, ("data", "pipe"))

        # ---- per-stage flat layouts ---------------------------------
        sym = self.symbol
        arg_shapes, _, aux_shapes = sym.infer_shape(**{
            n: s for n, s in self._input_shapes.items()})
        shape_of = dict(zip(sym.list_arguments(), arg_shapes))
        aux_shape_of = dict(zip(sym.list_auxiliary_states(), aux_shapes))

        self._pspecs = [
            _FlatSpec([(n, shape_of[n], jnp.float32)
                       for n in sorted(self._stage_params[s])])
            for s in range(S)]
        self._auxspecs = [
            _FlatSpec([(n, aux_shape_of[n], jnp.float32)
                       for n in sorted(self._stage_aux[s])])
            for s in range(S)]
        self._n_max = max(1, max(sp.size for sp in self._pspecs))
        self._aux_max = max(1, max(sp.size for sp in self._auxspecs))

        # optimizer-state layout: per stage, params in sorted order, each
        # param's state pytree flattened in tree order (treedefs read off
        # the REAL bound opt state, so any optimizer structure works)
        self._state_treedefs = []
        self._sspecs = []
        for s in range(S):
            defs, items = {}, []
            for n in sorted(self._stage_params[s]):
                leaves, treedef = jax.tree.flatten(self._opt_state[s][n])
                defs[n] = treedef
                for i, leaf in enumerate(leaves):
                    items.append((f"{n}#{i}", tuple(leaf.shape),
                                  jnp.asarray(leaf).dtype))
            self._state_treedefs.append(defs)
            self._sspecs.append(_FlatSpec(items))
        self._state_max = max(1, max(sp.size for sp in self._sspecs))

        # ---- abstract eval for boundary/head shapes (local microbatch)
        # (batch divisibility by M * dp was already enforced in bind)
        mb_scale = M * dp
        self._mb_inputs = {
            n: (shp[0] // mb_scale,) + tuple(shp[1:])
            for n, shp in self._input_shapes.items()}
        in_avals = {n: jax.ShapeDtypeStruct(s, jnp.float32)
                    for n, s in self._mb_inputs.items()}
        key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
        self._envspecs = []            # boundary s -> s+1
        head_avals: List[Optional[jax.ShapeDtypeStruct]] = \
            [None] * len(self._head_keys)
        env_avals: Dict[str, Any] = {}
        for s in range(S):
            p_av = {n: jax.ShapeDtypeStruct(shape_of[n], jnp.float32)
                    for n in self._stage_params[s]}
            a_av = {n: jax.ShapeDtypeStruct(aux_shape_of[n], jnp.float32)
                    for n in self._stage_aux[s]}
            i_av = {n: in_avals[n] for n in self._stage_inputs[s]}
            env_out, heads_s, _ = jax.eval_shape(
                functools.partial(self._stage_apply, s, is_train=True),
                p_av, a_av, env_avals, i_av, key_aval)
            pos = 0
            for idx, (k, hs) in enumerate(self._head_keys):
                if hs == s:
                    head_avals[idx] = heads_s[pos]
                    pos += 1
            if s < S - 1:
                self._envspecs.append(_FlatSpec(
                    [(k, tuple(env_out[k].shape), env_out[k].dtype)
                     for k in self._env_after[s]]))
            env_avals = env_out
        self._head_avals = head_avals
        self._env_max = max(
            [1] + [sp.size for sp in self._envspecs])

        # ---- pack bound params/opt/aux into stacked sharded buffers --
        def stack(specs, per_stage_values, pad):
            rows = [spec.flatten({k: np.asarray(v) for k, v in vals.items()},
                                 pad, np_mod=np)
                    for spec, vals in zip(specs, per_stage_values)]
            return np.stack([np.asarray(r) for r in rows])

        pipe_sh = NamedSharding(self.mesh, P("pipe", None))
        self._pflat = jax.device_put(
            stack(self._pspecs, self._params, self._n_max), pipe_sh)
        self._auxflat = jax.device_put(
            stack(self._auxspecs, self._aux, self._aux_max), pipe_sh)
        state_rows = []
        for s in range(S):
            vals = {}
            for n in sorted(self._stage_params[s]):
                leaves = jax.tree.leaves(self._opt_state[s][n])
                for i, leaf in enumerate(leaves):
                    vals[f"{n}#{i}"] = np.asarray(leaf)
            state_rows.append(np.asarray(
                self._sspecs[s].flatten(vals, self._state_max, np_mod=np)))
        self._sflat = jax.device_put(np.stack(state_rows), pipe_sh)
        # per-stage dicts now live only in the stacked buffers
        self._params = self._aux = self._opt_state = None

        self._fwd_tbl, self._bwd_tbl = schedule_1f1b(S, M)
        # arrival tables: what last tick's ppermute delivered.  A fwd env
        # sent by stage s-1 at tick t lands at stage s at t+1; it may sit
        # several ticks before stage s consumes it (and is read again at
        # backward time for the remat), so receipts go into rings indexed
        # by microbatch — depth computed exactly from the tables.
        T = self._fwd_tbl.shape[0]
        arr_f = -np.ones((T, S), np.int32)
        arr_b = -np.ones((T, S), np.int32)
        arr_f[1:, 1:] = self._fwd_tbl[:-1, :-1]
        arr_b[1:, :S - 1] = self._bwd_tbl[:-1, 1:]
        self._arr_f, self._arr_b = arr_f, arr_b
        self._ring_k = self._ring_depth()
        # donate the param/opt/aux buffers: step() immediately rebinds
        # them, so double-buffering params+state would waste HBM
        self._step_jit = jax.jit(self._build_step(),
                                 donate_argnums=(0, 1, 2))
        self._fwd_jit = jax.jit(self._build_forward())

    def _ring_depth(self) -> int:
        """Smallest ring size K such that slot ``j % K`` is never
        overwritten (by microbatch ``j + K``) before its last read."""
        S, M = self.num_stages, self.num_microbatches
        F, B = {}, {}
        for t in range(self._fwd_tbl.shape[0]):
            for s in range(S):
                if self._fwd_tbl[t, s] >= 0:
                    F[(s, int(self._fwd_tbl[t, s]))] = t
                if self._bwd_tbl[t, s] >= 0:
                    B[(s, int(self._bwd_tbl[t, s]))] = t
        for k in range(1, 2 * S + M + 1):
            ok = True
            for s in range(S):
                for j in range(M - k):
                    wr_next = (F[(s - 1, j + k)] + 1 if s > 0
                               else F[(s, j + k)])
                    if wr_next <= B[(s, j)]:
                        ok = False
                    if s < S - 1 and B[(s + 1, j + k)] + 1 <= B[(s, j)]:
                        ok = False
                    if F[(s, j + k)] <= B[(s, j)]:  # aux ring
                        ok = False
            if ok:
                return k
        raise MXNetError("no valid ring depth (schedule bug)")

    # -- flat-space stage bodies --------------------------------------

    def _unflat_env(self, boundary: int, buf):
        if boundary < 0 or boundary >= len(self._envspecs):
            return {}
        return self._envspecs[boundary].unflatten(buf)

    def _flat_env(self, boundary: int, env: Dict[str, Any]):
        if boundary < 0 or boundary >= len(self._envspecs):
            return jnp.zeros((self._env_max,), jnp.float32)
        return self._envspecs[boundary].flatten(env, self._env_max)

    def _stage_fwd_flat(self, s, pflat, envflat, inputs_j, auxflat, key,
                        is_train=True):
        params_s = self._pspecs[s].unflatten(pflat)
        aux_s = self._auxspecs[s].unflatten(auxflat)
        env_in = self._unflat_env(s - 1, envflat)
        inputs_s = {n: inputs_j[n] for n in self._stage_inputs[s]}
        env_out, heads_s, aux_up = self._stage_apply(
            s, params_s, aux_s, env_in, inputs_s, key, is_train)
        heads_full = [jnp.zeros(h.shape, h.dtype) for h in self._head_avals]
        pos = 0
        for idx, (k, hs) in enumerate(self._head_keys):
            if hs == s:
                heads_full[idx] = heads_s[pos]
                pos += 1
        if aux_up:
            aux_s = dict(aux_s, **aux_up)
        return (self._flat_env(s, env_out), tuple(heads_full),
                self._auxspecs[s].flatten(aux_s, self._aux_max))

    def _stage_bwd_flat(self, s, pflat, envflat, inputs_j, aux_snap, key,
                        ct_env):
        aux_s = self._auxspecs[s].unflatten(aux_snap)
        inputs_s = {n: inputs_j[n] for n in self._stage_inputs[s]}

        def f(pf, ef):
            params_s = self._pspecs[s].unflatten(pf)
            env_in = self._unflat_env(s - 1, ef)
            env_out, heads_s, _ = self._stage_apply(
                s, params_s, aux_s, env_in, inputs_s, key, True)
            return self._flat_env(s, env_out), heads_s
        (eo, heads), vjp_fn = jax.vjp(f, pflat, envflat)
        # loss heads discard their cotangent (custom_vjp), as on the
        # host-driven path: seed ones
        ct_heads = tuple(jnp.ones(h.shape, h.dtype) for h in heads)
        gp, genv = vjp_fn((ct_env, ct_heads))
        return gp, genv

    def _stage_upd_flat(self, s, pflat, gflat, sflat, lr, t):
        opt = self.optimizer
        hyper = opt._hyper()
        hyper["rescale_grad"] = self._rescale_grad
        step_fn = type(opt)._functional_step
        params = self._pspecs[s].unflatten(pflat)
        grads = self._pspecs[s].unflatten(gflat)
        states_flat = self._sspecs[s].unflatten(sflat)
        new_p, new_s = {}, {}
        for n in sorted(params):
            defs = self._state_treedefs[s][n]
            leaves = [states_flat[f"{n}#{i}"]
                      for i in range(defs.num_leaves)]
            st = jax.tree.unflatten(defs, leaves)
            w2, st2 = step_fn(hyper, params[n], grads[n], st,
                              lr * self._lr_mult[n],
                              opt.wd * self._wd_mult[n], t, None)
            new_p[n] = w2
            for i, leaf in enumerate(jax.tree.leaves(st2)):
                new_s[f"{n}#{i}"] = leaf
        return (self._pspecs[s].flatten(new_p, self._n_max),
                self._sspecs[s].flatten(new_s, self._state_max))

    # -- the single program -------------------------------------------

    def _build_step(self):
        S, M = self.num_stages, self.num_microbatches
        K = self._ring_k
        fwd_tbl = jnp.asarray(self._fwd_tbl)
        bwd_tbl = jnp.asarray(self._bwd_tbl)
        arr_f = jnp.asarray(self._arr_f)
        arr_b = jnp.asarray(self._arr_b)
        fwd_branches = [functools.partial(self._stage_fwd_flat, s)
                        for s in range(S)]
        bwd_branches = [functools.partial(self._stage_bwd_flat, s)
                        for s in range(S)]
        upd_branches = [functools.partial(self._stage_upd_flat, s)
                        for s in range(S)]
        fwd_ring = [(i, i + 1) for i in range(S - 1)]
        bwd_ring = [(i, i - 1) for i in range(1, S)]

        dp = self.dp

        def sharded(pflat, sflat, auxflat, x_mb, lr, t, key):
            sid = jax.lax.axis_index("pipe")
            ploc = pflat[0]
            aloc = auxflat[0]
            sloc = sflat[0]

            def mb_key(j):
                kj = jax.random.fold_in(key, j)
                if dp > 1:
                    # decorrelate stochastic ops (dropout) across data
                    # shards; dp=1 stays bit-equal to the host path
                    kj = jax.random.fold_in(
                        kj, jax.lax.axis_index("data"))
                return kj

            def tick(carry, tbl_row):
                (fwd_recv, bwd_recv, ring_env, ring_ct, ring_aux, aux,
                 grads, heads_acc) = carry
                row_f, row_b, row_af, row_ab = tbl_row
                fj = row_f[sid]
                bj = row_b[sid]
                aj = row_af[sid]
                cj = row_ab[sid]

                # ---- bank last tick's ppermute deliveries ------------
                ring_env = jax.lax.cond(
                    aj >= 0,
                    lambda r: r.at[jnp.clip(aj, 0, M - 1) % K].set(fwd_recv),
                    lambda r: r, ring_env)
                ring_ct = jax.lax.cond(
                    cj >= 0,
                    lambda r: r.at[jnp.clip(cj, 0, M - 1) % K].set(bwd_recv),
                    lambda r: r, ring_ct)

                # ---- forward slot ----
                def run_f(ops):
                    aux, ring_aux, heads_acc = ops
                    j = jnp.clip(fj, 0, M - 1)
                    inputs_j = {n: x_mb[n][j] for n in x_mb}
                    kj = mb_key(j)
                    ring_aux = ring_aux.at[j % K].set(aux)
                    eo, heads, aux2 = jax.lax.switch(
                        sid, fwd_branches, ploc, ring_env[j % K], inputs_j,
                        aux, kj)
                    heads_acc = tuple(
                        acc.at[j].set(h)
                        for acc, h in zip(heads_acc, heads))
                    return eo, aux2, ring_aux, heads_acc

                def skip_f(ops):
                    aux, ring_aux, heads_acc = ops
                    return (jnp.zeros((self._env_max,), jnp.float32), aux,
                            ring_aux, heads_acc)

                eo, aux, ring_aux, heads_acc = jax.lax.cond(
                    fj >= 0, run_f, skip_f, (aux, ring_aux, heads_acc))

                # ---- backward slot ----
                def run_b(grads):
                    j = jnp.clip(bj, 0, M - 1)
                    inputs_j = {n: x_mb[n][j] for n in x_mb}
                    kj = mb_key(j)
                    gp, genv = jax.lax.switch(
                        sid, bwd_branches, ploc, ring_env[j % K], inputs_j,
                        ring_aux[j % K], kj, ring_ct[j % K])
                    return genv, grads + gp

                def skip_b(grads):
                    return jnp.zeros((self._env_max,), jnp.float32), grads

                genv, grads = jax.lax.cond(bj >= 0, run_b, skip_b, grads)

                # ---- unconditional ring moves ----
                fwd_recv = jax.lax.ppermute(eo, "pipe", fwd_ring)
                bwd_recv = jax.lax.ppermute(genv, "pipe", bwd_ring)
                return (fwd_recv, bwd_recv, ring_env, ring_ct, ring_aux,
                        aux, grads, heads_acc), None

            zero_env = jnp.zeros((self._env_max,), jnp.float32)
            heads0 = tuple(
                jnp.zeros((M,) + tuple(h.shape), h.dtype)
                for h in self._head_avals)
            carry0 = (zero_env, zero_env,
                      jnp.zeros((K, self._env_max), jnp.float32),
                      jnp.zeros((K, self._env_max), jnp.float32),
                      jnp.zeros((K, self._aux_max), jnp.float32),
                      aloc,
                      jnp.zeros((self._n_max,), jnp.float32),
                      heads0)
            (_, _, _, _, _, aux, grads, heads_acc), _ = jax.lax.scan(
                tick, carry0, (fwd_tbl, bwd_tbl, arr_f, arr_b))

            grads = jax.lax.psum(grads, "data")
            heads_acc = tuple(jax.lax.psum(h, "pipe") for h in heads_acc)
            aux = jax.lax.pmean(aux, "data")
            new_p, new_s = jax.lax.switch(
                sid, upd_branches, ploc, grads, sloc, lr, t)
            return (new_p[None], new_s[None], aux[None], heads_acc)

        in_specs = (
            P("pipe", None), P("pipe", None), P("pipe", None),
            {n: P(None, "data", *([None] * (len(shp) - 1)))
             for n, shp in self._mb_inputs.items()},
            P(), P(), P())
        out_specs = (
            P("pipe", None), P("pipe", None), P("pipe", None),
            tuple(P(None, "data") for _ in self._head_avals))
        return _shard_map(sharded, self.mesh, in_specs, out_specs)

    def _build_forward(self):
        """Fill-drain forward-only pipeline (eval path)."""
        S, M = self.num_stages, self.num_microbatches
        T = S + M - 1
        eval_branches = [
            functools.partial(self._stage_eval_flat, s) for s in range(S)]
        fwd_ring = [(i, i + 1) for i in range(S - 1)]

        def sharded(pflat, auxflat, x_mb, key):
            sid = jax.lax.axis_index("pipe")
            ploc = pflat[0]
            aloc = auxflat[0]

            def tick(carry, t):
                fwd_recv, heads_acc = carry
                fj = t - sid  # F(s, j) = s + j

                def run_f(ops):
                    fwd_recv, heads_acc = ops
                    j = jnp.clip(fj, 0, M - 1)
                    inputs_j = {n: x_mb[n][j] for n in x_mb}
                    kj = jax.random.fold_in(key, j)
                    eo, heads = jax.lax.switch(
                        sid, eval_branches, ploc, fwd_recv, inputs_j,
                        aloc, kj)
                    heads_acc = tuple(
                        acc.at[j].set(h)
                        for acc, h in zip(heads_acc, heads))
                    return eo, heads_acc

                def skip_f(ops):
                    fwd_recv, heads_acc = ops
                    return (jnp.zeros((self._env_max,), jnp.float32),
                            heads_acc)

                eo, heads_acc = jax.lax.cond(
                    (fj >= 0) & (fj < M), run_f, skip_f,
                    (fwd_recv, heads_acc))
                fwd_recv = jax.lax.ppermute(eo, "pipe", fwd_ring)
                return (fwd_recv, heads_acc), None

            heads0 = tuple(
                jnp.zeros((M,) + tuple(h.shape), h.dtype)
                for h in self._head_avals)
            zero_env = jnp.zeros((self._env_max,), jnp.float32)
            (_, heads_acc), _ = jax.lax.scan(
                tick, (zero_env, heads0), jnp.arange(T))
            return tuple(jax.lax.psum(h, "pipe") for h in heads_acc)

        in_specs = (
            P("pipe", None), P("pipe", None),
            {n: P(None, "data", *([None] * (len(shp) - 1)))
             for n, shp in self._mb_inputs.items()},
            P())
        out_specs = tuple(P(None, "data") for _ in self._head_avals)
        return _shard_map(sharded, self.mesh, in_specs, out_specs)

    def _stage_eval_flat(self, s, pflat, envflat, inputs_j, auxflat, key):
        env_flat, heads, _ = self._stage_fwd_flat(
            s, pflat, envflat, inputs_j, auxflat, key, is_train=False)
        return env_flat, heads

    # -- public API ----------------------------------------------------

    def _batch_to_mb(self, batch) -> Dict[str, jax.Array]:
        named = self._named_inputs(batch)
        M = self.num_microbatches
        out = {}
        for n in self._input_names:
            v = named[n]
            v = v.data if hasattr(v, "data") else v
            if not isinstance(v, jax.Array):
                v = np.asarray(v, np.float32)  # host input: one H2D put
            v = v.astype(jnp.float32) if v.dtype != np.float32 else v
            out[n] = v.reshape((M, v.shape[0] // M) + v.shape[1:])
        return out

    def step(self, batch) -> List[jax.Array]:
        if not self._bound:
            raise MXNetError("call bind() before step()")
        self._num_update += 1
        opt = self.optimizer
        lr = np.float32(opt.lr_scheduler(self._num_update)
                        if opt.lr_scheduler else opt.lr)
        key = np.asarray(jax.random.PRNGKey(self._num_update),
                         dtype=np.uint32)
        x_mb = self._batch_to_mb(batch)
        self._pflat, self._sflat, self._auxflat, heads = self._step_jit(
            self._pflat, self._sflat, self._auxflat, x_mb, lr,
            np.int32(self._num_update), key)
        self.dispatch_count += 1
        return [h.reshape((-1,) + tuple(h.shape[2:])) for h in heads]

    def forward(self, batch) -> List[jax.Array]:
        if not self._bound:
            raise MXNetError("call bind() before forward()")
        key = np.asarray(jax.random.PRNGKey(self._num_update),
                         dtype=np.uint32)
        x_mb = self._batch_to_mb(batch)
        heads = self._fwd_jit(self._pflat, self._auxflat, x_mb, key)
        self.dispatch_count += 1
        return [h.reshape((-1,) + tuple(h.shape[2:])) for h in heads]

    def get_params(self):
        from ..ndarray import array as nd_array
        pflat = np.asarray(self._pflat)
        auxflat = np.asarray(self._auxflat)
        arg, aux = {}, {}
        for s in range(self.num_stages):
            for n, v in self._pspecs[s].unflatten(
                    jnp.asarray(pflat[s])).items():
                arg[n] = nd_array(np.asarray(v))
            for n, v in self._auxspecs[s].unflatten(
                    jnp.asarray(auxflat[s])).items():
                aux[n] = nd_array(np.asarray(v))
        return arg, aux
