"""Pure-functional symbol-graph evaluation.

The single tracing core shared by :class:`mxnet_tpu.executor.Executor`
(single-device bind) and :mod:`mxnet_tpu.parallel` (mesh-sharded compiled
train steps).  In the reference the graph is walked twice — once by
``GraphExecutor::InitGraph`` to plan memory and once per batch by ``RunOps``
(``src/symbol/graph_executor.cc:303,833``); here the walk happens once under
``jax.jit`` tracing and XLA owns scheduling and buffers.

``eval_symbol`` is pure in (arg values, aux values, rng) -> (head values,
aux updates) so it can sit inside ``jax.vjp``/``jax.jit``/``shard_map``
transforms without modification.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax

from .ops.registry import OpContext

__all__ = ["eval_symbol", "graph_fingerprint"]


def graph_fingerprint(symbol, topo=None) -> str:
    """Stable structural identity of a symbol graph, for compile-cache
    keys (:func:`mxnet_tpu.compile_cache.program_key`).

    Hashes, in topological order: each node's op name, its parsed
    parameters, its annotation attrs (``remat_scope`` etc. change the
    traced program), and its input wiring as topo indices, plus the head
    entries.  Two graphs with the same fingerprint trace to the same
    jaxpr for the same avals; any op/param/wiring change produces a new
    fingerprint.  Node *names* are excluded — renamed-but-identical
    graphs share programs.
    """
    import hashlib
    if topo is None:
        topo = symbol._topo()
    gidx = {id(n): i for i, n in enumerate(topo)}
    h = hashlib.sha256()
    for n in topo:
        if n.is_variable:
            h.update(b"var\x00")
            continue
        h.update(n.op.name.encode())
        h.update(repr(sorted(n.parsed_params().items())).encode())
        h.update(repr(sorted(n.anno_attrs().items())).encode())
        h.update(repr([(gidx[id(s)], k) for (s, k) in n.inputs]).encode())
        h.update(b"\x00")
    h.update(repr([(gidx[id(n)], i) for (n, i) in symbol._heads]).encode())
    return h.hexdigest()


def eval_symbol(symbol, arg_vals: Dict[str, jax.Array],
                aux_vals: Dict[str, jax.Array], rng, is_train: bool,
                want_internals: bool = False, topo=None, placement=None):
    """Evaluate a Symbol graph on jax values.

    Parameters
    ----------
    symbol : Symbol
        Graph to evaluate; outputs are its head entries.
    arg_vals : dict name -> jax.Array
        Values for every variable node (params + data + labels).
    aux_vals : dict full_name -> jax.Array
        Auxiliary state values keyed ``{node_name}_{aux_name}``.
    rng : jax PRNG key or None
        Folded per-node for dropout/sampling ops.
    is_train : bool
        Train-mode flag passed to each op (dropout on, BatchNorm batch
        stats + moving-average updates).
    want_internals : bool
        Also return every node output keyed ``{node_name}_{output_name}``
        (the monitor-hook path, reference ``graph_executor.cc:890-905``).
    topo : list of nodes, optional
        Pre-computed ``symbol._topo()`` to skip re-sorting in hot paths.
    placement : dict node-name -> jax.Device, optional
        Model-parallel device placement (``ctx_group``/``group2ctx``,
        reference ``graph_executor.cc:390+``): each node's inputs are
        transferred to its device before execution — the analog of the
        auto-inserted ``_CrossDeviceCopy`` nodes.  Only valid in eager
        (non-jit) evaluation.

    Returns ``(heads, aux_updates)`` or ``(heads, aux_updates, internals)``.
    """
    if topo is None:
        topo = symbol._topo()
    vals: Dict[tuple, jax.Array] = {}
    aux_updates: Dict[str, jax.Array] = {}
    internals: Dict[str, jax.Array] = {}
    gidx = {id(n): i for i, n in enumerate(topo)}
    head_set = {(id(n), i) for (n, i) in symbol._heads}

    def eval_node(node, in_vals):
        """One op node; returns (outs list, aux_updates dict).  Its ops
        carry the node's name in their HLO ``op_name`` (metadata only),
        so a device trace can say which layer an instruction is for."""
        with jax.named_scope(node.name):
            return _eval_node(node, in_vals)

    def _eval_node(node, in_vals):
        op = node.op
        params = node.parsed_params()
        aux_full = node.aux_full_names()
        short = op.list_aux_states(params)
        aux = {sh: aux_vals[f] for sh, f in zip(short, aux_full)}
        node_rng = (jax.random.fold_in(rng, gidx[id(node)])
                    if rng is not None else None)
        opctx = OpContext(is_train=is_train, rng=node_rng, aux=aux,
                          name=node.name)
        anno = node.anno_attrs()
        if anno.get("force_mirroring") in ("True", "true", "1") and not aux_full:
            # recompute-in-backward (reference gradient mirroring,
            # static_graph.cc:404-437) == jax.checkpoint around the node
            fwd = jax.checkpoint(
                lambda *xs, _f=op.forward, _c=opctx, _p=params: _f(_c, _p, *xs))
            out = fwd(*in_vals)
        else:
            out = op.forward(opctx, params, *in_vals)
        outs = list(out) if isinstance(out, (tuple, list)) else [out]
        node_aux = {f: opctx.aux_updates[sh]
                    for sh, f in zip(short, aux_full)
                    if sh in opctx.aux_updates}
        return outs, node_aux

    def record(node, outs):
        for i, o in enumerate(outs):
            vals[(id(node), i)] = o
        if want_internals:
            out_names = node.op.list_outputs(node.parsed_params())
            for i, o in enumerate(outs):
                internals[f"{node.name}_{out_names[i]}"] = o

    # consumers of each produced entry — needed to find what escapes a
    # remat scope (monitor mode disables remat: it needs every internal)
    # monitor mode needs every internal, and legacy device placement is
    # applied per node — both disable scope grouping
    use_remat = not want_internals and placement is None and any(
        not n.is_variable and n.anno_attrs().get("remat_scope")
        for n in topo)
    consumers: Dict[tuple, List[int]] = {}
    if use_remat:
        for n in topo:
            if n.is_variable:
                continue
            for (src, k) in n.inputs:
                if not src.is_variable:
                    consumers.setdefault((id(src), k), []).append(id(n))

    i = 0
    while i < len(topo):
        node = topo[i]
        if node.is_variable:
            vals[(id(node), 0)] = arg_vals[node.name]
            if want_internals:
                internals[node.name] = arg_vals[node.name]
            i += 1
            continue
        scope = (node.anno_attrs().get("remat_scope")
                 if use_remat else None)
        if scope is None:
            in_vals = [vals[(id(s), k)] for (s, k) in node.inputs]
            if placement is not None and node.name in placement:
                # no-op for values already on the device; under jax.vjp
                # tracing it records a transfer primitive
                dev = placement[node.name]
                in_vals = [jax.device_put(v, dev) for v in in_vals]
            outs, node_aux = eval_node(node, in_vals)
            record(node, outs)
            aux_updates.update(node_aux)
            i += 1
            continue

        # ---- remat scope: one jax.checkpoint over the whole run -------
        # (long-context lever: only the scope's BOUNDARY activations are
        # stored for backward; everything inside recomputes)
        run: List[Any] = []
        j = i
        while j < len(topo):
            nj = topo[j]
            if nj.is_variable:
                vals[(id(nj), 0)] = arg_vals[nj.name]
                j += 1
                continue
            if nj.anno_attrs().get("remat_scope") != scope:
                break
            run.append(nj)
            j += 1
        run_ids = {id(n) for n in run}
        ext_keys: List[tuple] = []
        for n_ in run:
            for (src, k) in n_.inputs:
                if src.is_variable or id(src) in run_ids:
                    continue
                if (id(src), k) not in ext_keys:
                    ext_keys.append((id(src), k))
        out_keys: List[tuple] = []
        for n_ in run:
            nout = len(n_.op.list_outputs(n_.parsed_params()))
            for k in range(nout):
                key = (id(n_), k)
                outside = any(c not in run_ids
                              for c in consumers.get(key, []))
                if outside or key in head_set:
                    out_keys.append(key)

        def scope_fn(*ext_vals):
            local: Dict[tuple, jax.Array] = dict(zip(ext_keys, ext_vals))
            local_aux: Dict[str, jax.Array] = {}
            for n_ in run:
                ins = []
                for (src, k) in n_.inputs:
                    if src.is_variable:
                        ins.append(arg_vals[src.name])
                    else:  # in-run values and scope inputs both live
                        ins.append(local[(id(src), k)])  # in `local`
                outs, n_aux = eval_node(n_, ins)
                for k, o in enumerate(outs):
                    local[(id(n_), k)] = o
                local_aux.update(n_aux)
            return tuple(local[k] for k in out_keys), local_aux

        outs, scope_aux = jax.checkpoint(scope_fn)(
            *[vals[k] for k in ext_keys])
        for key, o in zip(out_keys, outs):
            vals[key] = o
        aux_updates.update(scope_aux)
        i = j

    heads = tuple(vals[(id(n), i)] for (n, i) in symbol._heads)
    if want_internals:
        return heads, aux_updates, internals
    return heads, aux_updates
