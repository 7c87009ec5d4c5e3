"""Mesh-sharded training: ONE compiled program over all chips.

This is the TPU-native successor to the reference's data-parallel stack —
``DataParallelExecutorManager`` + KVStore reduce (``python/mxnet/
executor_manager.py:180``, ``src/kvstore/kvstore_local.h:135-236``) — where
Python slices the batch per device, runs one executor per device, and
funnels gradients through merge buffers.  Here the whole training step
(forward, backward, gradient all-reduce, optimizer update) is a single
``jax.jit`` over a named :class:`~jax.sharding.Mesh`:

* the batch is sharded over the ``data`` axis (SPMD replaces Python
  slicing),
* params are placed by :class:`ShardingRules` — replicated for pure DP or
  ``PartitionSpec``-sharded over ``model`` for tensor parallelism (the
  capability upgrade SURVEY §2.4 flags as absent in the 2016 reference),
* XLA inserts the gradient ``all-reduce``/``all-gather`` collectives over
  ICI; there is no host participation in the step at all,
* the optimizer's functional core (:meth:`mxnet_tpu.optimizer.Optimizer.
  _functional_step`) runs inside the same program, so updates fuse with the
  tail of the backward pass (the comm/compute overlap the reference gets
  from engine priorities, ``model.py:89-99``, falls out of XLA scheduling).
"""
from __future__ import annotations

import logging
import re
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from ..graph_eval import eval_symbol
from ..context import Context, cpu
from .. import ndarray as nd_mod
from .. import resilience
from .. import telemetry
from ..ndarray import NDArray, array as nd_array
from .mesh import (DATA_AXIS, SEQ_AXIS, batch_sharding, data_parallel_mesh,
                   default_mesh, replicated)

__all__ = ["ShardingRules", "ShardedTrainer", "megatron_rules"]


class ShardingRules:
    """Regex -> PartitionSpec placement rules for parameters/activations.

    The analog of the reference's ``group2ctx`` device-placement map
    (``symbolic.h:366-377``) lifted to mesh axes: instead of pinning a
    layer to one GPU, a rule shards a weight over mesh axes, e.g.::

        ShardingRules([("fc\\d+_weight", P("model", None))])

    Unmatched params are replicated (pure data parallelism).
    """

    def __init__(self, rules: Optional[Sequence[Tuple[str, P]]] = None):
        self._rules = [(re.compile(pat), spec) for pat, spec in (rules or [])]

    def spec_for(self, name: str) -> P:
        for pat, spec in self._rules:
            if pat.search(name):
                return spec
        return P()


def megatron_rules(model_axis: str = "model") -> ShardingRules:
    """Megatron-style tensor-parallel placement for ``transformer-lm``.

    FullyConnected weights are ``(out, in)``:

    * qkv + ffn1 are **column-parallel** — the output dim shards over
      ``model`` (each chip computes its head/ffn slice), biases shard too;
    * proj + ffn2 are **row-parallel** — the input dim shards, XLA inserts
      the partial-sum all-reduce, bias stays replicated;
    * embedding + lm_head shard the vocab dim.

    LayerNorm scales/offsets replicate.  Compose with a
    ``{"data": N//tp, "model": tp}`` mesh; the batch still shards over
    ``data``.  SURVEY §2.4 TP row (no 2016 analog).
    """
    m = model_axis
    return ShardingRules([
        (r"(^|_)(embed|lm_head)_weight$", P(m, None)),
        (r"(^|_)lm_head_bias$", P(m)),
        (r"_(q|k|v|ffn1)_weight$", P(m, None)),
        (r"_(q|k|v|ffn1)_bias$", P(m)),
        (r"_(proj|ffn2)_weight$", P(None, m)),
    ])


class _PlacedBatch(dict):
    """Marker for dicts already staged onto the mesh by ``place_batch`` —
    ``_place_batch`` passes them through without re-dispatching puts."""


def _key_to_meta(key) -> Dict[str, Any]:
    """PRNG key -> JSON-safe manifest meta (handles both raw uint32
    keys and typed key arrays)."""
    try:
        data = np.asarray(jax.random.key_data(key))
        typed = bool(jnp.issubdtype(key.dtype, jax.dtypes.prng_key))
    except Exception:
        data, typed = np.asarray(key), False
    return {"data": [int(x) for x in data.ravel().tolist()],
            "shape": list(data.shape), "typed": typed}


def _key_from_meta(meta: Dict[str, Any]):
    data = np.asarray(meta["data"], np.uint32).reshape(meta["shape"])
    if meta.get("typed"):
        return jax.random.wrap_key_data(jnp.asarray(data))
    return jnp.asarray(data)


def _quant_block_key(compression: Optional[str]) -> Optional[int]:
    """Scale-block size for the program cache key — it changes the
    traced quantization layout, but only for the block-scaled formats."""
    if compression in ("int8", "fp8"):
        from .. import quant
        return quant.default_block_size()
    return None


class ShardedTrainer:
    """Compiled data/tensor-parallel trainer for a Symbol.

    Parameters
    ----------
    symbol : Symbol
        Network whose heads are loss outputs (SoftmaxOutput etc. — loss
        heads define their own backward and ignore head cotangents).
    optimizer : str or Optimizer
    mesh : jax.sharding.Mesh, optional
        Defaults to a 1-D data-parallel mesh over all local devices.
    rules : ShardingRules, optional
        Parameter placement (tensor parallelism); default replicated.
    data_axis : str
        Mesh axis the batch dim is sharded over.
    """

    def __init__(self, symbol, optimizer="sgd", optimizer_params=None,
                 mesh: Optional[Mesh] = None, rules: Optional[ShardingRules] = None,
                 data_axis: Optional[str] = None, initializer=None,
                 matmul_precision: Optional[str] = None,
                 shard_optimizer: bool = False,
                 compute_dtype: Optional[str] = None,
                 grad_accum: int = 1,
                 grad_compression: Optional[str] = None,
                 grad_bucket_bytes: Optional[int] = None,
                 error_feedback: Optional[bool] = None,
                 fused_update: Optional[bool] = None,
                 guard: Optional[bool] = None,
                 clip_global_norm: Optional[float] = None,
                 loss_scale=None,
                 guard_params: Optional[Dict[str, Any]] = None,
                 logger=None):
        from .. import optimizer as opt_mod
        from ..initializer import Uniform
        from .collectives import DEFAULT_BUCKET_BYTES, check_compression
        self.symbol = symbol
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        if data_axis is None:
            # auto: shard the batch over DATA_AXIS when the mesh has it;
            # a mesh without one replicates the batch (e.g. the pure
            # seq-parallel long-context layout)
            self.data_axis = (DATA_AXIS if DATA_AXIS in self.mesh.axis_names
                              else None)
        else:
            if data_axis not in self.mesh.axis_names:
                raise MXNetError(f"mesh has no axis {data_axis!r}; "
                                 f"axes: {self.mesh.axis_names}")
            self.data_axis = data_axis
        self.rules = rules or ShardingRules()
        self.initializer = initializer or Uniform(0.07)
        self.logger = logger or logging.getLogger(__name__)
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        self.optimizer = optimizer
        # 'bfloat16' runs f32 matmuls/convs as single-pass bf16 on the MXU
        # (weights/activations stay f32 in HBM; XLA casts at the MXU edge)
        # — the TPU mixed-precision lever, vs the reference's all-f32 path
        self.matmul_precision = matmul_precision
        # ZeRO-1: shard optimizer state over the data axis.  Gradients are
        # reduce-scattered (instead of all-reduced), each chip updates only
        # its 1/N param shard, and updated params are all-gathered — the
        # TPU-native form of the reference's PS striping of optimizer state
        # across servers (src/kvstore/kvstore_dist.h:243-269).
        self.shard_optimizer = shard_optimizer
        # AMP policy ('bfloat16'): master params stay f32 in HBM; inside
        # the compiled step every f32 param is cast to the compute dtype,
        # so activations flow through the network at half the HBM traffic
        # and matmuls/convs run single-pass bf16 on the MXU.  Norm stats,
        # loss heads, and the optimizer update all stay f32 (the ops
        # enforce this).  This is the lever that takes ResNet-50 from
        # ~17% to ~30%+ MFU on a v5e chip; `matmul_precision` alone only
        # changes the MXU pass mode, not the HBM activation traffic.
        self.compute_dtype = (jnp.dtype(compute_dtype)
                              if compute_dtype else None)
        # gradient accumulation: the step scans over `grad_accum`
        # microbatches INSIDE one compiled program, summing grads before
        # a single optimizer update — activation memory scales with the
        # microbatch, so a big effective batch fits one chip (composes
        # with remat_scope for long context).  Per-microbatch BatchNorm
        # statistics, like every microbatching scheme.
        self.grad_accum = int(grad_accum)
        if self.grad_accum < 1:
            raise MXNetError("grad_accum must be >= 1")
        # explicit gradient communication: instead of XLA's implicit
        # all-reduce, the backward runs in a manual shard_map region over
        # the data axis and gradients are summed through fused flat
        # buckets (~grad_bucket_bytes each), optionally on a quantized
        # wire ('int8'/'bf16' — see collectives.psum_compressed).  Off by
        # default; requires replicated (non-TP) params and a data axis.
        self.grad_compression = check_compression(grad_compression)
        self.grad_bucket_bytes = (int(grad_bucket_bytes) if grad_bucket_bytes
                                  else DEFAULT_BUCKET_BYTES)
        if grad_compression is not None and self.data_axis is None:
            raise MXNetError("grad_compression needs a data axis to "
                             "reduce over; this mesh has none")
        # error feedback: carry each bucket's quantization error in a
        # persistent per-shard f32 residual (opt_state "efres:<i>") and
        # fold it into the next step's pre-quantization input, so the
        # compression bias cancels across steps instead of accumulating
        # in the weights.  Defaults ON for the lossy formats (int8/fp8;
        # MXNET_TPU_QUANT_EF overrides).  grad_accum>1 reduces inside
        # the microbatch scan, where a persistent residual has no home.
        from .. import quant as _quant
        if error_feedback is None:
            self.error_feedback = (
                self.grad_compression is not None and self.grad_accum == 1
                and _quant.error_feedback_default(self.grad_compression))
        else:
            if error_feedback and self.grad_compression is None:
                raise MXNetError("error_feedback=True needs a lossy "
                                 "grad_compression to feed back from")
            if error_feedback and self.grad_accum > 1:
                # EF needs a persistent per-step residual; under
                # grad_accum the reduction runs inside the microbatch
                # scan where that residual has no home, and silently
                # carrying it across microbatches computes the WRONG
                # correction.  Serve the combination safely: warn and
                # fall back to EF-off instead of poisoning the run
                # (pinned by tests/test_quant.py).
                logging.getLogger(__name__).warning(
                    "error_feedback=True does not compose with "
                    "grad_accum=%d (reduction runs inside the "
                    "microbatch scan); disabling error feedback for "
                    "this trainer", self.grad_accum)
                error_feedback = False
            self.error_feedback = bool(error_feedback)
        self._ef_keys: List[str] = []
        # single-pass fused optimizer update (ops/fused_update.py): one
        # primitive per flat grad bucket replaces the unfused jnp chain
        # (loss-scale unscale x clip x guard gating x optimizer step),
        # with optimizer state laid out bucket-aligned so grads, weights
        # and moments stream through VMEM in lockstep.  None = auto (on
        # for eligible configs; MXNET_TPU_FUSED_UPDATE=0 opts out);
        # True raises at bind() if the config cannot fuse; False forces
        # the unfused path.
        self._fused_req = fused_update
        self._fused = False
        self._fused_kind: Optional[str] = None
        self._fused_plan = None
        # step-level anomaly defense (resilience.py): a fused non-finite
        # guard gates the whole param/opt-state update with jnp.where (a
        # bad step leaves state bitwise-unchanged), dynamic loss scaling
        # rides the same stats for bf16/f16 compute, and global-norm
        # clipping folds into the same single pass over the gradients.
        # All in-graph, sync-free, donation-safe.  Off by default
        # (guard=None reads MXNET_TPU_GUARD); clip_global_norm falls back
        # to the optimizer's attribute so the legacy spelling works here.
        if clip_global_norm is None:
            clip_global_norm = getattr(self.optimizer, "clip_global_norm",
                                       None)
        # fp8 compute squeezes the backward's dynamic range from both
        # ends (e5m2 grads underflow early, e4m3 saturates at 448) —
        # default dynamic loss scaling ON when the symbol requests the
        # fp8 matmul path and the user set no explicit scale policy
        if loss_scale is None and guard is not False \
                and _quant.symbol_uses_fp8(symbol):
            loss_scale = "dynamic"
        # legacy-spelling parity: Optimizer(skip_nonfinite=True) turns
        # the guard on here exactly as it does on Module/FeedForward
        if guard is None and getattr(self.optimizer, "skip_nonfinite",
                                     None):
            guard = True
        self._resil = resilience.resolve(guard=guard,
                                         clip_global_norm=clip_global_norm,
                                         loss_scale=loss_scale,
                                         **(guard_params or {}))
        self._guard_state: Optional[Dict[str, jax.Array]] = None
        # host-side sentinel state: LR backoff multiplier (applied to the
        # traced lr argument at dispatch — changing it never retraces),
        # rollback count, and the last drained counter snapshot
        self._lr_scale = 1.0
        self._rollbacks = 0
        self._resil_drained: Dict[str, Any] = {}
        # cumulative base for the windowed guard counters: each sentinel
        # drain folds the on-device values in here (float64/Python int)
        # and zeroes them on device, so the f32 norm_sum accumulator
        # stays window-sized and per-step increments never fall below
        # f32 resolution on long runs
        self._resil_base: Dict[str, Any] = {k: 0 for k
                                            in resilience.WINDOW_KEYS}
        self._sentinel = None
        self._rollback_hook = None  # test/chaos hook: runs pre-rollback
        self._bound = False
        # steady-state instrumentation (same contract as pipeline_spmd):
        # dispatch_count counts compiled-program dispatches; trace_counts
        # counts how often each program (re)traced — exactly 1 per program
        # once shapes/dtypes are static.  strict_retrace turns a signature
        # change on the train path into a hard error instead of a warning.
        self.dispatch_count = 0
        self.trace_counts: Dict[str, int] = {"train": 0, "train_acc": 0,
                                             "eval": 0}
        self.strict_retrace = False
        self._train_sigs: List[Tuple] = []
        # AOT-compiled programs from Trainer.compile (kind -> Compiled);
        # step()/forward() dispatch through these when present, falling
        # back to the jit path on any aval mismatch (a mismatch raises
        # BEFORE donated buffers are consumed, so fallback is safe)
        self._aot: Dict[str, Any] = {}
        self.aot_stats: Dict[str, int] = {"hits": 0, "fallbacks": 0,
                                          "compile_errors": 0}
        self.compile_info: List[Dict[str, Any]] = []

    def _multiproc(self) -> bool:
        if not hasattr(self, "_multiproc_cached"):
            self._multiproc_cached = any(
                d.process_index != jax.process_index()
                for d in self.mesh.devices.flat)
        return self._multiproc_cached

    def _global_put(self, val, sh):
        """Place a host value under ``sh``; on a multi-host mesh each
        process materializes only its addressable shards (params must be
        initialized identically on every process — same seed)."""
        if self._multiproc():
            val = np.asarray(val)
            return jax.make_array_from_callback(
                val.shape, sh, lambda idx: val[idx])
        return jax.device_put(val, sh)

    def _precision_scope(self):
        import contextlib
        if self.matmul_precision is None:
            return contextlib.nullcontext()
        return jax.default_matmul_precision(self.matmul_precision)

    def _set_base_key(self, key) -> None:
        """Install the RNG base key with a PINNED placement (replicated on
        this mesh) so a fresh bind and a checkpoint restore produce the
        same jit signature — swapping the key never retraces."""
        try:
            typed = jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
        except Exception:
            typed = False
        if not typed:
            key = self._global_put(jnp.asarray(key), replicated(self.mesh))
        self._base_key = key

    # ------------------------------------------------------------------
    # Bind: infer shapes, initialize + place params, compile the step
    # ------------------------------------------------------------------

    def bind(self, data_shapes: Dict[str, Tuple[int, ...]],
             label_shapes: Optional[Dict[str, Tuple[int, ...]]] = None,
             arg_params: Optional[Dict[str, Any]] = None,
             aux_params: Optional[Dict[str, Any]] = None) -> "ShardedTrainer":
        """``data_shapes``/``label_shapes`` carry the GLOBAL batch size —
        the per-chip shard is batch // mesh.shape[data_axis]."""
        sym = self.symbol
        input_shapes = dict(data_shapes)
        input_shapes.update(label_shapes or {})
        ndata = (self.mesh.shape[self.data_axis]
                 if self.data_axis is not None else 1)
        for name, shape in input_shapes.items():
            if shape[0] % (ndata * self.grad_accum):
                raise MXNetError(
                    f"global batch {shape[0]} for {name!r} not divisible by "
                    f"data-axis size {ndata} x grad_accum {self.grad_accum}")
        arg_names = sym.list_arguments()
        self._input_names = [n for n in arg_names if n in input_shapes]
        self._label_names = [n for n in arg_names
                             if n in (label_shapes or {})]
        self._param_names = [n for n in arg_names if n not in input_shapes]
        self._aux_names = sym.list_auxiliary_states()

        # under grad_accum the graph evaluates PER MICROBATCH — symbols
        # that bake the batch into Reshape ops (transformer-lm) must be
        # built for the microbatch size, and inference validates that
        infer_shapes = {n: (s[0] // self.grad_accum,) + tuple(s[1:])
                        for n, s in input_shapes.items()}
        arg_shapes, _, aux_shapes = sym.infer_shape(**infer_shapes)
        if any(s is None for s in arg_shapes):
            raise MXNetError("bind: incomplete shape inference")
        shape_of = dict(zip(arg_names, arg_shapes))
        # _input_shapes keeps the FULL global batch (external consumers
        # like the bench FLOPs twin rely on that); only inference above
        # used the microbatch view
        self._input_shapes = {n: tuple(input_shapes[n])
                              for n in self._input_names}

        # initialize on host, then place onto the mesh with the rule's spec
        host = cpu()
        params: Dict[str, jax.Array] = {}
        for n in self._param_names:
            nd = NDArray(np.zeros(shape_of[n], np.float32), ctx=host)
            if arg_params and n in arg_params:
                src = arg_params[n]
                nd._write(jnp.asarray(src.data if isinstance(src, NDArray)
                                      else src))
            else:
                self.initializer(n, nd)
            params[n] = self._global_put(
                nd.data, NamedSharding(self.mesh, self.rules.spec_for(n)))
        aux: Dict[str, jax.Array] = {}
        for n, s in zip(self._aux_names, aux_shapes):
            nd = NDArray(np.zeros(s, np.float32), ctx=host)
            if aux_params and n in aux_params:
                src = aux_params[n]
                nd._write(jnp.asarray(src.data if isinstance(src, NDArray)
                                      else src))
            else:
                self.initializer(n, nd)
            aux[n] = self._global_put(nd.data, replicated(self.mesh))

        opt = self.optimizer
        # loss-head gradients are per-sample (summed into weight grads), so
        # default rescale to 1/global-batch like the estimator path does
        # (reference model.py rescale_grad=1/batch_size); an explicitly
        # chosen rescale_grad wins, and the shared optimizer object is not
        # mutated — the override lives on this trainer
        if getattr(opt, "_rescale_set", True):
            self._rescale_grad = opt.rescale_grad
        else:
            batch0 = next(iter(data_shapes.values()))[0]
            self._rescale_grad = 1.0 / float(batch0)
        plans = {n: self._zero_plan(n, shape_of[n])
                 for n in self._param_names}
        self._zero_specs = {n: p[0] for n, p in plans.items()}
        self._zero_flat = {n: p[1] for n, p in plans.items()}
        if self.shard_optimizer and self.data_axis is not None:
            rule_sharded = [n for n in self._param_names
                            if any(ax is not None
                                   for ax in self.rules.spec_for(n))]
            dim_sharded = [n for n, (sp, fl) in plans.items()
                           if fl is None and n not in rule_sharded
                           and any(ax is not None for ax in sp)]
            flat = [n for n, (_, fl) in plans.items() if fl is not None]
            left = [n for n in self._param_names
                    if n not in rule_sharded and n not in dim_sharded
                    and n not in flat]
            self.logger.info(
                "ZeRO: %d params dim-sharded, %d flatten-pad-sharded, "
                "%d TP-rule-sharded, %d replicated%s", len(dim_sharded),
                len(flat), len(rule_sharded), len(left),
                (" (" + ", ".join(left) + ")") if left else "")
        self._num_update = opt.begin_num_update
        self._lr_mult = {n: opt.lr_mult.get(n, 1.0)
                         for n in self._param_names}
        self._wd_mult = {}
        for n in self._param_names:
            if n in opt.wd_mult:
                self._wd_mult[n] = opt.wd_mult[n]
            elif n.endswith(("_gamma", "_beta", "_bias")):
                self._wd_mult[n] = 0.0
            else:
                self._wd_mult[n] = 1.0
        self._setup_fused(shape_of, params)
        opt_state = {}
        if self._fused:
            # bucket-aligned optimizer state: moments live as replicated
            # flat f32 buffers in the SAME streaming order as the reduced
            # grad buckets, keyed "fused:<i>" (checkpoints namespace them
            # opt:fused:<i>:<leaf> like any other opt-state entry)
            rep = replicated(self.mesh)
            for i, blen in enumerate(self._fused_plan.bucket_sizes):
                opt_state[f"fused:{i}"] = jax.tree.map(
                    lambda z: self._global_put(z, rep),
                    opt.state_zeros_like(jnp.zeros((blen,), jnp.float32)))
        else:
            for n in self._param_names:
                flat_len = self._zero_flat[n]
                template = (jnp.zeros((flat_len,), params[n].dtype)
                            if flat_len is not None else params[n])
                opt_state[n] = jax.tree.map(
                    lambda z, _n=n: self._global_put(
                        z, NamedSharding(self.mesh, self._zero_specs[_n])),
                    opt.state_zeros_like(template))
        if self._fused and not self._fused_wd_uniform:
            # per-bucket wd segment vectors (satellite of ROADMAP item
            # 4): each element holds its param's effective wd, laid out
            # in bucket order, so the kernel's wd multiply stays one
            # elementwise op.  Static config, not training state — they
            # ride opt_state for donation/placement but are excluded
            # from checkpoints (_state_arrays) so a restore never
            # resurrects a stale wd schedule.
            rep = replicated(self.mesh)
            for i, bucket in enumerate(self._fused_plan.buckets):
                vec = np.empty(sum(s1 - s0 for _, s0, s1 in bucket),
                               np.float32)
                off = 0
                for n, s0, s1 in bucket:
                    vec[off:off + (s1 - s0)] = np.float32(
                        opt.wd * self._wd_mult[n])
                    off += s1 - s0
                opt_state[f"fusedwd:{i}"] = self._global_put(vec, rep)
        self._ef_keys = []
        if self.error_feedback:
            # one persistent f32 residual per grad bucket, sharded over
            # the data axis (each shard carries ITS OWN quantization
            # error).  Flat 1-D so a cross-mesh checkpoint restore can
            # pad/slice it mechanically (checkpoint/reader._adapt_shape)
            # — a sliced residual loses at most one step's sub-quantum
            # correction, never correctness.
            ndata = self.mesh.shape[self.data_axis]
            ef_sh = NamedSharding(self.mesh, P(self.data_axis))
            for i, blen in enumerate(self._grad_bucket_lens(params)):
                key = f"efres:{i}"
                opt_state[key] = self._global_put(
                    np.zeros(ndata * blen, np.float32), ef_sh)
                self._ef_keys.append(key)

        self._params, self._aux, self._opt_state = params, aux, opt_state
        if self._resil is not None:
            # replicated scalars with PINNED placement (like the RNG base
            # key): swapping values — dynamic scale updates, checkpoint
            # restore, rollback — never changes the program signature
            rep = replicated(self.mesh)
            self._guard_state = {
                k: self._global_put(v, rep)
                for k, v in resilience.init_state(self._resil).items()}
            self._resil_base = {k: 0 for k in resilience.WINDOW_KEYS}
        if self.grad_compression is not None:
            sharded = [n for n in self._param_names
                       if any(ax is not None
                              for ax in self.rules.spec_for(n))]
            if sharded:
                raise MXNetError(
                    "grad_compression runs the backward in a manual "
                    "region with replicated params; tensor-parallel "
                    f"rules shard {sharded[:3]}... — use the implicit "
                    "GSPMD path for TP models")
        self._compile()
        self._bound = True
        return self

    def _zero_plan(self, name: str,
                   shape: Tuple[int, ...]) -> Tuple[P, Optional[int]]:
        """Placement plan for the optimizer state (and in-step update) of
        one param: ``(spec, flat_padded_len)``.  Without ZeRO the spec is
        the param's own rule spec (flat None).  With ZeRO, rule-replicated
        params get their first data-axis-divisible dim sharded over
        ``data``; params with NO divisible dim (biases, BN scales) fall
        back to a FLATTEN-AND-PAD layout — state lives as a 1-D array
        padded to a multiple of the data-axis size and sharded ``P(data)``
        — so at pod scale nothing stays replicated.  TP-sharded params
        keep their rule spec (already distributed)."""
        rule_spec = self.rules.spec_for(name)
        if not self.shard_optimizer or self.data_axis is None:
            return rule_spec, None
        if any(ax is not None for ax in rule_spec):
            return rule_spec, None
        n = self.mesh.shape[self.data_axis]
        for dim, size in enumerate(shape):
            if size % n == 0 and size > 0:
                spec = [None] * len(shape)
                spec[dim] = self.data_axis
                return P(*spec), None
        numel = int(np.prod(shape)) if shape else 1
        padded = -(-numel // n) * n  # ceil to a multiple of the data axis
        return P(self.data_axis), padded

    def _setup_fused(self, shape_of, params) -> None:
        """Decide whether this bind runs the single-pass fused update
        (ops/fused_update.py) and build the bucket plan if so.  The gate
        is conservative: any configuration the kernel cannot express
        bitwise (per-param multipliers, sharded state, non-f32 masters)
        silently falls back to the unfused path — unless the user forced
        ``fused_update=True``, which makes ineligibility an error."""
        from ..ops import fused_update as fu
        self._fused = False
        self._fused_kind = None
        self._fused_plan = None
        req = self._fused_req
        if req is False or (req is None and not fu.fused_enabled()):
            return
        kind = fu.fused_kind(self.optimizer)
        why = []
        if not self._param_names:
            why.append("no parameters")
        if kind is None:
            why.append(f"optimizer {type(self.optimizer).__name__} has "
                       "no fused twin")
        if self.shard_optimizer:
            why.append("shard_optimizer (ZeRO state layout)")
        if any(ax is not None for n in self._param_names
               for ax in self.rules.spec_for(n)):
            why.append("tensor-parallel param sharding")
        if any(params[n].dtype != jnp.float32 for n in self._param_names):
            why.append("non-f32 master params")
        if any(int(np.prod(shape_of[n], dtype=np.int64)) == 0
               for n in self._param_names):
            why.append("zero-size params")
        if len({float(v) for v in self._lr_mult.values()}) > 1:
            why.append("per-param lr_mult")
        # per-param effective wd (gamma/beta/bias exclusion) is fused-
        # eligible: a non-uniform layout rides a per-bucket wd segment
        # vector operand into the kernel (opt_state "fusedwd:<i>")
        self._fused_wd_uniform = len(
            {float(self.optimizer.wd * v)
             for v in self._wd_mult.values()}) <= 1
        if kind == "adam" and any(
                float(self.optimizer.wd * v) != 0.0
                for v in self._wd_mult.values()):
            # adam FOLDS wd into the gradient (g + wd*w) and that fold
            # feeds both moments; LLVM's FMA contraction of it is
            # context-dependent, so the fused twin is 1 ulp off the
            # inline unfused step — no bitwise twin exists.  (adamw's
            # DECOUPLED wd never touches the grad and stays bitwise;
            # sgd's fold has a single consumer and contracts the same
            # way in both contexts.)
            why.append("adam with weight decay (folded wd has no "
                       "bitwise fused twin; use adamw)")
        if why:
            if req:
                raise MXNetError("fused_update=True but this "
                                 "configuration cannot fuse: "
                                 + "; ".join(why))
            self.logger.debug("fused update off: %s", "; ".join(why))
            return
        self._fused_kind = kind
        self._fused_plan = fu.build_plan(self._param_names, shape_of,
                                         self.grad_bucket_bytes)
        self._fused = True

    def _zero_spec(self, name: str, shape: Tuple[int, ...]) -> P:
        return self._zero_plan(name, shape)[0]

    def optimizer_state_bytes_per_device(self) -> int:
        """Per-chip bytes held by optimizer state (the ZeRO savings gauge)."""
        total = 0
        for st in self._opt_state.values():
            for leaf in jax.tree.leaves(st):
                shard = leaf.sharding.shard_shape(leaf.shape)
                total += int(np.prod(shard)) * leaf.dtype.itemsize
        return total

    def _grad_bucket_lens(self, params) -> List[int]:
        """Element count of every grad bucket ``reduce_grads`` will emit,
        in dispatch order — the bind-time mirror that sizes the error-
        feedback residuals.  Must iterate exactly like ``reduce_grads``
        (reversed param order, dtype classes in first-seen order, greedy
        ``plan_buckets`` fill); grad dtype == master param dtype."""
        from .collectives import plan_buckets
        order = [n for n in reversed(self._param_names)]
        by_dtype: Dict[Any, List[str]] = {}
        for n in order:
            by_dtype.setdefault(jnp.dtype(params[n].dtype), []).append(n)
        lens: List[int] = []
        for dtype, names in by_dtype.items():
            counts = [int(np.prod(params[n].shape, dtype=np.int64))
                      for n in names]
            counts = [c for c in counts if c > 0]
            if not counts:
                continue
            plan = plan_buckets(counts, dtype.itemsize,
                                self.grad_bucket_bytes)
            lens.extend(sum(s1 - s0 for _, s0, s1 in b) for b in plan)
        return lens

    def _explicit_comm_grads(self, base, resil: bool = False,
                             bucket_out: bool = False, ef: bool = False):
        """Wrap the grad computation in a manual shard_map region over the
        data axis: per-shard backward, then explicit bucketed (and
        optionally quantized) psums of the gradients — the comm path this
        trades for XLA's implicit all-reduce.

        Buckets are emitted last-declared-params-first: their grads exit
        backward earliest, so their reductions can overlap with the
        differentiation of earlier layers.  Manual-region semantics
        caveats (same family as ``SpmdPipelineTrainer``): loss heads
        should keep the default ``normalization='null'`` (per-shard
        'batch'/'valid' normalization applies before the cross-shard
        sum), BatchNorm batch statistics are per-shard with pmean'd
        running aux, and dropout draws a distinct stream per shard.

        With ``resil`` the wrapper threads the loss-scale scalar through
        to ``base`` and piggybacks the guard's square-sum statistic on the
        bucket traversal: each reduced flat bucket is already a contiguous
        f32-castable buffer, so the finite/norm stat costs one fused
        reduction per bucket and NO extra pass over the per-tensor grads.
        The body then returns it as a fourth (replicated) output.

        With ``bucket_out`` (the fused-update path) the reduced flat
        buckets are returned AS-IS — a list in plan order — instead of
        being scattered back to per-tensor grads: the fused kernel
        consumes them directly, so the scatter pass (one extra
        read+write of every bucket) disappears entirely.

        With ``ef`` the body additionally takes the list of per-shard
        error-feedback residuals (one flat f32 per bucket, in dispatch
        order) and returns the updated residuals as its last output:
        each bucket quantizes ``grads + residual`` and the residual
        becomes exactly the quantization error just committed
        (collectives.psum_compressed).
        """
        from .collectives import plan_buckets, psum_compressed
        daxis = self.data_axis
        comp = self.grad_compression
        bucket_bytes = self.grad_bucket_bytes
        param_names = list(self._param_names)

        def reduce_grads(grads, ef_res=None):
            order = [n for n in reversed(param_names) if n in grads]
            by_dtype: Dict[Any, List[str]] = {}
            for n in order:
                by_dtype.setdefault(jnp.dtype(grads[n].dtype), []).append(n)
            out = dict(grads)
            flat_buckets: List[jax.Array] = []
            new_ef: List[jax.Array] = []
            bidx = 0
            sq = jnp.float32(0.0)
            for dtype, names in by_dtype.items():
                names = [n for n in names
                         if int(np.prod(grads[n].shape, dtype=np.int64)) > 0]
                if not names:
                    continue
                counts = [int(np.prod(grads[n].shape, dtype=np.int64))
                          for n in names]
                plan = plan_buckets(counts, dtype.itemsize, bucket_bytes)
                pieces: Dict[str, List[jax.Array]] = {n: [] for n in names}
                for bucket in plan:
                    segs = [grads[names[pi]].ravel()[s0:s1]
                            for pi, s0, s1 in bucket]
                    flat = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
                    if ef_res is not None:
                        red, nres = psum_compressed(
                            flat, daxis, comp, residual=ef_res[bidx])
                        new_ef.append(nres)
                    else:
                        red = psum_compressed(flat, daxis, comp)
                    bidx += 1
                    if resil:
                        # fused guard stat on the reduced flat bucket
                        sq = sq + jnp.sum(jnp.square(
                            red.astype(jnp.float32)))
                    if bucket_out:
                        # fused update consumes the flat bucket directly
                        flat_buckets.append(red)
                        continue
                    off = 0
                    for pi, s0, s1 in bucket:
                        pieces[names[pi]].append(red[off:off + (s1 - s0)])
                        off += s1 - s0
                if bucket_out:
                    continue
                for n in names:
                    ps = pieces[n]
                    flat = ps[0] if len(ps) == 1 else jnp.concatenate(ps)
                    out[n] = flat.reshape(grads[n].shape)
            res = (flat_buckets, sq) if bucket_out else (out, sq)
            return res + ((new_ef,) if ef_res is not None else ())

        # the residual lists ride in/out as pytrees; P(data_axis) as a
        # pytree-prefix spec shards every flat residual over data — each
        # shard sees/updates only ITS OWN (bucket_len,) error slice
        ef_spec = (P(self.data_axis),) if ef else ()
        if resil:
            def body(params, aux, batch, rng, scale, *ef_res):
                rng = jax.random.fold_in(rng, jax.lax.axis_index(daxis))
                grads, heads, auxu = base(params, aux, batch, rng, scale)
                red = reduce_grads(grads, *ef_res)
                auxu = {k: jax.lax.pmean(v, daxis) for k, v in auxu.items()}
                return (red[0], heads, auxu, red[1]) + tuple(red[2:])

            kwargs = dict(mesh=self.mesh,
                          in_specs=(P(), P(), P(self.data_axis), P(), P())
                          + ef_spec,
                          out_specs=(P(), P(self.data_axis), P(), P())
                          + ef_spec)
        else:
            def body(params, aux, batch, rng, *ef_res):
                # distinct per-shard stream (dropout etc.); GSPMD gets the
                # same effect from per-example positions in the global batch
                rng = jax.random.fold_in(rng, jax.lax.axis_index(daxis))
                grads, heads, auxu = base(params, aux, batch, rng)
                red = reduce_grads(grads, *ef_res)
                auxu = {k: jax.lax.pmean(v, daxis) for k, v in auxu.items()}
                return (red[0], heads, auxu) + tuple(red[2:])

            kwargs = dict(mesh=self.mesh,
                          in_specs=(P(), P(), P(self.data_axis), P())
                          + ef_spec,
                          out_specs=(P(), P(self.data_axis), P())
                          + ef_spec)
        return jax.shard_map(body, check_vma=False, **kwargs)

    def _compile(self):
        sym, opt = self.symbol, self.optimizer
        topo = sym._topo()
        input_names = list(self._input_names)
        param_names = list(self._param_names)
        hyper = opt._hyper()
        hyper["rescale_grad"] = self._rescale_grad
        step_fn = type(opt)._functional_step
        lr_mult, wd_mult = dict(self._lr_mult), dict(self._wd_mult)
        base_wd = opt.wd
        needs_rng = type(opt)._needs_rng

        fused = self._fused
        if fused:
            from ..analysis.program import tag as _tag_val
            from ..ops import fused_update as _fu
            fused_plan = self._fused_plan
            fused_kind = self._fused_kind
            n_buckets = len(fused_plan.buckets)
            # the gate proved lr_mult uniform across params; wd is either
            # uniform (scalar into the kernel) or rides the per-bucket
            # "fusedwd:<i>" segment vectors built at bind
            lr_common = float(next(iter(lr_mult.values())))
            wd_uniform = self._fused_wd_uniform
            wd_common = (float(base_wd * next(iter(wd_mult.values())))
                         if wd_uniform else 0.0)
            f_momentum = float(getattr(opt, "momentum", 0.0) or 0.0)
            f_b1 = float(getattr(opt, "beta1", 0.0) or 0.0)
            f_b2 = float(getattr(opt, "beta2", 0.0) or 0.0)
            f_eps = float(getattr(opt, "epsilon", 0.0) or 0.0)
            f_clip = hyper.get("clip_gradient")

        # per-step RNG keys fold from the update counter INSIDE the
        # program (no per-step host->device key transfer to wait on
        # before dispatch), and the base key is a PROGRAM
        # ARGUMENT rather than a closure constant: restore_state swaps
        # ``self._base_key`` without retracing (the jit cache keys on the
        # key's shape/dtype/sharding, which _set_base_key pins), and a
        # persistent-cache executable stays valid across runs that resume
        # with different keys.
        from .. import random as _random
        from ..analysis.program import mark_grads as _mark_grads
        if getattr(self, "_base_key", None) is None:
            self._set_base_key(_random._next_key())

        zero_shardings = {
            n: (NamedSharding(self.mesh, self._zero_specs[n])
                if self.shard_optimizer
                and self._zero_specs[n] != self.rules.spec_for(n) else None)
            for n in param_names}
        zero_flat = dict(self._zero_flat)

        cdt = self.compute_dtype

        def cast_params(p):
            if cdt is None:
                return dict(p)
            # f32 -> compute dtype at the program edge; the vjp of the
            # cast delivers f32 grads back to the master params
            return {n: (v.astype(cdt) if v.dtype == jnp.float32 else v)
                    for n, v in p.items()}

        accum = self.grad_accum
        resil = self._resil
        scaling = bool(resil is not None and resil.scaling)

        def _grads_and_heads(params, aux, batch, rng, *scale_arg):
            def fwd(p):
                args = cast_params(p)
                args.update(batch)
                heads, auxu = eval_symbol(sym, args, aux, rng, True,
                                          topo=topo)
                return heads, auxu
            heads, vjp_fn, auxu = jax.vjp(fwd, params, has_aux=True)
            if scaling:
                # loss scaling = scaled head cotangents: the whole
                # backward runs at `scale`x magnitude so bf16/f16
                # gradients clear the subnormal floor; the unscale folds
                # into the combined clip multiplier below (f32 master
                # grads — no precision loss)
                (scale,) = scale_arg
                ones = tuple(jnp.broadcast_to(scale.astype(h.dtype),
                                              h.shape) for h in heads)
            else:
                ones = tuple(jnp.ones(h.shape, h.dtype) for h in heads)
            (grads,) = vjp_fn(ones)
            return grads, heads, auxu

        explicit = (self.grad_compression is not None
                    and self.data_axis is not None)
        # zero-copy handoff: on the explicit-comm path (accum == 1) the
        # reduced flat buckets skip the scatter-back entirely and feed
        # the fused kernel as-is; under accum > 1 grads must still sum
        # per-tensor across the scan, so the fused path gathers them
        explicit_fused = explicit and fused and accum == 1
        ef = bool(self.error_feedback and explicit and accum == 1)
        ef_keys = list(self._ef_keys) if ef else []
        if explicit:
            _grads_and_heads = self._explicit_comm_grads(
                _grads_and_heads, resil=resil is not None,
                bucket_out=explicit_fused, ef=ef)

        if fused:
            def _fused_apply(params, grads, opt_state, lr, t, mult, ok):
                """One fused primitive per bucket.  ``grads`` is either
                the per-param dict (gathered into plan order here) or,
                on the explicit-comm path, the already-reduced flat
                buckets.  The scalar chain below mirrors the unfused
                ``_functional_step`` op-for-op so parity is bitwise."""
                lr_eff = lr * lr_common
                if fused_kind in ("sgd", "sgd_momentum"):
                    scalars = (lr_eff,)
                else:
                    # Adam/AdamW bias correction, exactly as in
                    # optimizer.py (t cast to the f32 weight dtype)
                    tf = jnp.asarray(t, dtype=jnp.float32)
                    lr_t = (lr_eff * jnp.sqrt(1.0 - f_b2 ** tf)
                            / (1.0 - f_b1 ** tf))
                    # with a wd segment vector the kernel forms lrwd =
                    # lr_eff * wdvec elementwise; the scalar stays lr_eff
                    scalars = ((lr_t,) if fused_kind == "adam"
                               else (lr_t, lr_eff * wd_common)
                               if wd_uniform else (lr_t, lr_eff))
                if isinstance(grads, dict):
                    buckets = [fused_plan.gather(grads, i)
                               for i in range(n_buckets)]
                else:
                    buckets = grads
                new_w_buckets = []
                new_opt = {}
                for i, g in enumerate(buckets):
                    w = fused_plan.gather(params, i)
                    # auditor anchor: everything after this tag must be
                    # the ONE fused eqn (program.fused-update rule)
                    g = _tag_val(g, label=f"gradbucket:{i}")
                    leaves, treedef = jax.tree_util.tree_flatten(
                        opt_state[f"fused:{i}"])
                    res = _fu.fused_update(
                        g, w, tuple(leaves), scalars, kind=fused_kind,
                        mult=mult, ok=ok, momentum=f_momentum,
                        beta1=f_b1, beta2=f_b2, epsilon=f_eps,
                        wd=wd_common, rescale_grad=self._rescale_grad,
                        clip_gradient=f_clip,
                        wd_vec=(None if wd_uniform
                                else opt_state[f"fusedwd:{i}"]),
                        mesh=self.mesh)
                    new_w_buckets.append(res[0])
                    new_opt[f"fused:{i}"] = jax.tree_util.tree_unflatten(
                        treedef, list(res[1:]))
                return fused_plan.scatter(new_w_buckets), new_opt

        def _unfused_apply(params, grads, opt_state, lr, t, rng, ok):
            new_params, new_opt = {}, {}
            for i, n in enumerate(param_names):
                prng = jax.random.fold_in(rng, i) if needs_rng else None
                w, g = params[n], grads[n]
                flat_len = zero_flat[n]
                if flat_len is not None:
                    # ZeRO flatten-and-pad: indivisible params (biases,
                    # BN scales) update in a padded 1-D layout sharded
                    # over data; the zero-padded tail stays zero under
                    # every elementwise optimizer (g=0, w=0)
                    shape = w.shape
                    pad = flat_len - int(np.prod(shape))
                    w = jnp.pad(w.reshape(-1), (0, pad))
                    g = jnp.pad(g.reshape(-1), (0, pad))
                if zero_shardings[n] is not None:
                    # ZeRO: constrain grad + weight to the data-sharded
                    # spec — XLA emits reduce-scatter for the grad sum and
                    # a local slice of the replicated weight; the update
                    # below then runs on 1/N of the param, and the
                    # replicated out_sharding all-gathers the result
                    g = jax.lax.with_sharding_constraint(g, zero_shardings[n])
                    w = jax.lax.with_sharding_constraint(w, zero_shardings[n])
                w2, s2 = step_fn(hyper, w, g, opt_state[n],
                                 lr * lr_mult[n], base_wd * wd_mult[n],
                                 t, prng)
                if flat_len is not None:
                    w2 = w2[:int(np.prod(shape))].reshape(shape)
                if ok is not None:
                    # the non-finite gate: a bad step selects the OLD
                    # param/opt buffers, so the update is a bitwise no-op
                    # while staying donation-safe (same program, same
                    # buffer flow) and requiring no host sync
                    w2 = jnp.where(ok, w2, params[n])
                    s2 = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(ok, a, b), s2, opt_state[n])
                new_params[n] = w2
                new_opt[n] = s2
            return new_params, new_opt

        def train_step(params, aux, opt_state, batch, lr, t, base_key,
                       gstate=None):
            rng = jax.random.fold_in(base_key, t)
            scale_args = ((gstate["scale"],) if resil is not None else ())
            sq = None
            new_ef = None

            if accum > 1:
                # [B, ...] -> [k, B/k, ...]; grads sum across the scan,
                # one update at the end; activations live per-microbatch
                def to_micro(v):
                    r = v.reshape((accum, v.shape[0] // accum) + v.shape[1:])
                    if self.data_axis is not None:
                        # keep the PER-MICROBATCH rows sharded over data
                        spec = P(None, self.data_axis,
                                 *([None] * (r.ndim - 2)))
                        r = jax.lax.with_sharding_constraint(
                            r, NamedSharding(self.mesh, spec))
                    return r
                mb = {n: to_micro(v) for n, v in batch.items()}
                gzero = jax.tree.map(jnp.zeros_like, params)

                # distinct stream from the per-param optimizer keys
                # (which fold small ints from the same rng)
                accum_rng = jax.random.fold_in(rng, 0xACC)

                def micro(carry, xs):
                    aux_c, gsum, i = carry
                    res = _grads_and_heads(
                        params, aux_c, xs, jax.random.fold_in(accum_rng, i),
                        *scale_args)
                    grads, heads, auxu = res[0], res[1], res[2]
                    aux_n = dict(aux_c)
                    aux_n.update(auxu)
                    return (aux_n, jax.tree.map(jnp.add, gsum, grads),
                            i + 1), heads
                (auxf, grads, _), heads_k = jax.lax.scan(
                    micro, (dict(aux), gzero, jnp.int32(0)), mb)
                heads = tuple(h.reshape((-1,) + h.shape[2:])
                              for h in heads_k)
                auxu = auxf
            else:
                ef_args = (([opt_state[k] for k in ef_keys],) if ef else ())
                res = _grads_and_heads(params, aux, batch, rng, *scale_args,
                                       *ef_args)
                grads, heads, auxu = res[0], res[1], res[2]
                rest = list(res[3:])
                if resil is not None and explicit:
                    # explicit-comm path: guard stat came fused off the
                    # reduced flat buckets (no extra pass over grads)
                    sq = rest.pop(0)
                new_ef = rest.pop(0) if ef else None

            # identity-tag the grads for the static auditor's HBM-pass
            # counter: mxtpu_tag lowers to nothing, so HLO, executables
            # and compile-cache keys are unchanged (analysis/program.py).
            # The fused path tags its flat buckets (gradbucket:<i>)
            # inside _fused_apply instead.
            if not fused:
                grads = _mark_grads(grads)

            ok = None
            mult = None
            if resil is not None:
                if sq is None:
                    sq = resilience.tree_sq_sum(grads)
                # overflow of the f32 square-sum reads as non-finite —
                # exactly right: a gradient too large to measure is a step
                # we must not take (and dynamic scaling backs off)
                ok = jnp.isfinite(sq)
                eff_norm = jnp.sqrt(sq) * jnp.float32(
                    abs(self._rescale_grad) or 1.0)
                if scaling:
                    inv_scale = jnp.float32(1.0) / gstate["scale"]
                    eff_norm = eff_norm * inv_scale
                    mult = inv_scale
                if resil.clip_global_norm is not None:
                    coef = jnp.minimum(
                        jnp.float32(1.0),
                        jnp.float32(resil.clip_global_norm)
                        / jnp.maximum(eff_norm, jnp.float32(1e-12)))
                    mult = coef if mult is None else mult * coef
                if mult is not None and not fused:
                    # ONE combined multiplier (unscale x clip) applied
                    # once; with neither feature on, no multiply at all —
                    # a guard-on clean run stays bitwise identical to
                    # guard-off (pinned by tests/test_resilience.py).
                    # On the fused path mult rides INTO the kernel.
                    grads = {n: g * mult.astype(g.dtype)
                             for n, g in grads.items()}
            if fused:
                # single streaming pass per bucket: combined multiplier,
                # guard verdict and the whole optimizer update ride ONE
                # primitive (ops/fused_update.py); the where-gating lives
                # inside it, so a bad step stays a bitwise no-op
                new_params, new_opt = _fused_apply(
                    params, grads, opt_state, lr, t, mult, ok)
            else:
                new_params, new_opt = _unfused_apply(
                    params, grads, opt_state, lr, t, rng, ok)
            if ef:
                # a bad step keeps the OLD residual: the new one was
                # computed from non-finite grads and would poison every
                # following step's feedback
                for k, nres in zip(ef_keys, new_ef):
                    new_opt[k] = (jnp.where(ok, nres, opt_state[k])
                                  if ok is not None else nres)
            for k in opt_state:
                # static opt-state riders (wd segment vectors) pass
                # through unchanged — identity keeps donation aliasing
                if k not in new_opt:
                    new_opt[k] = opt_state[k]
            new_aux = dict(aux)
            if resil is not None:
                for k, v in auxu.items():
                    new_aux[k] = jnp.where(ok, v, aux[k])
                new_gstate = resilience.state_update(gstate, ok, eff_norm,
                                                     resil)
                return new_params, new_aux, new_opt, heads, new_gstate
            new_aux.update(auxu)
            return new_params, new_aux, new_opt, heads

        def eval_step(params, aux, batch, t, base_key):
            # distinct stream for eval so eval-mode rng never correlates
            # with the train step that shares a counter value
            rng = jax.random.fold_in(jax.random.fold_in(base_key, 0x5EED), t)
            if accum > 1:
                # batch-baked symbols evaluate at the MICROBATCH size;
                # map the graph over the k microbatches and restitch
                mb = {n: v.reshape((accum, v.shape[0] // accum)
                                   + v.shape[1:]) for n, v in batch.items()}

                def one(xs):
                    args = cast_params(params)
                    args.update(xs)
                    heads, _ = eval_symbol(sym, args, aux, rng, False,
                                           topo=topo)
                    return heads
                heads_k = jax.lax.map(one, mb)
                return tuple(h.reshape((-1,) + h.shape[2:])
                             for h in heads_k)
            args = cast_params(params)
            args.update(batch)
            heads, _ = eval_symbol(sym, args, aux, rng, False, topo=topo)
            return heads

        p_shard = {n: NamedSharding(self.mesh, self.rules.spec_for(n))
                   for n in param_names}
        a_shard = {n: replicated(self.mesh) for n in self._aux_names}
        # opt state keys are param names on the unfused path, "fused:<i>"
        # bucket keys on the fused path (always replicated there);
        # error-feedback residuals are per-shard, pinned to P(data)
        def _opt_spec(k):
            if k.startswith("efres:"):
                return P(self.data_axis)
            return self._zero_specs.get(k, P())
        o_shard = {k: jax.tree.map(
            lambda _, _s=NamedSharding(self.mesh, _opt_spec(k)): _s,
            self._opt_state[k]) for k in self._opt_state}
        # retrace guards: the counter bump is a host side effect, so it
        # fires only while jax traces the function — in steady state each
        # program's count stays at exactly 1 (asserted by
        # assert_steady_state / tests/test_step_overhead.py)
        def _counted(kind, fn):
            def wrapped(*args):
                self.trace_counts[kind] += 1
                return fn(*args)
            return wrapped

        self.trace_counts = {"train": 0, "train_acc": 0, "eval": 0}
        self._train_sigs = []
        g_shard = ({k: replicated(self.mesh) for k in resilience.STATE_KEYS}
                   if resil is not None else None)
        train_out_sh = ((p_shard, a_shard, o_shard, None, g_shard)
                        if resil is not None
                        else (p_shard, a_shard, o_shard, None))
        self._train_step = jax.jit(
            _counted("train", train_step),
            out_shardings=train_out_sh,
            donate_argnums=(0, 1, 2))
        self._eval_step = jax.jit(_counted("eval", eval_step))

        # fit()'s fused-metric variant: the Accuracy fold runs INSIDE the
        # compiled step (zero extra dispatches, zero per-batch host
        # syncs).  jit is lazy — this never compiles unless fit() uses it.
        label_names = list(self._label_names)

        def _fold_acc(heads, batch, c):
            for ln, head in zip(label_names, heads):
                pred = head
                if pred.ndim > 1:
                    pred = jnp.argmax(pred, axis=1)
                # keep the carry a dtype fixed point: under x64 a bool-sum
                # promotes to int64 and int32+int64 widens the output,
                # which retraces the whole step program on the next batch
                c = c + jnp.sum(pred.astype(jnp.int32).reshape(-1)
                                == batch[ln].astype(jnp.int32).reshape(-1)
                                ).astype(c.dtype)
            return c

        if resil is not None:
            def train_step_acc(params, aux, opt_state, batch, lr, t, carry,
                               base_key, gstate):
                new_p, new_a, new_o, heads, gs = train_step(
                    params, aux, opt_state, batch, lr, t, base_key, gstate)
                return (new_p, new_a, new_o, heads,
                        _fold_acc(heads, batch, carry), gs)
            acc_out_sh = (p_shard, a_shard, o_shard, None, None, g_shard)
        else:
            def train_step_acc(params, aux, opt_state, batch, lr, t, carry,
                               base_key):
                new_p, new_a, new_o, heads = train_step(
                    params, aux, opt_state, batch, lr, t, base_key)
                return (new_p, new_a, new_o, heads,
                        _fold_acc(heads, batch, carry))
            acc_out_sh = (p_shard, a_shard, o_shard, None, None)

        self._train_step_acc = jax.jit(
            _counted("train_acc", train_step_acc),
            out_shardings=acc_out_sh,
            donate_argnums=(0, 1, 2))
        self._aot.clear()

    # ------------------------------------------------------------------
    # AOT warmup (compile_cache integration)
    # ------------------------------------------------------------------

    def _program_key(self, kind: str, in_avals):
        """Cache key for one step program: graph fingerprint + call avals
        + every trainer config that changes the traced computation."""
        from .. import compile_cache as cc
        from ..graph_eval import graph_fingerprint
        if getattr(self, "_graph_fp", None) is None:
            self._graph_fp = graph_fingerprint(self.symbol)
        extra = {
            "kind": kind,
            "optimizer": type(self.optimizer).__name__,
            "hyper": sorted(self.optimizer._hyper().items()),
            "rescale_grad": self._rescale_grad,
            "lr_mult": sorted(self._lr_mult.items()),
            "wd_mult": sorted(self._wd_mult.items()),
            "grad_accum": self.grad_accum,
            "compute_dtype": str(self.compute_dtype),
            "matmul_precision": self.matmul_precision,
            "shard_optimizer": self.shard_optimizer,
            "zero_specs": sorted((n, str(s))
                                 for n, s in self._zero_specs.items()),
            "grad_compression": self.grad_compression,
            "grad_bucket_bytes": self.grad_bucket_bytes,
            "error_feedback": self.error_feedback,
            "quant_block": _quant_block_key(self.grad_compression),
            "fused": self._fused_kind if self._fused else None,
            "fused_wd_vec": bool(self._fused
                                 and not self._fused_wd_uniform),
            "data_axis": self.data_axis,
            "rules": sorted((n, str(self.rules.spec_for(n)))
                            for n in self._param_names),
            "x64": bool(jax.config.jax_enable_x64),
            "resilience": (self._resil.describe()
                           if self._resil is not None else None),
        }
        donate = () if kind == "eval" else (0, 1, 2)
        return cc.program_key(self._graph_fp, in_avals, donate=donate,
                              mesh=self.mesh, extra=extra)

    def _program_avals(self):
        """Shape/dtype/sharding snapshots of the non-batch program
        arguments ``(params, aux, opt, key, guard state)``, taken on the
        calling thread — no live buffers, so background lowering or a
        later audit never touches arrays a concurrent step may donate."""
        sds = jax.ShapeDtypeStruct
        p_avals = {n: sds(v.shape, v.dtype, sharding=v.sharding)
                   for n, v in self._params.items()}
        a_avals = {n: sds(v.shape, v.dtype, sharding=v.sharding)
                   for n, v in self._aux.items()}
        o_avals = {k: jax.tree.map(
            lambda l: sds(l.shape, l.dtype, sharding=l.sharding),
            self._opt_state[k]) for k in self._opt_state}
        bkey = self._base_key
        k_aval = sds(bkey.shape, bkey.dtype,
                     sharding=getattr(bkey, "sharding", None))
        g_avals = None
        if self._guard_state is not None:
            g_avals = {k: sds(v.shape, v.dtype, sharding=v.sharding)
                       for k, v in self._guard_state.items()}
        return p_avals, a_avals, o_avals, k_aval, g_avals

    def _norm_batch_spec(self, spec):
        """One batch_spec dict -> ``{input: ShapeDtypeStruct}`` with the
        data-axis batch sharding applied."""
        sds = jax.ShapeDtypeStruct
        bsh = (batch_sharding(self.mesh, self.data_axis)
               if self.data_axis is not None else replicated(self.mesh))
        out = {}
        for n in self._input_names:
            if n not in spec:
                raise MXNetError(f"batch_spec missing input {n!r}")
            v = spec[n]
            if isinstance(v, jax.ShapeDtypeStruct):
                shape, dtype = tuple(v.shape), v.dtype
            elif isinstance(v, tuple) and len(v) == 2 \
                    and isinstance(v[0], (tuple, list)):
                shape, dtype = tuple(v[0]), jnp.dtype(v[1])
            elif hasattr(v, "shape") and hasattr(v, "dtype"):
                shape, dtype = tuple(v.shape), jnp.dtype(v.dtype)
            else:
                shape, dtype = tuple(v), jnp.float32
            out[n] = sds(shape, dtype, sharding=bsh)
        return out

    def _program_call_args(self, kind: str, b_avals, avals=None):
        """``(jit_fn, in_args)`` for one step program at the given batch
        avals — the single definition of each program's call signature,
        shared by AOT compilation and the static auditor.

        lr/t are concrete python scalars: lowering abstracts them to the
        same weak-typed avals the real dispatch produces, so a compiled
        program accepts any python float/int."""
        if avals is None:
            avals = self._program_avals()
        p_avals, a_avals, o_avals, k_aval, g_avals = avals
        sds = jax.ShapeDtypeStruct
        if kind == "train":
            jit_fn = self._train_step
            in_args = (p_avals, a_avals, o_avals, b_avals, 0.5, 1,
                       k_aval)
            if g_avals is not None:
                in_args += (g_avals,)
        elif kind == "train_acc":
            carry = sds((), jnp.int32, sharding=replicated(self.mesh))
            jit_fn = self._train_step_acc
            in_args = (p_avals, a_avals, o_avals, b_avals, 0.5, 1,
                       carry, k_aval)
            if g_avals is not None:
                in_args += (g_avals,)
        elif kind == "eval":
            jit_fn = self._eval_step
            in_args = (p_avals, a_avals, b_avals, 1, k_aval)
        else:
            raise MXNetError(f"unknown program kind {kind!r} "
                             "(train/train_acc/eval)")
        return jit_fn, in_args

    def trace_program(self, kind: str = "train", batch_spec=None):
        """Trace one step program to a ``jax.stages.Traced`` for static
        analysis (:func:`mxnet_tpu.analysis.audit_trainer`) without
        executing or caching anything.  Returns ``(traced, in_args)``;
        ``traced.jaxpr`` is the closed jaxpr, ``traced.lower()`` the
        lowering the auditor inspects for donation/sharding."""
        if not self._bound:
            raise MXNetError("call bind() before trace_program()")
        spec = batch_spec if batch_spec is not None else self._input_shapes
        b_avals = self._norm_batch_spec(spec)
        jit_fn, in_args = self._program_call_args(kind, b_avals)
        with default_mesh(self.mesh), self._precision_scope():
            return jit_fn.trace(*in_args), in_args

    def compile(self, batch_spec=None, programs: Sequence[str] = ("train",),
                background: bool = False):
        """Ahead-of-time compile the step programs for known batch shapes
        (``jit(...).lower(...).compile()``), resolving each through the
        global :class:`~mxnet_tpu.compile_cache.ProgramCache` — a warm
        restart attaches yesterday's executable from disk instead of
        re-compiling.

        ``batch_spec``: ``{input name: shape | (shape, dtype) |
        ShapeDtypeStruct | example array}`` (default: the bound
        ``data/label_shapes`` at float32), or a LIST of such dicts to
        pre-warm several bucket shapes.  ``programs`` from
        ``train`` / ``train_acc`` (fit's fused-metric variant) /
        ``eval``.  With ``background=True`` compilation runs on a
        daemon thread (overlapping the first epoch's data loading) and
        the started Thread is returned; avals are snapshotted HERE, on
        the calling thread, so later donating steps can't race the
        lowering.  Otherwise returns a list of per-program info dicts
        (``kind``/``source``/``seconds``).

        The last program compiled per kind is installed for dispatch:
        :meth:`step`/:meth:`forward` run it directly (the jit dispatch
        cache is NOT populated by AOT compilation), falling back to the
        jit path on batch-signature mismatch.
        """
        if not self._bound:
            raise MXNetError("call bind() before compile()")
        from .. import compile_cache as cc
        specs = batch_spec if batch_spec is not None else self._input_shapes
        if isinstance(specs, dict):
            specs = [specs]

        # aval snapshots taken on THIS thread (see _program_avals)
        avals = self._program_avals()

        work = []
        for spec in specs:
            b_avals = self._norm_batch_spec(spec)
            for kind in programs:
                work.append((kind, b_avals))

        def compile_one(kind, b_avals):
            jit_fn, in_args = self._program_call_args(kind, b_avals,
                                                      avals=avals)
            key = self._program_key(kind, in_args)

            def build():
                with default_mesh(self.mesh), self._precision_scope():
                    traced = jit_fn.trace(*in_args)
                    # offer the fresh trace to registered observers
                    # (analysis.audit_on_compile) before committing it
                    cc.notify_lowering(f"trainer.{kind}", traced)
                    return traced.lower().compile()

            compiled, info = cc.get_cache().get_or_compile(
                key, build, label=f"trainer.{kind}")
            self._aot[kind] = compiled
            info = dict(info)
            info["kind"] = kind
            self.compile_info.append(info)
            return info

        if background:
            import threading

            def run():
                for kind, b_avals in work:
                    try:
                        compile_one(kind, b_avals)
                    except Exception:
                        # the step still runs (through jit), so count
                        # the failure where callers can assert on it
                        self.aot_stats["compile_errors"] += 1
                        self.logger.exception(
                            "background AOT compile of %r failed", kind)
            th = threading.Thread(target=run, daemon=True,
                                  name="mxnet-tpu-aot-compile")
            th.start()
            return th
        return [compile_one(kind, b_avals) for kind, b_avals in work]

    # ------------------------------------------------------------------
    # Stepping
    # ------------------------------------------------------------------

    def _place_batch(self, batch) -> Dict[str, jax.Array]:
        """Accept a DataBatch / dict / aligned list; shard dim 0 over the
        data axis.  A dict returned by a previous ``place_batch`` passes
        through untouched (no repeat device_put dispatches)."""
        if isinstance(batch, _PlacedBatch):
            return batch
        sh = (batch_sharding(self.mesh, self.data_axis)
              if self.data_axis is not None else replicated(self.mesh))
        if hasattr(batch, "data"):  # DataBatch
            vals = list(batch.data) + list(batch.label or [])
            named = dict(zip(self._input_names, vals))
        elif isinstance(batch, dict):
            named = batch
        else:
            named = dict(zip(self._input_names, batch))
        multiproc = self._multiproc()
        out = {}
        for n in self._input_names:
            v = named[n]
            v = v.data if isinstance(v, NDArray) else jnp.asarray(v)
            if multiproc:
                # pod case: every process feeds ITS shard of the global
                # batch (dim 0 = this host's rows); assembled into one
                # global array without cross-host data movement
                out[n] = jax.make_array_from_process_local_data(
                    sh, np.asarray(v))
            else:
                out[n] = jax.device_put(v, sh)
        return _PlacedBatch(out)

    def _guard_train_signature(self, placed: Dict[str, jax.Array]) -> None:
        """Retrace guard: jax.jit caches executables keyed on input
        shape/dtype/sharding, so a signature change silently recompiles
        the whole step.  Record each distinct train-input signature; on a
        change, name the offending inputs — warning by default, hard
        MXNetError when ``strict_retrace`` is set."""
        sig = tuple(sorted((n, tuple(v.shape), str(v.dtype))
                           for n, v in placed.items()))
        if sig in self._train_sigs:
            return
        if self._train_sigs:
            prev = dict((n, (s, d)) for n, s, d in self._train_sigs[-1])
            changed = [f"{n}: {prev.get(n)} -> {(s, d)}"
                       for n, s, d in sig if prev.get(n) != (s, d)]
            msg = ("train step input signature changed — this retraces and "
                   "recompiles the step program (pad batches to a static "
                   "shape instead): " + "; ".join(changed))
            if self.strict_retrace:
                raise MXNetError(msg)
            self.logger.warning(msg)
        self._train_sigs.append(sig)

    def assert_steady_state(self) -> None:
        """Raise unless every compiled step program traced exactly once —
        the `dispatch_count == 1`-per-step contract pipeline_spmd asserts."""
        bad = {k: v for k, v in self.trace_counts.items() if v > 1}
        if bad:
            raise MXNetError(
                f"steady-state violated: programs retraced {bad}; distinct "
                f"train signatures seen: {len(set(self._train_sigs))}")

    def step(self, batch) -> List[jax.Array]:
        """Run one training step; returns the head outputs (global arrays).

        ``batch`` may be a DataBatch / dict / aligned list of host arrays,
        or the result of a previous :meth:`place_batch` call (the
        double-buffering hook: place batch i+1 while step i runs).
        """
        if not self._bound:
            raise MXNetError("call bind() before step()")
        self._num_update += 1
        opt = self.optimizer
        # schedulers may hand back np.float64 — keep the dispatch scalar a
        # python float so every step (and the AOT-lowered signature) sees
        # the same weak-typed aval
        lr = float(opt.lr_scheduler(self._num_update) if opt.lr_scheduler
                   else opt.lr)
        if self._lr_scale != 1.0:
            # sentinel backoff: lr is already a traced program argument,
            # so scaling it host-side costs nothing and never retraces
            lr *= self._lr_scale
        placed = dict(self._place_batch(batch))
        self._guard_train_signature(placed)
        self.dispatch_count += 1
        nd_mod.note_donation(
            f"ShardedTrainer.step #{self._num_update} "
            "(donate_argnums: params, aux, opt_state)")
        # scope the mesh so mesh-aware ops (RingAttention) pick up the seq
        # axis when this step traces
        with telemetry.span("step.dispatch", step=self._num_update), \
                default_mesh(self.mesh), self._precision_scope():
            fn = self._aot_or_jit("train", self._train_step)
            if self._resil is not None:
                (self._params, self._aux, self._opt_state, heads,
                 self._guard_state) = fn(
                    self._params, self._aux, self._opt_state, placed, lr,
                    self._num_update, self._base_key, self._guard_state)
            else:
                self._params, self._aux, self._opt_state, heads = \
                    fn(self._params, self._aux, self._opt_state,
                       placed, lr, self._num_update, self._base_key)
        return list(heads)

    def _aot_or_jit(self, kind: str, jit_fn):
        """Dispatch wrapper preferring the AOT-compiled program for
        ``kind`` when one exists.  An aval mismatch (different batch
        shape/dtype than the program was lowered for) raises BEFORE the
        executable consumes donated buffers, so falling back to the jit
        path is safe; the stale AOT entry is dropped so the cost is paid
        once."""
        compiled = self._aot.get(kind)
        if compiled is None:
            return jit_fn

        def dispatch(*args):
            try:
                out = compiled(*args)
            except (TypeError, ValueError) as e:
                self._aot.pop(kind, None)
                self.aot_stats["fallbacks"] += 1
                telemetry.counter("trainer.aot_fallbacks").inc()
                self.logger.warning(
                    "AOT program %r does not match this call (%s); "
                    "falling back to jit", kind, e)
                return jit_fn(*args)
            self.aot_stats["hits"] += 1
            telemetry.counter("trainer.aot_hits").inc()
            return out
        return dispatch

    def place_batch(self, batch) -> Dict[str, jax.Array]:
        """Asynchronously stage a batch onto the mesh (prefetch hook)."""
        return self._place_batch(batch)

    def _step_acc(self, batch, carry):
        """step() variant whose program also folds the Accuracy correct
        count into ``carry`` — fit()'s zero-extra-dispatch metric path."""
        self._num_update += 1
        opt = self.optimizer
        lr = float(opt.lr_scheduler(self._num_update) if opt.lr_scheduler
                   else opt.lr)
        if self._lr_scale != 1.0:
            lr *= self._lr_scale
        placed = dict(self._place_batch(batch))
        self._guard_train_signature(placed)
        self.dispatch_count += 1
        nd_mod.note_donation(
            f"ShardedTrainer.step #{self._num_update} "
            "(donate_argnums: params, aux, opt_state)")
        with telemetry.span("step.dispatch", step=self._num_update), \
                default_mesh(self.mesh), self._precision_scope():
            fn = self._aot_or_jit("train_acc", self._train_step_acc)
            if self._resil is not None:
                (self._params, self._aux, self._opt_state, heads, carry,
                 self._guard_state) = fn(
                    self._params, self._aux, self._opt_state, placed, lr,
                    self._num_update, carry, self._base_key,
                    self._guard_state)
            else:
                self._params, self._aux, self._opt_state, heads, carry = \
                    fn(self._params, self._aux, self._opt_state, placed, lr,
                       self._num_update, carry, self._base_key)
        return list(heads), carry

    def forward(self, batch) -> List[jax.Array]:
        """Inference forward (no aux update, no dropout)."""
        self._eval_count = getattr(self, "_eval_count", 0) + 1
        self.dispatch_count += 1
        placed = dict(self._place_batch(batch))
        with default_mesh(self.mesh), self._precision_scope():
            fn = self._aot_or_jit("eval", self._eval_step)
            return list(fn(self._params, self._aux, placed,
                           self._eval_count, self._base_key))

    # ------------------------------------------------------------------
    # Param access / training loop
    # ------------------------------------------------------------------

    def get_params(self) -> Tuple[Dict[str, NDArray], Dict[str, NDArray]]:
        arg = {n: nd_array(np.asarray(v)) for n, v in self._params.items()}
        aux = {n: nd_array(np.asarray(v)) for n, v in self._aux.items()}
        return arg, aux

    def set_params(self, arg_params, aux_params=None) -> None:
        for n, v in (arg_params or {}).items():
            if n in self._params:
                val = v.data if isinstance(v, NDArray) else jnp.asarray(v)
                self._params[n] = self._global_put(
                    val, NamedSharding(self.mesh, self.rules.spec_for(n)))
        for n, v in (aux_params or {}).items():
            if n in self._aux:
                val = v.data if isinstance(v, NDArray) else jnp.asarray(v)
                self._aux[n] = self._global_put(val, replicated(self.mesh))

    # ------------------------------------------------------------------
    # Checkpointing (full trainer state: params, aux, opt_state, step, RNG)
    # ------------------------------------------------------------------

    def _state_arrays(self) -> Dict[str, jax.Array]:
        """Flat ``{name: array}`` view of the full trainer state.  Names
        are namespaced (``param:``/``aux:``/``opt:<key>:<leaf>`` where
        ``<key>`` is a param name or a fused bucket ``fused:<i>``) so one
        checkpoint dict round-trips through CheckpointManager and the
        optimizer pytree re-assembles leaf-by-leaf on restore."""
        if not self._bound:
            raise MXNetError("call bind() before save_state/restore_state")
        arrays = {f"param:{n}": self._params[n] for n in self._param_names}
        arrays.update({f"aux:{n}": self._aux[n] for n in self._aux_names})
        for key in self._opt_state:
            if key.startswith("fusedwd:"):
                # wd segment vectors are bind-time config, not training
                # state: a restore must use THIS run's wd schedule, not
                # resurrect the saving run's
                continue
            for i, leaf in enumerate(
                    jax.tree_util.tree_leaves(self._opt_state[key])):
                arrays[f"opt:{key}:{i}"] = leaf
        return arrays

    def _state_meta(self, extra_meta=None) -> Dict[str, Any]:
        meta = {"state": "sharded_trainer",
                "num_update": int(self._num_update),
                "optimizer": type(self.optimizer).__name__,
                "rng_key": _key_to_meta(self._base_key),
                "data_axis_size": (self.mesh.shape[self.data_axis]
                                   if self.data_axis is not None else 1)}
        if self._guard_state is not None:
            # loss scale + guard counters travel with the checkpoint, so a
            # resumed bf16 run continues at its working scale instead of
            # re-walking the growth schedule from init_scale.  Windowed
            # counters are saved cumulatively (host base + device window)
            vals = jax.device_get(self._guard_state)
            res = {}
            for k, v in vals.items():
                a = np.asarray(v)
                val = float(a) if a.dtype.kind == "f" else int(a)
                res[k] = val + self._resil_base.get(k, 0)
            meta["resilience"] = res
        if extra_meta:
            meta.update(extra_meta)
        return meta

    def save_state(self, manager, step: Optional[int] = None,
                   blocking: Optional[bool] = None,
                   extra_meta: Optional[Dict[str, Any]] = None) -> str:
        """Checkpoint the FULL trainer state (params, aux, optimizer
        state, update counter, RNG base key) through a
        :class:`~mxnet_tpu.checkpoint.CheckpointManager`.

        The device->host snapshot completes before this returns, so the
        next (donating) :meth:`step` is safe immediately; file writes
        overlap it on the manager's writer thread unless ``blocking``.
        """
        step = self._num_update if step is None else int(step)
        return manager.save(step, self._state_arrays(),
                            meta=self._state_meta(extra_meta),
                            blocking=blocking)

    def restore_state(self, manager, step: Optional[int] = None
                      ) -> Tuple[Dict[str, Any], int]:
        """Restore trainer state from ``manager`` (default: newest step),
        resharding every array onto THIS trainer's mesh — the saving
        run's device count/layout does not have to match.  Returns
        ``(meta, step)``; after it, the next :meth:`step` continues the
        interrupted run bitwise (same params, opt state, lr clock, and
        RNG stream)."""
        if not self._bound:
            raise MXNetError("call bind() before restore_state")
        shardings: Dict[str, Any] = {}
        target_shapes: Dict[str, Tuple[int, ...]] = {}
        names: List[str] = []
        for name, arr in self._state_arrays().items():
            names.append(name)
            shardings[name] = arr.sharding
            if name.startswith("opt:"):
                # ZeRO flat-pad lengths are f(data-axis size): restore to
                # THIS mesh's padded length, not the saved one
                target_shapes[name] = tuple(arr.shape)
        try:
            arrays, meta, step = manager.restore(
                step=step, shardings=shardings, target_shapes=target_shapes,
                names=names)
        except MXNetError as e:
            if "efres" not in str(e):
                raise
            # checkpoint predates error feedback: restore everything
            # else and keep the bind-time zero residuals (worst case one
            # step's sub-quantum correction is lost)
            names = [n for n in names if not n.startswith("opt:efres:")]
            arrays, meta, step = manager.restore(
                step=step, shardings=shardings, target_shapes=target_shapes,
                names=names)
            self.logger.warning(
                "restore_state: checkpoint has no error-feedback "
                "residuals; starting them at zero")
        for n in self._param_names:
            self._params[n] = arrays[f"param:{n}"]
        for n in self._aux_names:
            self._aux[n] = arrays[f"aux:{n}"]
        for key in list(self._opt_state):
            if key.startswith("fusedwd:"):
                continue  # bind-time config, never checkpointed
            treedef = jax.tree_util.tree_structure(self._opt_state[key])
            if any(f"opt:{key}:{i}" not in arrays
                   for i in range(treedef.num_leaves)):
                continue  # tolerated-missing (efres fallback above)
            leaves = [arrays[f"opt:{key}:{i}"]
                      for i in range(treedef.num_leaves)]
            self._opt_state[key] = jax.tree_util.tree_unflatten(treedef,
                                                                leaves)
        self._num_update = int(meta.get("num_update", step))
        if "rng_key" in meta:
            # the base key is a program ARGUMENT (pinned placement via
            # _set_base_key), so swapping it here reuses the already-
            # compiled step programs — zero new traces after resume
            self._set_base_key(_key_from_meta(meta["rng_key"]))
        if self._resil is not None and "resilience" in meta:
            # same pinned replicated placement as bind() — the restored
            # guard state slots into the compiled program without a
            # trace.  Cumulative counters land in the host-side base
            # (full float64/int precision) with zeroed device windows,
            # so the f32 accumulators restart window-sized; scale and
            # the good-step streak stay live on device.
            rep = replicated(self.mesh)
            base = resilience.init_state(self._resil)
            saved = meta["resilience"]
            self._guard_state = {}
            self._resil_base = {k: 0 for k in resilience.WINDOW_KEYS}
            for k in resilience.STATE_KEYS:
                v = saved.get(k, base[k])
                if k in resilience.WINDOW_KEYS:
                    self._resil_base[k] = (float(v) if k == "norm_sum"
                                           else int(v))
                    v = np.zeros((), base[k].dtype)
                self._guard_state[k] = self._global_put(
                    np.asarray(v, base[k].dtype), rep)
        self.logger.info("restore_state: resumed at update %d from %s",
                         self._num_update, manager.step_path(step))
        return meta, step

    def restore_or_initialize(self, manager) -> Optional[int]:
        """Auto-resume glue: restore the newest checkpoint if the manager
        has one (returning its step), else leave the freshly-bound state
        untouched and return None.  Idempotent across preemption
        restarts."""
        return manager.restore_or_initialize(
            lambda step: self.restore_state(manager, step=step)[1])

    # ------------------------------------------------------------------
    # Resilience: counter drain + divergence sentinel
    # ------------------------------------------------------------------

    def resilience_stats(self) -> Dict[str, Any]:
        """One-fetch snapshot of the guard counters (empty dict when the
        guard is off).  Counters are cumulative since bind/restore:
        each value is the host-side base (counters folded off-device by
        past sentinel drains, float64/int precision) plus the current
        on-device window.  Reading them here never resets anything."""
        if self._guard_state is None:
            return {}
        with telemetry.span("guard.drain"):  # the one periodic device wait
            vals = jax.device_get(self._guard_state)
        base = self._resil_base
        stats = {
            "skipped_steps": base["skipped"] + int(vals["skipped"]),
            "overflow_steps": base["overflows"] + int(vals["overflows"]),
            "good_steps": int(vals["good"]),
            "loss_scale": float(vals["scale"]),
            "norm_sum": base["norm_sum"] + float(vals["norm_sum"]),
            "norm_steps": base["norm_cnt"] + int(vals["norm_cnt"]),
            "lr_scale": self._lr_scale,
            "rollbacks": self._rollbacks,
            "num_update": self._num_update,
        }
        # freshest drained values double as the resilience gauges
        g = telemetry.gauge
        g("resilience.loss_scale").set(stats["loss_scale"])
        g("resilience.lr_scale").set(stats["lr_scale"])
        g("resilience.skipped_steps").set(stats["skipped_steps"])
        g("resilience.overflow_steps").set(stats["overflow_steps"])
        # rollbacks/backoffs already tick as counters in _sentinel_poll
        if stats["norm_steps"] > 0:
            g("resilience.grad_norm_mean").set(
                stats["norm_sum"] / stats["norm_steps"])
        return stats

    def _fold_guard_counters(self, stats: Dict[str, Any]) -> None:
        """Fold the windowed on-device counters into the host-side
        cumulative base and zero them on device.  ``stats`` is the
        snapshot just fetched by :meth:`resilience_stats` (already
        base + device, so it simply becomes the new base).  Bounds the
        f32 ``norm_sum`` accumulator to one drain window — a cumulative
        f32 sum would lose per-step resolution after ~1e7 steps and
        blind the divergence sentinel on exactly the long runs it
        guards.  The zeros keep the pinned replicated placement, so the
        compiled step program re-dispatches without a trace."""
        self._resil_base = {"skipped": stats["skipped_steps"],
                            "overflows": stats["overflow_steps"],
                            "norm_sum": stats["norm_sum"],
                            "norm_cnt": stats["norm_steps"]}
        rep = replicated(self.mesh)
        for k in resilience.WINDOW_KEYS:
            dt = self._guard_state[k].dtype
            self._guard_state[k] = self._global_put(
                np.zeros((), dt), rep)

    def _sentinel_poll(self, manager=None) -> Optional[str]:
        """Drain the guard counters and feed the divergence sentinel.

        Called every ``GuardConfig.check_every`` batches from fit — the
        only periodic device fetch the resilience tier makes.  On an
        anomaly the learning rate is backed off host-side; on a sustained
        streak the trainer rolls back to the manager's last good
        checkpoint and resumes (the step program is cached, so the
        rollback costs a restore, not a recompile)."""
        stats = self.resilience_stats()
        if not stats:
            return None
        self._fold_guard_counters(stats)
        last, self._resil_drained = self._resil_drained, stats
        if not last:
            return None  # first drain just baselines the counters
        steps = stats["num_update"] - last["num_update"]
        if steps <= 0:
            return None
        skipped = stats["skipped_steps"] - last["skipped_steps"]
        cnt = stats["norm_steps"] - last["norm_steps"]
        total = stats["norm_sum"] - last["norm_sum"]
        norm_mean = (total / cnt) if cnt > 0 else None
        if self._sentinel is None:
            self._sentinel = resilience.DivergenceSentinel(
                self._resil, logger=self.logger)
        action = self._sentinel.observe(norm_mean, skipped, steps)
        if action is None:
            return None
        from .. import profiler
        self._lr_scale = max(self._lr_scale * self._resil.lr_backoff,
                             self._resil.min_lr_scale)
        if action == "rollback" and manager is not None \
                and manager.latest_step() is not None:
            if self._rollback_hook is not None:
                self._rollback_hook()
            restoring = getattr(manager, "restoring", None)
            import contextlib
            with (restoring() if restoring is not None
                  else contextlib.nullcontext()):
                _, step = self.restore_state(manager)
            self._rollbacks += 1
            profiler.bump("resilience.rollbacks")
            # the ring holds the steps that led INTO the divergence —
            # dump before re-baselining overwrites the evidence
            telemetry.dump_flight(
                "divergence-rollback",
                extra={"restored_step": step,
                       "lr_scale": self._lr_scale,
                       "norm_mean": norm_mean})
            # guard counters rolled back with the state: re-baseline
            self._resil_drained = self.resilience_stats()
            self.logger.warning(
                "Resilience: rolled back to checkpoint at update %d, "
                "lr-scale=%g (cached step program, no recompile)",
                step, self._lr_scale)
        else:
            profiler.bump("resilience.backoffs")
            self.logger.warning(
                "Resilience: LR backed off, lr-scale=%g", self._lr_scale)
        return action

    def _metric_proxy(self, eval_metric):
        return _AsyncMetric(eval_metric)

    def score(self, eval_data, eval_metric):
        from ..metric import create as metric_create
        if isinstance(eval_metric, str):
            eval_metric = metric_create(eval_metric)
        eval_metric.reset()
        eval_data.reset()
        for batch in eval_data:
            outs = self.forward(batch)
            eval_metric.update(batch.label, [NDArray(np.asarray(o))
                                             for o in outs])
        return eval_metric

    def _fit_checkpoint(self, manager, am, epoch: int, nbatch: int) -> None:
        """Per-batch checkpoint hook for :meth:`fit`: policy-gated (or
        preemption-forced) full-state save.  The fused-metric carry is
        drained into the meta so a resumed epoch's running metric is not
        silently zero.  The snapshot runs here, on the dispatching thread,
        BEFORE the next step donates the buffers being saved."""

        def state_fn():
            extra = {"epoch": epoch, "nbatch": nbatch}
            if am._dev_sum is not None:
                # scalar sync — only paid on the (rare) batches that save
                extra["metric_sum"] = int(np.asarray(am._dev_sum))
                extra["metric_num"] = int(am._dev_num)
            return self._state_arrays(), self._state_meta(extra)

        manager.maybe_save(self._num_update, state_fn)

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            num_epoch: int = 1, begin_epoch: int = 0,
            batch_end_callback=None, epoch_end_callback=None,
            checkpoint_manager=None) -> None:
        """Mesh-native training loop: per batch, one compiled device step.

        Unlike the reference loop (``model.py:119``) there is no push/pull
        phase — gradient reduction is inside :meth:`step`.  ``begin_epoch``
        resumes checkpoint numbering and the optimizer's update count.

        ``checkpoint_manager`` enables in-loop checkpointing: after each
        step the manager's save policy may trigger a full
        :meth:`save_state` (snapshot on this thread, writes overlapped on
        the manager's background writer), and a SIGTERM preemption
        (``manager.preempted``) forces a final blocking save and stops the
        loop at the batch boundary.
        """
        from ..metric import create as metric_create
        if isinstance(eval_metric, str):
            eval_metric = metric_create(eval_metric)
        if begin_epoch and self._num_update == self.optimizer.begin_num_update:
            # resume: advance the lr-schedule clock past the done epochs
            # without paying a counting pass over the data
            # iterator-provided steps_per_epoch is authoritative (every
            # built-in iterator reports the count it actually yields);
            # the ceil fallback below is approximate for custom iterators
            # — use optimizer.begin_num_update for exact resume there
            batches = getattr(train_data, "steps_per_epoch", None)
            if batches is None:  # 0 is authoritative (empty shard)
                nd_ = getattr(train_data, "num_data", None)
                bs = getattr(train_data, "batch_size", None)
                if nd_ and bs:
                    batches = -(-nd_ // bs)
            if batches is not None:
                self._num_update += begin_epoch * int(batches)
            else:
                self.logger.warning(
                    "fit(begin_epoch=%d): train_data has no steps_per_epoch"
                    " attribute, lr-schedule clock not advanced (set "
                    "optimizer.begin_num_update for exact resume)",
                    begin_epoch)
        # async metric path (SURVEY §3.3 "Python stays ahead of the
        # devices"): supported metrics accumulate ON device, others
        # buffer head references — either way no per-batch host sync;
        # get()/get_name_value() (e.g. from a Speedometer callback)
        # drain exactly then
        am = self._metric_proxy(eval_metric)
        # chaos harness: when MXNET_TPU_CHAOS is set, deterministic fault
        # injection wraps the iterator HERE — upstream of the prefetch
        # thread, so injected crashes exercise the real retry path
        from .. import chaos as chaos_mod
        train_data = chaos_mod.maybe_wrap(train_data, logger=self.logger)
        # async double-buffered input placement: a background thread pulls
        # batch k+1 from the iterator and dispatches its sharded committed
        # device_put while step k's compute runs — the host never sits
        # between two device steps (the estimator-path analog of bench.py's
        # place_batch prefetch, now fully off the dispatching thread)
        from ..io import DevicePrefetchIter
        prefetch = DevicePrefetchIter(train_data, place_fn=self.place_batch)
        # the fused carry must start with the SAME aval+sharding the step
        # program emits, or the second call retraces the whole program
        # (caught by trace_counts: an uncommitted host int32(0) vs the
        # mesh-replicated step output is a cache miss)
        carry_sh = NamedSharding(self.mesh, P())
        am.carry_init = lambda: jax.device_put(jnp.int32(0), carry_sh)
        check_every = (self._resil.check_every if self._resil is not None
                       else 0)
        # flight-recorder clock: wall time between successive dispatch
        # returns — host-observable step cadence with NO device fetch
        # (a fetch here would serialize the async pipeline)
        t_last = time.perf_counter()
        try:
            for epoch in range(begin_epoch, num_epoch):
                tic = time.time()
                am.reset()
                nbatch = 0
                prefetch.reset()
                fused = am.supports_fused and bool(self._label_names)
                nheads = len(self.symbol.list_outputs())
                ninst_names = self._label_names[:nheads]
                for cur in prefetch:
                    if fused:
                        # accuracy folds inside the step program: ONE
                        # dispatch per batch, no extra host<->device
                        # traffic at all
                        outs, carry = self._step_acc(cur, am.take_carry())
                        am.put_carry(carry, sum(
                            int(np.prod(cur[n].shape))
                            for n in ninst_names))
                    else:
                        outs = self.step(cur)
                        # labels already live on device in the placed
                        # batch — no second host->device hop for the
                        # metric
                        lbls = ([cur[n] for n in self._label_names]
                                if self._label_names
                                else prefetch.current_source.label)
                        am.update_async(lbls, outs)
                    nbatch += 1
                    t_now = time.perf_counter()
                    drained = self._resil_drained
                    telemetry.record_step({
                        "step": self._num_update, "epoch": epoch,
                        "nbatch": nbatch,
                        "host_ms": (t_now - t_last) * 1e3,
                        "lr_scale": self._lr_scale,
                        "loss_scale": drained.get("loss_scale"),
                        "skipped_steps": drained.get("skipped_steps"),
                        "grad_norm_mean": (
                            drained["norm_sum"] / drained["norm_steps"]
                            if drained.get("norm_steps") else None),
                        "rollbacks": self._rollbacks,
                        "aot_hits": self.aot_stats["hits"],
                    })
                    t_last = t_now
                    if batch_end_callback is not None:
                        from ..model import BatchEndParam
                        batch_end_callback(BatchEndParam(
                            epoch=epoch, nbatch=nbatch, eval_metric=am,
                            locals=locals()))
                    if checkpoint_manager is not None:
                        self._fit_checkpoint(checkpoint_manager, am, epoch,
                                             nbatch)
                        if checkpoint_manager.preempted:
                            self.logger.warning(
                                "fit: preemption signal received — state "
                                "saved at update %d, stopping "
                                "(restore_or_initialize resumes on "
                                "restart)", self._num_update)
                            checkpoint_manager.wait_until_finished()
                            return
                    if check_every and nbatch % check_every == 0:
                        self._sentinel_poll(checkpoint_manager)
                name, value = am.get()
                names = name if isinstance(name, list) else [name]
                values = value if isinstance(value, list) else [value]
                for n_, v_ in zip(names, values):
                    self.logger.info("Epoch[%d] Mesh-Train-%s=%f",
                                     epoch, n_, v_)
                self.logger.info("Epoch[%d] Step-total=%d Elapsed=%.3fs",
                                 epoch, nbatch, time.time() - tic)
                if self._resil is not None:
                    rs = self.resilience_stats()
                    # one line per epoch, grep-stable for tools/parse_log
                    self.logger.info(
                        "Epoch[%d] Resilience: skipped=%d overflows=%d "
                        "rollbacks=%d loss-scale=%g lr-scale=%g",
                        epoch, rs["skipped_steps"], rs["overflow_steps"],
                        rs["rollbacks"], rs["loss_scale"], rs["lr_scale"])
                    telemetry.emit("resilience", {"epoch": epoch, **rs})
                # epoch boundary: force a metrics row so even sub-
                # interval runs leave a diffable JSONL stream
                telemetry.flush_metrics()
                if epoch_end_callback is not None:
                    arg_p, aux_p = self.get_params()
                    epoch_end_callback(epoch, self.symbol, arg_p, aux_p)
                if eval_data is not None:
                    m = self.score(eval_data, eval_metric)
                    for name, value in [m.get()]:
                        self.logger.info("Epoch[%d] Mesh-Validation-%s=%s",
                                         epoch, name, value)
        except Exception:
            # the ring holds the last N steps leading into the failure;
            # dump before the stack unwinds past whoever catches this
            telemetry.dump_flight("step-exception")
            raise
        finally:
            # an abandoned/preempted epoch must not leave the prefetch
            # thread alive holding staged device buffers
            prefetch.close()


# ---------------------------------------------------------------------------
# Async metric accumulation (fit() hot path)
# ---------------------------------------------------------------------------

@jax.jit
def _acc_fold1(carry, pred, label):
    """Device-side Accuracy.update for one (pred, label) pair folded into
    the carried correct-count scalar: one small async dispatch per batch
    (instance counts are static)."""
    if pred.ndim > 1:
        pred = jnp.argmax(pred, axis=1)
    p = pred.astype(jnp.int32).reshape(-1)
    l = label.astype(jnp.int32).reshape(-1)
    return carry + jnp.sum(p == l)


class _AsyncMetric:
    """Metric facade that never forces a device->host sync per batch.

    The reference keeps Python ahead of its engine by making metric reads
    lazy on engine completion (SURVEY §3.3); the XLA analog: ``Accuracy``
    folds into a carried on-device scalar (one tiny async add per batch),
    any other metric buffers head references and replays them into the
    wrapped metric every ``period`` batches (period sized so the buffer
    holds <= ~64 MB of head outputs).  ``get``/``get_name_value``/
    ``get_metric`` drain first, so Speedometer-cadence callbacks observe
    exact values at their own frequency and the training loop pays the
    sync only there.
    """

    _MAX_BUFFER_BYTES = 64 << 20

    def __init__(self, inner):
        from ..metric import Accuracy
        self.inner = inner
        self._on_device = type(inner) is Accuracy
        self._dev_sum = None   # carried device scalar (correct count)
        self._dev_num = 0      # static instance count
        self._buf: List[Tuple[Any, Any]] = []
        self._period: Optional[int] = None
        # optional factory for the epoch-initial carry; the trainer sets it
        # to a mesh-replicated zero so the first fused step sees the same
        # aval+sharding as every later one (no mid-epoch retrace)
        self.carry_init = None

    # -- fused path (the correct-count fold runs inside the train step) --

    @property
    def supports_fused(self):
        return self._on_device

    def take_carry(self):
        if self._dev_sum is not None:
            c = self._dev_sum
        elif self.carry_init is not None:
            c = self.carry_init()
        else:
            c = jnp.int32(0)
        self._dev_sum = None
        return c

    def put_carry(self, carry, ninst: int):
        self._dev_sum = carry
        self._dev_num += ninst

    # -- EvalMetric surface ------------------------------------------------

    @property
    def name(self):
        return self.inner.name

    def reset(self):
        self.inner.reset()
        self._dev_sum = None
        self._dev_num = 0
        self._buf.clear()

    def update(self, labels, preds):  # direct use falls through
        self.inner.update(labels, preds)

    def get(self):
        self._drain()
        return self.inner.get()

    def get_name_value(self):
        self._drain()
        return self.inner.get_name_value()

    def get_metric(self, index):
        self._drain()
        return self.inner.get_metric(index)

    # -- async accumulation ------------------------------------------------

    def update_async(self, labels, outs):
        labels = list(labels) if isinstance(labels, (list, tuple)) \
            else [labels]
        if self._period is None:
            nbytes = sum(int(np.prod(o.shape)) * o.dtype.itemsize
                         for o in outs) or 1
            self._period = max(1, min(32, self._MAX_BUFFER_BYTES // nbytes))
        if self._on_device:
            for label, pred in zip(labels, outs):
                lv = label.data if isinstance(label, NDArray) \
                    else jnp.asarray(np.asarray(label))
                carry = (self._dev_sum if self._dev_sum is not None
                         else jnp.int32(0))
                self._dev_sum = _acc_fold1(carry, pred, lv)
                self._dev_num += int(np.prod(lv.shape))
            return
        # keep labels as device references too — converting here would be
        # a device->host sync per batch, defeating the deferred-drain
        # design.  Snapshot NDArray wrappers to their immutable jax
        # buffer so later in-place writes can't alias the buffered batch.
        self._buf.append((
            [l.data if isinstance(l, NDArray) else np.asarray(l)
             for l in labels], list(outs)))
        if len(self._buf) >= self._period:
            self._drain()

    def _drain(self):
        if self._on_device:
            if self._dev_sum is not None:
                with telemetry.span("metric.drain", fused=True):
                    self.inner.sum_metric += int(np.asarray(self._dev_sum))
                self.inner.num_inst += self._dev_num
                self._dev_sum = None
                self._dev_num = 0
            return
        if not self._buf:
            return
        with telemetry.span("metric.drain", batches=len(self._buf)):
            for labels, outs in self._buf:
                self.inner.update([np.asarray(l) for l in labels],
                                  [NDArray(np.asarray(o)) for o in outs])
            self._buf.clear()
