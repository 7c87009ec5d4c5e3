"""Training as a user's loop runs it: ``models.get_symbol`` ->
``ShardedTrainer`` -> ``bind`` -> ``compile`` -> batches from
``io.NDArrayIter`` through ``io.DevicePrefetchIter`` -> ``step()``.

The loop keeps ``in_flight`` steps dispatched and fetches the heads of
the step before those (as a loop that logs its loss does), so the host
cannot run ahead without bound.  ``train_step_ms`` is the blocked
window over its steps: the clock starts with the device idle and stops
when the last step's heads have been fetched.

Configuration keys (``train``): ``symbol`` (name, kwargs), ``mesh``
(axis -> size), ``optimizer``, ``optimizer_params``, ``initializer``
(name, kwargs), ``compute_dtype``,
``matmul_precision``, ``loss_tolerance``.  Job keys (the traffic file):
``inputs`` (see ``harness.traffic.batch_arrays``), ``host_batches``,
``in_flight``, ``warm_steps``, ``trace_s``.
"""
from __future__ import annotations

import collections
import math
import time

import numpy as np

from benchmark.harness import spec, stats, traffic
from benchmark.harness.runtime import (Result, Run, TraceWindow,
                                       enable_program_spans,
                                       read_program_spans, say, temp_bytes)


def build_trainer(run: Run, batch_shapes):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import initializer as init_mod
    from mxnet_tpu import models
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh

    t = run.config["train"]
    mx.random.seed(int(run.seed) & 0x7FFFFFFF)
    sym = models.get_symbol(t["symbol"]["name"], **t["symbol"]["kwargs"])
    mesh = make_mesh({k: int(v) for k, v in t["mesh"].items()},
                     jax.local_devices()[:run.chips])
    init = getattr(init_mod, t["initializer"]["name"])(
        **t["initializer"].get("kwargs", {}))
    tr = ShardedTrainer(
        sym, mesh=mesh,
        optimizer=t["optimizer"], optimizer_params=dict(t["optimizer_params"]),
        initializer=init, matmul_precision=t.get("matmul_precision"),
        compute_dtype=t.get("compute_dtype"))
    label = t["label_name"]
    tr.bind(data_shapes={k: v for k, v in batch_shapes.items() if k != label},
            label_shapes={label: batch_shapes[label]})
    return tr


def run(run: Run) -> Result:
    from mxnet_tpu import io as mxio

    job = run.traffic
    t_cfg = run.config["train"]
    ref = spec.load_module("reference", run.config["family"])
    shapes = {k: tuple(int(s) for s in v["shape"])
              for k, v in job["inputs"].items()}
    label = t_cfg["label_name"]

    t = time.monotonic()
    tr = build_trainer(run, shapes)
    say(f"[setup] trainer bound on mesh {dict(tr.mesh.shape)} in "
        f"{time.monotonic() - t:.1f} s")
    info = tr.compile()
    say(f"[setup] train step compiled in {info[0]['seconds']:.1f} s "
        f"(source: {info[0]['source']}); fused update: {tr._fused}")

    count = int(job["host_batches"])
    t = time.monotonic()
    arrays = traffic.batch_arrays(job["inputs"], count, run.seed)
    batch = shapes[label][0]
    first = {k: v[:batch] for k, v in arrays.items()}
    say(f"[setup] {count} host batches "
        f"({sum(v.nbytes for v in arrays.values()) / 1e9:.2f} GB) drawn in "
        f"{time.monotonic() - t:.1f} s")

    # -- correctness, before the window: the first step's loss against the
    # plain reference's loss from the trainer's own initial parameters ----
    t = time.monotonic()
    init_params = {k: np.asarray(v.asnumpy()) for k, v in
                   tr.get_params()[0].items()}
    t_ref = time.monotonic()
    want = float(ref.reference_loss(init_params, first, run.config))
    t_step = time.monotonic()
    del init_params
    heads = tr.step(first)
    got = float(ref.program_loss(np.asarray(heads[0]), first, run.config))
    say(f"[correct] parameters to the host {t_ref - t:.1f} s, reference "
        f"loss {t_step - t_ref:.1f} s, the program's first step "
        f"{time.monotonic() - t_step:.1f} s")
    tol = float(t_cfg["loss_tolerance"])
    notes = []
    say(f"[correct] first-step loss {got:.5f} vs reference {want:.5f} "
        f"(|diff| {abs(got - want):.5f}, tolerance {tol})")
    run.compared["first_loss_diff"] = (abs(got - want), tol)
    if not (math.isfinite(got) and abs(got - want) <= tol):
        notes.append(f"first-step loss {got} differs from the reference's "
                     f"{want} by more than {tol}")

    data_names = [k for k in shapes if k != label]
    it = mxio.DevicePrefetchIter(
        mxio.NDArrayIter({k: arrays[k] for k in data_names},
                         {label: arrays[label]}, batch_size=batch),
        place_fn=tr.place_batch)

    def next_batch():
        try:
            return it.next()
        except StopIteration:
            it.reset()
            return it.next()

    losses = []

    def fetch(item):
        head, labels = item
        losses.append(float(ref.program_loss(
            np.asarray(head), {label: labels}, run.config)))

    try:
        t = time.monotonic()
        for _ in range(int(job["warm_steps"])):
            b = next_batch()
            fetch((tr.step(b)[0], it.getlabel()[0].asnumpy()))
        warm_losses = len(losses)
        say(f"[setup] {warm_losses} warm steps through the iterator in "
            f"{time.monotonic() - t:.1f} s")
        enable_program_spans(run.traced)
        tw = TraceWindow(run, float(job["trace_s"]))
        in_flight = int(job["in_flight"])
        pending = collections.deque()
        waits = []
        traces_before = dict(tr.trace_counts)
        run.compiles.mark()
        run.sample_memory()
        w0_ns = time.perf_counter_ns()
        w0 = time.monotonic()
        w1 = w0 + run.seconds
        steps = 0
        while True:
            now = time.monotonic()
            if now >= w1:
                break
            tw.tick(now, w1)
            t_in = time.monotonic()
            with run.region("next_batch"):
                b = next_batch()
                labels = it.getlabel()[0].asnumpy()
            t_st = time.monotonic()
            with run.region("train_step"):
                pending.append((tr.step(b)[0], labels))
            if len(pending) > in_flight:
                with run.region("fetch"):
                    fetch(pending.popleft())
            steps += 1
            waits.append((t_st - t_in) * 1e3)
        with run.region("fetch"):
            while pending:
                fetch(pending.popleft())
        w_end = time.monotonic()
        w1_ns = time.perf_counter_ns()
        run.sample_memory()
        tw.close()
    finally:
        it.close()
    in_window = run.compiles.mark()
    setup_s = run.setup_seconds(w0)

    window_losses = losses[warm_losses:]
    bad = sum(1 for x in window_losses if not math.isfinite(x))
    if bad:
        notes.append(f"{bad} step(s) with a non-finite loss")
    if dict(tr.trace_counts) != traces_before:
        notes.append(f"retraced in the window: {dict(tr.trace_counts)}")
    if tr.aot_stats["fallbacks"]:
        notes.append(f"steps fell back to jit: {dict(tr.aot_stats)}")
    if in_window:
        notes.append(f"{in_window} compilation(s) inside the window")
    if not steps:
        notes.append("no step ran in the window")
    elapsed = w_end - w0
    step_ms = elapsed * 1e3 / max(steps, 1)
    flops = float(ref.train_flops_per_step(run.config, shapes))
    say(f"[window] {steps} steps in {elapsed:.3f} s blocked "
        f"({step_ms:.3f} ms a step); loss first {window_losses[0]:.4f} last "
        f"{window_losses[-1]:.4f}; input wait ms p50 "
        f"{stats.percentile(waits, 50):.3f} p95 "
        f"{stats.percentile(waits, 95):.3f}; in-window compiles {in_window}")
    facts = {
        "window_s": elapsed, "kind": "train",
        "train": {"steps": steps, "step_ms": step_ms,
                  "flops_per_step": flops, "input_wait_ms": waits,
                  "fused_update": bool(tr._fused)},
        "spans": (read_program_spans(w0_ns, w1_ns) if run.traced else []),
        "trace": tw.summary(),
        "trace_cost_s": (tw.start_cost_s, tw.stop_cost_s),
    }
    return Result(correct=not notes, attempted=steps, failed=bad,
                  setup_s=setup_s,
                  end_to_end={"train_step_ms": step_ms} if steps else {},
                  facts=facts, notes=notes,
                  temp_bytes=temp_bytes(tr._aot.values()))
