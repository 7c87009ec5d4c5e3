"""What the two serving runners share: building the engine from a
configuration file, the correctness probe against the plain reference,
per-step bookkeeping, and the checks after the window."""
from __future__ import annotations

import time
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from . import spec
from .runtime import Run, say
from .traffic import RequestSpec


def build_engine(run: Run):
    """Weights on the device from the seed (one jitted call, in the type
    they are served in, under the engine's parameter names), the engine
    of the configuration file, and its two programs warmed.  Returns
    ``(engine, params, reference module)``."""
    import jax.numpy as jnp

    from mxnet_tpu.serve import Engine, EngineConfig

    cfg = run.config
    serve = cfg["serve"]
    ref = spec.load_module("reference", cfg["family"])
    t = time.monotonic()
    params = ref.init_params(run.seed, cfg, jnp.dtype(serve["weights_dtype"]))
    for leaf in params.values():
        leaf.block_until_ready()
    say(f"[setup] {ref.param_count(cfg):,} parameters made on the device in "
        f"{serve['weights_dtype']} in {time.monotonic() - t:.1f} s")
    ecfg = EngineConfig(heads=int(cfg["num_attention_heads"]),
                        dtype=jnp.dtype(serve["kv_dtype"]),
                        **serve["engine"])
    eng = Engine(params, ecfg)
    t = time.monotonic()
    infos = eng.warmup()
    say("[setup] warmup: " + ", ".join(
        f"{i['kind']}@{i['bucket']} {i['seconds']:.1f} s ({i['source']})"
        for i in infos) + f"; attn_impl={eng.attn_impl}; "
        f"{time.monotonic() - t:.1f} s")
    return eng, params, ref


def submit(eng, r: RequestSpec) -> int:
    return eng.submit(r.prompt, max_new_tokens=r.max_new_tokens,
                      temperature=r.temperature, top_k=r.top_k, seed=r.seed)


def probe(run: Run, eng, params, ref) -> Tuple[bool, List[str]]:
    """The logit-space correctness test, outside the window.

    A seeded sample of greedy requests goes through the engine together
    (chunked prefill, then decode through the paged cache).  The plain
    reference is then teacher-forced on prompt + the engine's own
    tokens, and every emitted token's reference logit has to lie within
    ``logit_tolerance`` of the reference's maximum at that position.  It
    needs no logits from the engine, and a wrong cache entry, mask or
    chunk boundary moves the engine's choice far below the maximum.
    """
    import jax
    import jax.numpy as jnp

    cfg = run.config
    p = cfg["serve"]["probe"]
    vocab = int(cfg["vocab_size"])
    heads = int(cfg["num_attention_heads"])
    new = int(p["max_new_tokens"])
    rng = np.random.default_rng([int(run.seed), 0x9B0BE])
    prompts = [rng.integers(1, vocab, int(n)).tolist()
               for n in p["prompt_tokens"]]
    ids = [eng.submit(pr, max_new_tokens=new, seed=1000 + i)
           for i, pr in enumerate(prompts)]
    eng.run()
    outs = [list(eng.request(i).tokens) for i in ids]
    notes = []
    for i, o in zip(ids, outs):
        if eng.request(i).state != "finished" or len(o) != new:
            notes.append(f"probe request {i} ended "
                         f"{eng.request(i).state!r} with {len(o)}/{new} "
                         "tokens")
    if notes:
        return False, notes
    length = -(-(max(map(len, prompts)) + new) // 128) * 128
    toks = np.zeros((len(prompts), length), np.int32)
    pos = np.zeros((len(prompts), new), np.int32)
    for i, (pr, o) in enumerate(zip(prompts, outs)):
        toks[i, :len(pr)] = pr
        toks[i, len(pr):len(pr) + new - 1] = o[:-1]
        pos[i] = len(pr) - 1 + np.arange(new)
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(params, toks, heads)
        rows = jnp.take_along_axis(logits, jnp.asarray(pos)[..., None], axis=1)
        top = jnp.max(rows, axis=-1)
        picked = jnp.take_along_axis(
            rows, jnp.asarray(outs, jnp.int32)[..., None], axis=-1)[..., 0]
        deficit = np.asarray(top - picked)
        spread = float(jnp.std(rows))
    tol = float(cfg["serve"]["logit_tolerance"])
    worst = float(deficit.max())
    agree = float((deficit == 0).mean())
    say(f"[correct] {len(prompts)} greedy probes x {new} tokens (prompts "
        f"{[len(x) for x in prompts]}): reference logit of the engine's "
        f"token is at most {worst:.4f} under the reference's maximum "
        f"(tolerance {tol}; logits' std {spread:.3f}); "
        f"{agree:.1%} are the reference's argmax")
    run.compared["logit_deficit"] = (worst, tol)
    if not np.isfinite(deficit).all() or worst > tol:
        notes.append(f"engine tokens fall {worst:.4f} under the reference's "
                     f"maximum logit, tolerance {tol}")
    return not notes, notes


def preemptions() -> int:
    """The program's count of requests it evicted to free KV blocks
    (``serve.preemptions``, ``Engine._preempt``), since the process
    started: a caller takes the difference over its own stretch."""
    from mxnet_tpu import telemetry
    return int(telemetry.counter("serve.preemptions").value())


class StepLog:
    """One record per engine step, taken by the runner after the step:
    wall interval, blocks in use, decode-ready rows and their cached
    tokens, and whether the profiler was on."""

    def __init__(self, eng):
        self.eng = eng
        self.rows: List[Tuple[float, float, int, int, int, bool]] = []

    def record(self, t0: float, t1: float, traced: bool) -> None:
        ready = [r for r in self.eng.sched.running
                 if r.prefilled >= r.prefill_target]
        self.rows.append((t0, t1, self.eng.alloc.num_used, len(ready),
                          sum(r.cached for r in ready), traced))

    def in_window(self, lo: float, hi: float) -> List[Dict[str, Any]]:
        keys = ("t0", "t1", "kv_used", "rows", "cached_tokens", "traced")
        return [dict(zip(keys, r)) for r in self.rows if lo <= r[1] < hi]


def program_temp_bytes(eng) -> int:
    from .runtime import temp_bytes
    return temp_bytes(p.compiled for p in eng._programs.values())


def engine_facts(eng, cfg) -> Dict[str, Any]:
    import jax.numpy as jnp
    return {"num_blocks": eng.config.num_blocks,
            "block_size": eng.config.block_size,
            "max_batch": eng.config.max_batch,
            "max_blocks_per_row": eng.max_blocks,
            "layers": eng.num_layers, "heads": eng.heads,
            "head_dim": eng.head_dim,
            "kv_itemsize": jnp.dtype(cfg["serve"]["kv_dtype"]).itemsize}


def settle(eng, sent: Sequence[Tuple[Any, RequestSpec]], vocab: int,
           traces_before: Dict[str, int], compiles_in_window: int
           ) -> Tuple[int, List[str]]:
    """After the window: every finished request in-vocabulary and at its
    budget, no request failed, no retrace, no compilation inside the
    window, and no KV block held once the rest is cancelled.  Returns
    ``(failed, notes)``; any note makes the run incorrect."""
    notes: List[str] = []
    failed = 0
    for req, r in sent:
        if req is None:                      # refused at submit
            failed += 1
            continue
        if req.state == "failed":
            failed += 1
        if any(t < 0 or t >= vocab for t in req.tokens):
            notes.append(f"request {req.id} produced an out-of-vocabulary "
                         "token")
        if req.state == "finished" and len(req.tokens) != r.max_new_tokens:
            notes.append(f"request {req.id} finished with "
                         f"{len(req.tokens)}/{r.max_new_tokens} tokens")
        if len(req.tokens) > r.max_new_tokens:
            notes.append(f"request {req.id} ran past its budget")
    if failed:
        notes.append(f"{failed} request(s) failed or were refused")
    if dict(eng.trace_counts) != traces_before:
        notes.append(f"retraced after warm-up: {dict(eng.trace_counts)} "
                     f"!= {traces_before}")
    if eng.aot_stats["fallbacks"]:
        notes.append(f"serve programs fell back to jit: {dict(eng.aot_stats)}")
    if compiles_in_window:
        notes.append(f"{compiles_in_window} compilation(s) inside the window")
    for req, _ in sent:
        if req is not None and not req.done():
            eng.cancel(req.id)
    for req in list(eng.sched.running) + list(eng.sched.queue):
        eng.cancel(req.id)
    eng.step()
    if eng.alloc.num_used:
        notes.append(f"{eng.alloc.num_used} KV blocks still held after the "
                     "drain")
    eng.check_tables()
    return failed, notes
