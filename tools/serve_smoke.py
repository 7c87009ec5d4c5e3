#!/usr/bin/env python
"""CI smoke for the serving tier (docs/serving.md).

Builds a tiny transformer-LM, warms a continuous-batching engine —
round-12 config: chunked prefill + fp8-quantized paged KV pools —
through the compile cache, then pushes 8 concurrent streams through it
and asserts:

1. every stream completes with its full token budget (or eos) and the
   KV pool drains back to zero used blocks;
2. the engine is WARM after step 1 — the admit -> prefill -> decode ->
   evict cycle runs zero new traces once warmup resolved the bucket
   programs (the retrace guard the serving tier lives or dies by);
3. serve telemetry is live: the exported Perfetto trace validates and
   carries the serve.prefill / serve.decode / serve.admit spans, and
   the metrics registry holds the serve.tokens_total counter, the
   serve.prefill_chunks counter (every prompt ingested through the
   chunk pump), and the fp8-aware kv_bytes_per_token gauge;
4. the round-12 control plane survives replica death: a 2-replica
   Router with a serve_crash chaos point on replica 0 finishes every
   stream byte-identical to a chaos-free fleet, with at least one
   failover and zero post-warmup retraces on the survivor;
5. the round-13 train→serve loop closes (docs/train_serve.md): a
   rollout trainer takes a few steps from the serving weights, the
   update publishes through CheckpointManager with the compat stamp,
   and ``Router.rolling_swap`` deploys it under 8 live streams —
   mode ``hot``, zero retraces, every stream finishes, no KV leak,
   and ``online.swaps`` == replica count;
6. round-15 speculative decoding holds its contract under the same
   traffic: a ``speculate=True`` engine (n-gram drafter, k=4, fp8 KV)
   warms the verify program family INSTEAD of decode, is warm after
   step 1, finishes all 8 streams with greedy rows byte-identical to
   the plain engine, advances ``serve.spec.steps`` /
   ``serve.spec.accepted``, and drains the pool to zero used blocks
   (the rejected-tail scrub keeps the block ledger exact);
7. the round-18 prefix cache reuses a shared system prompt across a
   same-step cohort: 8 streams over one 12-token prefix on a
   ``prefix_cache=True`` fp8 engine prefill the prefix EXACTLY once
   (7 second-chance hits, 1 miss), stay byte-identical to a cache-off
   engine, stay warm after step 1, advance the ``serve.prefix.*``
   counters, and drain with zero used blocks (the cached prefix
   blocks park refcount-0, not leaked).

Exit 0 on success, 1 with a reason on any failure.  Runs on the CPU
mesh in a few seconds; invoked by tools/ci_check.sh after the
telemetry smoke so the serving seams cannot silently rot.
"""
from __future__ import annotations

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CORE_SPANS = {"serve.warmup", "serve.step", "serve.admit", "serve.prefill",
              "serve.decode", "serve.fetch"}


def fail(msg: str) -> None:
    print(f"serve_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def main() -> None:
    import numpy as np

    from mxnet_tpu import telemetry
    from mxnet_tpu.models.transformer import transformer_lm
    from mxnet_tpu.serve import Engine, EngineConfig

    tmp = tempfile.mkdtemp(prefix="serve-smoke-")
    trace = os.path.join(tmp, "trace.json")
    telemetry.reset_for_tests()
    telemetry.configure(trace=trace)

    V, NL, D, H = 97, 2, 32, 4
    sym = transformer_lm(vocab_size=V, num_layers=NL, d_model=D, heads=H,
                         batch_size=1, seq_len=8)
    shapes, _, _ = sym.infer_shape(data=(1, 8), softmax_label=(1, 8))
    rng = np.random.RandomState(0)
    params = {n: (rng.randn(*s) * 0.05).astype(np.float32)
              for n, s in zip(sym.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}

    eng = Engine(params, EngineConfig(
        heads=H, block_size=4, num_blocks=64, max_batch=8,
        max_prompt_len=16, max_seq_len=48, prompt_bucket_min=8,
        prefill_chunk=8, kv_quant="fp8"))
    eng.warmup()

    r = np.random.RandomState(1)
    budgets = [int(r.randint(6, 13)) for _ in range(8)]
    prompts = [list(map(int, r.randint(1, V, int(r.randint(2, 9)))))
               for _ in budgets]
    ids = [eng.submit(p, max_new_tokens=m, temperature=0.8 * (i % 2),
                      seed=i)
           for i, (p, m) in enumerate(zip(prompts, budgets))]

    # 1 step = admit all 8 + prefill + first batched decode.  The engine
    # must already be warm here: zero traces from step 1 onward.
    traces_warm = dict(eng.trace_counts)
    eng.step()
    if dict(eng.trace_counts) != traces_warm:
        fail(f"step 1 retraced: {dict(eng.trace_counts)} != {traces_warm}")

    eng.run()
    if dict(eng.trace_counts) != traces_warm:
        fail("decode not warm after step 1: new traces "
             f"{dict(eng.trace_counts)} vs warmup {traces_warm}")

    for rid, budget in zip(ids, budgets):
        req = eng.requests[rid]
        if req.state != "finished":
            fail(f"request {rid} ended {req.state!r}, not finished")
        if len(req.tokens) != budget and req.finish_reason != "eos":
            fail(f"request {rid} produced {len(req.tokens)}/{budget} "
                 f"tokens (reason={req.finish_reason!r})")
    if eng.alloc.num_used != 0:
        fail(f"{eng.alloc.num_used} KV blocks leaked after drain")

    flat = telemetry.snapshot_flat()
    want = sum(len(eng.requests[i].tokens) for i in ids)
    if flat.get("serve.tokens_total") != want:
        fail(f"serve.tokens_total={flat.get('serve.tokens_total')} "
             f"!= {want} tokens generated")
    min_chunks = sum(-(-len(p) // eng.prefill_chunk) for p in prompts)
    chunks = flat.get("serve.prefill_chunks", 0)
    if chunks < min_chunks:
        fail(f"serve.prefill_chunks={chunks} < {min_chunks} (every "
             "prompt must ingest through the chunk pump)")
    from mxnet_tpu.serve import kvcache
    want_bpt = kvcache.kv_bytes_per_token(NL, H, D // H, "fp8")
    if flat.get("kv_bytes_per_token") != want_bpt:
        fail(f"kv_bytes_per_token gauge {flat.get('kv_bytes_per_token')}"
             f" != {want_bpt} (fp8 pool accounting)")

    path = telemetry.export_trace()
    info = telemetry.validate_trace(path)
    if info["events"] <= 0:
        fail("trace exported no events")
    missing = CORE_SPANS - set(info["span_names"])
    if missing:
        fail(f"trace missing serve spans {sorted(missing)} "
             f"(have {sorted(info['span_names'])})")

    # 4. control plane: replica crash mid-stream must be invisible to
    # clients.  Same params, 2 replicas, 4 mixed greedy/sampled
    # streams; the chaos fleet crashes replica 0 a few steps in.
    from mxnet_tpu.chaos import ChaosSpec
    from mxnet_tpu.serve import Router, RouterConfig

    ecfg = EngineConfig(
        heads=H, block_size=4, num_blocks=64, max_batch=4,
        max_prompt_len=16, max_seq_len=48, prompt_bucket_min=8,
        prefill_chunk=8, kv_quant="fp8")
    rprompts = prompts[:4]
    rkw = [dict(max_new_tokens=8, temperature=0.8 * (i % 2), seed=50 + i)
           for i in range(4)]

    def fleet(chaos):
        telemetry.reset_for_tests()
        rt = Router(params, engine_config=ecfg,
                    config=RouterConfig(replicas=2), chaos=chaos)
        rt.warmup()
        rids = [rt.submit(p, **kw) for p, kw in zip(rprompts, rkw)]
        warm = [dict(rep.engine.trace_counts) for rep in rt.replicas]
        rt.run()
        return rt, rids, warm

    ref, ref_ids, _ = fleet({})
    want_streams = [list(ref.request(i).tokens) for i in ref_ids]

    rt, rids, warm = fleet({0: ChaosSpec({"serve_crash": {4}})})
    flat = telemetry.snapshot_flat()
    if flat.get("serve.router.deaths{cause=crash}", 0) < 1:
        fail("chaos serve_crash never fired (no replica death recorded)")
    if flat.get("serve.router.failovers", 0) < 1:
        fail("replica died but no request failed over")
    for i, rid in enumerate(rids):
        req = rt.request(rid)
        if not req.done() or req.state != "finished":
            fail(f"router stream {rid} ended {req.state!r} after crash")
        if list(req.tokens) != want_streams[i]:
            fail(f"failover stream {rid} diverged: {list(req.tokens)} "
                 f"!= {want_streams[i]} (must be byte-identical)")
    survivor = rt.replicas[1]
    if dict(survivor.engine.trace_counts) != warm[1]:
        fail("survivor retraced during failover: "
             f"{dict(survivor.engine.trace_counts)} != {warm[1]}")
    if survivor.engine.alloc.num_used != 0:
        fail(f"survivor leaked {survivor.engine.alloc.num_used} KV "
             "blocks after failover drain")

    # 5. train -> publish -> rolling swap under live load.  8 streams
    # in flight (4 per replica, both replicas saturated), then a
    # weight update trained from the SAME serving weights deploys via
    # the compat-stamped checkpoint — the swap must be hot (zero
    # retraces) and invisible to the streams.
    import jax

    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.online import compat_stamp, make_rollout_trainer
    from mxnet_tpu.parallel import make_mesh

    telemetry.reset_for_tests()
    rt5 = Router(params, engine_config=ecfg,
                 config=RouterConfig(replicas=2), chaos={})
    rt5.warmup()
    live = [rt5.submit(p, max_new_tokens=m, temperature=0.8 * (i % 2),
                       seed=200 + i)
            for i, (p, m) in enumerate(zip(prompts, budgets))]
    for _ in range(2):
        rt5.step()                  # streams genuinely mid-flight
    warm5 = [dict(rep.engine.trace_counts) for rep in rt5.replicas]

    trainer = make_rollout_trainer(
        params, heads=H, batch=8, seq_len=32,
        mesh=make_mesh({"data": 1}, jax.devices()[:1]))
    tr_rng = np.random.RandomState(7)
    tdata = tr_rng.randint(1, V, (8, 32)).astype(np.float32)
    tlabels = np.full((8, 32), -1, np.float32)
    tlabels[:, :-1] = tdata[:, 1:]  # next-token; last position masked
    for _ in range(3):
        trainer.step({"data": tdata, "softmax_label": tlabels})
    arg, aux = trainer.get_params()
    mgr = CheckpointManager(os.path.join(tmp, "ckpt"))
    mgr.save_model(int(trainer._num_update), trainer.symbol, arg, aux,
                   meta={"compat": compat_stamp(dict(arg), heads=H)},
                   blocking=True)
    mgr.wait_until_finished()
    summary = rt5.rolling_swap(mgr.directory)
    mgr.close()
    if summary["mode"] != "hot":
        fail(f"trained update should hot-swap, got {summary['mode']} "
             f"({summary['report']})")
    rt5.run()
    for rid in live:
        req = rt5.request(rid)
        if req.state != "finished":
            fail(f"stream {rid} ended {req.state!r} across the swap")
    for rep in rt5.replicas:
        if dict(rep.engine.trace_counts) != warm5[rep.idx]:
            fail(f"replica {rep.idx} retraced during hot swap: "
                 f"{dict(rep.engine.trace_counts)} != {warm5[rep.idx]}")
        if rep.engine.alloc.num_used != 0:
            fail(f"replica {rep.idx} leaked {rep.engine.alloc.num_used} "
                 "KV blocks across the swap")
    flat = telemetry.snapshot_flat()
    if flat.get("online.swaps") != len(rt5.replicas):
        fail(f"online.swaps={flat.get('online.swaps')} != "
             f"{len(rt5.replicas)} replicas swapped")
    if flat.get("online.swap_ms.count") != len(rt5.replicas):
        fail("online.swap_ms histogram missing per-replica swap latency")
    swap_ms = summary["swap_ms"]

    # --- 6. speculative decoding (docs/serving.md, round 15) --------
    # the same 8 streams through a speculate=True engine (n-gram
    # drafter, fp8 KV): warm after step 1 — the verify program replaces
    # the decode family in the warmup set — greedy streams
    # byte-identical to the plain engine from section 1, acceptance
    # telemetry advancing, and the pool drains (rejected-tail scrub
    # keeps the block ledger exact).
    spec_eng = Engine(params, EngineConfig(
        heads=H, block_size=4, num_blocks=64, max_batch=8,
        max_prompt_len=16, max_seq_len=48, prompt_bucket_min=8,
        prefill_chunk=8, kv_quant="fp8", speculate=True, spec_k=4))
    spec_eng.warmup()
    kinds = {k for k, _ in spec_eng._programs}
    if "verify" not in kinds or "decode" in kinds:
        fail(f"speculative warmup compiled {sorted(kinds)}; expected "
             "the verify family to REPLACE decode")
    sids = [spec_eng.submit(p, max_new_tokens=m,
                            temperature=0.8 * (i % 2), seed=i)
            for i, (p, m) in enumerate(zip(prompts, budgets))]
    spec_warm = dict(spec_eng.trace_counts)
    spec_eng.step()
    if dict(spec_eng.trace_counts) != spec_warm:
        fail(f"speculative step 1 retraced: "
             f"{dict(spec_eng.trace_counts)} != {spec_warm}")
    spec_eng.run()
    if dict(spec_eng.trace_counts) != spec_warm:
        fail("speculative engine not warm after step 1: "
             f"{dict(spec_eng.trace_counts)} vs {spec_warm}")
    for i, (sid, rid) in enumerate(zip(sids, ids)):
        sreq = spec_eng.requests[sid]
        if sreq.state != "finished":
            fail(f"speculative stream {sid} ended {sreq.state!r}")
        if i % 2 == 0 and sreq.tokens != eng.requests[rid].tokens:
            fail(f"greedy stream {i} diverged under speculation: "
                 f"{sreq.tokens} != {eng.requests[rid].tokens}")
    if spec_eng.alloc.num_used != 0:
        fail(f"speculative engine leaked {spec_eng.alloc.num_used} "
             "KV blocks (rejected-tail scrub / cursor rollback broken)")
    flat = telemetry.snapshot_flat()
    spec_acc = int(flat.get("serve.spec.accepted", 0))
    if not flat.get("serve.spec.steps"):
        fail("serve.spec.steps counter never advanced")
    if spec_acc <= 0:
        fail("serve.spec.accepted never advanced (drafter accepted "
             "nothing on cycling greedy streams)")
    spec_stats = spec_eng.stats()["speculate"]

    # --- 7. cross-request prefix cache (docs/serving.md, round 18) --
    # 8 same-step streams over one shared 12-token system prompt: the
    # first stream prefills it, the other 7 map its published blocks
    # via the second-chance re-probe — one prefill of the prefix,
    # byte-identical streams, no retraces, no leak.
    pfx_cfg = dict(heads=H, block_size=4, num_blocks=64, max_batch=8,
                   max_prompt_len=16, max_seq_len=48,
                   prompt_bucket_min=8, prefill_chunk=4, kv_quant="fp8")
    shared = [int(t) for t in np.random.RandomState(3).randint(1, V, 12)]
    sfx_rng = np.random.RandomState(5)
    pfx_prompts = [shared + [int(t) for t in
                             sfx_rng.randint(1, V, int(sfx_rng.randint(2, 5)))]
                   for _ in range(8)]
    pfx_kw = [dict(max_new_tokens=6, temperature=0.8 * (i % 2),
                   seed=300 + i) for i in range(8)]

    telemetry.reset_for_tests()
    cold = Engine(params, EngineConfig(**pfx_cfg))
    cold.warmup()
    cold_ids = [cold.submit(p, **kw) for p, kw in zip(pfx_prompts, pfx_kw)]
    cold.run()
    cold_streams = [cold.requests[i].tokens for i in cold_ids]

    telemetry.reset_for_tests()
    pfx = Engine(params, EngineConfig(prefix_cache=True, **pfx_cfg))
    pfx.warmup()
    pfx_ids = [pfx.submit(p, **kw) for p, kw in zip(pfx_prompts, pfx_kw)]
    pfx_warm = dict(pfx.trace_counts)
    pfx.step()
    if dict(pfx.trace_counts) != pfx_warm:
        fail(f"prefix-cache step 1 retraced: {dict(pfx.trace_counts)} "
             f"!= {pfx_warm}")
    pfx.run()
    if dict(pfx.trace_counts) != pfx_warm:
        fail("prefix-cache engine not warm after step 1: "
             f"{dict(pfx.trace_counts)} vs {pfx_warm}")
    for i, pid in enumerate(pfx_ids):
        if pfx.requests[pid].tokens != cold_streams[i]:
            fail(f"prefix-cache stream {i} diverged: "
                 f"{pfx.requests[pid].tokens} != {cold_streams[i]} "
                 "(warm must be byte-identical to cache-cold)")
    pstats = pfx.stats()["prefix"]
    if pstats["hits"] != 7 or pstats["misses"] != 1:
        fail(f"prefix cohort expected 7 hits / 1 miss, got "
             f"{pstats['hits']} / {pstats['misses']} (second-chance "
             "re-probe must map what the first stream published)")
    flat = telemetry.snapshot_flat()
    if flat.get("serve.prefix.hit_tokens") != 7 * len(shared):
        fail(f"serve.prefix.hit_tokens="
             f"{flat.get('serve.prefix.hit_tokens')} != {7 * len(shared)}"
             " (7 warm streams x 12 shared-prefix tokens)")
    if flat.get("serve.prefix.shared_blocks", 0) != 7 * 3:
        fail(f"serve.prefix.shared_blocks="
             f"{flat.get('serve.prefix.shared_blocks')} != 21")
    pfx_chunks = int(flat.get("serve.prefill_chunks", 0))
    # miss stream: 12-token prefix + suffix = 4 chunks; each warm
    # stream runs ONE suffix chunk
    if pfx_chunks != 4 + 7:
        fail(f"prefix cohort ran {pfx_chunks} prefill chunks, expected "
             "11 (the shared prefix must prefill exactly once)")
    if pfx.alloc.num_used != 0:
        fail(f"prefix-cache engine leaked {pfx.alloc.num_used} KV "
             "blocks (cached prefix blocks must park refcount-0)")
    if pfx.alloc.num_cached < 3:
        fail(f"only {pfx.alloc.num_cached} blocks cached after drain; "
             "the shared prefix (3 blocks) should stay resident")
    pfx.check_tables()

    print(f"serve_smoke: OK (8 streams, {want} tokens, "
          f"hot-swap {len(swap_ms)} replicas "
          f"[{', '.join(f'{m:.0f}ms' for m in swap_ms)}] under load, "
          f"{eng.step_idx} steps, {int(chunks)} prefill chunks, "
          f"fp8 kv {want_bpt} B/token, traces "
          f"{sum(traces_warm.values())} at warmup + 0 after, "
          f"{info['events']} trace events, "
          f"{int(flat.get('serve.router.failovers', 0))} failovers "
          f"byte-identical, speculative k={spec_stats['k']} "
          f"accept={spec_stats['accept_rate']:.2f} "
          f"({spec_acc} drafts landed), prefix cache "
          f"{pstats['hits']}/8 hits {pfx_chunks} chunks "
          f"byte-identical, dir={{0}})".format(tmp))


if __name__ == "__main__":
    main()
