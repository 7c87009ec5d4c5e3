#!/usr/bin/env python
"""Static analysis gate for mxnet_tpu (docs/static_analysis.md).

Five subcommands:

- ``lint``  — AST linter over the repo sources (host syncs in traced
  code, nondeterminism, env-var doc drift, donated-buffer reads).
- ``audit`` — trace + lower the framework's own step programs (the
  default FullyConnected trainer and the transformer-LM trainer) and
  run the jaxpr/HLO rules: dtype widening, carried-state fixed points,
  host transfers, donation misses, captured constants.  Also reports
  how many passes the update chain makes over each flat grad bucket
  in the jaxpr.
- ``gate``  — CI entry: lint + audit must be clean AND every seeded
  violation in ``tests/golden/staticcheck/`` must still be detected
  (rule-regression coverage), with the corpus' negative control
  staying silent.
- ``races`` — runtime lockset sanitizer (``conc.*`` rules): drive the
  real threaded control-plane paths (telemetry record/dump, the async
  checkpoint writer, the device prefetcher) under
  ``analysis.audit_threads(instrument_framework=True)``, then re-run
  every seeded violation in ``tests/golden/staticcheck/bad_threads.py``
  (detector-regression coverage, with clean negative controls).
- ``schedules`` — deterministic schedule fuzzer: N seeded
  interleavings per hot concurrent scenario
  (``mxnet_tpu/analysis/schedules.py``), each asserting byte-identity
  invariants.  ``MXNET_TPU_CONC_SCHEDULES`` / ``MXNET_TPU_CONC_SEED``
  (or ``--n`` / ``--seed``) set the sweep; a failure prints the
  replayable ``(scenario, seed)`` pair.

Exit codes: 0 clean, 1 findings / missed expectations, 2 internal
error.  ``--json`` emits the machine-readable report (schema in
``mxnet_tpu/analysis/findings.py``); ``--suppress RULE[:LOCATION]``
(repeatable, globs allowed) silences known findings with an audit
trail, e.g. ``--suppress 'program.captured-const:trainer.*'``.
"""
import argparse
import importlib.util
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS_DIR = os.path.join(REPO_ROOT, "tests", "golden", "staticcheck")

# audited by `audit` and `gate`: the acceptance programs of the analysis
# subsystem — a plain data-parallel FC classifier and the shape-baking
# transformer-LM, both through the real ShardedTrainer path
AUDIT_NETWORKS = ("fc", "transformer-lm")


def _repo_import():
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from mxnet_tpu import analysis
    return analysis


# ----------------------------------------------------------------------
# Trainer builders (mirror tests/test_compile_cache.py fixtures)
# ----------------------------------------------------------------------

def _build_trainer(network):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh

    devs = jax.devices()
    mx.random.seed(7)
    if network == "fc":
        data = mx.symbol.Variable("data")
        net = mx.symbol.FullyConnected(data=data, num_hidden=32, name="fc1")
        net = mx.symbol.Activation(data=net, act_type="relu")
        net = mx.symbol.FullyConnected(data=net, num_hidden=10, name="fc2")
        sym = mx.symbol.SoftmaxOutput(data=net, name="softmax")
        tr = ShardedTrainer(sym, mesh=make_mesh({"data": len(devs)}, devs),
                            optimizer="sgd",
                            optimizer_params={"learning_rate": 0.1,
                                              "momentum": 0.9})
        tr.bind(data_shapes={"data": (16, 8)},
                label_shapes={"softmax_label": (16,)})
        return tr
    if network == "transformer-lm":
        from mxnet_tpu import models
        B, L, V = 8, 16, 128
        sym = models.get_symbol("transformer-lm", vocab_size=V,
                                num_layers=2, d_model=64, heads=2,
                                batch_size=B, seq_len=L)
        tr = ShardedTrainer(sym, mesh=make_mesh({"data": len(devs)}, devs),
                            optimizer="adam",
                            optimizer_params={"learning_rate": 1e-3})
        tr.bind(data_shapes={"data": (B, L)},
                label_shapes={"softmax_label": (B, L)})
        return tr
    raise ValueError(f"unknown audit network: {network!r} "
                     f"(choose from {AUDIT_NETWORKS})")


def _run_audit(analysis, networks, programs):
    report = analysis.Report(mode="audit")
    for network in networks:
        tr = _build_trainer(network)
        sub = analysis.audit_trainer(tr, programs=programs)
        # prefix program labels/metrics with the network name so the two
        # trainers' findings stay distinguishable in one report
        for f in sub.findings:
            if f.program:
                f.program = f"{network}.{f.program}"
        report.findings.extend(sub.findings)
        for k, v in sub.metrics.items():
            report.metrics[f"{network}.{k}"] = v
    return report


def _hbm_lines(report):
    lines = []
    for prog, m in sorted(report.metrics.items()):
        hbm = (m or {}).get("hbm_passes")
        if not hbm:
            continue
        lines.append(f"{prog}: hbm buckets={len(hbm.get('buckets', []))} "
                     f"max_reads={hbm.get('max_reads')} "
                     f"max_writes={hbm.get('max_writes')}")
        for b in hbm.get("buckets", []):
            lines.append(f"  bucket[{b['index']}] {b['dtype']} "
                         f"{b['bytes']} B ({len(b['params'])} params): "
                         f"{b['reads']} reads / {b['writes']} writes")
    return lines


# ----------------------------------------------------------------------
# Corpus self-check (gate)
# ----------------------------------------------------------------------

def _load_corpus_module():
    path = os.path.join(CORPUS_DIR, "bad_programs.py")
    spec = importlib.util.spec_from_file_location(
        "mxtpu_staticcheck_corpus", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check_corpus(analysis):
    """Returns (ok, failures, details).  A failure is a seeded violation
    the tooling no longer detects, or a finding on the negative
    control."""
    with open(os.path.join(CORPUS_DIR, "expected.json")) as f:
        expected = json.load(f)
    failures = []

    # --- lint rules over bad_source/ ---
    src_files = [os.path.join(CORPUS_DIR, e["file"])
                 for e in expected["source"]]
    lint = analysis.lint_paths(CORPUS_DIR, paths=sorted(set(src_files)))
    by_file_rule = {}
    for f in lint.findings:
        key = (f.path.replace(os.sep, "/"), f.rule)
        by_file_rule[key] = by_file_rule.get(key, 0) + 1
    for e in expected["source"]:
        got = by_file_rule.get((e["file"], e["rule"]), 0)
        want = e.get("min_count", 1)
        if got < want:
            failures.append(f"corpus: {e['rule']} fired {got}x on "
                            f"{e['file']} (expected >= {want})")

    # --- program rules over bad_programs.py ---
    mod = _load_corpus_module()
    prog_report = analysis.Report(mode="audit")
    for name, (builder, _rules) in mod.PROGRAMS.items():
        traced, kwargs = builder()
        analysis.audit_traced(traced, f"corpus.{name}",
                              report=prog_report, **kwargs)
    by_prog_rule = {}
    for f in prog_report.findings:
        key = (f.program, f.rule)
        by_prog_rule[key] = by_prog_rule.get(key, 0) + 1
    for e in expected["programs"]:
        prog = e["program"]
        if e.get("clean"):
            hits = [r for (p, r) in by_prog_rule if p == prog]
            if hits:
                failures.append(f"corpus: negative control {prog} "
                                f"triggered {sorted(hits)}")
            continue
        got = by_prog_rule.get((prog, e["rule"]), 0)
        if got < e.get("min_count", 1):
            failures.append(f"corpus: {e['rule']} did not fire on {prog}")

    details = {"lint_findings": len(lint.findings),
               "program_findings": len(prog_report.findings),
               "failures": failures}
    return not failures, failures, details


# ----------------------------------------------------------------------
# Concurrency: live lockset run + threads corpus (races), fuzzer sweep
# ----------------------------------------------------------------------

def _races_live(analysis):
    """Drive the real threaded control-plane paths under the lockset
    sanitizer and return the analyzed report.  This is the repo-wide
    "no data races in what actually runs" check: telemetry step
    recording + mid-append flight dumps, the JSONL emitter, the async
    checkpoint writer racing in-place mutation, and the device
    prefetch worker."""
    import logging
    import tempfile
    import threading

    import numpy as np

    from mxnet_tpu import telemetry

    report = analysis.Report(mode="races")
    telemetry.reset_for_tests()
    # the mid-append dumps are the point of the exercise, not news
    logging.getLogger("mxnet_tpu.telemetry.flight").setLevel(logging.ERROR)
    with tempfile.TemporaryDirectory(prefix="mxtpu_races_") as td:
        telemetry.configure(metrics_file=os.path.join(td, "m.jsonl"),
                            metrics_interval=0.0)
        with analysis.audit_threads(report=report,
                                    instrument_framework=True) as audit:
            def ticker(tag):
                for i in range(30):
                    telemetry.record_step({"step": i, "tag": tag})
                    telemetry.counter(f"races.{tag}").inc()

            ts = [threading.Thread(target=ticker, args=(k,),
                                   name=f"races-tick-{k}")
                  for k in ("a", "b")]
            for t in ts:
                t.start()
            for i in range(5):   # dumps race the appending tickers
                telemetry.dump_flight("races",
                                      path=os.path.join(td, f"f{i}.json"))
            for t in ts:
                t.join()
            telemetry.flush_metrics()

            from mxnet_tpu.checkpoint.manager import CheckpointManager
            arrays = {"w": np.arange(32, dtype=np.float32)}
            mgr = CheckpointManager(os.path.join(td, "ckpt"), keep_last=3,
                                    async_write=True)
            for s in range(2):
                mgr.save(s, arrays)
                arrays["w"] += 1.0   # in-place "train step" mutation
            mgr.wait_until_finished()
            mgr.close()

            from mxnet_tpu.io import DevicePrefetchIter, NDArrayIter
            it = DevicePrefetchIter(
                NDArrayIter(np.zeros((16, 4), dtype=np.float32),
                            batch_size=4), depth=2)
            for _ in it:
                pass
            it.close()
    telemetry.configure(metrics_file="")   # drop the tmpdir emitter
    races = report.metrics.get("races", {}).get("races_found", 0)
    telemetry.counter("staticcheck.races_found").inc(races)
    return report


def _load_threads_corpus():
    path = os.path.join(CORPUS_DIR, "bad_threads.py")
    spec = importlib.util.spec_from_file_location(
        "mxtpu_staticcheck_threads", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check_threads_corpus(analysis):
    """Re-run every seeded concurrency violation: each case drives real
    threads under its own audit window and must still produce its rule
    (negative controls must stay silent)."""
    with open(os.path.join(CORPUS_DIR, "expected.json")) as f:
        expected = json.load(f)
    mod = _load_threads_corpus()
    failures = []
    per_case = {}
    for e in expected.get("threads", []):
        name = e["case"]
        fn = mod.CASES[name]
        with analysis.audit_threads() as audit:
            fn(audit)
        rules = sorted({f.rule for f in audit.report.findings
                        if not f.suppressed})
        per_case[name] = rules
        if e.get("clean"):
            if rules:
                failures.append(f"threads corpus: negative control "
                                f"{name} triggered {rules}")
            continue
        got = sum(1 for f in audit.report.findings
                  if f.rule == e["rule"] and not f.suppressed)
        if got < e.get("min_count", 1):
            failures.append(f"threads corpus: {e['rule']} did not fire "
                            f"on {name}")
    details = {"cases": per_case, "failures": failures}
    return not failures, failures, details


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="staticcheck",
        description="jaxpr/HLO program auditor + repo linter "
                    "(docs/static_analysis.md)")
    ap.add_argument("command",
                    choices=("lint", "audit", "gate", "races", "schedules"))
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable report")
    ap.add_argument("--suppress", action="append", default=[],
                    metavar="RULE[:LOCATION]",
                    help="suppress findings (repeatable; fnmatch globs)")
    ap.add_argument("--show-suppressed", action="store_true")
    ap.add_argument("--root", default=REPO_ROOT,
                    help="repo root to lint (default: this checkout)")
    ap.add_argument("--networks", default=",".join(AUDIT_NETWORKS),
                    help="comma-separated audit networks "
                         f"(default {','.join(AUDIT_NETWORKS)})")
    ap.add_argument("--programs", default="train,train_acc",
                    help="trainer program kinds to audit")
    ap.add_argument("--scenarios", default="",
                    help="comma-separated schedule scenarios "
                         "(default: all; see analysis/schedules.py)")
    ap.add_argument("--n", type=int, default=None,
                    help="interleavings per scenario "
                         "(default MXNET_TPU_CONC_SCHEDULES=50)")
    ap.add_argument("--seed", type=int, default=None,
                    help="base fuzzer seed (default MXNET_TPU_CONC_SEED=0)")
    args = ap.parse_args(argv)

    analysis = _repo_import()
    networks = [n for n in args.networks.split(",") if n]
    programs = tuple(p for p in args.programs.split(",") if p)

    out = {"schema": analysis.SCHEMA_VERSION, "command": args.command}
    extra_lines = []
    corpus_ok = True
    corpus_failures = []
    if args.command == "lint":
        report = analysis.lint_paths(args.root)
    elif args.command == "audit":
        report = _run_audit(analysis, networks, programs)
        extra_lines = _hbm_lines(report)
    elif args.command == "races":
        report = _races_live(analysis)
        corpus_ok, corpus_failures, corpus_details = \
            _check_threads_corpus(analysis)
        out["corpus"] = corpus_details
        m = report.metrics.get("races", {})
        extra_lines = [f"races: live run: {m.get('events', 0)} events, "
                       f"{m.get('threads', 0)} threads, "
                       f"{m.get('locations', 0)} tracked locations, "
                       f"{m.get('races_found', 0)} race(s)"]
    elif args.command == "schedules":
        report = analysis.Report(mode="schedules")
        scenarios = [s for s in args.scenarios.split(",") if s] or None
        res = analysis.run_schedules(
            scenarios=scenarios, n=args.n, seed=args.seed,
            log=None if args.json else print)
        out["schedules"] = res
        corpus_ok = res["ok"]
        corpus_failures = [
            f"schedules: {f['scenario']} failed at seed {f['seed']} "
            f"({f['error']}) — replay: staticcheck schedules "
            f"--scenarios {f['scenario']} --n 1 --seed {f['seed']}"
            for f in res["failures"]]
    else:  # gate
        report = analysis.Report(mode="gate")
        report.merge(analysis.lint_paths(args.root))
        audit = _run_audit(analysis, networks, programs)
        report.merge(audit)
        extra_lines = _hbm_lines(audit)
        corpus_ok, corpus_failures, corpus_details = _check_corpus(analysis)
        out["corpus"] = corpus_details

    analysis.apply_cli(report.findings, args.suppress)
    ok = report.clean and corpus_ok

    out.update(report.to_dict())
    out["ok"] = ok
    if args.json:
        print(json.dumps(out, indent=2, default=str))
    else:
        print(report.format_text(show_suppressed=args.show_suppressed))
        for line in extra_lines:
            print(line)
        for fail in corpus_failures:
            print(fail)
        if args.command in ("gate", "races", "schedules"):
            print(f"{args.command}: {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 - CLI boundary
        print(f"staticcheck: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        sys.exit(2)
