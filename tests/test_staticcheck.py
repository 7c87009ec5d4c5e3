"""mxnet_tpu.analysis + tools/staticcheck.py: the jaxpr/HLO program
auditor, the repo linter, and the CI gate.

Covered contracts: (a) the acceptance programs — the default FC trainer
and the transformer-LM trainer — audit CLEAN through
``assert_program_clean`` and report the grad-bucket HBM-pass measuring
stick; (b) every rule in the seeded corpus
(``tests/golden/staticcheck/``) still fires, and the negative control
stays silent; (c) the CLI's JSON schema, exit codes, and suppression
plumbing; (d) the compile-path observer audits exactly what the
trainer compiles.
"""
import ast
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import analysis
from mxnet_tpu.analysis import findings as F
from mxnet_tpu.analysis import source as S
from mxnet_tpu.parallel import ShardedTrainer, make_mesh

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS = os.path.join(REPO_ROOT, "tests", "golden", "staticcheck")
CLI = os.path.join(REPO_ROOT, "tools", "staticcheck.py")

pytestmark = pytest.mark.staticcheck


def _mlp():
    data = mx.symbol.Variable("data")
    net = mx.symbol.FullyConnected(data=data, num_hidden=32, name="fc1")
    net = mx.symbol.Activation(data=net, act_type="relu")
    net = mx.symbol.FullyConnected(data=net, num_hidden=10, name="fc2")
    return mx.symbol.SoftmaxOutput(data=net, name="softmax")


def _fc_trainer(**kw):
    mx.random.seed(7)
    tr = ShardedTrainer(_mlp(), mesh=make_mesh({"data": len(jax.devices())}),
                        optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1,
                                          "momentum": 0.9}, **kw)
    tr.bind(data_shapes={"data": (16, 8)},
            label_shapes={"softmax_label": (16,)})
    return tr


def _lm_trainer(**kw):
    from mxnet_tpu import models
    B, L, V = 8, 16, 128
    sym = models.get_symbol("transformer-lm", vocab_size=V, num_layers=2,
                            d_model=64, heads=2, batch_size=B, seq_len=L)
    mx.random.seed(7)
    tr = ShardedTrainer(sym, mesh=make_mesh({"data": len(jax.devices())}),
                        optimizer="adam",
                        optimizer_params={"learning_rate": 1e-3}, **kw)
    tr.bind(data_shapes={"data": (B, L)},
            label_shapes={"softmax_label": (B, L)})
    return tr


# ---------------------------------------------------------------------------
# Findings / suppression plumbing
# ---------------------------------------------------------------------------

def test_every_rule_has_severity_and_description():
    for rule, (sev, desc) in F.RULES.items():
        assert sev in F.SEVERITIES and desc
        assert rule.split(".")[0] in ("program", "source", "conc")


def test_finding_defaults_severity_from_rule():
    f = F.Finding("program.captured-const", "m")
    assert f.severity == "warn"
    assert F.Finding("source.host-sync", "m").severity == "error"


def test_inline_suppression_same_line_and_next_line():
    src = textwrap.dedent("""\
        x = 1
        y = foo()  # staticcheck: disable=source.host-sync -- known safe
        # staticcheck: disable=source.nondet -- seeded clock
        z = bar()
    """)
    supp = F.parse_inline_suppressions(src)
    assert supp[2][0] == ["source.host-sync"]
    assert supp[2][1] == "known safe"
    assert 3 in supp and 4 in supp          # comment line covers the next
    f2 = F.Finding("source.host-sync", "m", path="f.py", line=2)
    f4 = F.Finding("source.nondet", "m", path="f.py", line=4)
    fx = F.Finding("source.nondet", "m", path="f.py", line=2)
    F.apply_inline([f2, f4, fx], supp)
    assert f2.suppressed and f4.suppressed and not fx.suppressed


def test_cli_suppression_rule_and_location_globs():
    fs = [F.Finding("program.widen", "m", program="trainer.train"),
          F.Finding("program.widen", "m", program="corpus.x"),
          F.Finding("source.nondet", "m", path="mxnet_tpu/a.py", line=3)]
    F.apply_cli(fs, ["program.widen:trainer.*"])
    assert fs[0].suppressed and not fs[1].suppressed
    F.apply_cli(fs, ["source.*"])
    assert fs[2].suppressed


def test_report_clean_ignores_warns_counts_errors():
    r = F.Report(mode="audit")
    r.add(F.Finding("program.captured-const", "m"))     # warn
    assert r.clean
    bad = r.add(F.Finding("program.widen", "m"))
    assert not r.clean
    bad.suppressed = True
    assert r.clean
    d = r.to_dict()
    assert d["schema"] == F.SCHEMA_VERSION
    assert set(d) >= {"mode", "clean", "counts", "findings", "metrics"}


# ---------------------------------------------------------------------------
# Linter behavior on targeted snippets
# ---------------------------------------------------------------------------

def _lint_src(src):
    return analysis.lint_file("snippet.py", src=src, rel="snippet.py")


def test_linter_flags_host_sync_and_honors_meta_untaint():
    rep = _lint_src(textwrap.dedent("""\
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            n = float(x.sum())          # concretizes a tracer
            pad = int(np.prod(x.shape)) # static shape math: fine
            return n + pad
    """))
    rules = [f.rule for f in rep.findings]
    assert rules == ["source.host-sync"]
    assert rep.findings[0].line == 6


def test_linter_tree_map_is_not_a_traced_region():
    rep = _lint_src(textwrap.dedent("""\
        import jax
        import numpy as np

        def place(val, sh):
            val = np.asarray(val)       # host-side placement: fine
            return jax.device_put(val, sh)

        def put_all(tree, sh):
            return jax.tree.map(lambda v: place(v, sh), tree)
    """))
    assert rep.findings == []


def test_linter_traced_directive_and_inline_suppression():
    rep = _lint_src(textwrap.dedent("""\
        import numpy as np

        def helper(x):  # staticcheck: traced
            a = np.tanh(x)  # staticcheck: disable=source.host-sync -- demo
            return np.exp(x)
    """))
    assert [f.rule for f in rep.findings if not f.suppressed] == \
        ["source.host-sync"]
    assert [f.line for f in rep.findings if f.suppressed] == [4]


def test_linter_donated_mutation_and_rebind_clears():
    rep = _lint_src(textwrap.dedent("""\
        import jax

        def update(params, grads, fresh):
            step = jax.jit(apply, donate_argnums=(0,))
            out = step(params, grads)
            bad = params                # read after donation
            params = fresh              # rebind: new buffer
            ok = params
            return out, bad, ok
    """))
    assert [f.rule for f in rep.findings] == ["source.donated-mutation"]
    assert rep.findings[0].line == 6


def test_env_reads_cover_wrappers_and_subscripts():
    src = textwrap.dedent("""\
        import os
        _K = "MXNET_TPU_BY_CONST"
        a = os.environ.get("MXNET_TPU_DIRECT")
        b = os.getenv(_K)
        c = os.environ["MXNET_TPU_SUBSCRIPT"]
        d = "MXNET_TPU_MEMBER" in os.environ
        e = _env_flag("MXNET_TPU_WRAPPED")
        f = unrelated("MXNET_TPU_NOT_A_READ")
    """)
    got = {v for v, _ in S.env_reads_in_source(src, ast.parse(src))}
    assert got == {"MXNET_TPU_DIRECT", "MXNET_TPU_BY_CONST",
                   "MXNET_TPU_SUBSCRIPT", "MXNET_TPU_MEMBER",
                   "MXNET_TPU_WRAPPED"}


def test_repo_lint_is_clean():
    """The shipped tree must lint clean — this IS the CI gate's lint
    half, kept as a test so a plain pytest run catches drift (e.g. a
    new env var nobody documented)."""
    rep = analysis.lint_paths(REPO_ROOT)
    assert rep.clean, rep.format_text()
    assert not rep.unsuppressed("warn"), rep.format_text()


# ---------------------------------------------------------------------------
# Program auditor: seeded corpus
# ---------------------------------------------------------------------------

def _load_corpus():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "corpus_programs", os.path.join(CORPUS, "bad_programs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_corpus_programs_trigger_their_rules():
    mod = _load_corpus()
    for name, (builder, want_rules) in mod.PROGRAMS.items():
        traced, kwargs = builder()
        rep = analysis.audit_traced(traced, f"corpus.{name}", **kwargs)
        got = {f.rule for f in rep.findings}
        for rule in want_rules:
            assert rule in got, f"{rule} did not fire on corpus.{name}"
        if not want_rules:      # negative control
            assert not rep.findings, rep.format_text()


def test_corpus_carry_widen_is_the_pr2_bug_class():
    """The int32 metric carry + unpinned bool-sum widens to int64 and is
    reported BOTH as a widen escape and as a carry dtype break."""
    mod = _load_corpus()
    traced, kwargs = mod.PROGRAMS["carry_widen"][0]()
    rep = analysis.audit_traced(traced, "corpus.carry_widen", **kwargs)
    carry = [f for f in rep.findings if f.rule == "program.carry-widen"]
    assert len(carry) == 1
    assert "int32" in carry[0].message and "int64" in carry[0].message


def test_corpus_lint_expectations_all_fire():
    with open(os.path.join(CORPUS, "expected.json")) as f:
        expected = json.load(f)
    paths = sorted({os.path.join(CORPUS, e["file"])
                    for e in expected["source"]})
    rep = analysis.lint_paths(CORPUS, paths=paths)
    by = {}
    for f in rep.findings:
        by[(f.path.replace(os.sep, "/"), f.rule)] = \
            by.get((f.path.replace(os.sep, "/"), f.rule), 0) + 1
    for e in expected["source"]:
        got = by.get((e["file"], e["rule"]), 0)
        assert got >= e.get("min_count", 1), \
            f"{e['rule']} fired {got}x on {e['file']}"


# ---------------------------------------------------------------------------
# Acceptance: framework step programs audit clean + HBM measuring stick
# ---------------------------------------------------------------------------

def test_fc_trainer_programs_audit_clean_with_hbm_baseline():
    # the update chain sweeps each gradient 5 times in the jaxpr (scale,
    # momentum read+update, weight read+update...); XLA fuses them
    tr = _fc_trainer()
    rep = analysis.assert_program_clean(tr, programs=("train", "train_acc"))
    hbm = rep.metrics["trainer.train"]["hbm_passes"]
    assert len(hbm["buckets"]) == 1
    assert hbm["max_reads"] == 5 and hbm["max_writes"] == 5
    assert sorted(hbm["per_grad"]) == sorted(
        f"grad:{n}" for n in tr._param_names)
    don = rep.metrics["trainer.train"]["donation"]
    assert don["donated_leaves"] == don["aliased_outputs"] > 0


def test_transformer_lm_trainer_audits_clean():
    tr = _lm_trainer()
    rep = analysis.assert_program_clean(tr, programs=("train",))
    hbm = rep.metrics["trainer.train"]["hbm_passes"]
    assert hbm["max_reads"] >= 8        # adam reads m/v/w + writes
    don = rep.metrics["trainer.train"]["donation"]
    assert don["donated_leaves"] == don["aliased_outputs"] > 0


def test_guardrail_stack_audits_clean_and_costs_hbm_passes():
    # every guardrail costs extra jaxpr sweeps over the grad bucket
    plain = analysis.audit_trainer(_lm_trainer(), programs=("train",))
    guarded = analysis.audit_trainer(
        _lm_trainer(guard=True, clip_global_norm=1.0, loss_scale="dynamic"),
        programs=("train",))
    assert plain.clean and guarded.clean
    assert (guarded.metrics["trainer.train"]["hbm_passes"]["max_reads"]
            > plain.metrics["trainer.train"]["hbm_passes"]["max_reads"])


def test_optimizer_update_audits_clean_and_weight_never_donated():
    from mxnet_tpu.optimizer import SGD
    rep = analysis.assert_program_clean(SGD(momentum=0.9, learning_rate=0.1))
    (prog,) = [k for k in rep.metrics if k.startswith("optimizer.")]
    don = rep.metrics[prog]["donation"]
    assert don["donated_leaves"] == don["aliased_outputs"] > 0


def test_assert_program_clean_raises_with_rule_names():
    def step(c, x):
        return c + jnp.sum(x.astype(jnp.int32) == 0)
    traced = jax.jit(step).trace(
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((8,), jnp.float32))
    rep = analysis.audit_traced(traced, "demo",
                                carry_pairs=[(0, 0, "carry")])
    with pytest.raises(AssertionError, match="program.carry-widen"):
        analysis.assert_program_clean(rep)


def test_audit_on_compile_sees_the_compiled_programs():
    from mxnet_tpu import profiler
    tr = _fc_trainer()
    before = len(profiler.audit_events())
    with analysis.audit_on_compile() as rep:
        tr.compile(programs=("train",))
    assert "trainer.train" in rep.metrics
    assert rep.clean, rep.format_text()
    assert len(profiler.audit_events()) > before


# ---------------------------------------------------------------------------
# CLI: JSON schema, exit codes, suppression
# ---------------------------------------------------------------------------

def _run_cli(*argv, cwd=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, CLI, *argv],
                          capture_output=True, text=True, env=env,
                          cwd=cwd or REPO_ROOT)


def test_cli_lint_clean_json_schema_and_exit_zero():
    proc = _run_cli("lint", "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert out["schema"] == F.SCHEMA_VERSION
    assert out["command"] == "lint" and out["ok"] and out["clean"]
    assert out["metrics"]["lint"]["files"] > 50


def test_cli_exit_codes_and_suppression_on_seeded_tree(tmp_path):
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(textwrap.dedent("""\
        import time
        import jax

        @jax.jit
        def step(x):
            return x + time.time()
    """))
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "env_vars.md").write_text("# none\n")

    proc = _run_cli("lint", "--root", str(tmp_path), "--json")
    assert proc.returncode == 1
    out = json.loads(proc.stdout)
    assert out["counts"] == {"source.nondet": 1}
    (bad,) = [f for f in out["findings"] if not f["suppressed"]]
    assert bad["path"].endswith("bad.py") and bad["line"] == 6

    proc = _run_cli("lint", "--root", str(tmp_path),
                    "--suppress", "source.nondet:*bad.py")
    assert proc.returncode == 0, proc.stdout

    proc = _run_cli("lint", "--root", str(tmp_path),
                    "--suppress", "source.nondet:*other.py")
    assert proc.returncode == 1          # location glob must not match


def test_cli_internal_error_is_exit_two(tmp_path):
    proc = _run_cli("gate", "--networks", "no-such-net")
    assert proc.returncode == 2
    assert "internal error" in proc.stderr


@pytest.mark.slow
def test_cli_gate_passes_on_shipped_tree():
    proc = _run_cli("gate", "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert out["ok"] and out["corpus"]["failures"] == []
