"""The engine step as one span tree, on the profiler's clock
(docs/observability.md "The serve step's span tree").

* the tree: ``serve.step`` > ``serve.prefill`` / ``serve.decode`` >
  ``serve.build`` / ``serve.dispatch`` / ``serve.fetch``, for chunked,
  whole-prompt and speculative engines; a prompt (or a chunk of one) and
  a speculative step are read in their own span, whose ``dur`` covers
  the blocking fetch; a pipelined decode step's tokens are read by one
  ``serve.fetch`` beside it, under ``serve.step`` (or ``serve.drain``);
* ``serve.prefill_ms`` (and the chunk EWMA the scheduler's SLO rule
  reads) is taken after the fetch;
* a recording span is a ``jax.profiler.TraceAnnotation`` for its
  lifetime: under ``jax.profiler.start_trace`` the spans are regions of
  the xplane's host plane, nested as the ring nests them;
* off is off: a disabled span constructs nothing, and recording changes
  no token stream, trace count or AOT statistic;
* the programs are named ``jit_fn_<kind>`` and their parts carry
  ``jax.named_scope`` names in the compiled module's ``op_name``s;
* ``prefetch.wait`` is recorded on the consumer's thread.
"""
import glob
import re

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models.transformer import transformer_lm
from mxnet_tpu.serve import Engine, EngineConfig
from mxnet_tpu.telemetry import tracing

V, NL, D, H = 61, 2, 32, 4


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def _make_params(seed=0):
    rng = np.random.RandomState(seed)
    sym = transformer_lm(vocab_size=V, num_layers=NL, d_model=D, heads=H,
                         batch_size=1, seq_len=8)
    shapes, _, _ = sym.infer_shape(data=(1, 8), softmax_label=(1, 8))
    return {n: (rng.randn(*s) * 0.05).astype(np.float32)
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}


_PARAMS = _make_params()
_DRAFT = _make_params(seed=7)
_KINDS = {
    "chunked": dict(prefill_chunk=4),
    "whole": dict(),
    "speculative": dict(prefill_chunk=4, speculate=True),
}
_PROMPTS = [list(range(1, 11)), [20, 21, 22], [30, 31, 32, 33, 34, 35]]
_KW = [dict(max_new_tokens=6, seed=11),
       dict(max_new_tokens=5, temperature=0.9, top_k=7, seed=12),
       dict(max_new_tokens=4, seed=13)]


def _engine(**over):
    cfg = dict(heads=H, block_size=4, num_blocks=64, max_batch=4,
               max_prompt_len=16, max_seq_len=48, prompt_bucket_min=8)
    cfg.update(over)
    kw = {}
    if cfg.get("spec_draft") == "model":
        kw = dict(draft_params=_DRAFT, draft_heads=H)
    return Engine(_PARAMS, EngineConfig(**cfg), **kw)


def _serve(eng):
    ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    eng.run()
    return [list(eng.request(i).tokens) for i in ids]


def _recorded(kind):
    """Spans of a warmed engine serving the three requests, by id."""
    eng = _engine(**_KINDS[kind])
    eng.warmup()
    tracing.configure(None, enable=True)
    _serve(eng)
    tracing.configure(None, enable=False)
    return eng, {ev["args"]["id"]: ev for ev in tracing.tail(10**6)}


# -- (a) the tree ------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(_KINDS))
def test_engine_step_is_one_span_tree(kind, tmp_path):
    _, spans = _recorded(kind)
    names = [ev["name"] for ev in spans.values()]
    for want in ("serve.step", "serve.admit", "serve.prefill", "serve.decode",
                 "serve.build", "serve.dispatch", "serve.fetch", "serve.emit"):
        assert want in names, (want, sorted(set(names)))

    def parent(ev):
        return spans.get(ev["args"].get("parent"))

    # a program read where it is dispatched holds its fetch (and a
    # decode step its emit); a pipelined engine reads beside its spans
    in_place = {"chunked": ("serve.prefill",), "whole": ("serve.prefill",),
                "speculative": ("serve.prefill", "serve.decode")}[kind]
    for ev in spans.values():
        up = parent(ev)
        if ev["name"] in ("serve.build", "serve.dispatch"):
            assert up["name"] in ("serve.prefill", "serve.decode"), (ev, up)
            assert parent(up)["name"] == "serve.step", (ev, up)
        elif ev["name"] in ("serve.fetch", "serve.emit"):
            assert up["name"] in ("serve.step", "serve.drain") + in_place, (
                ev, up)
        elif ev["name"] in ("serve.admit", "serve.prefill", "serve.decode",
                            "serve.drain"):
            assert up["name"] == "serve.step", ev
    # every decode step's tokens are read by exactly one fetch, after the
    # step was dispatched
    decodes = {ev["args"]["step"]: ev for ev in spans.values()
               if ev["name"] == "serve.decode"}
    reads = [ev for ev in spans.values()
             if ev["name"] == "serve.fetch" and "step" in ev["args"]]
    assert decodes and sorted(f["args"]["step"] for f in reads) == sorted(
        decodes)
    for f in reads:
        assert f["ts"] >= decodes[f["args"]["step"]]["ts"]
    # a prompt (or a chunk of one) is read inside its own span, so its
    # time is the program's and not the dispatch's
    fetch_of = {}
    for ev in spans.values():
        if ev["name"] == "serve.fetch" and parent(ev)["name"] == "serve.prefill":
            assert ev["args"]["parent"] not in fetch_of, "one fetch a program"
            fetch_of[ev["args"]["parent"]] = ev
    prefills = [ev for ev in spans.values() if ev["name"] == "serve.prefill"]
    assert prefills and len(fetch_of) == len(prefills)
    for ev in prefills:
        f = fetch_of[ev["args"]["id"]]
        assert ev["dur"] >= f["dur"]
        assert ev["ts"] <= f["ts"] and f["ts"] + f["dur"] <= ev["ts"] + ev["dur"]
    steps = [ev for ev in spans.values() if ev["name"] == "serve.step"]
    assert all({"step", "queued", "rows", "chunk"} <= set(ev["args"])
               for ev in steps)
    assert any(ev["args"]["chunk"] == 1 for ev in steps)
    assert 2 <= max(ev["args"]["rows"] for ev in steps) <= len(_PROMPTS)
    dispatched = {ev["args"]["kind"] for ev in spans.values()
                  if ev["name"] == "serve.dispatch"}
    assert dispatched == {"chunked": {"prefill_chunk", "decode"},
                          "whole": {"prefill", "decode"},
                          "speculative": {"prefill_chunk", "verify"}}[kind]
    info = tracing.validate(tracing.export(str(tmp_path / "trace.json")))
    assert info["events"] == len(spans)


@pytest.mark.parametrize("kind", ["chunked", "whole"])
def test_decode_spans_count_live_and_table_blocks(kind):
    """``serve.decode`` says how much of its tables the step's attention
    reads: each active row's ``length + 1`` positions, against ``bucket
    x max_blocks`` columns (``tools/trace_report.py`` prints the
    window's ratio)."""
    eng, spans = _recorded(kind)
    decodes = [ev["args"] for ev in spans.values()
               if ev["name"] == "serve.decode"]
    assert decodes
    for args in decodes:
        assert args["table_blocks"] == args["bucket"] * eng.max_blocks
        assert args["active"] <= args["live_blocks"] <= args["table_blocks"]
    # the longest request alone: 10 prompt + 5 fed tokens, blocks of 4
    assert max(a["live_blocks"] for a in decodes if a["active"] == 1) >= 3


@pytest.mark.parametrize("kind", sorted(_KINDS))
def test_decode_spans_name_the_samplers_branch(kind):
    """``serve.decode`` says which branch of the sampler its program
    took, by the program's predicate on the host's ``temps``: the one
    sampled request (5 tokens, the first its prefill's) opens the
    sampled branch in the steps that decode it; a step of greedy rows
    alone takes the ``argmax``."""
    _, spans = _recorded(kind)
    branches = [ev["args"]["sampler"] for ev in spans.values()
                if ev["name"] == "serve.decode"]
    assert set(branches) == {"greedy", "select"}
    if kind == "speculative":       # a verify step emits 1..K+1 tokens
        assert 1 <= branches.count("select") <= 4
    else:
        assert branches.count("select") == 4


# -- (b) the clock stops after the fetch -------------------------------------

@pytest.mark.parametrize("kind", ["chunked", "whole"])
def test_prefill_ms_is_the_prefill_spans_time(kind):
    eng, spans = _recorded(kind)
    span_ms = sum(ev["dur"] for ev in spans.values()
                  if ev["name"] == "serve.prefill") / 1e3
    flat = telemetry.snapshot_flat()
    hist_ms = flat["serve.prefill_ms.sum"]
    assert flat["serve.prefill_ms.count"] == sum(
        1 for ev in spans.values() if ev["name"] == "serve.prefill")
    assert span_ms > 0 and abs(hist_ms - span_ms) <= 0.2 * span_ms, (
        hist_ms, span_ms)
    if kind == "chunked":
        # the scheduler's backlog estimate is fed the same clock
        fetch_ms = min(ev["dur"] for ev in spans.values()
                       if ev["name"] == "serve.fetch") / 1e3
        assert eng._chunk_ms >= fetch_ms > 0


# -- (c) the profiler's clock ------------------------------------------------

def _host_regions(logdir, prefixes):
    """{line: [(name, start, end)]} of the written xplane's host plane."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(str(logdir / "plugins/profile/*/*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events if ev.name.startswith(prefixes)]
            if evs:
                out[(plane.name, i, line.name)] = evs
    return out


def _profiled(logdir, body):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    tracing.configure(None, enable=True)
    jax.profiler.start_trace(str(logdir), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
        tracing.configure(None, enable=False)


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_serve_spans_are_regions_of_the_profilers_host_plane(tmp_path):
    eng = _engine(prefill_chunk=4)
    eng.warmup()
    _profiled(tmp_path, lambda: _serve(eng))
    lines = _host_regions(tmp_path, "serve.")
    assert len(lines) == 1, list(lines)         # the engine's one thread
    evs = next(iter(lines.values()))
    by = {}
    for ev in evs:
        by.setdefault(ev[0], []).append(ev)
    ring = {}
    for ev in tracing.tail(10**6):
        ring[ev["name"]] = ring.get(ev["name"], 0) + 1
    for name in ("serve.step", "serve.prefill", "serve.decode", "serve.fetch",
                 "serve.build", "serve.dispatch", "serve.emit"):
        assert len(by[name]) == ring[name], name   # one region a span
    mids = by["serve.prefill"] + by["serve.decode"]
    assert all(_inside(ev, by["serve.step"]) for ev in mids)
    for name in ("serve.build", "serve.dispatch"):
        assert all(_inside(ev, mids) for ev in by[name]), name
    for name in ("serve.fetch", "serve.emit"):
        assert all(_inside(ev, by["serve.step"]) for ev in by[name]), name


def _prefetch_some(n=4):
    """Four batches and the end through a DevicePrefetchIter."""
    data = np.arange(n * 2 * 3, dtype=np.float32).reshape(n * 2, 3)
    it = mx.io.DevicePrefetchIter(
        mx.io.NDArrayIter(data, np.zeros(n * 2, np.float32), batch_size=2))
    try:
        for _ in range(n):
            it.next()
        with pytest.raises(StopIteration):
            it.next()
    finally:
        it.close()


def test_prefetch_spans_are_regions_on_two_host_threads(tmp_path):
    _profiled(tmp_path, _prefetch_some)
    lines = _host_regions(tmp_path, "prefetch.")
    where = {}
    for key, evs in lines.items():
        for ev in evs:
            where.setdefault(ev[0], set()).add(key)
    assert where["prefetch.wait"] and where["prefetch.batch"]
    assert where["prefetch.wait"].isdisjoint(where["prefetch.batch"])


# -- (f) the consumer's side of the queue ------------------------------------

def test_prefetch_wait_is_recorded_on_the_consumers_thread():
    import threading
    tracing.configure(None, enable=True)
    _prefetch_some()
    tracing.configure(None, enable=False)
    tids = {}
    for ev in tracing.tail(10**6):
        tids.setdefault(ev["name"], set()).add(ev["tid"])
    assert tids["prefetch.wait"] == {threading.get_ident()}
    assert tids["prefetch.batch"].isdisjoint(tids["prefetch.wait"])
    waits = [ev for ev in tracing.tail(10**6) if ev["name"] == "prefetch.wait"]
    assert len(waits) == 5                     # four batches and the end


# -- (d) off is off ----------------------------------------------------------

def test_a_disabled_span_constructs_nothing(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("TraceAnnotation constructed with recording off")

    eng = _engine(prefill_chunk=4)
    eng.warmup()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    assert not tracing.enabled()
    assert telemetry.span("serve.step", step=1) is tracing._NULL
    _serve(eng)
    _prefetch_some()
    assert tracing.tail() == []
    # and enabled, the same patch is reached: the span is the annotation
    tracing.configure(None, enable=True)
    with pytest.raises(AssertionError, match="TraceAnnotation"):
        with telemetry.span("serve.step"):
            pass


@pytest.mark.parametrize("kind", sorted(_KINDS))
def test_recording_changes_no_stream_trace_count_or_aot_stat(kind):
    runs = []
    for on in (False, True):
        eng = _engine(**_KINDS[kind])
        eng.warmup()
        warm = dict(eng.trace_counts), dict(eng.aot_stats)
        tracing.configure(None, enable=on)
        out = _serve(eng)
        tracing.configure(None, enable=False)
        # serving after warm-up traces nothing and resolves no program,
        # recording or not
        assert (dict(eng.trace_counts), dict(eng.aot_stats)) == warm
        assert eng.aot_stats["fallbacks"] == 0
        runs.append(out)
        assert bool(tracing.tail()) == on
    assert runs[0] == runs[1]
    assert [len(o) for o in runs[0]] == [k["max_new_tokens"] for k in _KW]


# -- (e) names on the device timeline ----------------------------------------

_PROGRAMS = {
    "prefill": (dict(), {"embed", "qkv", "attn", "proj", "ffn", "lm_head",
                         "kv_write", "sample"}),
    "prefill_chunk": (dict(prefill_chunk=4),
                      {"embed", "qkv", "kv_write", "pool_read", "attn", "proj",
                       "ffn", "lm_head", "sample"}),
    "decode": (dict(), {"embed", "qkv", "kv_write", "pool_read", "attn",
                        "proj", "ffn", "lm_head", "sample"}),
    "verify": (dict(speculate=True),
               {"embed", "qkv", "kv_write", "pool_read", "attn", "proj", "ffn",
                "lm_head", "sample"}),
    "draft": (dict(speculate=True, spec_draft="model"),
              {"qkv", "proj", "ffn", "lm_head"}),
}


@pytest.mark.parametrize("kind", sorted(_PROGRAMS))
def test_programs_and_their_parts_are_named(kind):
    over, scopes = _PROGRAMS[kind]
    eng = _engine(**over)
    eng.warmup()
    texts = [p.compiled.as_text() for (k, _), p in eng._programs.items()
             if k == kind]
    assert texts, sorted(eng._programs)
    for text in texts:
        assert re.match(rf"HloModule jit_fn_{kind}[,\s]", text), text[:80]
        ops = [o for o in set(re.findall(r'op_name="([^"]*)"', text))
               if o.startswith(f"jit(fn_{kind})/")]
        found = {part for o in ops for part in o.split("/")}
        assert scopes <= found, (kind, scopes - found)
