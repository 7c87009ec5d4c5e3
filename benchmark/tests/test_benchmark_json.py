"""``BENCHMARK.json`` against the contract's limits on names and units,
every ``moves`` pointing at a metric its cells report, every file found
by name — and the proof that the harness is driven by data: a throw-away
configuration, traffic mix, cell and per-layer metric are added to a
temporary copy as NEW files plus NEW entries, with no edit to a file
that is there, and the copy runs them."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def all_metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_whys_within_the_allowed_characters():
    names = [m["name"] for m in all_metrics()]
    names += [c["name"] for c in BENCH["configs"]]
    for w in BENCH["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    for n in names:
        assert NAME.match(n), n
    for m in all_metrics():
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    assert len(set(n["name"] for n in all_metrics())) == len(all_metrics())


def test_no_more_than_a_quarter_of_the_cells_on_four_chips():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    for w in BENCH["workloads"]:
        e2e = [m["name"] for m in spec.metrics_for(BENCH, w["name"],
                                                   "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert spec.metrics_for(BENCH, w["name"], "per_layer"), w["name"]


def test_every_moves_points_at_a_metric_all_its_cells_report():
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in cells
            e2e = [x["name"] for x in spec.metrics_for(BENCH, cell,
                                                       "end_to_end")]
            assert m["moves"] in e2e, (m["name"], cell)


def test_every_file_is_found_by_name_and_declares_what_the_json_says():
    for c in BENCH["configs"]:
        cfg = spec.load_config(BENCH, c["name"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        spec.load_module("reference", cfg["family"])
    for w in BENCH["workloads"]:
        mix = spec.load_traffic(w["traffic"])
        assert hasattr(spec.load_module("runners", mix["kind"]), "run")
    for m in BENCH["per_layer"]:
        assert callable(spec.load_reader(m["name"]).read)
    # one reader serves every split of a name; an unknown name has none
    assert (spec.load_reader("decode_step_ms.chat")
            is spec.load_reader("decode_step_ms.batch"))
    with pytest.raises(spec.SpecError):
        spec.load_reader("no_such_metric.chat")
    for m in all_metrics():
        for cell in m.get("workloads", []):
            spec.find_cell(BENCH, cell)


def test_the_lm_configuration_is_not_presented_as_opt():
    cfg = spec.load_config(BENCH, "nope-lm-2048x24")
    assert cfg["reduced"] == [] and cfg["stand_in_for"]
    assert "position_encoding" in cfg["assumed"] and "lm_head" in cfg["assumed"]
    # every width equals the source's (facebook/opt-1.3b config.json)
    assert (cfg["hidden_size"], cfg["ffn_dim"], cfg["num_attention_heads"],
            cfg["num_hidden_layers"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (2048, 8192, 32, 24, 50272, 2048)
    for entry in BENCH["workloads"] + BENCH["configs"] + all_metrics():
        assert not re.match(r"(?i)^opt", entry["name"])
    for root, _dirs, files in os.walk(spec.BENCH_DIR):
        for f in files:
            assert not re.match(r"(?i)^opt[-_0-9]", f), os.path.join(root, f)


def test_references_import_nothing_from_the_program():
    ref_dir = os.path.join(spec.BENCH_DIR, "reference")
    for f in os.listdir(ref_dir):
        if f.endswith(".py"):
            src = open(os.path.join(ref_dir, f)).read()
            assert not re.search(r"^\s*(from|import)\s+mxnet_tpu", src, re.M), f


def test_run_py_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip().endswith("}")


THROWAWAY_METRIC = '''
def read(facts):
    return float(len(facts.get("steps", ())))
'''


def test_a_config_mix_cell_and_metric_are_added_as_new_files_and_entries(
        tmp_path):
    copy = tmp_path / "repo"
    copy.mkdir()
    shutil.copytree(spec.BENCH_DIR, copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(spec.REPO, "mxnet_tpu"), copy / "mxnet_tpu")
    before = {}
    for root, _d, files in os.walk(copy / "benchmark"):
        for f in files:
            p = os.path.join(root, f)
            before[p] = open(p, "rb").read()

    # NEW files: a configuration (another depth of the same family), a
    # mix (other lengths, another client count), a per-layer reader
    cfg = spec.load_config(BENCH, "nope-lm-2048x24")
    cfg.update(name="throwaway-lm", num_hidden_layers=3)
    (copy / "benchmark/configs/throwaway-lm.json").write_text(json.dumps(cfg))
    mix = spec.load_traffic("batch-closed")
    mix["rehearsal"].update(clients=3)
    (copy / "benchmark/traffic/throwaway-mix.json").write_text(json.dumps(mix))
    (copy / "benchmark/metrics/steps_in_window.py").write_text(THROWAWAY_METRIC)
    # NEW entries
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "throwaway-lm", "source": cfg["source"],
        "file": "benchmark/configs/throwaway-lm.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({
        "name": "throwaway-cell", "config": "throwaway-lm",
        "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tok_s":
            m["workloads"].append("throwaway-cell")
    bench["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "engine step",
        "moves": "serve_tok_s", "workloads": ["throwaway-cell"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    out = subprocess.run(
        [sys.executable, str(copy / "benchmark/run.py"), "--workload",
         "throwaway-cell", "--seed", "4", "--seconds", "1.5", "--trace", "1",
         "--rehearsal"], capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert "3 clients" in out.stdout            # the new mix was read
    assert "steps_in_window" in out.stdout      # the new reader answered
    for p, content in before.items():           # nothing there was edited
        assert open(p, "rb").read() == content, p
