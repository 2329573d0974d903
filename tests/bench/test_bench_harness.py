"""The harness end to end on the CPU at a tiny size: cells, mixes,
metrics and model families found by name, the result line's shape, the
refusals off a TPU and without the program, and ``correct`` turning false
when the served path is broken underneath or the bf16 control stands in
for it."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import control, families, harness

from benchtree import ROOT, tiny_tree

RUN = [sys.executable, "bench/run.py", "--workload", "trecqa-interactive",
       "--seed", "3", "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_exits_nonzero_off_a_tpu():
    p = subprocess.run(RUN, cwd=ROOT, env=_env(), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert "TPU" in p.stderr and not p.stdout.strip()


def test_run_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    p = subprocess.run(RUN, cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and not p.stdout.strip()


NEW_METRIC = '''
def read(run):
    return float(len(run.requests)) if run.requests else None
'''


def test_new_cell_mix_and_metric_found_by_name(tmp_path):
    root = tiny_tree(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    conf = json.loads((root / "bench/configs/sm-cnn-trecqa.json").read_text())
    conf["queries"]["words"] = [4, 6]
    (root / "bench/configs/sm-cnn-short.json").write_text(json.dumps(conf))
    (root / "bench/traffic/slow-open.json").write_text(json.dumps(
        {"loop": "open", "rate_qps": 8.0, "schedule_seed": 4, "connections": 2,
         "warm_requests": 2}))
    (root / "bench/metrics/requests_seen.py").write_text(NEW_METRIC)
    spec["configs"].append({"name": "sm-cnn-short", "source": "test",
                            "file": "bench/configs/sm-cnn-short.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "short-open", "config": "sm-cnn-short",
                              "traffic": "slow-open", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "p50_ms":
            m["workloads"].append("short-open")
    spec["per_layer"].append({"name": "requests_seen", "unit": "requests",
                              "better": "higher", "source": "program_span",
                              "layer": "Load", "moves": "p50_ms",
                              "workloads": ["short-open"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell(root, "short-open")
    assert [m["name"] for m in cell.per_layer] == ["requests_seen"]
    out = harness.run(cell, 2**31 + 17, 1.5, trace=False,
                      require_chip=False)
    assert out["correct"], out["compared"]
    assert sorted(out["metrics"]) == ["p50_ms", "setup_s"]
    assert out["attempted"] == 12 and out["failed"] == 0
    # the result line: required keys, the compared numbers last
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "compared"
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["compared"]["score_gap"]["value"] < 1e-4
    traced = harness.run(cell, 5, 1.5, trace=True, require_chip=False)
    assert traced["metrics"]["requests_seen"]["value"] == 12.0
    assert {"busy_s", "window_s"} <= set(traced["device"])
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    json.dumps(traced)


COPY_FAMILY = '''"""sm-cnn again, under another family name."""
from bench.families.sm_cnn import *  # noqa: F401,F403
'''


def test_new_family_is_a_new_file_and_an_entry(tmp_path):
    root = tiny_tree(tmp_path)
    (root / "bench/families/sm_cnn_copy.py").write_text(COPY_FAMILY)
    conf = json.loads((root / "bench/configs/sm-cnn-trecqa.json").read_text())
    conf["family"] = "sm-cnn-copy"
    (root / "bench/configs/sm-cnn-copy.json").write_text(json.dumps(conf))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "sm-cnn-copy", "source": "test",
                            "file": "bench/configs/sm-cnn-copy.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "copy-bulk", "config": "sm-cnn-copy",
                              "traffic": "bulk-4x4", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "qps":
            m["workloads"].append("copy-bulk")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell(root, "copy-bulk")
    family = families.load(root, cell.config)
    assert family.__file__ == str(root / "bench/families/sm_cnn_copy.py")
    out = harness.run(cell, 2**31 + 29, 1.0, trace=False,
                      require_chip=False)
    assert out["correct"], out["compared"]
    assert sorted(out["metrics"]) == ["qps", "setup_s"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_unknown_family_names_the_missing_module(tiny):
    path = tiny / "bench/configs/sm-cnn-trecqa.json"
    conf = json.loads(path.read_text())
    conf["family"] = "no-such-family"
    path.write_text(json.dumps(conf))
    cell = harness.load_cell(tiny, "trecqa-interactive")
    with pytest.raises(FileNotFoundError,
                       match="bench/families/no_such_family.py"):
        harness.run(cell, 3, 1.0, trace=False, require_chip=False)


def _scores_altered(monkeypatch):
    from repro.core import backends
    call = backends.Scorer.__call__

    def altered(self, q, a, f):
        return call(self, q, a, f) * 0.98
    monkeypatch.setattr(backends.Scorer, "__call__", altered)


def _retrieval_altered(monkeypatch):
    from repro.core import bm25
    many = bm25.retrieve_many

    def altered(index, queries_terms, h, budget=16384):
        return [(s, (ids + 1) % index.n_docs)
                for s, ids in many(index, queries_terms, h, budget)]
    monkeypatch.setattr(bm25, "retrieve_many", altered)


@pytest.mark.parametrize("workload", ["msmarco-bulk", "trecqa-bulk"])
@pytest.mark.parametrize("fault,number", [
    (_scores_altered, "score_gap"),
    (_retrieval_altered, "misses"),
])
def test_broken_served_path_is_not_correct(tiny, monkeypatch, fault, number,
                                           workload):
    cell = harness.load_cell(tiny, workload)
    fault(monkeypatch)
    out = harness.run(cell, 11, 1.0, trace=False, require_chip=False)
    row = out["compared"][number]
    assert row["value"] > row["limit"]
    assert out["correct"] is False


def _shed_every_third_in_the_window(monkeypatch):
    from repro.serving import admission
    admit = admission.AdmissionController.try_admit
    drive = harness.drive
    calls = [0]
    window = [False]

    def shedding(self, n_rows, *args, **kwargs):
        calls[0] += window[0]
        if window[0] and calls[0] % 3 == 0:
            return admission.SHED_QUEUE_FULL
        return admit(self, n_rows, *args, **kwargs)

    def driving(*args, **kwargs):
        window[0] = True
        return drive(*args, **kwargs)
    monkeypatch.setattr(admission.AdmissionController, "try_admit",
                        shedding)
    monkeypatch.setattr(harness, "drive", driving)


@pytest.mark.parametrize("workload", ["trecqa-interactive", "msmarco-bulk",
                                      "trecqa-bulk"])
def test_shed_requests_are_not_correct(tiny, monkeypatch, workload):
    cell = harness.load_cell(tiny, workload)
    _shed_every_third_in_the_window(monkeypatch)
    out = harness.run(cell, 2**31 + 3, 1.5, trace=False, require_chip=False)
    assert 0 < out["failed"] < out["attempted"]
    assert out["compared"]["failed"]["value"] == out["failed"]
    assert out["compared"]["failed"]["limit"] == 0
    assert out["correct"] is False


@pytest.mark.parametrize("workload", ["trecqa-interactive", "msmarco-bulk",
                                      "trecqa-bulk"])
def test_bf16_control_is_not_correct(tiny, workload):
    cell = harness.load_cell(tiny, workload)
    for seed in (21, 2**31 + 22):
        rows = control.control_numbers(cell, seed, 8)
        assert not harness.reference.passes(rows), rows
