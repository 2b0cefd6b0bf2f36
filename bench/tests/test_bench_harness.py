"""The harness as data: a cell, a mix, a metric and a generator added as
files are found by name; a run without a TPU gives no result; and a run
whose timed path is broken comes out as not correct."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness

ROOT = harness.ROOT
TOY_GENERATOR = '''
import numpy as np


def generate(n, seed):
    return np.random.default_rng(seed).uniform(0.0, 10.0, size=(n, 3))
'''
TOY_METRIC = '''
def read(ctx):
    return 42.0 if ctx.cell.name == "toy.cell" else None
'''


@pytest.fixture
def toy_root(tmp_path):
    """A copy of the benchmark with one more configuration, mix, metric
    and cell, added as new files and entries only."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "bench"
    cfg = json.load(open(b / "configs" / "road3d.json"))
    cfg.update(name="toy", generator="toy", n_points=600, check_sample=64)
    json.dump(cfg, open(b / "configs" / "toy.json", "w"))
    (b / "generators" / "toy.py").write_text(TOY_GENERATOR)
    json.dump({"loop": "open", "rate_qps": 16, "queries": "uniform_bbox",
               "k": 8, "r_frac": 0.15, "set_seed": 7},
              open(b / "traffic" / "toy-mix.json", "w"))
    (b / "metrics" / "toy.metric.py").write_text(TOY_METRIC)
    spec = json.load(open(tmp_path / "BENCHMARK.json"))
    spec["configs"].append({"name": "toy", "source": "https://example.org/toy",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "toy.cell", "config": "toy",
                              "traffic": "toy-mix", "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("toy.cell")
    spec["per_layer"].append({"name": "toy.metric", "unit": "1",
                              "better": "higher", "source": "program_counter",
                              "layer": "test", "moves": "read_p95_ms",
                              "workloads": ["toy.cell"]})
    json.dump(spec, open(tmp_path / "BENCHMARK.json", "w"))
    return str(tmp_path)


def test_new_files_are_found_by_name(toy_root):
    c = harness.cell("toy.cell", toy_root)
    assert c.config["n_points"] == 600 and c.traffic["rate_qps"] == 16
    assert [m["name"] for m in c.end_to_end] == ["read_p95_ms", "setup_s"]
    assert [m["name"] for m in c.per_layer] == ["toy.metric"]
    assert c.generator()(5, 0).shape == (5, 3)
    assert c.reader("toy.metric")(type("Ctx", (), {"cell": c})) == 42.0
    # the cells already there are unchanged by the addition
    assert harness.cell("road3d.open", toy_root).per_layer == \
        harness.cell("road3d.open").per_layer


def test_every_cell_reports_setup_another_e2e_and_a_per_layer_metric():
    spec = harness.load_benchmark()
    for w in spec["workloads"]:
        c = harness.cell(w["name"])
        names = [m["name"] for m in c.end_to_end]
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert c.per_layer, w["name"]
        for m in c.per_layer:
            assert os.path.isfile(harness.reader_path(m["name"])), m["name"]


def test_a_metric_reads_with_its_own_file_or_its_base_names(toy_root):
    metrics = os.path.join(toy_root, "bench", "metrics")
    assert harness.reader_path("toy.metric", toy_root) == \
        os.path.join(metrics, "toy.metric.py")
    assert harness.reader_path("engine.dispatch_ms.sat", toy_root) == \
        os.path.join(metrics, "engine.dispatch_ms.py")


def test_warm_pairs_keep_the_likely_batch_and_width_classes():
    # a quarter of the queries in each of four width classes
    wcls = np.repeat([1, 2, 4, 8], 256)
    pairs = harness.warm_pairs(wcls, (1, 2, 4, 8, 16, 32, 64), 1e-6)
    assert {(1, w) for w in (1, 2, 4, 8)} <= set(pairs)
    # 64 rows all of class 1: a chance of 4**-33 at the least
    assert (64, 1) not in pairs and (64, 8) in pairs
    # a pair is kept if the fewest rows its batch class holds make it
    # likely enough: 9 rows of class 1 at 4**-9, 17 rows at 4**-17
    assert (16, 1) in pairs and (32, 1) not in pairs
    assert (16, 1) not in harness.warm_pairs(wcls, (1, 2, 4, 8, 16), 1e-5)


def test_every_seed_does_the_same_work_in_another_order(toy_root):
    c = harness.cell("toy.cell", toy_root)
    pts = c.generator()(600, c.config["data_seed"])
    s = type("Served", (), {"cell": c, "points": pts})

    def work(seed):
        return harness.window_work(s, seed, 30.0)

    (q1, t1), (q1b, t1b), (q2, t2) = work(3000000021), work(3000000021), work(11)
    np.testing.assert_array_equal(q1, q1b)
    np.testing.assert_array_equal(t1, t1b)
    assert len(t1) == 16 * 30 and not np.array_equal(q1, q2)
    # the same queries and the same gaps between arrivals, reordered
    key = lambda q: q[np.lexsort(q.T)]
    np.testing.assert_array_equal(key(q1), key(q2))
    np.testing.assert_allclose(np.sort(np.diff(t1, prepend=0.0)),
                               np.sort(np.diff(t2, prepend=0.0)), atol=1e-9)
    assert np.all(np.diff(t1) >= 0) and 0 < t1[-1] <= 30.0
    assert t1[-1] == pytest.approx(t2[-1])


def test_no_tpu_means_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cwd in (ROOT, None):
        if cwd is None:   # a directory with the benchmark and nothing else
            shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
            shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                            ignore=shutil.ignore_patterns("__pycache__", "tests"))
            cwd = str(tmp_path)
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", "road3d.open",
             "--seed", "3000000007", "--seconds", "1", "--trace", "0"],
            cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode != 0
        assert p.stdout.strip() == "", p.stdout
        assert "no TPU" in p.stderr


# -- the timed path broken underneath -------------------------------------


def _altered(kind):
    from repro.index.search import StreamResult
    from repro.index.streaming import StreamingIndex

    produce = StreamingIndex.constrained_knn

    def broken(self, queries, k, r):
        res = produce(self, queries, k, r)
        g, d = np.array(res.gids), np.array(res.distances)
        if kind == "id":
            g = np.where(g >= 0, (g + 1) % 600, g)
        elif kind == "distance":
            d = d * np.float32(1.01)
        elif kind == "half_batch":
            # the later half, rounded up: a batch of one loses its row
            g[len(g) // 2:], d[len(g) // 2:] = -1, np.inf
        return StreamResult(g, d, res.partial)

    return broken


@pytest.mark.parametrize("fault", [None, "id", "distance", "half_batch"])
def test_broken_timed_path_is_not_correct(toy_root, monkeypatch, fault):
    from repro.index.streaming import StreamingIndex

    # the shape warm-up is for the window's timing only; on the CPU it
    # would compile every (batch, frontier) class in interpret mode
    monkeypatch.setattr(harness, "_warm", lambda s: None)
    if fault is not None:
        monkeypatch.setattr(StreamingIndex, "constrained_knn", _altered(fault))
    c = harness.cell("toy.cell", toy_root)
    out = harness.run_cell(c, 3000000019, 1.0, False, time.perf_counter())
    assert out.attempted == 16 and out.checks["compared"]["value"] == 16
    assert out.correct is (fault is None), out.checks
