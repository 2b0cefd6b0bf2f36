"""Serving frontend: continuous batching correctness under concurrent
clients, warmup/dispatch accounting on the obs registry, and the
per-request sampling keys of the generation engine."""
import threading

import numpy as np
import pytest

import jax

from repro import obs
from repro.index import StreamingConfig, StreamingIndex
from repro.serve.frontend import FrontendConfig, SearchFrontend, next_pow2


@pytest.fixture(scope="module")
def served_index():
    rng = np.random.default_rng(3)
    idx = StreamingIndex(StreamingConfig(dim=8, delta_capacity=128))
    idx.add(rng.normal(size=(600, 8)).astype(np.float32))
    idx.flush()
    return idx


def test_concurrent_clients_match_direct_search(served_index):
    rng = np.random.default_rng(8)
    cfg = FrontendConfig(k=5, radius=2.5, max_batch=16)
    fe = SearchFrontend(served_index, cfg)
    vecs = rng.normal(size=(80, 8)).astype(np.float32)
    results = [None] * len(vecs)
    with fe:
        def client(lo, hi):
            futs = [(i, fe.submit(vecs[i])) for i in range(lo, hi)]
            for i, f in futs:
                results[i] = f.result(60)

        threads = [
            threading.Thread(target=client, args=(j * 20, (j + 1) * 20))
            for j in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    direct = served_index.constrained_knn(vecs, 5, 2.5)
    for i, reply in enumerate(results):
        np.testing.assert_array_equal(reply.gids, direct.gids[i])
        np.testing.assert_array_equal(reply.distances, direct.distances[i])


def test_dispatches_bounded_by_batch_classes(served_index):
    """The acceptance check: batching may split traffic any way load
    dictates, but every dispatch lands in one of the O(log max_batch)
    pow2 classes — verified via the obs registry counters."""
    cfg = FrontendConfig(k=4, max_batch=16)
    fe = SearchFrontend(served_index, cfg)
    base = {
        b: fe._c_dispatch[b].value for b in cfg.batch_classes
    }
    warm0 = fe._c_warmup.value
    rng = np.random.default_rng(9)
    with fe:
        futs = [
            fe.submit(rng.normal(size=8).astype(np.float32))
            for _ in range(50)
        ]
        for f in futs:
            f.result(60)
    # every request is answered by some class dispatch…
    per_class = {
        b: fe._c_dispatch[b].value - base[b] for b in cfg.batch_classes
    }
    assert sum(per_class.values()) > 0
    # …and the registry shows no dispatch outside the class set
    assert set(per_class) == set(cfg.batch_classes)
    assert all(b == next_pow2(b) for b in per_class)
    # warmup compiled each class exactly once, counted separately
    assert fe._c_warmup.value - warm0 == len(cfg.batch_classes)
    # the registry carries the labeled series (what BENCH_serve reads)
    for b in cfg.batch_classes:
        assert (
            obs.REGISTRY.find("serve.frontend.dispatches", qclass=str(b))
            is fe._c_dispatch[b]
        )


def test_stop_drains_pending_requests(served_index):
    fe = SearchFrontend(
        served_index, FrontendConfig(k=3, max_batch=8, warmup=False)
    )
    fe.start()
    rng = np.random.default_rng(10)
    futs = [
        fe.submit(rng.normal(size=8).astype(np.float32)) for _ in range(20)
    ]
    fe.stop()  # graceful: everything already submitted is answered
    for f in futs:
        reply = f.result(1)
        assert reply.gids.shape == (3,)
    with pytest.raises(RuntimeError):
        fe.submit(np.zeros(8, np.float32))


class _Recorder:
    """Forwards to a registry histogram and keeps each value, in order."""

    def __init__(self, hist):
        self.hist, self.values = hist, []

    def observe(self, v):
        self.values.append(v)
        self.hist.observe(v)


def test_queue_wait_one_value_per_request(served_index):
    """N requests give N `serve.frontend.queue_wait_ms` values, each at
    least 0 and at most that request's submit->resolve latency."""
    fe = SearchFrontend(served_index, FrontendConfig(k=3, max_batch=8))
    h = obs.REGISTRY.histogram("serve.frontend.queue_wait_ms", unit="ms")
    count0 = h.count
    wait, lat = _Recorder(fe._h_queue_wait), _Recorder(fe._h_latency)
    fe._h_queue_wait, fe._h_latency = wait, lat
    rng = np.random.default_rng(11)
    n = 37
    with fe:
        futs = [
            fe.submit(rng.normal(size=8).astype(np.float32)) for _ in range(n)
        ]
        for f in futs:
            f.result(60)
    assert h.count - count0 == n
    assert len(wait.values) == len(lat.values) == n
    for w, total in zip(wait.values, lat.values):
        assert 0.0 <= w <= total


def test_dispatch_span_per_dispatch(served_index, monkeypatch):
    """The dispatcher's `serve.frontend.dispatch` span opens once per
    batch it dispatches: its count equals the sum of
    `serve.frontend.dispatches{qclass=*}`; warm-up dispatches, made
    before the dispatcher starts, open none. While serving, profiler
    annotations open on the dispatcher thread alone."""
    cfg = FrontendConfig(k=4, max_batch=16)
    span = obs.REGISTRY.histogram("span.serve.frontend.dispatch", unit="s")

    def dispatched():
        return sum(
            obs.REGISTRY.counter(
                "serve.frontend.dispatches", qclass=str(b)
            ).value
            for b in cfg.batch_classes
        )

    span0, disp0 = span.count, dispatched()
    fe = SearchFrontend(served_index, cfg)
    rng = np.random.default_rng(12)
    annotating = set()
    annotation = obs.trace._annotation

    def spy(name):
        annotating.add(threading.current_thread().name)
        return annotation(name)

    with fe:
        monkeypatch.setattr(obs.trace, "_annotation", spy)
        futs = [
            fe.submit(rng.normal(size=8).astype(np.float32))
            for _ in range(45)
        ]
        for f in futs:
            f.result(60)
    assert dispatched() - disp0 > 0
    assert span.count - span0 == dispatched() - disp0
    assert annotating == {"repro-serve-dispatch"}
    for name in ("serve.frontend.wait", "serve.frontend.batch"):
        assert obs.REGISTRY.find(f"span.{name}").count >= 1, name

# -- per-request sampling keys (serve/engine.py) ------------------------------
@pytest.fixture(scope="module")
def tiny_engine():
    from repro import configs
    from repro.models import model as M
    from repro.models.layers import split_params
    from repro.serve.engine import Engine

    cfg = configs.get("qwen2-0.5b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    values, _ = split_params(params)
    return Engine(cfg, values, cache_len=24), cfg


def test_generate_samples_fresh_key_per_request(tiny_engine):
    """Regression: generate() used to fall back to PRNGKey(0) on every
    call, making temperature sampling identical across requests."""
    eng, cfg = tiny_engine
    prompt = jax.numpy.ones((1, 8), jax.numpy.int32)
    tok_a, _ = eng.generate(prompt, 8, temperature=5.0)
    tok_b, _ = eng.generate(prompt, 8, temperature=5.0)
    # deterministic given the engine seed: fold_in(base, 1) vs (base, 2)
    assert not np.array_equal(tok_a, tok_b)


def test_generate_explicit_key_reproducible(tiny_engine):
    eng, cfg = tiny_engine
    prompt = jax.numpy.ones((1, 8), jax.numpy.int32)
    key = jax.random.PRNGKey(3)
    tok_a, _ = eng.generate(prompt, 8, temperature=5.0, key=key)
    tok_b, _ = eng.generate(prompt, 8, temperature=5.0, key=key)
    np.testing.assert_array_equal(tok_a, tok_b)
    # greedy decode ignores keys entirely
    g_a, _ = eng.generate(prompt, 4, temperature=0.0)
    g_b, _ = eng.generate(prompt, 4, temperature=0.0)
    np.testing.assert_array_equal(g_a, g_b)
