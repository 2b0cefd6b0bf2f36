"""Unified observability layer.

One process-wide substrate for the accounting every other subsystem
needs: `metrics` (thread-safe labeled counters / gauges / mergeable
log2-bucket histograms), `trace` (host spans that double as XLA profile
annotations, plus the per-engine-call `QueryTrace` carrying the paper's
nodes-visited / distance-evaluation metrics), and `export` (the
`BENCH_obs.json` section + human tables).

Instrumented layers: `query/engine.py` (dispatch/signature/stack-cache
accounting, stage spans, per-query paper metrics), `index/streaming.py`
and `index/delta.py` (write-path counters, occupancy/garbage gauges),
`kernels/ops.py` (per-call block/bytes/FLOP accounting for the roofline
report), `serve/retrieval.py` (end-to-end latency histograms), and
`train/loop.py` (structured twins of the log lines). `compiles` turns
JAX's program-build events into `jit.{trace,lower,compile}_s{fun=…}`
histograms; importing this package installs it, once per process.
"""
from . import compiles, export, metrics, trace
from .metrics import REGISTRY, Registry, reset, snapshot
from .trace import QueryTrace, span

compiles.install()

__all__ = [
    "REGISTRY",
    "Registry",
    "QueryTrace",
    "compiles",
    "export",
    "metrics",
    "reset",
    "snapshot",
    "span",
    "trace",
]
