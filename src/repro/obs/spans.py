"""Performance spans and compile counters: where the program's time goes.

The telemetry channels measure the SIMULATED cluster on its simulated
clock; this module measures the program itself on the real one.  It is
always on and has no switch:

  * device scopes — the round body wraps its phases in `jax.named_scope`
    with the five names below.  A scope is HLO metadata: every op the
    phase lowers to carries it in its `op_name`, so a profiler trace
    attributes each device op to the innermost `dfl.*` scope around it
    (`dfl.reduce` nests inside `dfl.aggregate`; the innermost wins);
  * host spans — `span(name)` is a `jax.profiler.TraceAnnotation` (a no-op
    unless a profiler is capturing, then a host event on the same clock
    as the device ops) that also adds its host seconds, its count and the
    compile counters that moved inside it to an in-process table,
    `span_table()`, for operators who run without a profiler.
    `Experiment` opens `dfl.run` around `run()` with `dfl.run.dispatch`,
    `dfl.run.fetch` and `dfl.run.account` inside the fused schedule, and
    `dfl.compile.lower` / `dfl.compile.load` inside `compile()`;
  * compile counters — listeners on JAX's own monitoring events, registered
    once per process on first use.  `counters()` is a snapshot; a caller
    takes `counter_diff` of two:

      lower_s            seconds spent tracing to a jaxpr or lowering to
                         an MLIR module (nested events, such as a jitted
                         callee traced inside its caller, count once)
      load_s             backend compile requests: the cache-key hash,
                         the persistent-cache read and load, or a real
                         compile on a miss
      compile_requests   how many such requests (a cache load counts)
      cache_hits         persistent-cache hits among them; real compiles
                         are compile_requests - cache_hits
      cache_retrieval_s  seconds spent reading the persistent cache

    `compile_times()` keeps the wall-clock (start, end) of the latest
    compile requests, to set beside a profile's own clock.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, Iterator, List, Tuple

import jax

TRAIN = "dfl.train"          # local training: batches, fwd, bwd, optimizer
EXCHANGE = "dfl.exchange"    # trigger, encode, wire, decode, delivery
REDUCE = "dfl.reduce"        # table view, segment_avg gather kernel
AGGREGATE = "dfl.aggregate"  # flatten, weights, the update, unflatten
EVAL = "dfl.eval"            # the eval pass and its params probes
SCOPES = (TRAIN, EXCHANGE, REDUCE, AGGREGATE, EVAL)

_LOWER_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
_LOAD_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_LOG = 1024  # compile requests whose wall-clock times are kept

_counters: Dict[str, float] = {"lower_s": 0.0, "load_s": 0.0,
                               "compile_requests": 0, "cache_hits": 0,
                               "cache_retrieval_s": 0.0}
_compiles: collections.deque = collections.deque(maxlen=COMPILE_LOG)
# lowering events open on this thread: JAX records a scalar when one
# starts and a time span when it ends, so a nested one is seen inside
_open = threading.local()
_table: Dict[str, Dict[str, float]] = {}
_lock = threading.Lock()  # JAX may compile on any thread
_listening = False


def _on_duration(event: str, secs: float, **_) -> None:
    with _lock:
        if event == _LOAD_EVENT:
            _counters["load_s"] += secs
            _counters["compile_requests"] += 1
        elif event == _RETRIEVAL_EVENT:
            _counters["cache_retrieval_s"] += secs


def _on_event(event: str, **_) -> None:
    if event == _HIT_EVENT:
        with _lock:
            _counters["cache_hits"] += 1


def _on_scalar(event: str, value: float, **_) -> None:
    if event in _LOWER_EVENTS:
        _open.depth = getattr(_open, "depth", 0) + 1


def _on_time_span(event: str, start: float, end: float, **_) -> None:
    if event in _LOWER_EVENTS:
        depth = getattr(_open, "depth", 0)
        _open.depth = max(depth - 1, 0)
        if depth <= 1:  # the outermost: the ones inside it are in it
            with _lock:
                _counters["lower_s"] += end - start
    elif event == _LOAD_EVENT:
        with _lock:
            _compiles.append((start, end))


def _listen() -> None:
    global _listening
    with _lock:
        if _listening:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        jax.monitoring.register_scalar_listener(_on_scalar)
        _listening = True


def counters() -> Dict[str, float]:
    """A snapshot of the process's compile counters."""
    _listen()
    with _lock:
        return dict(_counters)


def counter_diff(after: Dict[str, float],
                 before: Dict[str, float]) -> Dict[str, float]:
    """What moved between two `counters()` snapshots."""
    return {k: after[k] - before[k] for k in after}


def compile_times() -> List[Tuple[float, float]]:
    """Wall-clock (`time.time()`) start and end of the latest
    `COMPILE_LOG` compile requests, oldest first."""
    _listen()
    with _lock:
        return list(_compiles)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """A named host span: a profiler annotation, and one more occurrence
    of `name` in `span_table()` with its host seconds and the compile
    counters that moved inside it."""
    before = counters()
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        secs = time.perf_counter() - t0
        moved = counter_diff(counters(), before)
        with _lock:
            row = _table.setdefault(name, {"count": 0, "seconds": 0.0,
                                           **{k: 0 for k in moved}})
            row["count"] += 1
            row["seconds"] += secs
            for k, v in moved.items():
                row[k] += v


def span_table() -> Dict[str, Dict[str, float]]:
    """{span name: {count, seconds, and each compile counter}} over every
    occurrence in this process so far."""
    with _lock:
        return {name: dict(row) for name, row in _table.items()}
