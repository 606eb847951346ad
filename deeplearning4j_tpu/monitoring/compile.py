"""Compile-time observability + persistent compilation cache wiring.

Reference analog: the reference JIT-compiled nothing — op dispatch cost was
fixed JNI overhead — so it had no notion of compile-time visibility. In an
XLA world every new (program, shape) pair costs seconds-to-minutes of
compilation, and a fit loop that recompiles per ragged tail shape hides that
cost inside ordinary step time. Two tools here:

- **install_hooks()** registers ``jax.monitoring`` listeners. Every build
  of a program lands in the start-up record (``monitoring.startup()``),
  monitoring on or off, as three spans with JAX's own start and end:
  ``compile.trace`` (the jaxpr trace), ``compile.lower`` (the MLIR
  lowering; Mosaic lowers Pallas kernels here) and ``compile`` (the backend
  compile, or on a warm persistent cache its read). Each carries
  ``program``, JAX's name out of its ``jit(...)`` or ``jit_`` (``train_step``
  for the step in every phase), and ``compile`` carries ``cache``: ``hit``,
  ``miss``, or ``off`` where no cache was asked. A trace inside another
  program's trace (each ``jnp`` function the step calls is its own jit) is
  part of that one and not recorded. A span's parent is the span open on
  its thread; with none open, the phase of the same program just before it.
  With monitoring on, every backend compile also lands in
  ``dl4j_compile_seconds``/``dl4j_compiles_total`` and persistent-cache
  hits/misses in ``dl4j_compile_cache_events_total`` — cold-vs-warm compile
  time becomes a /metrics read. Registration is idempotent and the callbacks
  fire only on builds and cache probes, never on a step that reuses a built
  program.
- **configure_compile_cache()** is the one place that decides where JAX's
  persistent compilation cache lives: where ``JAX_COMPILATION_CACHE_DIR``
  says if it is set, otherwise ``<checkout>/.jax_cache``. Entry points
  (``bench.py``, ``chip_smoke.py``, ``tests/conftest.py``) call it; nothing
  else sets ``jax_compilation_cache_dir``.
"""

from __future__ import annotations

import os
import re
import threading
import time
from pathlib import Path

_installed = False

#: a fixed path, never a temp name, pid or time: a cache that moves never hits
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")
#: LRU cap of the in-checkout cache — the chip tool copies the tree as it
#: stands, cache included, and refuses a copy over 256 MiB
CHECKOUT_CACHE_CAP_BYTES = 192 << 20

#: JAX's time-span events of a program's build -> the span each becomes
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
PHASES = {TRACE_EVENT: "compile.trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
          "/jax/core/compile/backend_compile_duration": "compile"}
_BEFORE = {"compile.lower": "compile.trace", "compile": "compile.lower"}
_JIT_NAME = re.compile(r"jit\((.+)\)|jit_(.+)")
#: per thread: the traces open on it, the newest phase recorded of each
#: program, and the newest persistent-cache outcome with the time it was seen
_thread = threading.local()


def _trace_begins(event: str, value: float, **kwargs) -> None:
    if event == TRACE_EVENT:
        _thread.tracing = getattr(_thread, "tracing", 0) + 1


def _cache_event(event: str, **kwargs) -> None:
    """Fired inside a backend compile, on its thread: the cache is asked (a
    miss unless a hit follows), then a hit or, once the compiled program is
    written, a miss."""
    from deeplearning4j_tpu import monitoring

    if event.endswith("compile_requests_use_cache"):
        _thread.cache = ("miss", time.time())
        return
    if event.endswith("cache_hits"):
        kind = "hit"
        _thread.cache = (kind, time.time())
    elif event.endswith("cache_misses"):
        kind = "miss"
    else:
        return
    mon = monitoring.compile_monitor()
    if mon is not None:
        mon.cache_events.labels(kind=kind).inc()


def _record_phase(event: str, start: float, end: float, fun_name: str = "",
                  **kwargs) -> None:
    name = PHASES.get(event)
    if name is None:
        return
    if event == TRACE_EVENT:
        depth = getattr(_thread, "tracing", 0) - 1
        _thread.tracing = max(depth, 0)
        if depth > 0:
            # traced inside another program's trace (each jnp function is
            # its own jit): part of that trace's time, not a build of its own
            return
    from deeplearning4j_tpu import monitoring

    m = _JIT_NAME.fullmatch(fun_name)
    program = next(g for g in m.groups() if g) if m else fun_name
    args = {"program": program}
    if name == "compile":
        kind, seen = getattr(_thread, "cache", (None, 0.0))
        _thread.cache = (None, 0.0)
        args["cache"] = kind if kind is not None and seen >= start else "off"
        mon = monitoring.compile_monitor()
        if mon is not None:
            mon.compiles.inc()
            mon.compile_seconds.observe(end - start)
    phases = getattr(_thread, "phases", None)
    if phases is None:
        phases = _thread.phases = {}
    record = monitoring.startup()
    parent = record.current()
    if parent is None:
        before = phases.get(program)
        if before is not None and before[0] == _BEFORE.get(name):
            parent = before[1]
    phases[program] = (name, record.record(
        name, int(start * 1e9), int(end * 1e9), parent, **args))


def install_hooks() -> bool:
    """Register the jax.monitoring bridge (idempotent). Returns True when
    hooks are (already) installed. The listeners are process-global and
    permanent: builds go to the start-up record always; the metrics gate on
    ``monitoring.enabled()`` at fire time, so the default-off state touches
    no registry."""
    global _installed
    if _installed:
        return True
    import jax.monitoring as jax_monitoring

    jax_monitoring.register_event_listener(_cache_event)
    jax_monitoring.register_scalar_listener(_trace_begins)
    jax_monitoring.register_event_time_span_listener(_record_phase)
    _installed = True
    return True


def configure_compile_cache() -> str:
    """Enable JAX's persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX already uses that directory:
    nothing is set here, and the directory is its owner's to manage (never
    trimmed). Otherwise the cache is ``<checkout>/.jax_cache``, LRU-trimmed
    to ``CHECKOUT_CACHE_CAP_BYTES``. Also installs the compile metrics
    hooks so an enabled registry sees the cold-vs-warm split."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        from deeplearning4j_tpu.native.lib import trim_compile_cache

        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
        trim_compile_cache(path, CHECKOUT_CACHE_CAP_BYTES)
    # 0 (not the 1s default): persist every program. Most compiles here are
    # small — kernel A/B rows, the steps a train loop re-traces per shape,
    # the test suite's thousands of sub-second programs — and a threshold
    # makes every process re-pay them (CPU suite, six files, warm: 115 s at
    # 0 against 289 s at 0.5; 2,587 entries in 15 MB)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    install_hooks()
    return path
