"""Compile-time observability + persistent compilation cache wiring.

Reference analog: the reference JIT-compiled nothing — op dispatch cost was
fixed JNI overhead — so it had no notion of compile-time visibility. In an
XLA world every new (program, shape) pair costs seconds-to-minutes of
compilation, and a fit loop that recompiles per ragged tail shape hides that
cost inside ordinary step time. Two tools here:

- **install_hooks()** registers ``jax.monitoring`` listeners that land every
  backend compile in ``dl4j_compile_seconds``/``dl4j_compiles_total`` and as
  a ``compile`` span under the span that caused it (and persistent-cache
  hits/misses in ``dl4j_compile_cache_events_total``) when monitoring is
  enabled — cold-vs-warm compile time becomes a /metrics
  read. Registration is idempotent and the callbacks fire only on compiles
  and cache probes, never on the step hot path.
- **configure_compile_cache()** is the one place that decides where JAX's
  persistent compilation cache lives: where ``JAX_COMPILATION_CACHE_DIR``
  says if it is set, otherwise ``<checkout>/.jax_cache``. Entry points
  (``bench.py``, ``chip_smoke.py``, ``tests/conftest.py``) call it; nothing
  else sets ``jax_compilation_cache_dir``.
"""

from __future__ import annotations

import os
from pathlib import Path

_installed = False

#: a fixed path, never a temp name, pid or time: a cache that moves never hits
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")
#: LRU cap of the in-checkout cache — the chip tool copies the tree as it
#: stands, cache included, and refuses a copy over 256 MiB
CHECKOUT_CACHE_CAP_BYTES = 192 << 20


def install_hooks() -> bool:
    """Register the jax.monitoring -> metrics-registry bridge (idempotent).
    Returns True when hooks are (already) installed. The listeners are
    process-global and permanent — they gate on ``monitoring.enabled()`` at
    fire time, so the default-off state records nothing."""
    global _installed
    if _installed:
        return True
    import jax.monitoring as jax_monitoring

    from deeplearning4j_tpu import monitoring

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        if not event.endswith("backend_compile_duration"):
            return
        mon = monitoring.compile_monitor()
        if mon is None:
            return
        mon.compiles.inc()
        mon.compile_seconds.observe(duration)
        tracer = monitoring.tracer()
        if tracer is not None:
            # an already-measured span whose parent is the span open on
            # this thread: a step that recompiles names itself
            tracer.complete("compile", duration)

    def _on_event(event: str, **kwargs) -> None:
        kind = None
        if event.endswith("cache_hits"):
            kind = "hit"
        elif event.endswith("cache_misses"):
            kind = "miss"
        if kind is None:
            return
        mon = monitoring.compile_monitor()
        if mon is None:
            return
        mon.cache_events.labels(kind=kind).inc()

    jax_monitoring.register_event_duration_secs_listener(_on_duration)
    jax_monitoring.register_event_listener(_on_event)
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
