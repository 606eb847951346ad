"""OpProfiler analog + NaN panic + jax.profiler trace wrapper."""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import defaultdict
from typing import Dict, List, Optional

import jax
import numpy as np


@dataclasses.dataclass
class ProfilerConfig:
    """org.nd4j.linalg.profiler.ProfilerConfig analog."""

    check_for_nan: bool = False
    check_for_inf: bool = False
    stack_trace: bool = False  # accepted for parity; python tb is implicit


class OpProfiler:
    """Aggregated timing per labeled section (OpProfiler.getInstance()).

    Usage::

        prof = OpProfiler()
        with prof.section("train_step"):
            loss = step(...)
            jax.block_until_ready(loss)
        prof.summary()

    Timings are host-observed wall clock around device work; for the device
    timeline use profiler.trace(logdir) which records an XLA trace viewable
    in TensorBoard/Perfetto.
    """

    def __init__(self, config: Optional[ProfilerConfig] = None):
        self.config = config or ProfilerConfig()
        self.times: Dict[str, List[float]] = defaultdict(list)
        self.invocations: Dict[str, int] = defaultdict(int)

    @contextlib.contextmanager
    def section(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.times[name].append(time.perf_counter() - t0)
            self.invocations[name] += 1

    def time_fn(self, name: str, fn, *args, sync: bool = True, **kwargs):
        with self.section(name):
            out = fn(*args, **kwargs)
            if sync:
                out = jax.block_until_ready(out)
        if self.config.check_for_nan or self.config.check_for_inf:
            check_numerics(out, name=name, inf=self.config.check_for_inf)
        return out

    def stats(self, name: str) -> Dict[str, float]:
        ts = np.asarray(self.times[name])
        if ts.size == 0:
            return {}
        return {"count": int(ts.size), "total_s": float(ts.sum()),
                "mean_ms": float(ts.mean() * 1e3),
                "p50_ms": float(np.percentile(ts, 50) * 1e3),
                "p99_ms": float(np.percentile(ts, 99) * 1e3)}

    def summary(self) -> str:
        lines = [f"{'section':<30}{'count':>8}{'mean ms':>12}{'total s':>10}"]
        for name in sorted(self.times, key=lambda n: -sum(self.times[n])):
            s = self.stats(name)
            lines.append(f"{name:<30}{s['count']:>8}{s['mean_ms']:>12.3f}"
                         f"{s['total_s']:>10.3f}")
        return "\n".join(lines)

    def reset(self):
        self.times.clear()
        self.invocations.clear()


def check_numerics(tree, name: str = "value", inf: bool = True):
    """Raise FloatingPointError on NaN (and optionally Inf) anywhere in a
    pytree — the OpProfiler PANIC mode, applied at step boundaries."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        if not np.issubdtype(a.dtype, np.floating):
            continue
        if np.isnan(a).any():
            raise FloatingPointError(
                f"NaN detected in {name} at {jax.tree_util.keystr(path)}")
        if inf and np.isinf(a).any():
            raise FloatingPointError(
                f"Inf detected in {name} at {jax.tree_util.keystr(path)}")
    return tree


@contextlib.contextmanager
def nan_panic():
    """Scoped jax_debug_nans — XLA re-runs the offending op un-jitted and
    raises at the exact primitive (the libnd4j panic-mode analog that
    actually points at the op)."""
    prev = jax.config.read("jax_debug_nans")
    jax.config.update("jax_debug_nans", True)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", prev)


@contextlib.contextmanager
def trace(logdir: str, host_tracer_level: int = 1):
    """Device-timeline trace via jax.profiler (TensorBoard/Perfetto
    viewable) — the libnd4j GraphProfile / nvprof replacement. With
    monitoring enabled the program's own spans (monitoring/tracing.py) are
    in it too, as ``TraceAnnotation``s beside the device's operations: the
    host tracer runs at level 1, which records them; the Python tracer
    stays off. On the v5e runtime level 1 also records an event for every
    block by which a host batch is re-tiled (11 million ``Transpose`` events
    and 385 MB over 17 ResNet-50 steps of 77 MB, each step stalled ~2.1 s:
    PERF.md §6): where the host stages big batches pass
    ``host_tracer_level=0`` and read the spans from ``monitoring.spans()``,
    which share the trace's clock."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
