"""Op registry with runtime implementation selection.

Reference analog: libnd4j's op dispatch. There, a DeclarableOp (e.g. conv2d in
libnd4j/include/ops/declarable/generic/nn/convo/conv2d.cpp) may be overridden
at runtime by a PLATFORM_IMPL (cudnn/mkldnn) chosen per-call by
``isUsablePlatform``-style checks. We reproduce that seam: each named op has

- exactly one ``xla`` implementation (always-correct lowering, lets the XLA
  compiler fuse/tile it), and
- zero or more accelerated implementations (``pallas`` kernels), each with a
  ``predicate(*args, **kwargs) -> bool`` deciding whether it applies to this
  call's shapes/dtypes/platform.

Selection honours the env flags (DL4J_TPU_DISABLE_PALLAS / FORCE_PALLAS), the
analog of adding/removing deeplearning4j-cuda from the classpath.

Unlike the reference there is no per-op device dispatch cost at execution
time: selection happens at *trace* time, and everything lands in one fused
XLA program.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax

from deeplearning4j_tpu.common.env import env


@dataclasses.dataclass
class OpImpl:
    name: str
    platform: str  # "xla" | "pallas"
    fn: Callable[..., Any]
    predicate: Callable[..., bool] | None = None   # perf heuristic (FORCE_PALLAS bypasses)
    requires: Callable[..., bool] | None = None    # structural: ALWAYS enforced
    priority: int = 0  # higher wins among applicable impls
    scope: str | None = None  # jax.named_scope the call runs under (kernel family)

    def supported(self, *args, **kwargs) -> bool:
        """Structural applicability — the impl can produce a correct answer
        for this call at all (e.g. flash attention cannot take a mask). Not
        bypassed by FORCE_PALLAS. Predicates are pure shape/dtype functions:
        one that raises is a bug and propagates — it must never turn into a
        silent choice of the XLA lowering."""
        return self.requires is None or bool(self.requires(*args, **kwargs))

    def applicable(self, *args, **kwargs) -> bool:
        return (self.supported(*args, **kwargs)
                and (self.predicate is None
                     or bool(self.predicate(*args, **kwargs))))


class _Op:
    """A named op: holds all registered impls and picks one per call."""

    def __init__(self, name: str):
        self.name = name
        self.impls: list[OpImpl] = []

    @property
    def xla(self) -> OpImpl:
        for impl in self.impls:
            if impl.platform == "xla":
                return impl
        raise KeyError(f"op '{self.name}' has no xla reference implementation")

    def select(self, *args, **kwargs) -> OpImpl:
        if not env.disable_pallas:
            candidates = [
                i
                for i in self.impls
                if i.platform != "xla"
                and (i.applicable(*args, **kwargs)
                     if not env.force_pallas
                     # FORCE_PALLAS overrides perf heuristics, never
                     # structural requirements — forcing an impl onto a call
                     # it cannot express would trade speed for wrong answers
                     else i.supported(*args, **kwargs))
            ]
            if candidates:
                return max(candidates, key=lambda i: i.priority)
        return self.xla

    def __call__(self, *args, **kwargs):
        impl = self.select(*args, **kwargs)
        if env.verbose:
            print(f"[dl4j-tpu] op {self.name} -> {impl.platform}")
        if impl.scope is None:
            out = impl.fn(*args, **kwargs)
        else:
            # the kernels' stable name in a trace: <scope>_fwd / <scope>_bwd*
            # are the pallas_call names, jvp(<scope>) / transpose(jvp(<scope>))
            # the op_name of whatever XLA runs around them
            with jax.named_scope(impl.scope):
                out = impl.fn(*args, **kwargs)
        if env.nan_panic:
            out = _nan_check(self.name, out)
        return out


_REGISTRY: dict[str, _Op] = {}


def get_op(name: str) -> _Op:
    if name not in _REGISTRY:
        _REGISTRY[name] = _Op(name)
    return _REGISTRY[name]


def register_op(name: str):
    """Decorator: register ``fn`` as the plain-XLA lowering of op ``name``."""

    def deco(fn):
        get_op(name).impls.append(OpImpl(name=name, platform="xla", fn=fn))
        return fn

    return deco


def register_impl(name: str, platform: str = "pallas", predicate=None,
                  requires=None, priority: int = 1, scope: str | None = None):
    """Decorator: register an accelerated implementation of op ``name``.

    ``predicate(*call_args, **call_kwargs)`` gates applicability on perf
    heuristics (the TPU-native ``isUsablePlatform``); FORCE_PALLAS bypasses
    it. ``requires`` states structural constraints the impl cannot operate
    without (unsupported arguments, shape contracts) — never bypassed.
    ``scope`` is the ``jax.named_scope`` a call of this impl runs under:
    for a Pallas impl the prefix its ``pallas_call`` names share.
    """

    def deco(fn):
        get_op(name).impls.append(
            OpImpl(name=name, platform=platform, fn=fn, predicate=predicate,
                   requires=requires, priority=priority, scope=scope)
        )
        return fn

    return deco


def op(name: str) -> Callable[..., Any]:
    """Callable handle for a named op (selection at each call/trace)."""
    return get_op(name)


@functools.partial(jax.tree_util.Partial)
def _identity(x):
    return x


def _nan_check(name: str, out):
    """NaN/Inf panic mode (OpProfiler PANIC analog) via jax.debug inside jit."""
    import jax.numpy as jnp

    def check(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            bad = ~jnp.all(jnp.isfinite(x))
            jax.debug.callback(
                lambda b, n=name: (_ for _ in ()).throw(FloatingPointError(f"NaN/Inf in op {n}"))
                if bool(b)
                else None,
                bad,
            )
        return x

    return jax.tree_util.tree_map(check, out)


def registered_ops() -> list[str]:
    return sorted(_REGISTRY)
