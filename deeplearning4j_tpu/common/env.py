"""Runtime environment flags, read from process env vars.

Reference analog: org.nd4j.config.ND4JEnvironmentVars (backend selection,
workspace debug, OMP threads) and libnd4j's Environment singleton
(verbose/debug toggles over JNI). Here the flags steer op-impl selection
(Pallas vs plain XLA), debug checks, and monitoring — the things that still
exist in an XLA world.
"""

from __future__ import annotations

import os


def _flag(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() not in ("", "0", "false", "off", "no")


def _int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or not v.strip():
        return default
    try:
        return int(v.strip())
    except ValueError:
        return default


class Environment:
    """Process-wide runtime switches (singleton, like libnd4j Environment)."""

    # Disable all Pallas kernels: every op uses its plain-XLA lowering.
    # Analog of removing deeplearning4j-cuda from the classpath (no cuDNN helpers).
    DISABLE_PALLAS = "DL4J_TPU_DISABLE_PALLAS"
    # Force Pallas kernels even where the predicate would pick XLA (testing).
    FORCE_PALLAS = "DL4J_TPU_FORCE_PALLAS"
    # Panic on NaN/Inf produced by ops (OpProfiler ANY_PANIC analog).
    NAN_PANIC = "DL4J_TPU_NAN_PANIC"
    # Verbose op-dispatch logging (libnd4j Environment::setVerbose analog).
    VERBOSE = "DL4J_TPU_VERBOSE"
    # Unified monitoring layer (metrics registry + fit-loop instrumentation,
    # deeplearning4j_tpu/monitoring). Default OFF: the fit hot path then
    # performs no registry/tracer calls (tests enforce zero overhead).
    MONITORING = "DL4J_TPU_MONITORING"
    # Force the fused LSTM to take the scan-recompute backward instead of
    # the Pallas backward kernel (A/B measurement + escape hatch).
    LSTM_SCAN_BWD = "DL4J_TPU_LSTM_SCAN_BWD"
    # Same escape hatch for the fused GRU backward.
    GRU_SCAN_BWD = "DL4J_TPU_GRU_SCAN_BWD"
    # Import-graph optimizer (modelimport/optimizer.py): constant folding,
    # layout-op elimination, attention fusion over TF/ONNX/Keras imports.
    # Default ON; DL4J_TPU_IMPORT_OPT=0 restores the raw parsed graph.
    IMPORT_OPT = "DL4J_TPU_IMPORT_OPT"
    # Deterministic fault injection (deeplearning4j_tpu.faults): spec
    # grammar "cls:rate[@cond]" plus its seed and simulated straggler
    # delay. Parsed by faults.configure()/reset() (not cached here);
    # unset = no plan installed = zero-overhead injection points.
    FAULTS = "DL4J_TPU_FAULTS"
    FAULTS_SEED = "DL4J_TPU_FAULTS_SEED"
    FAULTS_DELAY_S = "DL4J_TPU_FAULTS_DELAY_S"
    # Async training dispatch (optimize/async_dispatch.py): how many train
    # steps may be in flight before fit_batch drains the oldest loss.
    # Default 2 (double-buffered dispatch); 0 restores the per-step
    # host-sync behavior (fit_batch returns an eager float).
    ASYNC_STEPS = "DL4J_TPU_ASYNC_STEPS"
    # Tail-batch padding: pad partial epoch-tail batches up to the pow2
    # bucket of the largest batch seen (label-mask zeroed — loss-exact) so
    # ragged tails stop compiling one XLA program per shape. Default ON;
    # =0 feeds batches through at their raw shapes.
    PAD_TAIL = "DL4J_TPU_PAD_TAIL"
    # SpanTracer ring-buffer capacity: oldest events are dropped (and
    # counted in dl4j_trace_events_dropped_total) past this many, so a
    # long-running gateway with tracing armed holds memory flat.
    TRACE_MAX_EVENTS = "DL4J_TPU_TRACE_MAX_EVENTS"
    # Request tracing on serving gateways built without an explicit
    # ``trace=`` argument (monitoring/context.py). Unset/0 = the request
    # path performs zero tracer calls (spy-guarded contract).
    TRACING = "DL4J_TPU_TRACING"
    # Black-box flight recorder (monitoring/flight.py): =1 arms the
    # process-wide ring buffer of serving/training incidents; the DIR
    # variant also sets where trigger conditions dump postmortem bundles.
    FLIGHT = "DL4J_TPU_FLIGHT"
    FLIGHT_DIR = "DL4J_TPU_FLIGHT_DIR"
    FLIGHT_CAP = "DL4J_TPU_FLIGHT_CAP"
    # Training guardrails (deeplearning4j_tpu.guardrails): =1 arms the
    # numeric sentinel + policy ladder on every model's fit loop; the DIR
    # variant gives the ladder a rollback checkpoint directory (without
    # it, the ladder ends at clip-retry). Unset = zero-overhead unarmed
    # fit path (spy-guarded, like MONITORING/FAULTS).
    GUARDRAILS = "DL4J_TPU_GUARDRAILS"
    GUARDRAILS_DIR = "DL4J_TPU_GUARDRAILS_DIR"

    def __init__(self) -> None:
        self.reload()

    def reload(self) -> None:
        self.disable_pallas = _flag(self.DISABLE_PALLAS)
        self.force_pallas = _flag(self.FORCE_PALLAS)
        self.nan_panic = _flag(self.NAN_PANIC)
        self.verbose = _flag(self.VERBOSE)
        self.monitoring = _flag(self.MONITORING)
        self.lstm_scan_bwd = _flag(self.LSTM_SCAN_BWD)
        self.gru_scan_bwd = _flag(self.GRU_SCAN_BWD)
        self.import_opt = _flag(self.IMPORT_OPT, True)
        self.async_steps = max(0, _int(self.ASYNC_STEPS, 2))
        self.pad_tail = _flag(self.PAD_TAIL, True)
        self.trace_max_events = max(1, _int(self.TRACE_MAX_EVENTS, 100_000))
        self.tracing = _flag(self.TRACING)
        self.flight = _flag(self.FLIGHT)
        self.flight_dir = (os.environ.get(self.FLIGHT_DIR)
                           or "").strip() or None
        self.flight_cap = max(1, _int(self.FLIGHT_CAP, 512))
        self.guardrails = _flag(self.GUARDRAILS)
        self.guardrails_dir = (os.environ.get(self.GUARDRAILS_DIR)
                               or "").strip() or None


env = Environment()
