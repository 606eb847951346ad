"""The ``fit`` window: a model of the zoo trained through the entry point a
user calls — ``fit`` on the network on one chip, ``ParallelWrapper.fit`` on
more — with the default async window and the program's own prefetch thread
staging host batches.

Set-up builds one model, gives it the benchmark's seeded weights, and drives
it through its first three steps by the same call and feed as the window
(the first compiles, or reads the cache); the readings the comparison needs
are taken there. The same object then runs the window. Once the window has
closed and the memory has been read, the model is dropped and the plain
reference follows the same three batches.
"""

from __future__ import annotations

import gc
import glob
import importlib
import os
import shutil
import statistics
import time

import jax
import numpy as np

from benchmarks import program_trace, reference_train, trace_reduce
from benchmarks.harness import ROOT, Cell, device_stamp
from benchmarks.traffic_gen import make_pool

PROGRAM = "train_step"          # the jitted step's name in both step builders
TRACE_DIR = ROOT / ".bench_trace"      # the last traced run only; .gitignore lists it


def build_model(config: dict):
    """The program's model by the dotted path in the configuration's file."""
    module, _, attr = config["builder"].rpartition(".")
    return getattr(importlib.import_module(module), attr)(**config["builder_args"]).init()


def moment_tree(opt_state, key: str):
    """The optimizer state's ``key`` moment, shaped like the parameters."""
    if isinstance(opt_state, dict):
        return {name: st[key] for name, st in opt_state.items()}
    return [st[key] if st else st for st in opt_state]


def memory_peak(devices) -> tuple:
    """(the peak of the fullest chip, every counter PJRT gives for it).

    On this runtime ``peak_bytes_in_use`` counts the live buffers (arguments,
    results, staged batches) and ``peak_bytes_reserved`` the arena XLA's
    programs take their temporaries from; the two are disjoint (free =
    limit - reserved - in use) and their sum is what agrees with the
    compiler's ``memory_analysis()`` (arguments + temporaries), see PERF.md."""
    def footprint(s):
        return s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)

    fullest = max((d.memory_stats() or {} for d in devices), key=footprint)
    return footprint(fullest), fullest


class Program:
    """The program under test, set up for one cell: the model with the
    benchmark's seeded weights, its ``fit`` and its staging, and the readings
    of its first three steps. ``patch`` (tests, the calibration of faults)
    is called with the model once it is built."""

    def __init__(self, cell: Cell, seed: int, devices: list, patch=None):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterators import AsyncPrefetchIterator, DataSetIterator
        from deeplearning4j_tpu.optimize.listeners import TrainingListener
        from deeplearning4j_tpu.parallel import DeviceMesh, ParallelWrapper

        cfg, traffic = cell.config, cell.traffic
        self.cell, self.devices, self.key = cell, devices, jax.random.key(seed)
        self.module = importlib.import_module(cfg["reference"])
        self.pool = make_pool(cfg["inputs"], traffic, seed)
        sets = [DataSet(x, y) for x, y in self.pool]

        class Pool(DataSetIterator):
            """Batches of the pool in turn: ``count`` of them, or until ``until``."""

            def __init__(self, first: int, count=None, until=None):
                super().__init__(traffic["batch"])
                self.first, self.count, self.until = first, count, until

            def _produce(self):
                i = 0
                while ((self.count is None or i < self.count)
                       and (self.until is None or time.perf_counter() < self.until)):
                    with jax.profiler.TraceAnnotation("bench.next_batch"):
                        ds = sets[(self.first + i) % len(sets)]
                    yield ds
                    i += 1

        class Stamps(TrainingListener):
            """The time each step's score is delivered, in step order."""

            def __init__(self):
                self.at, self.scores, self.on_delivery = [], [], None

            def iteration_done(self, model, iteration, epoch, score):
                self.at.append(time.perf_counter())
                self.scores.append(float(score))
                if self.on_delivery is not None:
                    self.on_delivery(len(self.at))

        self.Pool = Pool
        self.model = model = build_model(cfg)
        if patch is not None:
            patch(model)
        start, start_state = jax.jit(lambda k: self.module.make_params(k, cfg))(self.key)
        if jax.tree.structure(start) != jax.tree.structure(model.params):
            raise SystemExit("the program's parameter tree is not the reference's: "
                             f"{jax.tree.structure(model.params)} != {jax.tree.structure(start)}")
        self._start_host = jax.device_get(start)     # the step donates its buffers
        model.params, model.state = start, start_state
        self.stamps = Stamps()
        model.set_listeners(self.stamps)
        if len(devices) > 1:
            self._wrapper = ParallelWrapper(model, DeviceMesh(devices=devices))
            self.fit, self.stage = self._wrapper.fit, lambda it: it   # the wrapper stages itself
        else:
            self._wrapper = None
            self.fit, self.stage = model.fit, AsyncPrefetchIterator

    def first_steps(self) -> dict:
        """The first three steps through the window's own call and feed (the
        first compiles, or reads the cache), and what the comparison takes
        from them."""
        spec, model = self.cell.config["updater"], self.model
        state_key, factor = reference_train.first_gradient_from_moment(spec)
        self.fit(self.stage(self.Pool(0, count=1)))
        moment = moment_tree(model.opt_state, state_key)
        grad_norms = [factor * float(n) for n in reference_train.leaf_norms(moment)]
        record = {}
        if reference_train.takes_direction(self.cell.limits):
            # the first gradient itself, as the timed step left it in the optimizer's
            # state (a factor apart, which a direction does not see), kept on the
            # host: only where the cell's limits name its direction
            record["grad1"] = jax.device_get(moment)
        del moment
        self.fit(self.stage(self.Pool(1, count=reference_train.CHECK_STEPS - 1)))
        change = [float(c) for c in
                  reference_train.leaf_norms_of_change(model.params, self._start_host)]
        self._start_host = None
        return {"losses": list(self.stamps.scores), "grad_norms": grad_norms,
                "change_norms": change,
                "state_norms": [float(n) for n in reference_train.leaf_norms(model.state)],
                **record}

    def train_programs(self) -> int:
        return self.model._jit_cache["train"]._cache_size()

    def free(self):
        """Drop the model and everything of it on the device."""
        self.model.set_listeners()
        if self._wrapper is not None:
            self._wrapper.model = None
        self.model.params = self.model.state = self.model.opt_state = None
        self.model = self._wrapper = self.fit = self.stage = self.Pool = None
        gc.collect()
        jax.clear_caches()

    def reference(self, **how) -> dict:
        """The plain reference over the same three batches (after ``free``)."""
        return reference_train.follow(
            self.module, self.cell.config, self.key,
            _to_devices(self.pool[:reference_train.CHECK_STEPS], self.devices),
            in_shardings=_reference_shardings(self.devices),
            keep_gradient=reference_train.takes_direction(self.cell.limits), **how)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, devices: list, peaks: dict,
        clock0: float, patch=None) -> dict:
    from deeplearning4j_tpu import monitoring

    traffic, chips = cell.traffic, len(devices)
    prog = Program(cell, seed, devices, patch)
    readings = prog.first_steps()
    programs_before = prog.train_programs()
    stamps = prog.stamps

    # ---- the window
    tracing = _Tracing(traffic, monitoring) if trace else None
    if tracing is not None:
        monitoring.enable()
        stamps.on_delivery = tracing.on_delivery
    done_before = len(stamps.at)
    setup_s = time.perf_counter() - clock0
    t0 = time.perf_counter()
    prog.fit(prog.stage(prog.Pool(reference_train.CHECK_STEPS, until=t0 + seconds)))
    jax.block_until_ready(prog.model.params)
    t1 = time.perf_counter()
    if tracing is not None:
        tracing.stop()
        monitoring.disable()
    deliveries = stamps.at[done_before:]
    steps = len(deliveries)
    programs_after = prog.train_programs()
    peak, counters = memory_peak(devices)
    gaps_ms = [1e3 * (b - a) for a, b in zip([t0] + deliveries[:-1], deliveries)]
    facts = {"steps": steps, "window_s": t1 - t0,
             "train_step_p50_ms": statistics.median(gaps_ms) if gaps_ms else None,
             "train_step_programs": programs_after,
             "compiled_in_window": programs_after - programs_before,
             "memory_counters": {k: v for k, v in counters.items() if "bytes" in k},
             "last_loss": stamps.scores[-1]}
    end_to_end = {
        "train_samples_per_s_per_chip": steps * traffic["batch"] / (t1 - t0) / chips,
        "train_step_p95_ms": _percentile(gaps_ms, 95) if gaps_ms else float("nan"),
        "setup_s": setup_s}

    # ---- free the program's state, then the plain reference on the same batches
    prog.free()
    t_ref = time.perf_counter()
    verdict = reference_train.compare(readings, prog.reference(), cell.limits)
    facts["reference_s"] = time.perf_counter() - t_ref
    if facts["compiled_in_window"]:
        verdict["correct"] = False
        verdict["checks"]["compiled_in_window"] = {"value": facts["compiled_in_window"], "limit": 0}
    out = {"end_to_end": end_to_end, "attempted": steps, "failed": 0, "facts": facts,
           "device": device_stamp(devices, peak), "verdict": verdict}
    if tracing is not None:
        t_read = time.perf_counter()
        red = tracing.reduced()
        facts["trace_read_s"] = time.perf_counter() - t_read
        facts["trace_bytes"] = tracing.counters["trace_bytes"]
        busy = statistics.mean(trace_reduce.busy_seconds(red, d) for d in red.devices)
        out["device"].update(busy_s=busy, window_s=red.window_s)
        out["layer_context"] = {
            "trace": red, "cell": cell, "chips": chips, "peaks": peaks, "module": prog.module,
            "counters": tracing.counters}
        out["breakdown"] = breakdown(out["layer_context"])
    return out


def breakdown(context: dict) -> dict:
    """The heaviest device operations and the longest idle gaps of the busiest
    device; a gap takes the name of the program's span over it (the spine,
    ``program_trace.named_gaps``) where the traced run recorded spans on the
    trace's clock, else of the benchmark's own host span."""
    out = trace_reduce.breakdown(context["trace"])
    named = program_trace.named_gaps(context)
    if named:
        out["idle_gaps"] = named
    return out


def _percentile(values: list, q: float) -> float:
    """Nearest-rank percentile over every value."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, max(0, -(-len(ordered) * q // 100) - 1))]


def _reference_shardings(devices):
    """On one chip nothing; on more, the batch split over them and the rest
    on every chip — XLA puts the reductions in."""
    if len(devices) == 1:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("data",))
    everywhere, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    return (everywhere, everywhere, everywhere, everywhere, split, split)


def _to_devices(batches, devices):
    shardings = _reference_shardings(devices)
    where = shardings[-1] if shardings else devices[0]
    return [(jax.device_put(x, where), jax.device_put(y, where)) for x, y in batches]


class _Tracing:
    """Switches the profiler on for ``trace_steps`` deliveries inside the
    window, once ``trace_after_steps`` have gone by, and reads the program's
    counters over the same stretch."""

    def __init__(self, traffic: dict, monitoring):
        self.after, self.steps = traffic["trace_after_steps"], traffic["trace_steps"]
        self.cut = (traffic["trace_skip_first"], traffic["trace_skip_last"],
                    traffic["trace_stall_s"], traffic["trace_min_steps"])
        self.host_level = traffic["trace_host_level"]
        self.monitoring, self.first, self.on = monitoring, None, False
        self.counters, self.t_on, self.wait_on = {}, None, None
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    def _data_wait(self):
        family = self.monitoring.registry().get("dl4j_train_data_wait_seconds")
        return None if family is None or not family.count else family.sum

    def on_delivery(self, n: int):
        if self.first is None:
            self.first = n
        seen = n - self.first
        if seen == self.after and not self.on:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            # 0 where the host moves big batches: the host tracer records an
            # event for every block a batch is re-tiled by (1.8 million a thread
            # over 16 ResNet steps, 393 MB) and halts the fit loop to flush them
            options.host_tracer_level = self.host_level
            options.enable_hlo_proto = False
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
            self.on, self.t_on, self.wait_on = True, time.perf_counter(), self._data_wait()
        elif seen == self.after + self.steps and self.on:
            self.stop()

    def stop(self):
        if not self.on:
            return
        wait_off, t_off = self._data_wait(), time.perf_counter()
        jax.profiler.stop_trace()
        self.on = False
        self.counters["traced_host_s"] = t_off - self.t_on
        if wait_off is not None:
            self.counters["data_wait_s"] = wait_off - (self.wait_on or 0.0)

    def reduced(self):
        files = sorted(glob.glob(str(TRACE_DIR / "plugins/profile/*/*.xplane.pb")),
                       key=os.path.getmtime)
        if not files:
            raise SystemExit("the traced run left no .xplane.pb: the window was shorter "
                             "than trace_after_steps + trace_steps deliveries")
        self.counters["trace_bytes"] = os.path.getsize(files[-1])
        return trace_reduce.read_xplane(files[-1], PROGRAM, *self.cut)
