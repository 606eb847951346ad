"""Multi-host distribution + fault tolerance.

Reference analog (SURVEY.md §2.4, §5): the Spark TrainingMaster / Aeron
VoidParameterServer stack — worker membership, heartbeat/mesh repair
(MeshOrganizer), RDD-lineage retry. TPU-native, the transport disappears
entirely: jax.distributed + XLA collectives over ICI/DCN own communication,
so what remains of "fault tolerance" is (a) coordinated multi-host init from
environment and (b) checkpoint-based restart — a crashed job relaunches,
re-initializes, restores the latest step, and continues (the elastic story
the reference implements with Spark retries).
"""

from __future__ import annotations

import json
import os
import threading
import warnings
from typing import Callable, Optional

import jax


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None,
                           retry=None) -> dict:
    """jax.distributed.initialize wrapper, env-driven like the reference's
    VoidParameterServer config (COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID;
    on TPU pods the args auto-detect from the metadata server).

    The coordinator connect runs under a :class:`faults.RetryPolicy`
    (``retry`` overrides the default 5-attempt exponential backoff): a
    coordinator that is still coming up after a pod relaunch refuses a few
    connects before accepting — one-shot init turned that into a dead job.
    Fault class ``coord_connect`` injects exactly that refusal.

    Returns a summary dict; a no-op single-process summary when no
    coordinator is configured.
    """
    from deeplearning4j_tpu import faults

    coordinator_address = coordinator_address or os.environ.get(
        "COORDINATOR_ADDRESS")
    if num_processes is None and "NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and "PROCESS_ID" in os.environ:
        process_id = int(os.environ["PROCESS_ID"])
    if coordinator_address is not None or num_processes is not None:
        def _connect():
            plan = faults.active()
            if plan is not None and plan.fires("coord_connect"):
                raise faults.CoordinatorConnectFault(
                    f"injected connection refusal to coordinator "
                    f"{coordinator_address}")
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)

        policy = retry or faults.RetryPolicy(
            max_attempts=5, base_delay_s=0.2, max_delay_s=5.0,
            deadline_s=120.0)
        policy.call(_connect, component="distributed")
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }


class FaultTolerantTrainer:
    """Checkpoint-restart training loop.

    Wraps any model exposing fit_batch/params with a TrainingCheckpointer:
    on construction it restores the newest checkpoint if one exists (the
    relaunch path), and during training it saves every ``save_every`` steps.
    A crash at any point loses at most ``save_every`` steps — the same
    guarantee the reference gets from Spark's retry + param-averaging
    master, without a parameter server.

        trainer = FaultTolerantTrainer(model, ckpt_dir, save_every=50)
        trainer.fit(iterator, epochs=3)    # safe to kill + rerun
    """

    def __init__(self, model, checkpoint_dir: str, save_every: int = 100,
                 keep_last: int = 3, on_restore: Optional[Callable] = None,
                 max_restarts_without_progress: int = 3):
        from deeplearning4j_tpu.util.checkpoints import TrainingCheckpointer

        self.model = model
        # r5: a parallel facade (ParallelWrapper / TensorParallel) trains,
        # but its .model owns params/opt_state/step_count — train through
        # the facade, checkpoint the owner. The unwrap is deliberately
        # narrow (isinstance, not duck-typed .model) so an unrelated
        # object with a .model attribute is checkpointed as itself.
        # Under jax.distributed EVERY process constructs the trainer and
        # calls save/restore at the same steps; orbax coordinates the
        # multi-process write and its committed step directories make the
        # recovery point atomic.
        from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
        from deeplearning4j_tpu.parallel.tensor_parallel import TensorParallel

        self._target = (model.model
                        if isinstance(model, (ParallelWrapper, TensorParallel))
                        else model)
        self.save_every = max(1, save_every)
        self.checkpoint_dir = str(checkpoint_dir)
        self.checkpointer = TrainingCheckpointer(checkpoint_dir,
                                                 keep_last=keep_last)
        self.restored_step = self.checkpointer.restore_latest(self._target)
        self._check_crash_loop(max_restarts_without_progress)
        if self.restored_step is not None and on_restore:
            on_restore(self.restored_step)
        # set whenever no fit() loop is mid-step: the preemption drain's
        # emergency save waits on it so it never serializes arrays a
        # concurrent (donating) train step is about to delete
        self._parked = threading.Event()
        self._parked.set()

    # --------------------------------------------------- crash-loop bound
    def _crashloop_path(self) -> str:
        return os.path.join(self.checkpoint_dir, ".crashloop.json")

    def _check_crash_loop(self, bound: int) -> None:
        """A relaunch that restores the SAME step as the previous relaunch
        made no progress — the crash is deterministic (bad batch, poisoned
        state), and restarting forever burns the pod. Bound it: after
        ``bound`` restarts at one step, fail loud instead of looping.
        State lives in a marker file so it survives the process boundary
        the way the crashes do."""
        if self.restored_step is None or bound <= 0:
            return
        path = self._crashloop_path()
        count = 1
        try:
            with open(path) as f:
                prev = json.load(f)
            if int(prev.get("step", -1)) == int(self.restored_step):
                count = int(prev.get("count", 0)) + 1
        except (OSError, ValueError):
            pass
        if jax.process_index() == 0:
            try:
                with open(path, "w") as f:
                    json.dump({"step": int(self.restored_step),
                               "count": count}, f)
            except OSError:
                pass
        if count > bound:
            from deeplearning4j_tpu import monitoring

            mon = monitoring.recovery_monitor()
            if mon is not None:
                mon.recovery_total.labels(component="trainer",
                                          outcome="crash_loop").inc()
            raise RuntimeError(
                f"crash loop detected: {count} consecutive relaunches "
                f"restored step {self.restored_step} without progressing "
                f"past it (bound {bound}). The failure is likely "
                f"deterministic — inspect the step, the data at it, and "
                f"{path} before relaunching (delete the file to override).")

    # ------------------------------------------------------- preemption
    def register_lifecycle(self, manager) -> "FaultTolerantTrainer":
        """Register the emergency checkpoint with a
        :class:`~deeplearning4j_tpu.serving.lifecycle.LifecycleManager`:
        on SIGTERM (or an injected ``preempt`` fault) the drain saves the
        current step inside the grace budget, so the relaunch loses zero
        steps instead of up to ``save_every``."""
        manager.register_checkpoint(self._emergency_save)
        return self

    def _emergency_save(self) -> None:
        self._parked.wait(timeout=30.0)
        self.checkpointer.save(self._target.step_count, self._target)
        self.checkpointer.wait()
        from deeplearning4j_tpu import monitoring

        mon = monitoring.recovery_monitor()
        if mon is not None:
            mon.recovery_total.labels(component="trainer",
                                      outcome="preempt_save").inc()

    @staticmethod
    def _preempting() -> bool:
        """A managed preemption drain is in progress (the fit loop exits
        between batches so the emergency save captures settled state)."""
        from deeplearning4j_tpu.serving import lifecycle

        mgr = lifecycle.manager()
        return mgr is not None and mgr.reason is not None

    def fit_batch(self, ds) -> float:
        from deeplearning4j_tpu import faults

        plan = faults.active()
        if plan is not None and plan.fires("preempt",
                                           step=self._target.step_count):
            # in-process SIGTERM equivalent: managed -> the lifecycle
            # drain starts (this call returns and the fit loop exits at
            # the next batch boundary); unmanaged -> PreemptionFault
            # propagates into fit()'s save-on-exception path
            from deeplearning4j_tpu.serving import lifecycle

            lifecycle.deliver_preemption(source="trainer",
                                         step=self._target.step_count)
            if self._preempting():
                # managed: the grace budget pays for the checkpoint, not
                # another train step — the drain saves the current one
                return float("nan")
        loss = self.model.fit_batch(ds)
        step = self._target.step_count
        if step % self.save_every == 0:
            self.checkpointer.save(step, self._target)
        return loss

    def fit(self, data, epochs: int = 1):
        self._parked.clear()
        try:
            for _ in range(epochs):
                for ds in data:
                    self.fit_batch(ds)
                    if self._preempting():
                        # the drain's checkpoint callback (see
                        # register_lifecycle) saves this step
                        return self.model
                if hasattr(data, "reset"):
                    data.reset()
                self._target.epoch_count += 1
        except Exception:
            # save-on-exception: capture the last good in-memory state so
            # the relaunch resumes from HERE, not save_every steps back.
            # Best effort — the original failure always propagates.
            try:
                self.checkpointer.save(self._target.step_count, self._target)
                self.checkpointer.wait()
                from deeplearning4j_tpu import monitoring

                mon = monitoring.recovery_monitor()
                if mon is not None:
                    mon.recovery_total.labels(
                        component="trainer", outcome="save_on_error").inc()
            except Exception as save_err:  # noqa: BLE001 — never mask the
                # original failure with a checkpoint error
                warnings.warn(f"save-on-exception failed: {save_err}")
            raise
        finally:
            self._parked.set()
        self.checkpointer.save(self._target.step_count, self._target)
        self.checkpointer.wait()
        return self.model

    def close(self):
        self.checkpointer.close()
