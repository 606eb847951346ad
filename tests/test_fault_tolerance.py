"""Fault-tolerance tests: kill-and-restart training resumes from checkpoint.

Reference analog (SURVEY.md §5 "Failure detection"): Spark worker-retry
tests. Here the whole process is killed mid-training (the kill-a-host
integration test) and a fresh process resumes from the latest orbax
checkpoint.
"""

import os
import subprocess
import sys

import numpy as np

from deeplearning4j_tpu.parallel.distributed import (
    FaultTolerantTrainer, initialize_distributed,
)

_TRAIN_SCRIPT = r"""
import sys, os
sys.path.insert(0, {repo!r})
import jax
import numpy as np
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize import Sgd
from deeplearning4j_tpu.parallel.distributed import FaultTolerantTrainer

ckpt_dir, n_steps, crash_at = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
conf = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(lr=0.1)).list()
        .layer(DenseLayer(n_out=8, activation="relu"))
        .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(4)).build())
model = MultiLayerNetwork(conf).init()
trainer = FaultTolerantTrainer(model, ckpt_dir, save_every=5,
                               on_restore=lambda s: print(f"RESTORED {{s}}"))
rng = np.random.default_rng(0)
x = rng.normal(size=(16, 4)).astype(np.float32)
y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 16)]
while model.step_count < n_steps:
    trainer.fit_batch((x, y))
    if crash_at >= 0 and model.step_count == crash_at:
        trainer.checkpointer.wait()
        print(f"CRASHING at {{model.step_count}}", flush=True)
        os._exit(137)  # simulated host failure
trainer.checkpointer.save(model.step_count, model)
trainer.checkpointer.wait()
print(f"DONE {{model.step_count}} {{float(model.score_value):.6f}}")
"""


def _run(ckpt_dir, n_steps, crash_at):
    script = _TRAIN_SCRIPT.format(repo=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", script, str(ckpt_dir),
                           str(n_steps), str(crash_at)],
                          capture_output=True, text=True, env=env,
                          timeout=300)


class TestFaultTolerance:
    def test_kill_and_resume(self, tmp_path):
        ckpt = tmp_path / "ck"
        # run 1: crashes at step 12 (checkpoints at 5, 10)
        r1 = _run(ckpt, 30, 12)
        assert r1.returncode == 137, r1.stderr[-2000:]
        assert "CRASHING at 12" in r1.stdout
        # run 2: relaunch — must restore step 10 and finish
        r2 = _run(ckpt, 30, -1)
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert "RESTORED 10" in r2.stdout
        assert "DONE 30" in r2.stdout

    def test_corrupted_latest_checkpoint_resumes_previous(self, tmp_path):
        """Kill-and-resume where the newest checkpoint is a torn write:
        the relaunch must fall back to the previous VALID step (5) and
        finish — a corrupted latest checkpoint costs save_every steps,
        never the job."""
        ckpt = tmp_path / "ck"
        r1 = _run(ckpt, 30, 12)            # checkpoints at 5, 10
        assert r1.returncode == 137, r1.stderr[-2000:]
        # torn write on the newest step: truncate its payload files
        latest = ckpt / "10"
        assert latest.is_dir(), sorted(os.listdir(ckpt))
        clipped = 0
        for dirpath, _dirs, files in os.walk(latest):
            for name in files:
                p = os.path.join(dirpath, name)
                size = os.path.getsize(p)
                if size > 16:
                    with open(p, "r+b") as f:
                        f.truncate(size // 2)
                    clipped += 1
        assert clipped, "nothing to corrupt under the step dir"
        r2 = _run(ckpt, 30, -1)
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert "RESTORED 5" in r2.stdout, r2.stdout[-2000:]
        assert "DONE 30" in r2.stdout

    def test_uninterrupted_run_equivalence(self, tmp_path):
        """Crash+resume reaches the same state as an uninterrupted run
        because restore is exact and data replay is deterministic."""
        r_plain = _run(tmp_path / "a", 20, -1)
        # crash exactly on a checkpoint step => zero lost work
        _run(tmp_path / "b", 20, 10)
        r_resumed = _run(tmp_path / "b", 20, -1)
        assert r_plain.returncode == 0 and r_resumed.returncode == 0
        loss_plain = r_plain.stdout.strip().split()[-1]
        loss_resumed = r_resumed.stdout.strip().split()[-1]
        # both ran the same data; after restore-from-10 the remaining 10
        # steps replay the same batches -> identical final loss
        assert loss_plain == loss_resumed, (r_plain.stdout, r_resumed.stdout)


class TestDistributedInit:
    def test_single_process_summary(self):
        info = initialize_distributed()
        assert info["process_index"] == 0
        assert info["process_count"] >= 1
        assert info["global_devices"] >= 1
