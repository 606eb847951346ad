"""chip_smoke.py's legs at tiny sizes on the 8 virtual CPU devices.

The script itself refuses to run off-chip (no switch); what tier-1 can hold
is that every leg function runs end to end — kernels in interpret mode, the
multi-device branches included — so a chip call is never spent on a typo.
"""

import jax
import pytest

import chip_smoke
from deeplearning4j_tpu.common.env import env

TINY_RESNET = dict(batch_per_device=2, height=32, width=32, num_classes=10,
                   n_batches=2, steady_steps=2)


@pytest.fixture
def force_pallas(monkeypatch):
    """Tiny shapes sit below the registry's perf thresholds; the repo's own
    FORCE_PALLAS side keeps the structural ``requires`` and picks the
    kernel, which then runs in interpret mode."""
    monkeypatch.setattr(env, "force_pallas", True)


def test_main_refuses_cpu():
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)


def test_device_memory_is_not_optional():
    # XLA:CPU reports no memory stats: an error, never an empty result
    with pytest.raises(TypeError):
        chip_smoke.device_memory()


def test_train_leg_data_parallel():
    assert len(jax.devices()) == 8
    out = chip_smoke.train_leg(**TINY_RESNET)
    assert out["global_batch"] == 16 and out["batch_shards"] == 8
    assert out["train_step_programs"] == 1 and len(out["losses"]) == 3


def test_train_leg_single_device(monkeypatch):
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    # the same global batch as the data-parallel case: BatchNorm over two
    # 1x1 feature maps diverges within the three steps
    out = chip_smoke.train_leg(**{**TINY_RESNET, "batch_per_device": 16})
    assert out["global_batch"] == 16 and "batch_shards" not in out
    assert out["train_step_programs"] == 1 and len(out["losses"]) == 3


def test_kernel_leg(force_pallas):
    out = chip_smoke.kernel_leg(
        flash_t=128, long_t=256, long_heads=1, rnn_batch=8, rnn_t=2,
        rnn_hidden=128, blocked_batch=16, blocked_hidden=128,
        lrn_shape=(2, 32, 32, 32))
    assert len(out) == 17
    assert all(v["max_rel_err"] <= v["tol"] for v in out.values())


def test_kernel_leg_fails_when_registry_picks_xla():
    # without FORCE_PALLAS the tiny shapes route to XLA: the leg must refuse
    with pytest.raises(AssertionError, match="registry picks 'xla'"):
        chip_smoke.kernel_leg(flash_t=128)


def test_charrnn_leg(force_pallas):
    out = chip_smoke.charrnn_leg(batch=8, steps=2, vocab_size=12, units=16,
                                 timesteps=8, layers=1)
    assert len(out["losses"]) == 2 and out["max_rel_err_vs_xla"] <= 5e-3


def test_serve_leg():
    out = chip_smoke.serve_leg(n_requests=4, slots=2, max_len=32,
                               max_prompt=6, max_new=6, vocab_size=11,
                               units=16)
    assert out["decode_programs"] == 1 and out["requests"] == 4


def test_ring_leg():
    out = chip_smoke.ring_leg(t_local=16, heads=1)
    assert out["seq_devices"] == 8 and out["T"] == 128
