"""Fault tolerance: checkpoint atomicity/roundtrip, crash-restart resume
equivalence, elastic re-mesh, heartbeat stall detection, gradient
compression convergence parity."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat, configs
from repro.models.model import build_model
from repro.training import optimizer as opt
from repro.training.checkpoint import CheckpointManager
from repro.training.data import DataConfig, synthetic_batch
from repro.training.fault_tolerance import (
    HeartbeatMonitor,
    reshard_state,
    resume_or_init,
)
from repro.training.train_step import TrainConfig, init_state, train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(arch="qwen1_5_0_5b", lr=1e-3, compression="none"):
    cfg = configs.get_reduced(arch)
    model = build_model(cfg)
    tcfg = TrainConfig(
        adamw=opt.AdamWConfig(learning_rate=lr, warmup_steps=0, total_steps=100),
        remat=False,
        grad_compression=compression,
    )
    dcfg = DataConfig(seed=3, batch=2, seq=32)
    return cfg, model, tcfg, dcfg


def _run_steps(model, tcfg, dcfg, cfg, state, start, end):
    step_fn = jax.jit(lambda s, b: train_step(s, b, model, tcfg))
    losses = []
    for step in range(start, end):
        state, m = step_fn(state, synthetic_batch(cfg, dcfg, step))
        losses.append(float(m["loss"]))
    return state, losses


def test_checkpoint_roundtrip(tmp_path):
    cfg, model, tcfg, dcfg = _setup()
    state = init_state(model, jax.random.PRNGKey(0), tcfg)
    ckpt = CheckpointManager(str(tmp_path), async_write=False)
    ckpt.save(7, state, {"note": "x"})
    assert ckpt.latest_step() == 7
    step, restored, extra = ckpt.restore(state)
    assert step == 7 and extra["note"] == "x"
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_crash_restart_bitwise_equivalent(tmp_path):
    """Train 6 steps straight vs train 3 + crash + resume + 3: identical."""
    cfg, model, tcfg, dcfg = _setup()

    state_a = init_state(model, jax.random.PRNGKey(0), tcfg)
    state_a, losses_a = _run_steps(model, tcfg, dcfg, cfg, state_a, 0, 6)

    ckpt = CheckpointManager(str(tmp_path), async_write=False)
    state_b = init_state(model, jax.random.PRNGKey(0), tcfg)
    state_b, _ = _run_steps(model, tcfg, dcfg, cfg, state_b, 0, 3)
    ckpt.save(3, state_b)
    del state_b  # "crash"

    start, state_c, resumed = resume_or_init(
        ckpt, lambda: init_state(model, jax.random.PRNGKey(0), tcfg)
    )
    assert resumed and start == 3
    state_c, losses_c = _run_steps(model, tcfg, dcfg, cfg, state_c, 3, 6)

    for a, b in zip(jax.tree.leaves(state_a.params), jax.tree.leaves(state_c.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert losses_a[3:] == pytest.approx(losses_c, abs=1e-5)


def test_checkpoint_atomic_under_partial_write(tmp_path):
    """A leftover .tmp dir (simulated mid-write crash) must not be visible
    as a checkpoint, and a subsequent save must succeed."""
    cfg, model, tcfg, dcfg = _setup()
    state = init_state(model, jax.random.PRNGKey(0), tcfg)
    ckpt = CheckpointManager(str(tmp_path), async_write=False)
    os.makedirs(tmp_path / ".tmp-5")
    (tmp_path / ".tmp-5" / "arrays.npz").write_bytes(b"garbage")
    assert ckpt.latest_step() is None
    ckpt.save(5, state)
    assert ckpt.latest_step() == 5
    _, restored, _ = ckpt.restore(state)


def test_async_checkpoint_writer(tmp_path):
    cfg, model, tcfg, dcfg = _setup()
    state = init_state(model, jax.random.PRNGKey(0), tcfg)
    ckpt = CheckpointManager(str(tmp_path), async_write=True)
    ckpt.save(1, state)
    ckpt.save(2, state)
    ckpt.wait()
    assert ckpt.latest_step() == 2


def test_retention_gc(tmp_path):
    cfg, model, tcfg, dcfg = _setup()
    state = init_state(model, jax.random.PRNGKey(0), tcfg)
    ckpt = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    for s in (1, 2, 3, 4):
        ckpt.save(s, state)
    steps = sorted(
        int(d.split("-")[1]) for d in os.listdir(tmp_path) if d.startswith("step-")
    )
    assert steps == [3, 4]


@pytest.mark.slow
def test_elastic_remesh_subprocess():
    """Save under a (2,4) mesh, restore under (4,2) and single-device;
    forward results identical. Runs with 8 fake devices in a subprocess."""
    code = r"""
import jax, json
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import compat, configs
from repro.models.model import build_model
from repro.launch.sharding import params_shardings
from repro.training.fault_tolerance import reshard_state

cfg = configs.get_reduced("qwen1_5_0_5b")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
batch = {"tokens": jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) % cfg.vocab_size}
want, _ = model.forward(params, batch)

m1 = compat.make_mesh((2, 4), ("data", "model"))
s1 = params_shardings(jax.eval_shape(lambda: params), cfg, m1)
p1 = reshard_state(params, s1)
got1, _ = jax.jit(lambda p, b: model.forward(p, b))(p1, batch)

m2 = compat.make_mesh((4, 2), ("data", "model"))
s2 = params_shardings(jax.eval_shape(lambda: params), cfg, m2)
p2 = reshard_state(p1, s2)  # re-mesh from the *sharded* state
got2, _ = jax.jit(lambda p, b: model.forward(p, b))(p2, batch)

print(json.dumps({
  "m1_ok": bool(np.allclose(np.asarray(want), np.asarray(got1), atol=1e-5)),
  "m2_ok": bool(np.allclose(np.asarray(want), np.asarray(got2), atol=1e-5)),
}))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["m1_ok"] and res["m2_ok"], res


def test_heartbeat_detects_stall():
    stalls = []
    mon = HeartbeatMonitor(timeout_s=0.3, on_stall=stalls.append)
    mon.beat(1)
    time.sleep(0.8)
    assert mon.stalled and stalls == [1]
    mon.stop()


def test_heartbeat_no_false_positive():
    mon = HeartbeatMonitor(timeout_s=0.5)
    for i in range(5):
        mon.beat(i)
        time.sleep(0.1)
    assert not mon.stalled
    mon.stop()


def test_grad_compression_converges_like_uncompressed():
    """int8 + error feedback must track the uncompressed loss curve."""
    cfg, model, tcfg_plain, dcfg = _setup(lr=3e-3)
    _, _, tcfg_int8, _ = _setup(lr=3e-3, compression="int8")

    s0 = init_state(model, jax.random.PRNGKey(0), tcfg_plain)
    s1 = init_state(model, jax.random.PRNGKey(0), tcfg_int8)
    _, plain = _run_steps(model, tcfg_plain, dcfg, cfg, s0, 0, 12)
    _, comp = _run_steps(model, tcfg_int8, dcfg, cfg, s1, 0, 12)

    # both must make progress and end within 5% of each other
    assert plain[-1] < plain[0]
    assert comp[-1] < comp[0]
    assert abs(plain[-1] - comp[-1]) / plain[-1] < 0.05, (plain[-1], comp[-1])


def test_train_driver_restart_cli(tmp_path):
    """End-to-end: the launch/train.py driver resumes from its checkpoint
    after an injected crash."""
    from repro.launch.train import run

    ckpt_dir = str(tmp_path / "ck")
    args = [
        "--arch", "qwen1_5_0_5b", "--reduced", "--steps", "8", "--batch", "2",
        "--seq", "16", "--ckpt-dir", ckpt_dir, "--ckpt-every", "2",
        "--log-every", "2",
    ]
    with pytest.raises(RuntimeError, match="injected failure"):
        run(args + ["--fail-at-step", "5"])
    losses = run(args)  # resumes from step 5's checkpoint (saved at 4+1... latest)
    assert losses, "resumed run produced no losses"
