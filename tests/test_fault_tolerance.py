"""Fault-tolerance paths: SIGTERM checkpoint-and-exit, elastic restore
across mesh shapes, straggler watchdog plumbing."""

import os
import signal
import subprocess
import sys
import time

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    return env


def test_sigterm_checkpoints_and_exits(tmp_path):
    """A pre-empted trainer (SIGTERM) must write a checkpoint and exit 0,
    and a restarted trainer must resume from it."""
    ck = str(tmp_path / "ck")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.train", "--arch", "glm4-9b",
         "--steps", "400", "--batch", "2", "--seq-len", "32",
         "--ckpt-dir", ck, "--ckpt-every", "1000", "--log-every", "1"],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    # wait until training is underway, then pre-empt
    t0 = time.time()
    started = False
    lines = []
    while time.time() - t0 < 240:
        line = proc.stdout.readline()
        lines.append(line)
        if "step=3" in line:
            started = True
            break
    assert started, "".join(lines[-20:])
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=240)
    assert proc.returncode == 0, out[-2000:]
    assert "SIGTERM received; checkpointed" in out
    steps = [d for d in os.listdir(ck) if d.startswith("step_")]
    assert steps, "no checkpoint written on SIGTERM"

    # resume must pick the checkpoint up
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "glm4-9b",
         "--steps", "8", "--batch", "2", "--seq-len", "32",
         "--ckpt-dir", ck, "--resume", "auto", "--log-every", "1"],
        env=_env(), capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-1000:]
    assert "resuming from step" in r.stdout


def test_elastic_restore_across_meshes(tmp_path):
    """A checkpoint written from a sharded 8-device run restores bit-exact
    onto a DIFFERENT mesh (elasticity after losing/gaining hardware)."""
    script = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.models import steps as S
from repro.optim import AdamWConfig
from repro.checkpoint import save_checkpoint, restore_checkpoint

ck = sys.argv[1]
cfg = configs.get_smoke("glm4-9b")
opt = AdamWConfig()
state = S.init_train_state(cfg, jax.random.PRNGKey(0), opt)
mesh_a = jax.make_mesh((4, 2), ("data", "model"))
specs = S.state_specs(cfg, jax.eval_shape(lambda: state))
sh_a = jax.tree.map(lambda sp: NamedSharding(mesh_a, sp), specs,
                    is_leaf=lambda x: isinstance(x, P))
state_a = jax.device_put(state, sh_a)
save_checkpoint(ck, 1, state_a)

mesh_b = jax.make_mesh((2, 4), ("data", "model"))
sh_b = jax.tree.map(lambda sp: NamedSharding(mesh_b, sp), specs,
                    is_leaf=lambda x: isinstance(x, P))
restored = restore_checkpoint(ck, 1, jax.eval_shape(lambda: state), sh_b)
for a, b in zip(jax.tree.leaves(state_a), jax.tree.leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("ELASTIC_OK")
"""
    r = subprocess.run([sys.executable, "-c", script,
                        str(tmp_path / "ck")],
                       env=_env(), capture_output=True, text=True,
                       timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ELASTIC_OK" in r.stdout
