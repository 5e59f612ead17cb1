import os
import subprocess
import sys

import numpy as np
import pytest

# NOTE: no XLA_FLAGS here — tests must see the real (single) device.
# Multi-device behaviour is tested via run_subprocess(..., devices=N).

# Planner dispatch assertions must exercise the heuristics, not whatever
# autotune winners a previous run persisted on this host. Tests that cover
# persistence point this at a tmp path explicitly.
os.environ.setdefault("REPRO_AUTOTUNE_CACHE", "off")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_subprocess(code: str, *, devices: int = 8, timeout: int = 600):
    """Run python code in a fresh interpreter with N fake CPU devices.
    The child is held to the CPU: it must never reach for a chip that the
    test process (or another) may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr}")
    return proc.stdout


@pytest.fixture(scope="session")
def small_study():
    """(dm, grouping, inv_gs, mat2) for a 48-sample 3-group study."""
    import jax.numpy as jnp
    from repro.core import distance, permutations
    from repro.data.microbiome import synthetic_study

    x, grouping = synthetic_study(48, 32, 3, effect_size=0.0, seed=7)
    dm = np.asarray(distance.braycurtis(jnp.asarray(x)))
    inv_gs = np.asarray(permutations.inv_group_sizes(jnp.asarray(grouping), 3))
    return dm, grouping, inv_gs, (dm * dm).astype(np.float32)
