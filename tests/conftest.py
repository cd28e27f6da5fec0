"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Must run before any backend resolution so multi-chip sharding paths can be
exercised without TPU hardware (XLA_FLAGS is read once, when the CPU
client is created). The one file that compiles for a chip,
tests/solver/test_tpu_compile.py, describes a v5e topology inside its own
fixture; nothing here touches the TPU library.
"""

import os

from kube_batch_tpu.utils.backend import force_cpu_devices

if not force_cpu_devices(8):
    raise RuntimeError(
        "tests need an 8-device virtual CPU mesh, but a jax backend with "
        "fewer devices was already initialized before conftest ran"
    )

# Pin allocate_tpu to the JAX kernel: on a CPU host with a toolchain the
# action would otherwise auto-route to native/greedy.cpp, and the
# accelerator path — the product's main solve path — would lose all its
# action/e2e coverage. Native-route tests override per-test via
# monkeypatch.setenv.
os.environ.setdefault("KBT_SOLVER", "jax")
