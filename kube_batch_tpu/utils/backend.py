"""JAX process set-up shared by the entry points.

- ``force_cpu_devices`` puts a process on an n-device virtual CPU mesh
  (tests, ``sim --host-devices``). It must run before the first backend
  resolution: XLA_FLAGS is read once, when the CPU client is created.
- ``enable_compile_cache`` points JAX's persistent compilation cache at
  a fixed path, so a cold process reuses the solver compiles of the last
  one. Process entry points call it; importing a module never does.
"""

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# <checkout>/.jax_cache: a fixed path (the path is part of what the cache
# is keyed by, so it is never built from a temp name, pid or time).
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def set_host_device_count(n, env=None):
    """Ensure XLA_FLAGS in ``env`` (default os.environ) requests at least
    ``n`` virtual host devices, replacing a smaller existing value."""
    env = os.environ if env is None else env
    flags = env.get("XLA_FLAGS", "")
    match = re.search(_COUNT_FLAG + r"=(\d+)", flags)
    if match is None:
        flags = (flags + f" {_COUNT_FLAG}={n}").strip()
    elif int(match.group(1)) < n:
        flags = flags[:match.start(1)] + str(n) + flags[match.end(1):]
    env["XLA_FLAGS"] = flags


def force_cpu_devices(n):
    """Put jax on >=n virtual CPU devices. Returns False when this process
    already created a CPU client with fewer devices."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    set_host_device_count(n)
    import jax

    jax.config.update("jax_platforms", "cpu")
    return len(jax.devices("cpu")) >= n


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for this process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    into its config and nothing else is set here. Otherwise the cache
    lives at the fixed in-checkout ``.jax_cache`` (gitignored). Returns
    the directory in use."""
    import jax

    chosen = jax.config.jax_compilation_cache_dir
    if chosen:
        return chosen
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
