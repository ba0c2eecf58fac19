"""The JAX host-CPU platform with N virtual devices, for test/dev rigs.

``JAX_PLATFORMS=cpu`` in the environment selects the platform; what it
cannot give is the device count, which XLA reads from ``XLA_FLAGS`` when
the CPU backend first initializes.  This is the one copy of that recipe.

Import this module (or the package) freely before calling: importing jax
does not initialize a backend; only device queries/computation do.
"""

from __future__ import annotations

import os


def force_cpu_platform(n_devices: int = 1) -> None:
    """Pin jax to ``n_devices`` virtual host-CPU devices, in this process
    and in the children that inherit its environment.

    Must run before anything touches a JAX backend (``jax.devices()``,
    any computation); afterwards neither setting has an effect.
    """
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    # jax reads JAX_PLATFORMS at import, which may already have happened.
    jax.config.update("jax_platforms", "cpu")
