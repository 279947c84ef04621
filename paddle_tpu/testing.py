"""Virtual-device helpers for tests and dry-runs.

Mirrors the reference's fake-device testing pattern (SURVEY.md §4: the
custom_cpu plugin masquerading as a device, test/custom_runtime/): here the
fake devices are XLA host-platform devices, so multi-chip sharding code
paths (pjit/shard_map/collectives) execute for real without TPU hardware.
"""
import os
import re


def force_host_cpu_devices(n: int) -> None:
    """Force JAX onto ``n`` virtual CPU devices, pre-backend-init.

    Process-global and irreversible by design: callers are dedicated test /
    dry-run processes, never a process that later needs the real chip.

    The platform is forced through jax.config (not only the environment)
    so the call also works when jax was imported earlier in the process;
    it must still precede the (lazy) backend initialisation, where
    XLA_FLAGS is read.
    """
    xla_flags = os.environ.get("XLA_FLAGS", "")
    xla_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       xla_flags)
    os.environ["XLA_FLAGS"] = (
        xla_flags + f" --xla_force_host_platform_device_count={n}").strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    backend, ndev = jax.default_backend(), len(jax.devices())
    if backend != "cpu" or ndev != n:
        raise RuntimeError(
            f"could not force {n} virtual CPU devices (got backend="
            f"{backend!r}, {ndev} devices) — a JAX backend was already "
            "initialised in this process; call force_host_cpu_devices() "
            "before any jax operation")
