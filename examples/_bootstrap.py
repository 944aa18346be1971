"""Shared example bootstrap: under `JAX_PLATFORMS=cpu` the mesh examples
self-provision their virtual CPU devices. Call before jax is imported
(the flag is read when the backend starts). On a TPU host it does
nothing: the examples run on the chips that are there."""
import os


def virtual_cpu_devices(n):
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        ).strip()
