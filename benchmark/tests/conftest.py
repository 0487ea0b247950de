"""The benchmark's own tests run on the CPU, on eight virtual devices (the
weight check is tried under two shardings of one mesh); set before JAX
starts.  The rehearsals of `test_harness.py` are processes of their own
and drop the flag."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
