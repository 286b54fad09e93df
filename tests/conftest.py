"""Test configuration. By default the suite runs on the CPU, stated
explicitly (`JAX_PLATFORMS=cpu`), over an 8-device virtual mesh so the
sharding paths are exercised without hardware.

`NOMAD_TPU_TEST_PLATFORM=tpu` runs it on the attached chip instead —
for the device-facing files, in ONE process (`-p no:xdist`: every xdist
worker would want the chip)."""
import os
import sys

TEST_PLATFORM = os.environ.get("NOMAD_TPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = TEST_PLATFORM
if TEST_PLATFORM == "cpu":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Debug hook: `kill -USR2 <pytest pid>` dumps every thread's stack to
# stderr without killing the run — for diagnosing in-process hangs.
import faulthandler  # noqa: E402
import signal  # noqa: E402

faulthandler.register(signal.SIGUSR2, all_threads=True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: longer integration/soak tests")
    if TEST_PLATFORM != "cpu":
        # take the chip once, loudly, before any test: a run that asked
        # for the device and got the CPU must not pass (lib/backend.py)
        from nomad_tpu.lib import backend

        backend.resolve()
