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
import hashlib  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402

faulthandler.register(signal.SIGUSR2, all_threads=True)

import pytest  # noqa: E402

#: One test (set-up, call and teardown) may take this long: nine times
#: the slowest phase of any test here (20 s), and six stuck tests in a
#: row on one worker still end inside the driver's 1470 s. Every wait
#: inside a test is shorter, so that it fails by its own assertion first.
TEST_LIMIT_S = 180.0
_stderr = sys.__stderr__  # pytest_configure: the one that is not captured


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item):
    """A stuck test fails alone: at the limit every thread's stack goes
    to stderr and the test fails from the main thread (the worker lives,
    teardown runs, the run goes on). A hang in C never comes back to the
    interpreter for that: 60 s later the watchdog thread dumps the stacks
    and ends the process (xdist: `node down`, a new worker takes over).
    xdist's loadfile scheduler hands the dead worker's file out again
    with the case that ended it, so a worker notes the case it runs in a
    file that outlives it, and a case found noted fails without running."""
    def on_alarm(_signum, _frame):
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        pytest.fail(f"{item.nodeid} exceeded the per-test limit of "
                    f"{limit:g} s (tests/conftest.py); every thread's "
                    "stack is on stderr", pytrace=False)

    note = None
    run = os.environ.get("PYTEST_XDIST_TESTRUNUID")  # one per xdist run
    if run is not None:
        note = os.path.join(
            tempfile.gettempdir(), "nomad-tier1-%s-%s" % (
                run, hashlib.sha1(item.nodeid.encode()).hexdigest()[:16]))
        if os.path.exists(note):
            item.setup = lambda: pytest.fail(
                f"{item.nodeid} ended the worker that ran it (see `node "
                "down` and the stacks above it); not run again",
                pytrace=False)
        else:
            open(note, "w").close()
    # the soak tests are the ones meant to take longer
    limit = TEST_LIMIT_S * (10 if item.get_closest_marker("slow") else 1)
    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(limit + 60.0, exit=True,
                                      file=_stderr)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)
        if note is not None and os.path.exists(note):
            os.unlink(note)


def pytest_configure(config):
    global _stderr
    _stderr = os.fdopen(os.dup(2), "w")  # capture is not on yet
    config.addinivalue_line(
        "markers", "slow: longer integration/soak tests")
    if TEST_PLATFORM != "cpu":
        # take the chip once, loudly, before any test: a run that asked
        # for the device and got the CPU must not pass (lib/backend.py)
        from nomad_tpu.lib import backend

        backend.resolve()
