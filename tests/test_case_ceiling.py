"""The ceiling every case runs under (`tests/conftest.py`) and the check
of what a session leaves behind, held to what they promise."""

import os
import signal
import subprocess
import sys
import time

import pytest

from tests import conftest


def test_a_case_over_its_ceiling_fails_with_the_frame_it_waited_in():
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as e:
        with conftest.case_ceiling(0.2):
            time.sleep(30)
    assert time.monotonic() - t0 < 5
    text = str(e.value)
    assert "ceiling of 0.2 s" in text
    # faulthandler names the function and the line that slept.
    assert "in test_a_case_over_its_ceiling_fails_with_the_frame" in text
    assert "most recent call first" in text


def test_a_ceiling_inside_another_hands_it_back():
    """Every case runs under the autouse ceiling; one nested in it (as in
    the case above) must leave that one armed, with its handler."""
    armed = signal.getsignal(signal.SIGALRM)
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 0.5 * conftest.CASE_CEILING_S
    with conftest.case_ceiling(0.2):
        assert signal.getsignal(signal.SIGALRM) is not armed
    assert signal.getsignal(signal.SIGALRM) is armed
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 0.5 * conftest.CASE_CEILING_S
    time.sleep(0.3)  # the inner alarm is gone: nothing fires


def test_the_session_check_finds_a_child_that_is_alive():
    child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    try:
        found = dict(conftest._children_of(os.getpid()))
        assert "time.sleep(60)" in found[child.pid]
    finally:
        child.kill()
        child.wait()
    assert child.pid not in dict(conftest._children_of(os.getpid()))
