"""The ceiling every case runs under (`tests/conftest.py`) and the check
of what a session leaves behind, held to what they promise."""

import contextlib
import os
import signal
import subprocess
import sys
import time
import types

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


def _report(nodeid, when, duration):
    return types.SimpleNamespace(nodeid=nodeid, when=when, duration=duration)


def test_the_run_table_names_a_file_whose_cases_are_not_all_in():
    table = conftest.RunTable()
    ids = ([f"tests/test_a.py::test_{i}" for i in range(3)]
           + ["tests/test_b.py::test_x[1]", "tests/test_b.py::test_x[2]"])
    for worker in range(2):  # every worker collects the same cases
        table.pytest_xdist_node_collection_finished(worker, ids)
    for nodeid in ids[:4]:
        for when, s in (("setup", 1.0), ("call", 5.0), ("teardown", 0.5)):
            table.pytest_runtest_logreport(_report(nodeid, when, s))
    table.pytest_runtest_logreport(_report(ids[4], "setup", 70.0))
    lines = table.lines()
    assert "4 of 5 cases in, 96 case-seconds" in lines[0]
    at = lines.index("files not yet finished (cases in / collected):")
    assert lines[at + 1].split() == ["test_b", "1/2"]  # and not test_a
    assert lines[at + 3].split()[:2] == ["test_b::test_x[2]", "70"]
    assert lines[-1].split() == ["test_b", "76/1", "test_a", "20/3"]
    # However many files and cases: a screen, not a scroll.
    many = [f"tests/test_{i:03}.py::test_{'y' * 60}[{j}]"
            for i in range(300) for j in range(2)]
    table = conftest.RunTable()
    table.pytest_xdist_node_collection_finished(0, many)
    for nodeid in many[::2]:
        table.pytest_runtest_logreport(_report(nodeid, "teardown", 1.0))
    assert len(table.lines()) <= table.LINES
    assert max(map(len, table.lines())) <= table.WIDTH + 2


def test_a_run_that_is_cut_says_where_it_stood_and_dies_of_the_signal(tmp_path):
    """A child `pytest` of two cases under xdist, the second still asleep
    when SIGTERM comes (as `timeout` sends it to the driver's run): the
    table is on its stderr, and the signal is what it died of."""
    (tmp_path / "test_cut.py").write_text(
        "import pathlib, time\n"
        "def test_quick():\n    pass\n"
        "def test_asleep():\n"
        f"    pathlib.Path({str(tmp_path / 'asleep')!r}).touch()\n"
        "    time.sleep(120)\n")
    with open(tmp_path / "err", "w+") as err:
        child = subprocess.Popen(
            [sys.executable, "-m", "pytest", str(tmp_path / "test_cut.py"),
             "-q", "-p", "tests.conftest", "-p", "no:cacheprovider",
             "-p", "xdist", "-n", "1", "--dist", "loadfile",
             "--rootdir", str(tmp_path)],
            cwd=os.path.dirname(os.path.dirname(conftest.__file__)),
            stdout=err, stderr=err, start_new_session=True)
        try:
            deadline = time.monotonic() + 120
            while not (tmp_path / "asleep").exists():
                assert child.poll() is None and time.monotonic() < deadline
                time.sleep(0.1)
            time.sleep(0.5)  # test_quick's teardown report is on its way
            child.send_signal(signal.SIGTERM)
            assert child.wait(timeout=30) == -signal.SIGTERM
        finally:
            with contextlib.suppress(ProcessLookupError):
                os.killpg(child.pid, signal.SIGKILL)  # the worker, orphaned
            child.wait()
        err.seek(0)
        said = err.read()
    assert "1 of 2 cases in" in said, said[-2000:]
    assert "files not yet finished (cases in / collected):\n  test_cut 1/2" in said
