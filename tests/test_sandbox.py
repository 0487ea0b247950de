"""Sandbox fences for code-reward grading (interfaces/sandbox.py).

Models the boundary the reference delegates to its FaaS sandbox
(realhf/functioncall/code/verify.py): runaway-resource programs must fail
grading without harming the trial process.
"""

import os
import subprocess
import sys
import tempfile
import time
import uuid

import pytest

import areal_tpu.interfaces.sandbox as sandbox
from areal_tpu.interfaces.reward import MultiTaskRewardInterface
from areal_tpu.interfaces.sandbox import _unshare_prefix, run_sandboxed


class TestRunSandboxed:
    def test_good_program_passes(self):
        rc, out = run_sandboxed(
            [sys.executable, "-c", "print(int(input()) * 2)"],
            input_text="21\n",
            timeout_s=10.0,
        )
        assert rc == 0
        assert out.strip() == "42"

    def test_wall_timeout_kills(self):
        rc, _ = run_sandboxed(
            [sys.executable, "-c", "while True: pass"], timeout_s=1.0
        )
        assert rc != 0

    def test_memory_bomb_killed(self):
        rc, _ = run_sandboxed(
            [sys.executable, "-c", "x = bytearray(1 << 31); print('no')"],
            timeout_s=10.0,
            mem_mb=256,
        )
        assert rc != 0

    def test_file_size_limited(self, tmp_path):
        rc, _ = run_sandboxed(
            [
                sys.executable, "-c",
                "open('big.bin','wb').write(b'x' * (8 << 20)); print('no')",
            ],
            timeout_s=10.0,
            cwd=str(tmp_path),
            fsize_mb=1,
        )
        assert rc != 0

    def test_cwd_is_the_jail(self, tmp_path):
        rc, out = run_sandboxed(
            [sys.executable, "-c",
             "import os; open('x','w').write('1'); print(os.getcwd())"],
            timeout_s=10.0,
            cwd=str(tmp_path),
        )
        assert rc == 0
        assert out.strip() == str(tmp_path)
        assert (tmp_path / "x").exists()

    @pytest.mark.skipif(
        not _unshare_prefix(), reason="no user+net namespace here"
    )
    def test_network_unreachable(self):
        rc, _ = run_sandboxed(
            [
                sys.executable, "-c",
                "import socket; s = socket.create_connection("
                "('127.0.0.1', 9), timeout=2); print('no')",
            ],
            timeout_s=10.0,
        )
        assert rc != 0


@pytest.fixture()
def fresh_probe():
    """Reset the cached `unshare -rn` probe so a test can exercise the
    probe itself, restoring the real result afterwards."""
    old = sandbox._UNSHARE
    sandbox._UNSHARE = None
    yield
    sandbox._UNSHARE = old


class TestUnshareProbe:
    """Hosts without user+net namespaces (locked-down kernels, nested
    containers) must degrade to rlimits + jail, not crash grading."""

    def test_no_unshare_binary_falls_back(self, fresh_probe, monkeypatch):
        monkeypatch.setattr(sandbox.shutil, "which", lambda _: None)
        assert _unshare_prefix() == []
        # The sandbox still runs (rlimits + tmpdir jail, no namespace).
        rc, out = run_sandboxed(
            [sys.executable, "-c", "print('ok')"], timeout_s=10.0
        )
        assert rc == 0 and out.strip() == "ok"

    def test_probe_failure_falls_back(self, fresh_probe, monkeypatch):
        """`unshare` exists but the kernel refuses -rn (EPERM under
        seccomp/userns restrictions): probe caches the empty prefix."""
        monkeypatch.setattr(
            sandbox.shutil, "which", lambda _: "/usr/bin/unshare"
        )

        def deny(argv, **kw):
            return subprocess.CompletedProcess(argv, returncode=1)

        monkeypatch.setattr(sandbox.subprocess, "run", deny)
        assert _unshare_prefix() == []

    def test_probe_exception_falls_back(self, fresh_probe, monkeypatch):
        monkeypatch.setattr(
            sandbox.shutil, "which", lambda _: "/usr/bin/unshare"
        )

        def boom(argv, **kw):
            raise subprocess.TimeoutExpired(argv, 5)

        monkeypatch.setattr(sandbox.subprocess, "run", boom)
        assert _unshare_prefix() == []

    def test_probe_success_cached(self, fresh_probe, monkeypatch):
        monkeypatch.setattr(
            sandbox.shutil, "which", lambda _: "/bin/unshare"
        )
        calls = []

        def allow(argv, **kw):
            calls.append(argv)
            return subprocess.CompletedProcess(argv, returncode=0)

        monkeypatch.setattr(sandbox.subprocess, "run", allow)
        assert _unshare_prefix() == ["/bin/unshare", "-rn"]
        assert _unshare_prefix() == ["/bin/unshare", "-rn"]
        assert len(calls) == 1  # probed once, cached after


def _procs_with_marker(marker: str):
    """PIDs whose cmdline carries the marker (the graded program and any
    children it forked — fork preserves cmdline)."""
    found = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                if marker.encode() in f.read():
                    found.append(pid)
        except OSError:
            pass  # raced with process exit
    return found


@pytest.fixture()
def fork_bomb():
    """A bounded fork bomb: children park in sleep so any survivor is
    visible in /proc by its marker.  Teardown asserts the sandbox left
    no process behind — the rlimit (`ulimit -u`) caps the spawn and the
    session kill reaps whatever did spawn."""
    marker = f"AREAL_FORKBOMB_{uuid.uuid4().hex}"
    prog = (
        f"# {marker}\n"
        "import os, time\n"
        "for _ in range(64):\n"
        "    try:\n"
        "        pid = os.fork()\n"
        "    except OSError:\n"
        "        break\n"
        "    if pid == 0:\n"
        "        time.sleep(300)\n"
        "        os._exit(0)\n"
        "time.sleep(300)\n"
    )
    yield prog, marker
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and _procs_with_marker(marker):
        time.sleep(0.2)
    assert not _procs_with_marker(marker), "fork bomb outlived the sandbox"


class TestForkBomb:
    def test_fork_bomb_contained(self, fork_bomb):
        prog, _ = fork_bomb
        rc, _ = run_sandboxed(
            [sys.executable, "-c", prog], timeout_s=2.0, nproc=64
        )
        # EAGAIN'd out (rlimit) or wall-killed with its whole session
        # (killpg) — either way it grades as a failure...
        assert rc != 0
        # ...and the fixture teardown asserts nothing survived.


class TestCodeRewardUsesSandbox:
    def _grade(self, code_body: str) -> bool:
        iface = MultiTaskRewardInterface(code_timeout_s=6.0)
        return iface._verify_code(
            f"```python\n{code_body}\n```",
            {"input_output": {"inputs": ["3\n"], "outputs": ["9"]}},
        )

    def test_correct_solution(self):
        assert self._grade("print(int(input()) ** 2)") is True

    def test_wrong_output(self):
        assert self._grade("print(int(input()) + 1)") is False

    def test_hanging_solution_times_out(self):
        assert self._grade("while True: pass") is False

    def test_jail_cleaned_up(self, tmp_path, monkeypatch):
        before = set(os.listdir(tmp_path.parent))
        # The jail of THIS grade: `/tmp` at large also holds the jails of
        # grades in flight in other workers, and that of a run that was
        # killed in one (`/tmp/areal_grade_nrzlpuse`, the driver's run of
        # PR 61, failed this case in every run after it).
        jails, make = [], tempfile.TemporaryDirectory

        def watched(**kw):
            jails.append(make(**kw))
            return jails[-1]

        monkeypatch.setattr(tempfile, "TemporaryDirectory", watched)
        self._grade("open('leftover','w').write('x'); print(9)")
        # The jail tmpdir (and anything the program wrote) is gone.
        (jail,) = jails
        assert os.path.basename(jail.name).startswith("areal_grade_")
        assert not os.path.exists(jail.name)
        assert set(os.listdir(tmp_path.parent)) == before
