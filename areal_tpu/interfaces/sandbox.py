"""Sandboxed execution of untrusted reward-verification programs.

Capability parity: the reference offloads code grading to a remote FaaS
sandbox (realhf/functioncall/code/verify.py); its local fallback
(local_verify) is a bare subprocess.  Here the LOCAL path itself is
fenced, since TPU trials routinely grade model-written code in-process:

- rlimits (a `sh -c 'ulimit ...'` wrapper — no preexec_fn, which is
  fork-unsafe in threaded hosts): CPU seconds, address
  space, file size, process/thread count, open files, core dumps off;
- a throwaway tmpdir jail as cwd (the program file lives there; the dir
  is deleted after grading);
- minimal environment and a fresh session (process group) so timeout
  kills reach grandchildren;
- a user+network namespace (`unshare -rn`) when the kernel allows it,
  removing network access entirely — probed once and cached.

Trust model: this blocks the accident class (fork bombs, memory bombs,
giant files, stray network calls, clobbering the trial's cwd) but it is
NOT a container boundary — a kernel exploit or writes to world-writable
paths remain possible.  Grade genuinely hostile code only behind the
remote reward service on an isolated machine (interfaces/reward_service).
"""

import os
import shutil
import subprocess
from typing import List, Optional, Tuple

from areal_tpu.base import logging

logger = logging.getLogger("sandbox")

_UNSHARE: Optional[List[str]] = None


def _unshare_prefix() -> List[str]:
    """`unshare -rn` argv prefix when user+net namespaces work here."""
    global _UNSHARE
    if _UNSHARE is None:
        exe = shutil.which("unshare")
        ok = False
        if exe:
            try:
                ok = (
                    subprocess.run(
                        [exe, "-rn", "true"], capture_output=True, timeout=5
                    ).returncode
                    == 0
                )
            except Exception:
                ok = False
        _UNSHARE = [exe, "-rn"] if ok else []
        if not _UNSHARE:
            logger.warning(
                "unshare -rn unavailable: sandboxed code keeps network "
                "access (rlimits + tmpdir jail still apply)"
            )
    return _UNSHARE


def _ulimit_wrapper(
    cpu_s: int, mem_mb: int, fsize_mb: int, nproc: Optional[int]
) -> List[str]:
    """Apply rlimits via a `sh -c 'ulimit ...; exec "$@"'` wrapper rather
    than preexec_fn: running Python between fork and exec is documented
    deadlock-prone in multithreaded processes, and reward grading runs
    inside model workers full of ZMQ/JAX threads — a child stuck in
    _set_limits would burn the whole timeout and grade a correct solution
    as wrong.  The shell applies limits post-exec (posix_spawn-safe).

    NPROC is a PER-UID limit (threads included): the cap must sit above
    the trial user's existing task count — a busy JAX host easily holds
    hundreds — or legitimate solutions that fork/thread fail with EAGAIN
    and grade as wrong.  The default (4096) only stops runaway fork
    bombs; nproc=None skips it.  `ulimit -v` is in KiB, `-f` in 512-byte
    blocks, `-t` in seconds."""
    # Mandatory limits are &&-joined: if one fails to apply, the graded
    # program must NOT run unlimited (fail closed, like the setrlimit
    # error the old preexec_fn surfaced) — the run grades False via the
    # nonzero shell exit.
    parts = [
        f"ulimit -t {cpu_s + 1}",
        f"ulimit -v {mem_mb << 10}",
        f"ulimit -f {(fsize_mb << 20) // 512}",
        "ulimit -n 256",
        "ulimit -c 0",
    ]
    script = " && ".join(parts)
    if nproc is not None:
        # Not all shells implement -u; failing to tighten this optional
        # fork-bomb cap must not fail the grading run.
        script += f" && {{ ulimit -u {nproc} 2>/dev/null || true; }}"
    script += ' && exec "$@"'
    return ["sh", "-c", script, "sh"]


def run_sandboxed(
    argv: List[str],
    input_text: str = "",
    timeout_s: float = 8.0,
    cwd: Optional[str] = None,
    mem_mb: int = 1024,
    fsize_mb: int = 32,
    nproc: Optional[int] = 4096,
) -> Tuple[int, str]:
    """Run `argv` jailed; returns (returncode, stdout).  Timeouts and
    resource kills surface as nonzero returncodes (-1 for wall timeout)."""
    proc = subprocess.Popen(
        _unshare_prefix()
        + _ulimit_wrapper(max(1, int(timeout_s)), mem_mb, fsize_mb, nproc)
        + argv,
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        cwd=cwd,
        env={"PATH": "/usr/bin:/bin", "HOME": cwd or "/tmp"},
        start_new_session=True,
    )
    try:
        stdout, _ = proc.communicate(input=input_text, timeout=timeout_s)
        return proc.returncode, stdout
    except subprocess.TimeoutExpired:
        # Kill the whole session, not just the child: a graded program's
        # own subprocesses must not outlive the timeout.
        try:
            os.killpg(proc.pid, 9)
        except ProcessLookupError:
            pass
        proc.wait()
        return -1, ""
