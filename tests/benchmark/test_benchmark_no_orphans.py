"""Whatever ends a run, nothing the run started outlives it.

A CPU rehearsal is started as the driver starts a run (a process of its
own) and ended from outside — SIGTERM, SIGINT, SIGKILL — while it loads
the store and again inside its window.  Within 15 s of the signal no
process of the run is alive (its daemon, which leads a session of its
own; its writers; their resource tracker), the daemon's port refuses a
connection, the exit code is 128 + the signal's number (the signal
itself for SIGKILL, which nothing handles) and standard output holds no
result line.  A run that ends by itself still takes the daemon's
graceful path: `Server shut down` closes its log.  And stop() itself,
against a stand-in for a daemon that does not end on SIGTERM (as one
inside a compile of minutes does not): its whole group is killed.

What a process of the run is, is read from /proc: whatever descends from
the runner or sits in a session one of its processes led, recorded while
the run was alive, and whatever names the run's scratch directory."""

import os
import signal
import socket
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from index_checks import REPO  # noqa: E402

RUN = os.path.join(REPO, "benchmark", "run.py")
GONE_WITHIN_S = 15.0


def processes() -> dict[int, tuple[int, int, str]]:
    """Every live process: pid -> (parent, session, command line).  A
    zombie has ended; it waits for a parent to read its exit code."""
    table = {}
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open("/proc/%s/stat" % name) as fh:
                fields = fh.read().rpartition(")")[2].split()
            with open("/proc/%s/cmdline" % name, "rb") as fh:
                cmd = fh.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue        # ended while we read
        if fields[0] != "Z":
            table[int(name)] = (int(fields[1]), int(fields[3]), cmd)
    return table


def family(root: int, table: dict) -> dict[int, tuple[int, int, str]]:
    """`root` and whatever descends from it."""
    found = {root}
    while True:
        more = {pid for pid, (parent, _, _) in table.items()
                if parent in found} - found
        if not more:
            return {pid: table[pid] for pid in found if pid in table}
        found |= more


class Rehearsal:
    """One run of `heavy-replay-solo` at a rehearsal's size, in a
    session of its own, its output in files."""

    def __init__(self, tmp_path, seconds: int, seed: int):
        self.out = str(tmp_path / "out")
        self.stdout = str(tmp_path / "stdout.txt")
        self.stderr = str(tmp_path / "stderr.txt")
        with open(self.stdout, "wb") as out, open(self.stderr, "wb") as err:
            self.proc = subprocess.Popen(
                [sys.executable, RUN, "--out", self.out, "--workload",
                 "heavy-replay-solo", "--seed", str(seed), "--seconds",
                 str(seconds), "--trace", "0", "--rehearse",
                 "hosts=40,hours=2"],
                cwd=REPO, stdout=out, stderr=err, start_new_session=True)
        self.seen: dict[int, tuple[int, int, str]] = {}

    def text(self, path: str) -> str:
        with open(path) as fh:
            return fh.read()

    def look(self) -> dict:
        """The run's processes as they are now, remembered."""
        now = family(self.proc.pid, processes())
        self.seen.update(now)
        return now

    def wait_until(self, what: str, ready, limit: float = 240.0) -> None:
        give_up = time.monotonic() + limit
        while not ready():
            assert self.proc.poll() is None, (
                "the run ended (rc %s) before %s:\n%s" % (
                    self.proc.returncode, what, self.text(self.stderr)))
            assert time.monotonic() < give_up, "no %s within %.0f s" % (
                what, limit)
            time.sleep(0.1)

    def daemon_port(self) -> int:
        daemon = next(cmd for _, _, cmd in self.seen.values()
                      if os.path.join(self.out, "tsd.conf") in cmd)
        words = daemon.split()
        return int(words[words.index("--port") + 1])

    def left_behind(self) -> dict:
        """What is alive of the run: the processes seen while it ran,
        whatever sits in a session one of them led, whatever names its
        scratch directory."""
        sessions = {sid for _, sid, _ in self.seen.values()}
        return {pid: entry for pid, entry in processes().items()
                if pid != os.getpid() and (
                    pid in self.seen or entry[1] in sessions
                    or self.out in entry[2])}

    def assert_nothing_is_left(self, port: int) -> None:
        give_up = time.monotonic() + GONE_WITHIN_S
        while (left := self.left_behind()) and time.monotonic() < give_up:
            time.sleep(0.1)
        assert not left, "still alive %.0f s after the end: %s" % (
            GONE_WITHIN_S, left)
        with pytest.raises(OSError):
            socket.create_connection(("127.0.0.1", port), 1.0).close()

    def end(self) -> None:
        """The test's own way out: nothing of a failed case stays."""
        for pid in list(self.left_behind()) + [self.proc.pid]:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        self.proc.wait(30)


def loading(run: Rehearsal) -> bool:
    """The daemon serves and the writer processes are there: the store
    is being loaded (or has just been, at this size)."""
    now = run.look()
    return (any("tsd.conf" in cmd for _, _, cmd in now.values())
            and any("multiprocessing.spawn" in cmd
                    for _, _, cmd in now.values()))


def in_the_window(run: Rehearsal) -> bool:
    """Warm-up's last line is out: the window of 8 s opens next, and the
    signal comes 2 s into it."""
    run.look()
    if "# warm-up by class" not in run.text(run.stdout):
        return False
    time.sleep(2.0)
    return True


@pytest.mark.parametrize("signum,code", [
    (signal.SIGTERM, 128 + signal.SIGTERM),
    (signal.SIGINT, 128 + signal.SIGINT),
    (signal.SIGKILL, -signal.SIGKILL)], ids=["SIGTERM", "SIGINT", "SIGKILL"])
@pytest.mark.parametrize("moment", [loading, in_the_window],
                         ids=lambda m: m.__name__)
def test_a_run_ended_from_outside_leaves_nothing_behind(tmp_path, moment,
                                                        signum, code):
    run = Rehearsal(tmp_path, seconds=8, seed=2147483900 + signum)
    try:
        run.wait_until(moment.__name__, lambda: moment(run))
        port = run.daemon_port()
        assert len(run.seen) >= 3       # the runner, its daemon, a writer
        os.kill(run.proc.pid, signum)
        assert run.proc.wait(60) == code, run.text(run.stderr)
        run.assert_nothing_is_left(port)
        out = run.text(run.stdout)
        assert not any(line.startswith("{") for line in out.splitlines())
        if signum != signal.SIGKILL:
            # the runner says where it was cut, and after how long (a
            # writer that was starting up may say after it that its
            # parent has gone)
            said = [line for line in run.text(run.stderr).splitlines()
                    if line.startswith("benchmark: ")]
            assert len(said) == 1 and said[0].startswith(
                "benchmark: cut by %s " % signal.Signals(signum).name), said
            assert " s after its start, in " in said[0]
    finally:
        run.end()


def test_a_run_that_ends_by_itself_takes_the_graceful_path(tmp_path):
    run = Rehearsal(tmp_path, seconds=2, seed=2147483999)
    try:
        run.wait_until("daemon and writers", lambda: loading(run))
        port = run.daemon_port()
        give_up = time.monotonic() + 240.0
        while run.proc.poll() is None and time.monotonic() < give_up:
            run.look()
            time.sleep(0.2)
        assert run.proc.poll() == 0, run.text(run.stderr)
        run.assert_nothing_is_left(port)
        assert run.text(run.stdout).strip().splitlines()[-1].startswith("{")
        log = run.text(os.path.join(run.out, "daemon.log"))
        assert log.strip().splitlines()[-1].endswith("Server shut down")
    finally:
        run.end()


STUBBORN = """
import os, signal, socket, sys, time
signal.signal(signal.SIGTERM, signal.SIG_IGN)
s = socket.socket()
s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
s.bind(("127.0.0.1", int(sys.argv[1])))
s.listen(8)
if os.fork() == 0:          # a child of the daemon, in its process group
    time.sleep(300)
    os._exit(0)
print("up", flush=True)
time.sleep(300)
"""


def test_stop_kills_the_whole_group_of_a_daemon_that_will_not_end():
    """What a daemon inside a compile of minutes is to SIGTERM: deaf.
    stop() waits its patience, kills the group — the daemon and what it
    started — and sees the port refuse."""
    from benchmark import daemon
    port = daemon.free_port()
    proc = subprocess.Popen([sys.executable, "-c", STUBBORN, str(port)],
                            stdout=subprocess.PIPE, start_new_session=True)
    try:
        assert proc.stdout.readline().strip() == b"up"
        group = {pid for pid, (_, sid, _) in processes().items()
                 if sid == proc.pid}
        assert len(group) == 2
        began = time.monotonic()
        assert daemon.stop(proc, port, patience=1.0) == -signal.SIGKILL
        assert time.monotonic() - began < 10.0
        assert not group & set(processes())
        with pytest.raises(OSError):
            socket.create_connection(("127.0.0.1", port), 1.0).close()
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.stdout.close()
