"""Supervisor hang detection: stale-heartbeat relaunch and restart
exhaustion (the process-level rung of the degradation ladder --
docs/robustness.md)."""
import sys
import textwrap

from repro.ft.supervisor import SupervisorConfig, supervise

HANG_ONCE = textwrap.dedent("""\
    import pathlib, sys, time
    work = pathlib.Path(sys.argv[1])
    sentinel = work / "ran_once"
    if sentinel.exists():
        sys.exit(0)                      # the relaunch succeeds
    sentinel.write_text("1")
    (work / "heartbeat").write_text(str(time.time()))
    time.sleep(60)                       # hang: heartbeat goes stale
""")


def test_stale_heartbeat_triggers_relaunch(tmp_path):
    """A child that stops touching its heartbeat is declared hung and
    killed (exit -9 in the history), and the relaunch runs to a clean
    exit: hangs are recoverable, not merely detectable."""
    script = tmp_path / "child.py"
    script.write_text(HANG_ONCE)
    report = supervise(
        [sys.executable, str(script), str(tmp_path)], tmp_path,
        SupervisorConfig(max_restarts=2, hang_timeout_s=1.5, poll_s=1.0))
    assert report.exit_code == 0
    assert report.restarts == 1
    assert report.history == [-9, 0]


def test_hang_restarts_exhaust(tmp_path):
    """A child that never heartbeats is killed on every launch; the
    supervisor gives up after ``max_restarts`` and reports the kill."""
    script = tmp_path / "child.py"
    script.write_text("import time; time.sleep(60)\n")
    report = supervise(
        [sys.executable, str(script)], tmp_path,
        SupervisorConfig(max_restarts=1, hang_timeout_s=0.2, poll_s=0.2))
    assert report.exit_code == -9
    assert report.restarts == 1
    assert report.history == [-9, -9]


def test_supervisor_parent_stays_off_jax():
    """The supervising parent never imports JAX: a parent that touches it
    would hold the chip its child needs."""
    import subprocess
    out = subprocess.run(
        [sys.executable, "-c", "import sys, repro.ft.supervisor; "
         "print('jax' in sys.modules)"],
        capture_output=True, text=True, timeout=120,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
