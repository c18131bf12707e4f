"""A run owns the whole life of what it starts: killed from outside, stopped by
a signal, past its own deadline or refused in the scoped warm-up, it leaves no
process behind. On the CPU rehearsal, through the benchmark's own command."""

import json
import re
import signal
import subprocess
import time

import harness
import pytest
from test_dropin import _drop_in_scoped

READ, SCOPED = "tiny-bert.read-c4", "tiny-bert-tenants.read-scoped-c4"
#: the line of standard error that says a run has reached a stage, and how long
#: after it the signal comes. In set-up the child is then importing JAX, with
#: the index build and the warm-up before it, and reads no standard input for
#: ten seconds: only the kernel's signal ends it in time. The reference is short.
#: Once the run has its result (the child has said ``checked`` and is ending by
#: itself, which takes seconds on the chip) it is too late for a loss.
REACHED = {"set-up": ("child started: pid", 1.0), "window": ("stage: window", 0.5),
           "check": ("stage: check", 0.0), "result": ("the run has its result", 0.0)}


class Run:
    """``run.py`` started and left running, its standard error in a file."""

    def __init__(self, tmp_path, workload: str, **how):
        self.err_path = tmp_path / "stderr.txt"
        with open(self.err_path, "w") as err:
            self.proc = subprocess.Popen(
                harness.command(workload, **how), stdout=subprocess.PIPE, stderr=err,
                text=True, env=harness.environment(), cwd=harness.ROOT)

    def err(self) -> str:
        return self.err_path.read_text()

    def wait_for(self, marker: str, within_s: float = 120.0) -> None:
        t_end = time.monotonic() + within_s
        while marker not in self.err():
            assert self.proc.poll() is None, f"ended before {marker!r}:\n{self.err()[-3000:]}"
            assert time.monotonic() < t_end, f"no {marker!r}:\n{self.err()[-3000:]}"
            time.sleep(0.02)

    def ended(self, within_s: float = 60.0) -> tuple[int, str, str]:
        """(exit code, standard output, standard error); never leaves it running."""
        try:
            out, _ = self.proc.communicate(timeout=within_s)
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait()
        return self.proc.returncode, out, self.err()


def last_line(err: str) -> str:
    return err.strip().splitlines()[-1]


@pytest.mark.parametrize("cell,sig,stage", [
    (READ, signal.SIGKILL, "set-up"),    # the child is building the deployment
    (SCOPED, signal.SIGKILL, "window"),  # the child serves, the parent sends
    (READ, signal.SIGKILL, "check"),     # the child runs the reference
    (SCOPED, signal.SIGTERM, "window"),
    (READ, signal.SIGINT, "set-up"),
    (READ, signal.SIGHUP, "check"),
    (READ, signal.SIGTERM, "result"),
    (SCOPED, signal.SIGINT, "result"),
])
def test_stopped_from_outside_it_leaves_nothing(tmp_path, cell, sig, stage):
    run = Run(tmp_path, cell, seconds=4, extra=("--control", "1"))
    marker, after_s = REACHED[stage]
    run.wait_for(marker)
    time.sleep(after_s)
    run.proc.send_signal(sig)
    code, out, err = run.ended()
    assert harness.child_gone(cell), err[-3000:]
    if stage == "result":
        # a run is a result or a loss, never both: this one stays a result
        assert code == 0, err[-3000:]
        assert json.loads(out.strip().splitlines()[-1])["correct"] is True
        assert "no result" not in err and last_line(err) == "correct = True"
        return
    assert not out.strip(), out  # no result line
    if sig == signal.SIGKILL:
        assert code == -signal.SIGKILL
    else:
        # an ending, not a loss: the code says which signal, the last line where
        assert code == 128 + sig, err[-3000:]
        assert sig.name in last_line(err) and f"stage {stage!r}" in last_line(err)
        assert "Traceback" not in err


def test_a_scoped_cell_too_slow_to_warm_is_refused_before_any_window(tmp_path):
    manifest = _drop_in_scoped(tmp_path)
    cell = "shared-drive.read-folder-c2"
    mix_path = tmp_path / "traffic" / "read-folder-c2.json"
    mix = json.loads(mix_path.read_text())
    mix["warm_batch_max"] = 16  # as the cell this is for: 2 x sum(1..16) = 272 posts
    mix_path.write_text(json.dumps(mix))
    # every search 1.5 s slower: about as today's program serves a filter at 1.2M rows
    code, result, err = harness.run_cell(cell, seconds=2, manifest=manifest, timeout=120,
                                         extra=("--fault", "slow"))
    assert code != 0 and result is None, err[-3000:]
    assert "stage: window" not in err
    said = re.search(r"a filtered request served alone takes ([\d.]+) s; warming this cell "
                     r"would take (\d+) s of a budget of (\d+) \(SCOPED_WARM_BUDGET_S\)",
                     last_line(err))
    assert said, last_line(err)
    alone, need, budget = map(float, said.groups())
    assert 1.5 <= alone < 2.5 and abs(need - alone * 272) < 3 and need > budget == 180
    assert harness.child_gone(cell)


@pytest.mark.parametrize("constant,limit_s,stage", [
    ("SETUP_LIMIT_S", 4.0, "set-up"),   # the child is still importing or building
    ("RUN_LIMIT_S", 6.0, "window"),     # set-up alone takes longer than the whole limit
])
def test_a_run_that_meets_its_deadline_ends_itself(tmp_path, constant, limit_s, stage):
    # through the one constant, as a later PR would change it: no flag sets it
    script = tmp_path / "run_with_limit.py"
    script.write_text(
        "import sys\n"
        f"sys.path.insert(0, {harness.BENCH!r})\n"
        "import run\n"
        f"run.{constant} = {limit_s}\n"
        "sys.exit(run.main())\n")
    code, result, err = harness.run_cell(READ, seconds=3, script=str(script))
    assert code == 5 and result is None, err[-3000:]
    assert f"{constant} = {limit_s:.0f} s" in last_line(err)
    assert f"stage {stage!r}" in last_line(err)
    assert harness.child_gone(READ)


def test_a_rehearsal_that_hangs_leaves_no_server_behind():
    # ``run_cell`` kills ``run.py`` alone at its timeout and sees the child follow
    with pytest.raises(subprocess.TimeoutExpired):
        harness.run_cell(READ, seconds=30, timeout=12)
    assert not harness.processes_of(READ)
