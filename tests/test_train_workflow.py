"""The training driver's workflow layout: segments on the device pilot,
eval and checkpoint commits on a host pilot beside them, and the slot
fault drill (reduced SmolLM config on the CPU)."""
import threading

import pytest

from repro.checkpoint.checkpoint import Checkpointer
from repro.core.spmd_executor import SPMDFunctionExecutor
from repro.launch.train import main

ARGS = ["--arch", "smollm-360m", "--reduced", "--batch", "2", "--seq", "32",
        "--segment", "2", "--no-resume"]


@pytest.mark.timeout(300)
def test_commit_overlaps_next_segment(tmp_path, monkeypatch):
    """A checkpoint commit holds no device slot: the segment after it
    starts while the commit is still writing."""
    segments_started = []
    second_segment = threading.Event()
    execute = SPMDFunctionExecutor.execute

    def counting_execute(self, task):
        if task.kind == "spmd":
            segments_started.append(task.uid)
            if len(segments_started) >= 2:
                second_segment.set()
        return execute(self, task)

    overlapped = []
    save = Checkpointer.save

    def waiting_save(self, step, tree):
        # the commit of step 2 finishes only once the next segment runs;
        # were they to share a slot, this wait would time out
        overlapped.append((step, second_segment.wait(timeout=60)))
        return save(self, step, tree)

    monkeypatch.setattr(SPMDFunctionExecutor, "execute", counting_execute)
    monkeypatch.setattr(Checkpointer, "save", waiting_save)
    losses = main(ARGS + ["--steps", "4", "--ckpt-every", "2",
                          "--eval-every", "2",
                          "--ckpt-dir", str(tmp_path / "ck")])
    assert len(losses) == 2
    assert overlapped == [(2, True), (4, True)]


@pytest.mark.timeout(300)
def test_inject_failure_reschedules_onto_spare_slot(tmp_path, capsys):
    losses = main(ARGS + ["--steps", "4", "--slots", "2",
                          "--inject-failure", "1", "--ckpt-every", "4",
                          "--eval-every", "4",
                          "--ckpt-dir", str(tmp_path / "ck")])
    assert len(losses) == 2
    out = capsys.readouterr().out
    assert "injected failure on 1 slots" in out
    assert "step     4" in out


def test_inject_failure_without_spare_slot_is_refused(tmp_path):
    """Failing the only device slot would leave the next segment waiting
    for capacity that never comes: refused before anything runs."""
    with pytest.raises(ValueError, match="raise --slots"):
        main(ARGS + ["--steps", "4", "--inject-failure", "1",
                     "--ckpt-dir", str(tmp_path / "ck")])
