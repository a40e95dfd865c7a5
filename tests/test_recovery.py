"""Recovery loop: rank death → detection → checkpoint-resume, end to end
(round-3 verdict missing #2; reference `is_recovery` rejoin,
`src/kvstore/kvstore_dist.h:52,138`, + CheckpointHandler resume,
`event_handler.py:336`).

Three launcher runs of `tests/dist_scripts/resume_worker.py`:
an uninterrupted oracle, an interrupted job whose rank 1 dies
mid-training (rank 0 must *detect* it via the heartbeat store and abort
cleanly), and a resumed job that must continue the oracle's loss
trajectory from the checkpoint exactly.
"""
import json
import os
import subprocess
import sys

import numpy as onp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_scripts", "resume_worker.py")


def _launch(mode, out_dir, timeout=600):
    env = dict(os.environ, MODE=mode, OUT_DIR=str(out_dir))
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", sys.executable, WORKER],
        capture_output=True, text=True, timeout=timeout, env=env)


def test_kill_rank_checkpoint_resume(tmp_path):
    # 1. uninterrupted oracle
    r = _launch("oracle", tmp_path)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    oracle = json.load(open(tmp_path / "oracle.json"))
    assert len(oracle["losses"]) == 8

    # 2. interrupted job: rank 1 dies after step 3; rank 0 must DETECT it
    #    through get_dead_nodes and abort (exit 3) instead of hanging
    r = _launch("part1", tmp_path)
    assert r.returncode != 0, "launcher must surface the dead rank"
    assert "SIMULATED CRASH" in r.stdout, r.stdout[-1500:]
    assert "DEAD DETECTED [1]" in r.stdout, (r.stdout[-1500:],
                                            r.stderr[-1500:])
    detected = json.load(open(tmp_path / "detected.json"))
    assert detected["dead"] == [1]
    assert json.load(open(tmp_path / "step.json"))["step"] == 3
    # the interrupted trajectory matches the oracle up to the crash
    onp.testing.assert_allclose(detected["losses"], oracle["losses"][:4],
                                rtol=1e-5)

    # 3. resume from the checkpoint: the continued trajectory and final
    #    weights must match the uninterrupted run
    r = _launch("part2", tmp_path)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    resumed = json.load(open(tmp_path / "resumed.json"))
    assert resumed["start"] == 4
    onp.testing.assert_allclose(resumed["losses"], oracle["losses"][4:],
                                rtol=1e-5, atol=1e-7)
    onp.testing.assert_allclose(onp.asarray(resumed["weight"]),
                                onp.asarray(oracle["weight"]),
                                rtol=1e-5, atol=1e-7)
