"""Failure recovery: SIGKILL mid-training + ``--snapshot auto`` resume.

SURVEY §5.3: the reference detected dead slaves and reissued their jobs
(veles/server.py::drop_slave [H]); on the SPMD substrate that elasticity is
deliberately downgraded to kill-and-resume — a killed run restarts from the
last atomically-published snapshot and must reach the IDENTICAL final state
an unkilled run reaches.  This test proves that contract end to end with a
real SIGKILL against a real training subprocess.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import time


WORKER = os.path.join(os.path.dirname(__file__), "resume_worker.py")


def _run_worker(out_dir, mode, epoch_sleep=0.0, wait=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, WORKER, str(out_dir), mode, str(epoch_sleep)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    if not wait:
        return proc
    out, _ = proc.communicate(timeout=240)
    assert proc.returncode == 0, out.decode(errors="replace")[-2000:]
    return proc


def test_sigkill_resume_reaches_identical_state(tmp_path):
    control_dir = tmp_path / "control"
    victim_dir = tmp_path / "victim"
    control_dir.mkdir()
    victim_dir.mkdir()

    # ---- control: straight 6-epoch run
    _run_worker(control_dir, "control")
    with open(control_dir / "control.json", encoding="utf-8") as f:
        control = json.load(f)
    assert control["epochs"] == 6

    # ---- victim: slowed run, SIGKILLed once >=2 snapshots are published
    proc = _run_worker(victim_dir, "victim", epoch_sleep=0.5, wait=False)
    snap_glob = str(victim_dir / "snaps" / "mnist_[0-9]*.pickle")
    deadline = time.time() + 180
    try:
        while time.time() < deadline:
            if len(glob.glob(snap_glob)) >= 2:
                break
            if proc.poll() is not None:
                out = proc.stdout.read().decode(errors="replace")
                raise AssertionError("victim exited before it could be "
                                     "killed:\n" + out[-2000:])
            time.sleep(0.05)
        else:
            raise AssertionError("victim produced no snapshots in time")
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -signal.SIGKILL
    assert not (victim_dir / "victim.json").exists(), \
        "victim finished cleanly — the kill came too late to prove anything"

    # ---- resume: --snapshot auto picks up the victim's latest snapshot
    _run_worker(victim_dir, "resume")
    with open(victim_dir / "resume.json", encoding="utf-8") as f:
        resumed = json.load(f)

    # identical FINAL state: bit-exact weights, same metric history
    assert resumed["weights_sha"] == control["weights_sha"]
    assert resumed["best_metric"] == control["best_metric"]
    assert resumed["best_epoch"] == control["best_epoch"]
    assert resumed["epochs"] == 6


def test_find_current_ignores_tmp_staging_files(tmp_path):
    """A crash can leave '*_current.pickle.gz.tmp' behind; the auto-resume
    resolver must never pick it (it is raw staged bytes, not a snapshot)."""
    from veles_tpu import snapshotter
    good = tmp_path / "wf_current.pickle.gz"
    good.write_bytes(b"x")
    stale = tmp_path / "wf_current.pickle.gz.tmp"
    stale.write_bytes(b"y")
    os.utime(good, (1000, 1000))  # tmp file is NEWER
    assert snapshotter.find_current(str(tmp_path)) == str(good)
    assert snapshotter.find_current(str(tmp_path), "wf") == str(good)
    assert snapshotter.find_current(str(tmp_path), "other") is None


def test_restore_keeps_runtime_shard_identity(tmp_path):
    """Restoring a process-0 snapshot on a differently-sharded process must
    keep the RUNTIME shard and re-plan, not adopt process 0's shard."""
    from veles_tpu import prng
    from veles_tpu.config import root
    prng.reset()
    prng.seed_all(1)
    root.mnist.update({
        "loader": {"minibatch_size": 10, "n_train": 40, "n_valid": 20},
        "decision": {"max_epochs": 1, "fail_iterations": 5},
        "layers": [
            {"type": "all2all_tanh", "output_sample_shape": 8,
             "learning_rate": 0.05, "momentum": 0.9},
            {"type": "softmax", "output_sample_shape": 10,
             "learning_rate": 0.05, "momentum": 0.9},
        ],
    })
    from veles_tpu.samples import mnist
    wf = mnist.build(fused=True)
    wf.initialize()
    state = wf.loader.state_dict()  # written as (0, 1) — the writer process

    # same topology: restored verbatim (bit-exact resume path)
    wf.loader.load_state_dict(state)
    assert wf.loader._shard == (0, 1)
    assert wf.loader._order is not None

    # different topology: runtime identity wins, plan is rebuilt
    wf.loader.shard(1, 2)
    wf.loader.load_state_dict(state)
    assert wf.loader._shard == (1, 2)
    assert wf.loader._order is None and wf.loader._position == 0
    wf.loader.run()  # re-plans for shard (1, 2) without error
    # both classes start at even offsets, so shard (1, 2) sees odd indices
    assert all(int(i) % 2 == 1 for i in wf.loader.minibatch_indices.mem), \
        "re-planned minibatch must come from THIS process's stride"


def test_snapshot_auto_fresh_run(tmp_path):
    """--snapshot auto with an empty snapshot dir is a fresh run."""
    _run_worker(tmp_path, "resume")
    with open(tmp_path / "resume.json", encoding="utf-8") as f:
        result = json.load(f)
    assert result["epochs"] == 6
