"""Multi-host SPMD: 2 real processes over one global mesh (loopback).

The reference tested its distributed backbone with master and slaves
in-process on localhost (SURVEY §4, test_client_server.py [M]); the
TPU-native analogue is N jax processes joined by
``jax.distributed.initialize`` over 127.0.0.1, each owning 4 virtual CPU
devices of one 8-device mesh.  Asserts (1) both processes compute
IDENTICAL per-step metrics — the collectives really span processes — and
(2) those metrics equal a single-process run on the same global batches,
i.e. multi-host changes the wiring, not the math.  Covered layouts:

- ``dp``: blocked mesh, batch split by process (the reference's only
  strategy, rebuilt as GSPMD all-reduce);
- ``tp``: interleaved mesh whose MODEL axis spans the two processes —
  megatron-style cross-host tensor parallelism, with layer-0 weights
  output-sharded across hosts and the batch replicated.
"""

import json
import os
import socket
import subprocess
import sys

import functools

import numpy
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # each worker re-adds its own 4-device flag; strip the conftest's 8
    env["XLA_FLAGS"] = ""
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


#: the error this jaxlib's CPU backend raises for any cross-process
#: collective — the whole multihost suite is hardware-gated on it
_NO_MULTIPROC = "Multiprocess computations aren't implemented on the CPU"


@functools.lru_cache(maxsize=1)
def _multiproc_skip_reason():
    """Probe ONCE whether this jaxlib can run cross-process collectives
    at all (one cheap 2-process broadcast instead of every test paying
    a full worker pair to rediscover the same missing backend).
    Returns the skip reason, or None when the backend is capable — any
    OTHER probe failure also returns None so the real tests surface it
    with their full diagnostics."""
    port = _free_port()
    code = ("import sys, jax\n"
            "jax.distributed.initialize('127.0.0.1:%d', 2, "
            "int(sys.argv[1]))\n"
            "from jax.experimental import multihost_utils\n"
            "multihost_utils.broadcast_one_to_all(jax.numpy.ones(1))\n"
            % port)
    procs = [subprocess.Popen([sys.executable, "-c", code, str(pid)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True,
                              env=_worker_env(), cwd=REPO)
             for pid in range(2)]
    gated = False
    try:
        for p in procs:
            _, stderr = p.communicate(timeout=120)
            if p.returncode != 0 and _NO_MULTIPROC in stderr:
                gated = True
    except Exception:   # noqa: BLE001 — probe hang/crash: let tests run
        return None
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    if gated:
        return ("multi-process collectives unsupported by this jaxlib "
                "CPU backend")
    return None


def _parse_metrics(stdout):
    for line in stdout.splitlines():
        if line.startswith("METRICS "):
            return json.loads(line[len("METRICS "):])
    raise AssertionError("no METRICS line in worker output:\n" + stdout)


def _spawn_workers(script, extra_args):
    """Launch 2 coordinated worker processes of ``script``; return their
    stdouts (asserting rc=0), killing stragglers on the way out.
    Hardware-gated environments (no cross-process collectives) skip —
    explicitly, with the reason — instead of failing."""
    reason = _multiproc_skip_reason()
    if reason:
        pytest.skip(reason)
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(HERE, script),
             "127.0.0.1:%d" % port, "2", str(pid)] + list(extra_args),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=_worker_env(), cwd=REPO)
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=300)
            if p.returncode != 0 and _NO_MULTIPROC in stderr:
                # hardware-gated, not broken: this jaxlib's CPU backend
                # has no cross-process collectives (they need a TPU/GPU
                # backend or a gloo-enabled jaxlib build).  Explicit
                # skip so the suite stays honest on capable platforms.
                pytest.skip("multi-process collectives unsupported by "
                            "this jaxlib CPU backend")
            assert p.returncode == 0, (
                "worker failed rc=%d\nstdout:\n%s\nstderr:\n%s"
                % (p.returncode, stdout, stderr[-4000:]))
            outs.append(stdout)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    return outs


def _run_workers(mode):
    return [_parse_metrics(out)
            for out in _spawn_workers("multihost_worker.py", [mode])]


@functools.lru_cache(maxsize=1)
def _single_process_reference(steps=3):
    """Expected per-step metrics from a single-process run on the same
    global batches (global plan, same PRNG → same minibatch order).
    Cached: the reference is mode-independent, so the dp and tp
    parametrizations share one build+compile+train."""
    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.parallel import make_mesh, ShardedTrainer
    from veles_tpu.loader.base import TRAIN
    prng.reset()
    prng.seed_all(1)
    root.mnist.update({
        "loader": {"minibatch_size": 32, "n_train": 128, "n_valid": 32},
        "decision": {"max_epochs": 1, "fail_iterations": 5},
        "layers": [
            {"type": "all2all_tanh", "output_sample_shape": 16,
             "learning_rate": 0.05, "momentum": 0.9},
            {"type": "softmax", "output_sample_shape": 10,
             "learning_rate": 0.05, "momentum": 0.9},
        ],
    })
    from veles_tpu.samples import mnist
    wf = mnist.build(fused=True)
    wf.initialize()
    import jax
    mesh = make_mesh(8, devices=jax.devices("cpu"))
    trainer = ShardedTrainer(wf._fused_runner, mesh)
    assert not trainer.multiprocess

    loader = wf.loader
    expect, step = [], 0
    while step < steps:
        loader.run()
        if loader.minibatch_class != TRAIN:
            continue
        metrics = trainer.train_step(
            numpy.asarray(loader.minibatch_data.mem),
            numpy.asarray(loader.minibatch_labels.mem),
            numpy.asarray(loader.minibatch_mask.mem),
            loader.minibatch_size, step=step)
        host = ShardedTrainer.fetch(metrics)
        expect.append({k: float(numpy.ravel(v)[0]) for k, v in host.items()})
        step += 1
    return expect


@pytest.mark.parametrize("mode", ["dp", "tp"])
def test_two_process_spmd_matches_single_process(mode):
    outs = _run_workers(mode)

    # (1) both processes saw the same replicated metrics each step
    assert outs[0] == outs[1]
    assert len(outs[0]) == 3

    # (2) equal to the single-process reference on the same global batches
    for step, expect in enumerate(_single_process_reference()):
        for key, val in expect.items():
            assert abs(outs[0][step][key] - val) <= 1e-4 * (1 + abs(val)), (
                mode, step, key, outs[0][step][key], val)


def test_cli_distributed_trains_spmd_and_matches_single_process():
    """The PRODUCT --distributed path (Launcher.boot(distributed=True)):
    both processes train lock-step through the mesh (identical per-epoch
    decision metrics and final weights), and the result matches a plain
    single-process run of the same config — the documented 'gradient
    averaging is the XLA all-reduce' semantics, now through the CLI
    graph loop itself."""
    outs = [_parse_metrics(out)
            for out in _spawn_workers("multihost_cli_worker.py", [])]
    assert outs[0] == outs[1]
    assert len(outs[0]["epochs"]) == 2

    # single-process reference: plain graph loop, same seed/config
    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    prng.reset()
    prng.seed_all(1)
    root.__dict__.pop("mnist", None)
    root.mnist.update({
        "loader": {"minibatch_size": 32, "n_train": 128, "n_valid": 32},
        "decision": {"max_epochs": 2, "fail_iterations": 5},
        "layers": [
            {"type": "all2all_tanh", "output_sample_shape": 16,
             "learning_rate": 0.05, "momentum": 0.9},
            {"type": "softmax", "output_sample_shape": 10,
             "learning_rate": 0.05, "momentum": 0.9},
        ],
    })
    from veles_tpu.samples import mnist
    wf = mnist.build(fused=True)
    Launcher(wf, stats=False).boot()
    ref_epochs = wf.decision.epoch_metrics
    assert len(ref_epochs) == len(outs[0]["epochs"])
    for ref, got in zip(ref_epochs, outs[0]["epochs"]):
        for set_name, metrics in ref.items():
            for key, val in metrics.items():
                if not isinstance(val, (int, float)):
                    continue
                g = got[set_name][key]
                assert abs(g - val) <= 1e-4 * (1 + abs(val)), (
                    set_name, key, g, val)
    wsum = float(numpy.abs(
        numpy.asarray(wf.forwards[0].weights.mem)).sum())
    assert abs(outs[0]["wsum"] - wsum) <= 1e-3 * (1 + wsum)


def test_cli_distributed_epoch_scan_matches_graph_loop():
    """--distributed --epoch-scan composed: 2 processes run k-epoch
    chunks as single programs under the global mesh and reach the same
    per-epoch metrics and weights as the 2-process per-minibatch path
    (which itself equals single-process — previous test)."""
    outs = [_parse_metrics(out)
            for out in _spawn_workers("multihost_cli_worker.py", ["2"])]
    assert outs[0] == outs[1]
    base = [_parse_metrics(out)
            for out in _spawn_workers("multihost_cli_worker.py", [])]
    assert len(outs[0]["epochs"]) == len(base[0]["epochs"])
    for ref, got in zip(base[0]["epochs"], outs[0]["epochs"]):
        for set_name, metrics in ref.items():
            for key, val in metrics.items():
                g = got[set_name][key]
                assert abs(g - val) <= 1e-4 * (1 + abs(val)), (
                    set_name, key, g, val)
    assert abs(outs[0]["wsum"] - base[0]["wsum"]) <= 1e-3 * (
        1 + base[0]["wsum"])


def test_two_process_divergent_init_detected():
    """ShardedTrainer assembles device shards from process-LOCAL host
    copies, so divergent init across processes must fail loudly at
    construction (digest cross-check, ADVICE r4) — not silently train a
    Frankenstein tensor."""
    for out in _spawn_workers("multihost_worker.py", ["diverge"]):
        assert "DIVERGE-CAUGHT" in out, out


def _run_resume_workers(phase, snap_dir):
    return _spawn_workers("multihost_resume_worker.py", [phase, snap_dir])


def _digests(outs):
    got = []
    for out in outs:
        for line in out.splitlines():
            if line.startswith("DIGEST "):
                got.append(json.loads(line[len("DIGEST "):]))
    return got


def test_two_process_snapshot_resume_bit_exact(tmp_path):
    """Interrupt + restore ACROSS THE MESH: a 2-process SPMD run
    snapshotted at step K and resumed in fresh processes must reach the
    bit-identical state of an uninterrupted 2-process run — the
    multi-host form of the kill-and-resume contract (SURVEY §5.3)."""
    full = _digests(_run_resume_workers("full", str(tmp_path)))
    assert len(full) == 2 and full[0] == full[1]

    outs = _run_resume_workers("first", str(tmp_path))
    assert all("SNAPSHOT OK" in o for o in outs)
    assert os.path.exists(os.path.join(str(tmp_path), "mid.pickle.gz"))

    resumed = _digests(_run_resume_workers("second", str(tmp_path)))
    assert len(resumed) == 2 and resumed[0] == resumed[1]
    assert resumed[0] == full[0], "resumed run diverged from straight run"


def test_spmd_loader_shard_single_process_collapses():
    """All devices in one process → one data block, full batch locally;
    the data axis is found by NAME, not position."""
    import jax
    from jax.sharding import Mesh
    from veles_tpu.parallel import spmd_loader_shard
    devices = jax.devices("cpu")[:8]
    blocked = Mesh(numpy.array(devices).reshape(4, 2), ("data", "model"))
    assert spmd_loader_shard(blocked) == (0, 1)
    swapped = Mesh(numpy.array(devices).reshape(2, 4), ("model", "data"))
    assert spmd_loader_shard(swapped) == (0, 1)
    with pytest.raises(ValueError):
        spmd_loader_shard(Mesh(numpy.array(devices[:2]), ("model",)))
