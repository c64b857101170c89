"""tools/stream_bench.py — the streaming windowed epoch-scan evidence
harness (ISSUE 3 acceptance: overlap is real and measured).

The sustained run is slow-marked (tier-1 skips it); the CLI contract
test runs the tiny shape so the tool itself stays covered.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@pytest.mark.slow
def test_stream_bench_overlap_and_dispatch_reduction():
    """The acceptance numbers, measured: dispatches per epoch drop from
    ~minibatches to ~windows, and the staging-stall fraction stays under
    50% with stage-ahead 1."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from stream_bench import run_stream_bench
    finally:
        sys.path.pop(0)
    record = run_stream_bench(samples=4096, minibatch=64, window=8,
                              stage_ahead=1, epochs=3)
    mbs = record["train_minibatches_per_epoch"]
    graph_d = record["graph_loop"]["dispatches_per_epoch"]
    stream_d = record["streaming"]["dispatches_per_epoch"]
    windows = record["streaming"]["windows_per_epoch"]
    # graph mode: ~one dispatch per minibatch (train + eval sets)
    assert graph_d >= mbs
    # streaming: ~one dispatch per window (+ per-epoch eval + replay)
    assert stream_d < graph_d / 2
    assert windows <= stream_d <= windows + 3
    assert record["dispatch_reduction"] > 2
    assert record["streaming"]["staging_stall_pct"] < 50.0
    assert record["parity"]["epochs_equal"]


def test_stream_bench_cli_one_json_line():
    """Standalone contract: one parseable JSON line on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "stream_bench.py"),
         "--samples", "256", "--minibatch", "16", "--window", "3",
         "--epochs", "2"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        cwd=REPO, timeout=300)
    assert proc.returncode == 0
    lines = [ln for ln in proc.stdout.decode().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1
    record = json.loads(lines[0])
    assert record["streaming"]["windows_per_epoch"] > 0
    assert record["graph_loop"]["samples_per_sec"] > 0
