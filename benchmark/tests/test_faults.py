"""``correct`` must come out false when the timed path is broken: once for
each fault a cell can have (``faulty_run.py``), at the rehearsal's sizes on
the CPU, through the whole harness but for its look for a chip.  And true
when nothing is broken.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

Not part of the repo's tier-1 tests (those are under ``tests/``); the
training cases take a minute or two each."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

CASES = [
    ("opt-1.3b.chat", "none", True, 6),
    ("opt-1.3b.chat", "token_altered", False, 6),
    ("alexnet.train", "none", True, 30),
    ("alexnet.train", "state_unchanged", False, 30),
    ("alexnet.train", "half_batch", False, 30),
]


def last_line(workload, fault, seconds, seed=41):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "faulty_run.py"), fault,
         "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,fault,correct,seconds", CASES)
def test_fault_is_seen(workload, fault, correct, seconds):
    line = last_line(workload, fault, seconds)
    assert line["correct"] is correct, line["compared"]
    if fault == "state_unchanged":
        # a state left unchanged reads 1 by the gradient's measure
        assert line["compared"]["first_gradient_gap"]["value"] > 0.9
