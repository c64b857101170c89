"""The control must come out as not correct: the reference, put in the
program's place and computed in a lower precision, has to fail at least one of
each cell's numbers, while the program itself passes them all.  Here at the
rehearsal's sizes on the CPU, where a lower precision means operands cast to
bfloat16 (the CPU ignores jax's precision names); on the chip the same script
reads the configuration's own ``control_precision`` at the cell's own size
(PERF.md gives those readings).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def readings(workload, seconds, seeds="51,52,53"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "read_limits.py"),
         "--workload", workload, "--seeds", seeds, "--seconds", str(seconds),
         "--control", "bfloat16", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=3000)
    assert done.returncode == 0, done.stderr[-3000:]
    return [json.loads(line[len("READING "):])
            for line in done.stdout.splitlines()
            if line.startswith("READING ")]


@pytest.mark.parametrize("workload,seconds", [("opt-1.3b.chat", 6),
                                              ("alexnet.train", 30)])
def test_control_fails_and_program_passes(workload, seconds):
    got = readings(workload, seconds)
    assert len(got) == 3
    for r in got:
        compared = r["compared"]
        assert all(c["value"] <= c["limit"] for c in compared.values()), r
        control = r["control_readings"]["bfloat16"]
        failed = [n for n, v in control.items()
                  if n in compared and v > compared[n]["limit"]]
        assert failed, (control, compared)
