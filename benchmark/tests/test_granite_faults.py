"""``correct`` must come out false for the cell of the Granite 4.0-H
configuration when the timed path is broken (``granite_faulty_run.py``: a
served token altered; a decode step that leaves the state unchanged; the
convolution tail not carried across chunks; a padded row allowed into the
state; the skip ``D x`` dropped) and true when nothing is; and the float8
control (the reference with its weights rounded to ``float8_e4m3fn``, put in
the program's place) must fail where the program passes.  At the rehearsal's
sizes on the CPU, through the whole harness but for its look for a chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_granite_faults.py -q

Not part of the repo's tier-1 tests (those are under ``tests/``)."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "granite-4.0-h-micro.toolchat"


def run(script, *args, timeout=1500):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, script)] + list(args),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    assert done.returncode == 0, done.stderr[-3000:]
    return done.stdout


@pytest.mark.parametrize("fault,correct", [
    ("none", True), ("token_altered", False), ("state_unchanged", False),
    ("conv_tail_not_carried", False), ("padded_row_in_state", False),
    ("skip_dropped", False)])
def test_fault_is_seen(fault, correct):
    out = run("granite_faulty_run.py", fault, "--workload", CELL,
              "--seed", "41", "--seconds", "6", "--trace", "0")
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is correct, line["compared"]
    assert line["failed"] == 0
    if fault not in ("none", "token_altered"):
        # a wrong state, a wrong convolution or a wrong sum show in the tokens
        gap = line["compared"]["served_token_gap"]
        assert gap["value"] > gap["limit"]


def test_float8_control_fails_and_program_passes():
    out = run("read_limits.py", "--workload", CELL, "--seeds", "51,52",
              "--seconds", "6", "--control", "float8_e4m3fn", "--rehearse",
              timeout=3000)
    got = [json.loads(line[len("READING "):]) for line in out.splitlines()
           if line.startswith("READING ")]
    assert len(got) == 2
    for r in got:
        gap = r["compared"]["served_token_gap"]
        assert gap["value"] <= gap["limit"], r
        off = r["compared"]["served_tokens_off_share"]
        assert off["value"] <= off["limit"], r
        control = r["control_readings"]["float8_e4m3fn"]
        assert control["served_token_gap"] > gap["limit"], control
        assert control["served_tokens_off_share"] > off["limit"], control
        assert control["tokens_changed"] > 0
