"""``correct`` must come out false for the cell of the JoyAI-LLM-Flash
configuration when the timed path is broken (``joyai_faulty_run.py``: the
module's ``h`` half or its embedding half left un-normed; the two halves of
``W_eh``'s input swapped; a rejected draft's rows left live; acceptance
compared against the wrong row; speculation silently off) and true when
nothing is; and the float8 control (the reference with its weights rounded to
``float8_e4m3fn``, put in the program's place) must fail where the program
passes.  At the rehearsal's sizes on the CPU, through the whole harness but
for its look for a chip.

Speculation is lossless whatever is drafted, so a fault of the MODULE leaves
every served token right and shows in the drafts themselves
(``mtp_drafts_off_share``: the reply's drafts against the reference's
module's choices); right drafts compared with the wrong row show in
``mtp_accept_gap``; a fault of the VERIFY step's bookkeeping shows in the
tokens.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_joyai_faults.py -q

Not part of the repo's tier-1 tests (those are under ``tests/``)."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "joyai-llm-flash-ep8.reason"


def run(script, *args, timeout=1500):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, script)] + list(args),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    assert done.returncode == 0, done.stderr[-3000:]
    return done.stdout


@pytest.mark.parametrize("fault,guard", [
    ("none", None), ("hnorm_dropped", "mtp_drafts_off_share"),
    ("enorm_dropped", "mtp_drafts_off_share"),
    ("eh_halves_swapped", "mtp_drafts_off_share"),
    ("rejected_row_left_live", "served_token_gap"),
    ("accept_wrong_row", "mtp_accept_gap"),
    ("speculation_off", "spec_steps_share")])
def test_fault_is_seen(fault, guard):
    out = run("joyai_faulty_run.py", fault, "--workload", CELL, "--seed",
              "41", "--seconds", "6", "--trace", "0")
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is (guard is None), line["compared"]
    assert line["failed"] == 0
    compared = line["compared"]
    if guard is None:
        assert compared["served_token_gap"]["value"] == 0.0
        assert compared["served_tokens_off_share"]["value"] == 0.0
        assert compared["spec_steps_share"]["value"] == 0.0
        assert compared["mtp_drafts_off_share"]["value"] == 0.0
    else:
        assert compared[guard]["value"] is None \
            or compared[guard]["value"] > compared[guard]["limit"]
    if guard == "mtp_drafts_off_share":
        # lossless whatever is drafted: every served token is still right
        assert compared["served_token_gap"]["value"] == 0.0


def test_float8_control_fails_and_program_passes():
    out = run("read_limits.py", "--workload", CELL, "--seeds", "51,52",
              "--seconds", "6", "--control", "float8_e4m3fn", "--rehearse",
              timeout=3000)
    got = [json.loads(line[len("READING "):]) for line in out.splitlines()
           if line.startswith("READING ")]
    assert len(got) == 2
    for r in got:
        for name, c in r["compared"].items():
            assert c["value"] is not None and c["value"] <= c["limit"], r
        control = r["control_readings"]["float8_e4m3fn"]
        assert control["served_token_gap"] \
            > r["compared"]["served_token_gap"]["limit"], control
        assert control["served_tokens_off_share"] \
            > r["compared"]["served_tokens_off_share"]["limit"], control
        assert control["mtp_drafts_off_share"] \
            > r["compared"]["mtp_drafts_off_share"]["limit"], control
        assert control["tokens_changed"] > 0
