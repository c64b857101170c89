"""``correct`` must come out false for the cell of the Xing4.0 configuration
when the timed path is broken (``xing4_faulty_run.py``: a served token
altered; a latent row written through the wrong table entry; the shared key
left unrotated; the streams' mixing matrix replaced by the identity; the
shared expert dropped) and true when nothing is; and the float8 control (the
reference with its weights rounded to ``float8_e4m3fn``, put in the program's
place) must fail where the program passes.  At the rehearsal's sizes on the
CPU, through the whole harness but for its look for a chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_xing4_faults.py -q

Not part of the repo's tier-1 tests (those are under ``tests/``)."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "xing4.0-29b-a4b.longdoc"


def run(script, *args, timeout=1500):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, script)] + list(args),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    assert done.returncode == 0, done.stderr[-3000:]
    return done.stdout


@pytest.mark.parametrize("fault,correct", [
    ("none", True), ("token_altered", False),
    ("latent_row_wrong_page", False), ("k_rope_unrotated", False),
    ("h_res_identity", False), ("shared_expert_dropped", False)])
def test_fault_is_seen(fault, correct):
    out = run("xing4_faulty_run.py", fault, "--workload", CELL, "--seed",
              "41", "--seconds", "6", "--trace", "0")
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is correct, line["compared"]
    assert line["failed"] == 0
    if fault not in ("none", "token_altered"):
        # wrong keys, a wrong mix or a wrong sum show in the tokens
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


def test_traced_chunks_share_the_prefill_turns_round_robin():
    """``lib/latent.py::traced_chunks``: two requests admitted together, of
    4 and 2 chunks, take turns (A0 B0 A1 B1), then the longer one goes alone
    (A2 A3): the expected starts are the engine's own."""
    import collections
    import numpy
    sys.path.insert(0, ROOT)
    from benchmark.lib import latent
    record = collections.namedtuple("R", "admit first_token prompt_len")
    columns = collections.namedtuple(
        "T", "COL_PREFILL_PROGRAM COL_STAMPS PREFILL_DISPATCH")(0, 1, 0)

    class Recorder:
        def requests(self):
            return [record(1, 61, 4 * 8), record(1, 41, 2 * 8)]

    turns = numpy.array([[1, 10 * i] for i in range(1, 9)] + [[0, 95]])
    art = {"trace_host_window": (0.0, 100e-9),
           "_spans_recorder": {"recorder": Recorder(), "turns": turns,
                               "tracing": columns}}
    assert latent.traced_chunks(art, 8) == [0.0, 0.0, 8.0, 8.0, 16.0, 24.0]
