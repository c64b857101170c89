"""``faulty_run.py`` for the cell of the Granite 4.0-H configuration: one
rehearsal run of the harness with the timed path broken underneath, each fault
planted in the program, where the thing is produced.

    python3 benchmark/tests/granite_faulty_run.py <fault> [--chip] --workload <cell> --seed <n> --seconds <s>

A rehearsal (the CPU, the tiny float32 sizes, where ``correct`` compares
exactly) unless ``--chip`` is given: then the run is the cell's own, at its
size and limits, to read what a fault leaves of the numbers there.

Faults: ``none``; ``token_altered`` (``faulty_run.py``'s: the engine's answer
has its last token changed); ``state_unchanged`` (a decode step hands every
state-space layer's state back as it got it: the lane answers from the state
its prompt left); ``conv_tail_not_carried`` (a prompt chunk's convolution
starts from zeros instead of the lane's last three rows); ``padded_row_in_state``
(``qwen3_next_faulty_run.py``'s, on the shared code: the padding behind a
prompt's last chunk is taken for real rows); ``skip_dropped`` (``D x`` is left
out of the mixer's output)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tests import faulty_run                     # noqa: E402
from benchmark.tests import qwen3_next_faulty_run as linear  # noqa: E402


def state_unchanged():
    from veles_tpu.ops import linear_attn
    step = linear_attn.linear_paged_chunk_step

    def state_kept(p, x, state, tail, cfg, rows, **kw):
        out, new, new_tail = step(p, x, state, tail, cfg, rows, **kw)
        return out, (state if x.shape[1] == 1 else new), new_tail
    linear_attn.linear_paged_chunk_step = state_kept


def conv_tail_not_carried():
    from veles_tpu.ops import linear_attn
    convolve = linear_attn._convolve

    def from_zeros(tail, qkv, w, rows, **kw):
        return convolve(tail * 0 if qkv.shape[1] > 1 else tail, qkv, w, rows,
                        **kw)
    linear_attn._convolve = from_zeros


def skip_dropped():
    from veles_tpu.ops import linear_attn
    output = linear_attn._output

    def without_skip(p, o, z, cfg, x=None):
        return output(p, o, z, cfg, None if x is None else 0.0 * x)
    linear_attn._output = without_skip


FAULTS = {"none": lambda: None,
          "token_altered": faulty_run.token_altered,
          "state_unchanged": state_unchanged,
          "conv_tail_not_carried": conv_tail_not_carried,
          "padded_row_in_state": linear.padded_row_in_state,
          "skip_dropped": skip_dropped}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    from benchmark import run
    rest = sys.argv[2:]
    sys.exit(run.main([a for a in rest if a != "--chip"]
                      + ([] if "--chip" in rest else ["--rehearse"])))
