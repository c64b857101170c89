"""``faulty_run.py`` for the cell of the Ling-3.0-flash configuration: one
rehearsal run of the harness with the timed path broken underneath, each fault
planted in the program, where the thing is produced.

    python3 benchmark/tests/ling3_faulty_run.py <fault> [--chip] --workload <cell> --seed <n> --seconds <s>

A rehearsal (the CPU, the tiny float32 sizes, where ``correct`` compares
exactly) unless ``--chip`` is given: then the run is the cell's own, at its
size and limits, to read what a fault leaves of the numbers there.

Faults: ``none``; ``token_altered`` (``faulty_run.py``'s: the engine's answer
has its last token changed); ``decay_one_a_head`` (every key channel of a head
decays by the head's mean log decay: the rule of the other linear
configuration); ``conv_tail_not_carried`` and ``padded_row_in_state``
(``qwen3_next_faulty_run.py``'s, on the shared code); ``group_limit_dropped``
(the router chooses its experts over all groups); ``head_gate_dropped`` (the
latent layer's heads go to ``W_o`` without their sigmoid gate)."""

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tests import faulty_run                     # noqa: E402
from benchmark.tests import qwen3_next_faulty_run as linear  # noqa: E402


def decay_one_a_head():
    import jax.numpy as jnp
    linear._gates(lambda beta, g: (beta, jnp.broadcast_to(
        g.mean(-1, keepdims=True), g.shape)))


def group_limit_dropped():
    from veles_tpu.ops import moe
    route = moe.route

    def over_all(params, flat, record):
        return route(params, flat, dataclasses.replace(record, n_group=1,
                                                       topk_group=1))
    moe.route = over_all


def head_gate_dropped():
    from veles_tpu.ops import latent
    merge = latent._merge

    def without_gate(p, o, cfg, x):
        return merge(p, o, dataclasses.replace(cfg, latent=dataclasses.replace(
            cfg.latent, head_gate=False)), x)
    latent._merge = without_gate


FAULTS = {"none": lambda: None,
          "token_altered": faulty_run.token_altered,
          "decay_one_a_head": decay_one_a_head,
          "conv_tail_not_carried": linear.conv_tail_not_carried,
          "padded_row_in_state": linear.padded_row_in_state,
          "group_limit_dropped": group_limit_dropped,
          "head_gate_dropped": head_gate_dropped}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    from benchmark import run
    rest = sys.argv[2:]
    sys.exit(run.main([a for a in rest if a != "--chip"]
                      + ([] if "--chip" in rest else ["--rehearse"])))
