"""``faulty_run.py`` for the cell of the Qwen3-Next configuration: one
rehearsal run of the harness with the timed path broken underneath, each fault
planted in the program, where the thing is produced.

    python3 benchmark/tests/qwen3_next_faulty_run.py <fault> [--chip] --workload <cell> --seed <n> --seconds <s>

A rehearsal (the CPU, the tiny float32 sizes, where ``correct`` compares
exactly) unless ``--chip`` is given: then the run is the cell's own, at its
size and limits, to read what a fault leaves of the numbers there.

Faults: ``none``; ``token_altered`` (``faulty_run.py``'s: the engine's answer
has its last token changed); ``decay_dropped`` (the delta rule's ``g`` is 0:
the state never forgets); ``beta_one`` (every real row writes with ``beta =
1``); ``conv_tail_not_carried`` (a prompt chunk's convolution starts from
zeros instead of the lane's last three rows); ``padded_row_in_state`` (the
padding behind a prompt's last chunk is taken for real rows, so it moves the
state and the convolution tail); ``shared_sigmoid_dropped`` (the shared
expert's output is added without its gate)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tests import faulty_run                     # noqa: E402


def _gates(change):
    from veles_tpu.ops import linear_attn
    inputs = linear_attn._inputs

    def changed(p, x, cfg, cached):
        qkv, z, beta, g = inputs(p, x, cfg, cached)
        return (qkv, z) + change(beta, g)
    linear_attn._inputs = changed


def decay_dropped():
    _gates(lambda beta, g: (beta, 0.0 * g))


def beta_one():
    _gates(lambda beta, g: (0.0 * beta + 1.0, g))


def conv_tail_not_carried():
    from veles_tpu.ops import linear_attn
    convolve = linear_attn._convolve

    def from_zeros(tail, qkv, w, rows):
        return convolve(tail * 0 if qkv.shape[1] > 1 else tail, qkv, w, rows)
    linear_attn._convolve = from_zeros


def padded_row_in_state():
    import jax.numpy as jnp
    from veles_tpu.ops import linear_attn
    step = linear_attn.linear_paged_chunk_step

    def all_rows_real(p, x, state, tail, cfg, rows, **kw):
        if x.shape[1] > 1:
            rows = jnp.full_like(rows, x.shape[1])
        return step(p, x, state, tail, cfg, rows, **kw)
    linear_attn.linear_paged_chunk_step = all_rows_real


def shared_sigmoid_dropped():
    import dataclasses
    from veles_tpu.ops import moe
    routed_ffn = moe.routed_ffn

    def without_gate(params, x, record, matmul=None, router_in=None):
        return routed_ffn(params, x,
                          dataclasses.replace(record, shared_gate=False),
                          matmul, router_in)
    moe.routed_ffn = without_gate


FAULTS = {"none": lambda: None,
          "token_altered": faulty_run.token_altered,
          "decay_dropped": decay_dropped,
          "beta_one": beta_one,
          "conv_tail_not_carried": conv_tail_not_carried,
          "padded_row_in_state": padded_row_in_state,
          "shared_sigmoid_dropped": shared_sigmoid_dropped}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    from benchmark import run
    rest = sys.argv[2:]
    sys.exit(run.main([a for a in rest if a != "--chip"]
                      + ([] if "--chip" in rest else ["--rehearse"])))
