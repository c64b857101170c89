"""``faulty_run.py`` for the cell of the AFMoE configuration: one rehearsal
run of the harness with the timed path broken underneath, each fault planted
in the program, where the thing is produced.

    python3 benchmark/tests/afmoe_faulty_run.py <fault> [--chip] --workload <cell> --seed <n> --seconds <s>

A rehearsal (the CPU, the tiny float32 sizes, where ``correct`` compares
exactly) unless ``--chip`` is given: then the run is the cell's own, at its
size and limits, to read what a fault leaves of the numbers there.

Faults: ``none``; ``token_altered`` (``faulty_run.py``'s: the engine's answer
has its last token changed); ``window_page_reused`` (a page of the sliding
layers goes back to the allocator one page early, while its newest tokens
are still inside the lane's window, and is handed out again and written: the
lane's window reads a page short); ``expert_left_out`` (the first held
expert's part of the routed sum is dropped)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tests import faulty_run                     # noqa: E402


def window_page_reused():
    from veles_tpu.serving import kv_pool
    first_live = kv_pool.WindowTables.first_live

    def one_early(self, pos):
        return first_live(self, pos + self.page)
    kv_pool.WindowTables.first_live = one_early


def expert_left_out():
    import jax.numpy as jnp
    from veles_tpu.ops import moe
    held_part = moe.held_part

    def without_first(params, flat, idx, w, lo, n):
        return held_part(params, flat, idx, jnp.where(idx == lo, 0.0, w),
                         lo, n)
    moe.held_part = without_first


FAULTS = {"none": lambda: None,
          "token_altered": faulty_run.token_altered,
          "window_page_reused": window_page_reused,
          "expert_left_out": expert_left_out}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    from benchmark import run
    rest = sys.argv[2:]
    sys.exit(run.main([a for a in rest if a != "--chip"]
                      + ([] if "--chip" in rest else ["--rehearse"])))
