"""``faulty_run.py`` for the cell of the Xing4.0 configuration: one rehearsal
run of the harness with the timed path broken underneath, each fault planted
in the program, where the thing is produced.

    python3 benchmark/tests/xing4_faulty_run.py <fault> [--chip] --workload <cell> --seed <n> --seconds <s>

A rehearsal (the CPU, the tiny float32 sizes, where ``correct`` compares
exactly) unless ``--chip`` is given: then the run is the cell's own, at its
size and limits, to read what a fault leaves of the numbers there.

Faults: ``none``; ``token_altered`` (``faulty_run.py``'s: the engine's answer
has its last token changed); ``latent_row_wrong_page`` (a decode step's latent
row goes through the table one entry off, so the page the lane reads lacks
it); ``k_rope_unrotated`` (the cached rows keep the shared key as projected,
not rotated); ``h_res_identity`` (the streams' mixing matrix replaced by the
identity: each stream keeps itself whole); ``shared_expert_dropped`` (the
routed layers leave the shared expert out)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tests import faulty_run                     # noqa: E402


def latent_row_wrong_page():
    import jax.numpy as jnp
    from veles_tpu.ops import latent
    paged_write = latent.paged_write

    def one_entry_off(pool, ptab, pos, rows, write_mask=None, kernel=False):
        return paged_write(pool, jnp.roll(ptab, 1, axis=-1), pos, rows,
                           write_mask, kernel=kernel)
    latent.paged_write = one_entry_off


def k_rope_unrotated():
    import jax.numpy as jnp
    from veles_tpu.ops import latent
    latent_rows = latent.latent_rows

    def as_projected(p, x, cfg, cos, sin, cached=False):
        return latent_rows(p, x, cfg, jnp.ones_like(cos),
                           jnp.zeros_like(sin), cached)
    latent.latent_rows = as_projected


def h_res_identity():
    import jax.numpy as jnp
    from veles_tpu.ops import hyper

    def identity(m, iters, eps):
        return jnp.broadcast_to(jnp.eye(m.shape[0], dtype=m.dtype)[..., None],
                                m.shape)
    hyper._sinkhorn = identity


def shared_expert_dropped():
    import dataclasses
    from veles_tpu.ops import moe
    routed_ffn = moe.routed_ffn

    def without_shared(params, x, record, matmul=None, router_in=None):
        return routed_ffn(params, x,
                          dataclasses.replace(record, shared=False),
                          matmul, router_in)
    moe.routed_ffn = without_shared


FAULTS = {"none": lambda: None,
          "token_altered": faulty_run.token_altered,
          "latent_row_wrong_page": latent_row_wrong_page,
          "k_rope_unrotated": k_rope_unrotated,
          "h_res_identity": h_res_identity,
          "shared_expert_dropped": shared_expert_dropped}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    from benchmark import run
    rest = sys.argv[2:]
    sys.exit(run.main([a for a in rest if a != "--chip"]
                      + ([] if "--chip" in rest else ["--rehearse"])))
