"""``faulty_run.py`` for the cell of the JoyAI-LLM-Flash configuration: one
rehearsal run of the harness with the timed path broken underneath, each fault
planted in the program, where the thing is produced.

    python3 benchmark/tests/joyai_faulty_run.py <fault> [--chip] --workload <cell> --seed <n> --seconds <s>

A rehearsal (the CPU, the tiny float32 sizes, where ``correct`` compares
exactly) unless ``--chip`` is given: then the run is the cell's own, at its
size and limits, to read what a fault leaves of the numbers there.

Faults: ``none``; ``hnorm_dropped`` (the module reads the last block's output
as it is, not normed); ``enorm_dropped`` (and the embedding as it is);
``eh_halves_swapped`` (the module's projection is given ``[rms(h) ;
rms(Emb)]``); ``rejected_row_left_live`` (a lane whose draft was
rejected moves two positions on all the same: the rejected draft's rows stay
in the pools, under every later row); ``accept_wrong_row`` (the draft is
compared with the pick after the SECOND row); ``speculation_off`` (the engine
is built without ``spec_k``, whatever the launcher passed).  The first three
leave every served token right (speculation is lossless whatever is drafted)
and show in the drafts (``mtp_drafts_off_share``)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _inputs(halves):
    """``mtp_inputs`` with the two halves as ``halves(e, h, mod, cfg)``
    makes them."""
    import jax.numpy as jnp
    from veles_tpu.ops import transformer as T

    def mtp_inputs(params, h, nxt, cfg):
        mod = params["mtp"][0]
        e = jnp.take(params["embed"], nxt, axis=0).astype(jnp.float32)
        x = jnp.concatenate(halves(e, h, mod, cfg), axis=-1)
        return T.cfg_matmul(cfg, x, mod["eh_proj"]).astype(jnp.float32)
    T.mtp_inputs = mtp_inputs


def hnorm_dropped():
    from veles_tpu.ops.transformer import rms_norm
    _inputs(lambda e, h, mod, cfg: [
        rms_norm(e, mod["enorm"], cfg.eps, cfg.dtype), h.astype(cfg.dtype)])


def enorm_dropped():
    from veles_tpu.ops.transformer import rms_norm
    _inputs(lambda e, h, mod, cfg: [
        e.astype(cfg.dtype), rms_norm(h, mod["hnorm"], cfg.eps, cfg.dtype)])


def eh_halves_swapped():
    from veles_tpu.ops.transformer import rms_norm
    _inputs(lambda e, h, mod, cfg: [
        rms_norm(h, mod["hnorm"], cfg.eps, cfg.dtype),
        rms_norm(e, mod["enorm"], cfg.eps, cfg.dtype)])


def _step(change):
    """``mtp_verify_step`` with what it hands on changed by ``change(draft
    fed, position fed, live, state, picked, count) -> (state, count)``."""
    from veles_tpu.ops import transformer as T
    real = T.mtp_verify_step

    def mtp_verify_step(params, pools, ptab, last, draft, pos, live, cfg,
                        attn_kernel=None):
        pools, state, picked, count, counts = real(
            params, pools, ptab, last, draft, pos, live, cfg,
            attn_kernel=attn_kernel)
        state, count = change(draft, pos, live, state, picked, count)
        return pools, state, picked, count, counts
    T.mtp_verify_step = mtp_verify_step


def rejected_row_left_live():
    import jax.numpy as jnp
    _step(lambda draft, pos, live, state, picked, count: (
        (state[0], state[1], jnp.where(live, pos + 2, pos)), count))


def accept_wrong_row():
    import jax.numpy as jnp

    def change(draft, pos, live, state, picked, count):
        accepted = live & (draft == picked[:, 1])
        count = jnp.where(live, 1 + accepted.astype(jnp.int32), 0)
        last = jnp.where(live, jnp.where(accepted, picked[:, 1],
                                         picked[:, 0]), state[0])
        return (last, state[1], pos + count), count
    _step(change)


def speculation_off():
    from veles_tpu.serving import lm_engine
    real = lm_engine.LMEngine.__init__

    def without(self, *args, **kwargs):
        kwargs["spec_k"] = 0
        real(self, *args, **kwargs)
    lm_engine.LMEngine.__init__ = without


FAULTS = {"none": lambda: None, "hnorm_dropped": hnorm_dropped,
          "enorm_dropped": enorm_dropped,
          "eh_halves_swapped": eh_halves_swapped,
          "rejected_row_left_live": rejected_row_left_live,
          "accept_wrong_row": accept_wrong_row,
          "speculation_off": speculation_off}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    from benchmark import run
    rest = sys.argv[2:]
    sys.exit(run.main([a for a in rest if a != "--chip"]
                      + ([] if "--chip" in rest else ["--rehearse"])))
