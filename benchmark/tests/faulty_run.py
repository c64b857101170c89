"""One rehearsal run of the harness (``run.py --rehearse``: no look for a
chip, everything else as in a run) with the timed path broken underneath.
Used by ``test_faults.py``; each fault is planted in the program, where the
thing is produced.

    python3 benchmark/tests/faulty_run.py <fault> --workload <cell> --seed <n> --seconds <s>

Faults: ``none``; ``token_altered`` (serving: the engine's answer has its last
token changed); ``state_unchanged`` (training: the step returns its state as
it got it); ``half_batch`` (training: half of every minibatch left out, the
mean taken over the rest)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def token_altered():
    import numpy
    from veles_tpu.serving import lm_engine
    generate = lm_engine.LMEngine.generate

    def altered(self, prompts, n_new, **kw):
        out = generate(self, prompts, n_new, **kw)
        toks = numpy.array(out[0] if isinstance(out, tuple) else out)
        toks[:, -1] = (toks[:, -1] + 1) % self.params["embed"].shape[0]
        return (toks,) + tuple(out[1:]) if isinstance(out, tuple) else toks
    lm_engine.LMEngine.generate = altered


def state_unchanged():
    from veles_tpu import compiled
    compiled.FusedRunner._apply_updates = (
        lambda self, state, all_grads, batch_size, step: list(state))


def half_batch():
    import jax.numpy as jnp
    from veles_tpu import compiled
    train_step = compiled.FusedRunner._train_step

    def half(self, state, x, y_ref, mask, batch_size, rng=None, step=0):
        mask = mask * (jnp.arange(mask.shape[0]) < mask.shape[0] // 2)
        return train_step(self, state, x, y_ref, mask,
                          mask.sum().astype(jnp.int32), rng, step)
    compiled.FusedRunner._train_step = half


FAULTS = {"none": lambda: None, "token_altered": token_altered,
          "state_unchanged": state_unchanged, "half_batch": half_batch}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    from benchmark import run
    sys.exit(run.main(sys.argv[2:] + ["--rehearse"]))
