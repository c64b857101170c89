"""Subprocess member trainer for parallel ensembles.

Ref: veles/ensemble evaluated member runs across slaves (SURVEY §2.1/§3.5);
this worker is one member: reads a JSON spec on stdin (config tree, sample
module, seed, snapshot path), trains on the HOST platform, pickles the
full workflow snapshot state to ``snapshot_path`` and prints the member
summary as one JSON line.  The parent restores the snapshot into its own
workflow instance, so parallel members are indistinguishable from
sequentially-trained ones.
"""

from __future__ import annotations

import importlib
import json
import pickle
import sys


def main():
    spec = json.load(sys.stdin)
    import jax
    jax.config.update("jax_platforms", "cpu")  # the parent keeps the chip

    from veles_tpu.config import root
    root.update(spec["config"])
    module = importlib.import_module(spec["module"])
    from veles_tpu.samples import run_sample
    wf = run_sample(module, seed=spec["seed"],
                    build_kwargs=spec.get("build_kwargs"))
    import veles_tpu
    from veles_tpu import snapshotter
    payload = {
        "format": snapshotter.FORMAT,
        "framework_version": veles_tpu.__version__,
        "workflow_name": wf.name,
        "epoch": int(wf.loader.epoch_number),
        "best_metric": wf.decision.best_metric,
        "state": wf.snapshot_state(),
        "config": root.as_dict(),
    }
    with open(spec["snapshot_path"], "wb") as f:
        pickle.dump(payload, f, protocol=pickle.HIGHEST_PROTOCOL)
    print(json.dumps({"seed": spec["seed"],
                      "best_metric": wf.decision.best_metric,
                      "best_epoch": wf.decision.best_epoch}))


if __name__ == "__main__":
    main()
