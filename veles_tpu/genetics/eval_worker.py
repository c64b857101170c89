"""Subprocess fitness evaluator for population-parallel genetics.

Ref: veles/genetics forked one process per individual (SURVEY §3.5); this
is that worker half: reads a JSON spec on stdin (config tree, gene values,
sample module, seed), trains the sample to its stopping criterion on the
HOST platform, and prints the fitness as one JSON line on stdout.
Individuals are screened on CPU workers in parallel; the winner re-trains
on the accelerator in the parent (see genetics.optimize_workflow).
"""

from __future__ import annotations

import importlib
import json
import sys


def main():
    spec = json.load(sys.stdin)
    import jax
    jax.config.update("jax_platforms", "cpu")  # the parent keeps the chip

    from veles_tpu.config import root
    from veles_tpu.genetics import set_leaf
    root.update(spec["config"])
    for path, value in spec["genes"].items():
        set_leaf(path, value)

    module = importlib.import_module(spec["module"])
    from veles_tpu.samples import run_sample
    wf = run_sample(module, seed=spec["seed"],
                    build_kwargs=spec.get("build_kwargs"))
    metric = wf.decision.best_metric
    print(json.dumps(
        {"fitness": None if metric is None else float(metric)}))


if __name__ == "__main__":
    main()
