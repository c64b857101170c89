"""The workflow file the training driver hands to the launcher:
``veles_tpu/samples/imagenet.py``'s own ``run``, with the benchmark's first
steps between building the workflow and booting it.  ``python -m veles_tpu
<this file> --epoch-scan ...`` is the ordinary way in; nothing of the program
is patched.  The first steps come from the driver through ``lib/handoff.py``
and run on the launcher's thread."""

from benchmark.lib import handoff
from veles_tpu.samples import imagenet


def run(load, main):
    kept = {}

    def load_and_keep(workflow_cls, **kwargs):
        kept["workflow"] = load(workflow_cls, **kwargs)
        return kept["workflow"]

    def first_steps_then_main():
        handoff.SLOT["first_steps"](kept["workflow"])
        main()

    imagenet.run(load_and_keep, first_steps_then_main)
