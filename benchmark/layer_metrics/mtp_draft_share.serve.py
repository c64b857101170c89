"""Share (%) of the decode program's device time that is the multi-token-
prediction module's: in every execution of ``jit_step_all`` in the traced
window, from the start of the first operation named by the scope
``mtp.draft`` (the module's attention's first Pallas call, its row write: the
trace names the compiler's own operations by opcode and shape, a Pallas call
by the innermost scope around it) to the execution's end, over the
executions' time.  The module runs behind the verification it depends on and
nothing but the lanes' new state follows it, so the stretch is its expert
layer, its final norm, the head over its rows and the pick; what comes before
its first kernel (``W_eh``, two norms, the projections of its attention:
under a hundredth of the module) is missed.  1/17 of the layers and a second
pass over the head: about a tenth is expected at this depth, a twentieth at
the published 40 layers.  Layer: engine and model step."""

import bisect

from benchmark.lib import latent


def read(art, ctx):
    trace = art.get("trace")
    if not trace or not trace["devices"]:
        return None
    dev = trace["devices"][0]
    runs = sorted((m for m in dev["modules"] if m.name in latent.DECODE),
                  key=lambda m: m.start)
    starts = [m.start for m in runs]
    first = {}
    for op in dev["ops"]:
        if op.module in latent.DECODE and op.name.startswith("mtp "):
            # (the execution an operation lies in: the last that began
            # before it)
            i = bisect.bisect_right(starts, op.start) - 1
            if i >= 0:
                first[i] = min(first.get(i, op.start), op.start)
    if not first:
        return None
    total = sum(runs[i].dur for i in first)
    module = sum(runs[i].start + runs[i].dur - at for i, at in first.items())
    return 100.0 * module / total if total else None
