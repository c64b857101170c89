"""Device milliseconds of one execution of the decode program
(``LMEngine._step_jit``, traced as ``jit_step_all``), averaged over its
executions in the traced window.  Layer: engine and model step."""

from benchmark.lib import readers


def read(art, ctx):
    return readers.module_mean_ms(art, "step_all")
