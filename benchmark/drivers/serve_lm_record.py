"""Driver for a served language model whose KIND the configuration states:
``serve_lm.Driver`` with the trainer built from the model's record
(``veles_tpu/model_config.py::from_published`` over the configuration's
published keys) instead of five sizes, the configuration handed to the
reference instead of a head count, and the launcher's ``--serve-max-new``
among the keywords (the table's answers are longer than its default).

It also keeps what every sample of ``/metrics.json`` said (the base driver
keeps three gauges), for the readers of the pools by kind.

The clients start ``start_gap_s`` (traffic file) apart, in index order, where
the base driver starts all threads at once: with 32 clients whose first
prompts reach the engine within one turn of its loop, the order in which they
were admitted (and so every lane's place in the round of prompt chunks for the
next ten seconds) was the thread scheduler's, and runs of ONE seed spread by
1.1 % (PERF.md section 6, PR 28).

``correct`` compares one number more than the base driver's three:
``served_tokens_off_share``, the share (%) of the checked served tokens that
are NOT the reference's choice (their reference logit lies more than a
roundoff tie, 1e-3, under the best).  A model whose activations are bfloat16
differs from the float32 reference by more than roundoff wherever a token's
router scores are near a tie (with weights from a seed the 256 scores are
close to uniform, so the fifth expert is often within a bfloat16 rounding of
the fourth): the widest gap of a run is then set by its unluckiest token and
cannot tell the program from the float8 control, while the share of tokens
off the reference's choice can (PERF.md, section 6, PR 28)."""

from __future__ import annotations

import time

from benchmark.lib import client as client_lib
from benchmark.lib.files import load_module

base = load_module("drivers", "serve_lm")

#: a served token whose reference logit lies this close under the best is
#: the reference's choice but for float32 roundoff (the accepted cell's
#: whole limit)
TIE = 1e-3


class WithConfig:
    """A reference whose ``token_gaps`` takes the configuration where the
    base driver passes the head count."""

    def __init__(self, reference, cfg):
        self.reference, self.cfg = reference, cfg
        self.make_weights = reference.make_weights
        self.served, self.lowered = [], []     # every call's gaps

    def token_gaps(self, weights, tokens, first, _heads, pad_to, rows_to,
                   control=None):
        served, low = self.reference.token_gaps(
            weights, tokens, first, self.cfg, pad_to, rows_to,
            control=control)
        self.served.append(served)
        if low is not None:
            self.lowered.append(low)
        return served, low


class InOrder(client_lib.ClosedLoop):
    """The closed loop with its clients started ``start_gap_s`` apart, in
    index order, so that the order of the first admissions is the table's."""

    def start(self):
        gap = float(self.traffic.get("start_gap_s", 0.0))
        for thread in self._threads:
            thread.start()
            time.sleep(gap)
        return self


def launcher_keywords(deployment):
    from veles_tpu.__main__ import build_argparser
    a = build_argparser().parse_args(
        ["workflow", "--serve", "0",
         "--serve-max-new", str(deployment["max_new"])])
    return dict(base.launcher_keywords(deployment), max_new=a.serve_max_new)


class Driver(base.Driver):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.reference = WithConfig(self.reference, self.cfg)
        self.samples = []            # (monotonic seconds, /metrics.json)

    def make_workflow(self):
        """A workflow whose LM trainer holds the record and the seeded
        weights: what ``--serve`` finds after a training run."""
        import jax
        from veles_tpu import model_config
        from veles_tpu.ops.nn_units import NNWorkflow
        from veles_tpu.ops.transformer import TransformerTrainer
        record = model_config.from_published(self.cfg)
        # the reference's weights are bfloat16 values; a configuration that
        # states another dtype (the float32 rehearsal) serves them raised
        # (to its own dtype ``astype`` hands the array back as it is)
        self.weights = jax.tree.map(
            lambda a: a.astype(record.dtype),
            self.reference.make_weights(self.ctx.seed, self.cfg))
        jax.block_until_ready(self.weights)
        wf = NNWorkflow(None, name="bench_lm")
        wf.trainer = TransformerTrainer(
            wf, vocab=self.cfg["vocab_size"],
            d_model=self.cfg["hidden_size"],
            n_layers=self.cfg["num_hidden_layers"],
            max_len=self.cfg["max_position_embeddings"],
            config=record)
        wf.trainer.params = self.weights
        return wf

    def setup(self):
        """``serve_lm.Driver.setup`` with ``max_new`` among the keywords."""
        from veles_tpu import compile_cache
        from veles_tpu.restful_api import serve_lm
        compile_cache.enable()
        wf = self.make_workflow()
        deployment = self.cfg["deployment"]
        self.api = serve_lm(wf, deadline_s=deployment["deadline_s"],
                            **launcher_keywords(deployment))
        self.base = "http://127.0.0.1:%d" % self.api.port
        self.clients = InOrder(
            self.base + "/predict", self.traffic, self.ctx.seed,
            self.cfg["vocab_size"]).start()
        time.sleep(float(self.traffic["lead_s"]))

    def snapshot(self):
        snap = super().snapshot()
        self.samples.append((time.monotonic(), snap))
        return snap

    def measure(self):
        art = super().measure()
        lo, hi = art["t_open"], art["t_open"] + art["window_s"]
        art["metrics_samples"] = [s for t, s in self.samples if lo <= t <= hi]
        return art

    def check(self, art, control=None):
        """The base driver's three numbers, and the share of the checked
        served tokens that are not the reference's choice."""
        import numpy
        self.reference.served, self.reference.lowered = [], []
        compared = super().check(art, control=control)

        def off_share(gaps):
            every = numpy.concatenate(gaps)
            return 100.0 * float((every > TIE).mean())

        compared["served_tokens_off_share"] = {
            "value": (off_share(self.reference.served)
                      if self.reference.served else None),
            "limit": self.cfg["limits"]["served_tokens_off_share"]}
        if self.reference.lowered:
            art["control"]["served_tokens_off_share"] = off_share(
                self.reference.lowered)
        return compared
