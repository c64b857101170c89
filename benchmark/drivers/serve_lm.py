"""Driver for a served language model: ``restful_api.serve_lm`` with the
keywords the launcher passes (``veles_tpu/__main__.py``), on a workflow whose
trainer holds weights the benchmark made from the seed; closed-loop HTTP
clients from the traffic file.

The launcher's keywords are taken from the launcher's own argument parser,
so a later PR that changes a default is measured.  The one keyword set here
that the launcher has no flag for is ``deadline_s`` (stated in the
configuration's ``deployment``)."""

from __future__ import annotations

import gc
import json
import time
import urllib.request

import numpy

from benchmark.lib import client as client_lib
from benchmark.lib.files import load_module


def launcher_keywords(deployment):
    """serve_lm's keywords as ``python -m veles_tpu <wf> --serve ...`` would
    pass them (``__main__.py``, the ``serve_lm(...)`` call), from the
    launcher's own parser and this deployment's flags."""
    from veles_tpu.__main__ import build_argparser
    flags = ["workflow", "--serve", "0",
             "--serve-slots", str(deployment["slots"]),
             "--serve-paged-kv", str(deployment["paged_kv"]),
             "--serve-prefill-chunk", str(deployment["prefill_chunk"]),
             "--serve-attn-kernel", str(deployment["attn_kernel"])]
    a = build_argparser().parse_args(flags)
    return dict(
        port=a.serve, slots=a.serve_slots,
        prefix_cache=a.serve_prefix_cache,
        prefill_chunk=a.serve_prefill_chunk, spec_k=a.serve_spec_k,
        paged_kv=(True if a.serve_paged_kv < 0 else a.serve_paged_kv),
        attn_kernel=(0 if a.serve_attn_kernel == "off"
                     else a.serve_attn_kernel),
        megastep=a.serve_megastep, tp=a.serve_tp,
        replicas=a.serve_replicas, router=a.serve_router,
        health=a.serve_health, hedge=a.serve_hedge,
        retries=a.serve_retries, fault_plan=None,
        model_dir=a.serve_model_dir,
        publish_interval_s=a.serve_publish_interval,
        canary=a.serve_canary, canary_watch_s=a.serve_canary_watch,
        trace=a.serve_trace, trace_last=a.serve_trace_last,
        telemetry=a.serve_telemetry,
        slo=(True if a.serve_slo == "default" else a.serve_slo),
        auto_rollback=not a.serve_no_auto_rollback)


def get_json(url):
    with urllib.request.urlopen(url, timeout=30) as resp:
        return json.loads(resp.read())


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.traffic = ctx.traffic
        self.api = None
        self.clients = None
        self.weights = None
        self.reference = load_module("reference", self.cfg["reference"])

    # ------------------------------------------------------------- set-up
    def make_workflow(self):
        """A workflow whose LM trainer holds the seeded weights: what
        ``--serve`` finds after a training run."""
        import jax
        from veles_tpu.ops.nn_units import NNWorkflow
        from veles_tpu.ops.transformer import TransformerTrainer
        self.weights = self.reference.make_weights(self.ctx.seed, self.cfg)
        jax.block_until_ready(self.weights)
        wf = NNWorkflow(None, name="bench_lm")
        wf.trainer = TransformerTrainer(
            wf, vocab=self.cfg["vocab_size"],
            d_model=self.cfg["hidden_size"],
            n_heads=self.cfg["num_attention_heads"],
            n_layers=self.cfg["num_hidden_layers"],
            max_len=self.cfg["max_position_embeddings"])
        wf.trainer.params = self.weights
        return wf

    def setup(self):
        from veles_tpu import compile_cache
        from veles_tpu.restful_api import serve_lm
        compile_cache.enable()
        wf = self.make_workflow()
        deployment = self.cfg["deployment"]
        self.api = serve_lm(wf, deadline_s=deployment["deadline_s"],
                            **launcher_keywords(deployment))
        self.base = "http://127.0.0.1:%d" % self.api.port
        self.clients = client_lib.ClosedLoop(
            self.base + "/predict", self.traffic, self.ctx.seed,
            self.cfg["vocab_size"]).start()
        # the window opens a fixed lead after the clients start, with every
        # lane decoding; the lead is set-up
        time.sleep(float(self.traffic["lead_s"]))

    # ------------------------------------------------------------- window
    def snapshot(self):
        return get_json(self.base + "/metrics.json")

    def measure(self):
        ctx = self.ctx
        sample_s = float(self.traffic["gauge_sample_s"])
        compiles0 = ctx.compiles()
        t_open = time.monotonic()
        tok0 = int(self.snapshot()["counters"].get("tokens_out", 0))
        t_close = t_open + ctx.seconds
        trace_at = (t_open + min(float(self.traffic["trace_offset_s"]),
                                 ctx.seconds / 4) if ctx.trace else None)
        trace_until = trace_window_s = trace_host_window = None
        gauges = []                   # the scheduler's gauges, sampled
        while True:
            now = time.monotonic()
            if now >= t_close:
                break
            if trace_at is not None and now >= trace_at:
                trace_at, trace_begin = None, now
                ctx.start_trace()
                trace_until = now + min(float(self.traffic["trace_s"]),
                                        max(t_close - now - 0.5, 0.25))
            elif trace_until is not None and now >= trace_until:
                trace_until = None
                trace_window_s = ctx.stop_trace()
                trace_host_window = (trace_begin, time.monotonic())
            else:
                gauges.append(self.snapshot()["gauges"])
            time.sleep(max(0.0, min(sample_s, t_close - time.monotonic())))
        if trace_until is not None:
            trace_window_s = ctx.stop_trace()
            trace_host_window = (trace_begin, time.monotonic())
        tok1 = int(self.snapshot()["counters"].get("tokens_out", 0))
        t_end = time.monotonic()
        compiles = ctx.compiles() - compiles0
        # no new requests; the replies in flight come back before the
        # engine can stop and give the chip's memory to the reference
        stuck = self.clients.stop(float(self.traffic["request_timeout_s"]))
        log = self.clients.snapshot()

        done = [r for r in log if r["t_done"] is not None
                and t_open <= r["t_done"] <= t_end]
        ok = [r for r in done if r["class"] == "ok"
              and r["tokens"] is not None
              and len(r["tokens"]) == r["prompt_len"] + r["n_new"]]
        window_s = t_end - t_open
        # tokens_out counts tokens as they are emitted: at least those of the
        # replies completed in the window less what those requests had
        # already emitted before it opened, at most that plus every request
        # in flight at either edge
        completed = sum(r["n_new"] for r in ok)
        edge = sum(r["n_new"] for r in log
                   if (r["t_send"] < t_open and
                       (r["t_done"] is None or r["t_done"] >= t_open))
                   or (r["t_send"] < t_end and
                       (r["t_done"] is None or r["t_done"] > t_end)))
        emitted = tok1 - tok0
        lat = [(r["t_done"] - r["t_send"]) * 1e3 for r in ok]
        return {
            "t_open": t_open, "window_s": window_s,
            "trace_window_s": trace_window_s,
            "trace_host_window": trace_host_window,
            "attempted": len(done), "failed": len(done) - len(ok),
            "end_to_end": {"out_tok_s": emitted / window_s},
            "client_log": log, "ok": ok, "latencies_ms": lat,
            "counters": {"tokens_out": emitted, "completed_tokens": completed,
                         "edge_tokens": edge, "compiles": compiles,
                         "clients_stuck": stuck,
                         "slots": int(self.cfg["deployment"]["slots"]),
                         "slots_busy": [g.get("slots_busy", 0)
                                        for g in gauges],
                         "queue_depth": [g.get("queue_depth", 0)
                                         for g in gauges],
                         "kv_pages_free": [g.get("kv_pages_free")
                                           for g in gauges]},
        }

    # ------------------------------------------------- release and compare
    def release(self):
        """Stop the server and free every device buffer the program held."""
        import jax
        self.api.stop()
        self.api = None
        self.weights = None
        gc.collect()
        for a in jax.live_arrays():
            a.delete()

    def sample(self, ok):
        """The requests the reference follows: the longest the window
        finished, and others drawn from the seed, up to the traffic file's
        ``check_requests``."""
        want = int(self.traffic["check_requests"])
        order = sorted(ok, key=lambda r: (r["client"], r["index"]))
        if not order:
            return []
        longest = max(order, key=lambda r: r["prompt_len"] + r["n_new"])
        rest = [r for r in order if r is not longest]
        rng = numpy.random.default_rng([int(self.ctx.seed), 7])
        picks = rng.permutation(len(rest))[:max(want - 1, 0)]
        return [longest] + [rest[i] for i in sorted(picks)]

    def check(self, art, control=None):
        """The widest gap by which a served token's reference logit lies
        below the reference's best, over the sampled requests (greedy, so the
        served token should BE the best); the round trip's prompt echo; the
        tokens_out cross-check."""
        reference = self.reference
        limits = self.cfg["limits"]
        counters = art["counters"]
        weights = reference.make_weights(self.ctx.seed, self.cfg)
        pad_to = max(p + n for p, n in self.traffic["table"])
        rows_to = max(n for _, n in self.traffic["table"])
        gaps, lowered, echo_bad = [], [], 0
        for r in self.sample(art["ok"]):
            if r["tokens"][:r["prompt_len"]] != r["prompt"]:
                echo_bad += 1
            served, low = reference.token_gaps(
                weights, r["tokens"], r["prompt_len"],
                self.cfg["num_attention_heads"], pad_to, rows_to,
                control=control)
            gaps.append(served)
            if low is not None:
                lowered.append(low)
        low_cross = counters["completed_tokens"] - counters["edge_tokens"]
        high_cross = counters["completed_tokens"] + counters["edge_tokens"]
        cross_miss = max(low_cross - counters["tokens_out"],
                         counters["tokens_out"] - high_cross, 0)
        art["checked"] = {"requests": len(gaps),
                          "tokens": int(sum(len(g) for g in gaps))}
        if lowered:
            every = numpy.concatenate(lowered)
            art["control"] = {
                "served_token_gap": float(every.max()),
                "tokens_changed": int((every > 0).sum())}
        return {
            "served_token_gap": {
                "value": (float(numpy.concatenate(gaps).max())
                          if gaps else None),
                "limit": limits["served_token_gap"]},
            "prompt_echo_mismatches": {"value": echo_bad, "limit": 0},
            "tokens_out_cross_check_miss": {"value": cross_miss, "limit": 0},
        }
