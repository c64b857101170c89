"""LM serving fast-path bench (ISSUE 4): TTFT, tokens/s, dispatches/token.

Measures the three fast-path features of ``veles_tpu.serving.LMEngine``
— radix prefix cache, chunked prefill, prompt-lookup speculative
decoding — each toggled against the same two workloads, and reports the
numbers docs/PERF.md records:

- ``shared_prefix``: 8 requests sharing a system-prompt prefix
  (``tools/load_gen.py::lm_prompts`` — the ONE prompt generator the
  serving load tests and this bench share), measuring prefilled-token
  count, prefix-cache hit tokens, and TTFT;
- ``repetitive``: structured/repetitive prompts (the prompt-lookup
  -friendly shape: templated text, code, logs), measuring decode
  dispatches per generated token and tokens/s.

The ``paged`` legs (ISSUE 6) run pages of ``chunk`` tokens (the
``baseline`` / ``spec`` legs the engine's default page) on the same
workloads plus a ``mixed_length`` one, and report the memory facts: KV
bytes resident, pages served by reference on prefix hits, and
copy-on-write copies.

The SHARDED legs (ISSUE 8) run every workload tensor-parallel
(``tp2`` — one engine over a 2-device mesh), data-parallel
(``replicas2`` — 2 engines behind the metrics-driven router, with
per-replica routing counts, queue-depth spread and balance ratio) and
stacked (``tp2_replicas2`` — 4 devices), each streaming the same
bench-style summary line; every record carries ``devices`` and
``mfu_per_device`` so fleet utilization reads honestly.  On a
single-device host these legs bank ``skipped`` records; ``--devices
N`` forces an N-device CPU dryrun host (the MULTICHIP suite's
forced-host-device-count gear).

The MEGASTEP legs (ISSUE 13) run the fused K-tokens-per-dispatch
decode program on every workload — ``megastep`` (plain greedy, K=16)
and ``megastep_all`` (K=8 stacked with the prefix cache, chunked
prefill and in-graph speculation) — streaming the dispatches/token
column per leg and ASSERTING < 0.1 on the single-lane greedy legs
(vs the 0.547 best single-lane record the megastep replaces), plus
``megastep_waste_frac`` (lane-iterations run frozen past a lane's
early exit) so the K tradeoff is measured, not guessed.

Every leg ALSO asserts its outputs bit-identical to the direct greedy
``ops/transformer.py::generate`` — a fast path that changed tokens
would be a bug, not a speedup, so the bench refuses to report it.

A full summary JSON line (``summary_record`` — the same record shape
as ``bench.py``) streams to stdout after EVERY completed leg,
last-line-wins: a run killed by an outer watchdog still banks a
parseable record.

Standalone (CPU is fine; the dispatches/token and hit-rate evidence is
platform-independent, wall-clock numbers scale with the platform)::

    python tools/lm_bench.py [--smoke] [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from load_gen import lm_prompts  # noqa: E402

# THE FLOPs/MFU model moved to veles_tpu/serving/timeseries.py
# (ISSUE 14): the live mfu_live gauge and the bench's per-leg MFU
# column must read the same numerator/denominator — re-exported here
# so every existing consumer keeps its import path
from veles_tpu.serving.timeseries import (  # noqa: E402,F401
    CPU_NOMINAL_FLOPS, TPU_PEAK_FLOPS, decode_flops_per_token,
    peak_flops_estimate)


def build_params(vocab=32, d_model=64, n_heads=4, n_layers=2,
                 max_len=256, seed=7):
    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.ops.transformer import init_transformer_params
    prng.reset()
    prng.seed_all(seed)
    host = init_transformer_params(prng.get("init"), vocab,
                                   d_model=d_model, n_heads=n_heads,
                                   n_layers=n_layers, max_len=max_len)
    return jax.tree.map(jnp.asarray, host)


def repetitive_prompts(n, vocab, length, seed=3):
    """Prompt-lookup-friendly prompts: a short random motif tiled to
    ``length`` (templated text / logs / code shape) — the n-gram draft
    finds the motif's continuation almost every step."""
    rng = numpy.random.RandomState(seed)
    out = []
    for _ in range(n):
        motif = rng.randint(0, vocab, rng.randint(4, 9))
        reps = length // len(motif) + 1
        out.append(numpy.tile(motif, reps)[:length].tolist())
    return out


def mixed_length_prompts(n, vocab, lo, hi, seed=13):
    """Lengths spread uniformly across [lo, hi] — the distribution
    where per-lane paging pays: a lane reserves its own span of pages,
    not the worst case."""
    rng = numpy.random.RandomState(seed)
    return [rng.randint(0, vocab, int(length)).tolist()
            for length in rng.randint(lo, hi + 1, n)]


def expected_rows(params, prompts, n_new, n_heads, max_len):
    import jax.numpy as jnp
    from veles_tpu.ops.transformer import generate
    return [numpy.asarray(generate(
        params, jnp.asarray([p], jnp.int32), n_new, n_heads,
        temperature=0.0, max_len=max_len))[0] for p in prompts]


def _emulate_device_latency(engines, seconds):
    """Wrap each engine's decode/verify/chunk dispatch with a
    block-until-ready + sleep — the DEVICE-BOUND serving regime on a
    CPU dryrun host.  On a real accelerator the engine worker thread
    idle-waits on the device per dispatch, which is exactly what
    data-parallel replicas overlap; on a shared-CPU dryrun box the
    'device' compute competes for the same cores, so raw replica legs
    measure core contention, not the router.  This emulation restores
    the regime the layer is FOR, and is always labeled
    (``emulated_step_latency_s``) in the records it touches."""
    import time as time_mod

    import jax

    def wrap(fn):
        def wrapped(*args):
            out = fn(*args)
            jax.block_until_ready(out)
            time_mod.sleep(seconds)
            return out
        return wrapped

    for engine in engines:
        for name in ("_step_jit", "_verify_jit", "_chunk_jit",
                     "_megastep_jit"):
            fn = getattr(engine, name, None)
            if fn is not None:
                setattr(engine, name, wrap(fn))


def run_leg(params, n_heads, max_len, prompts, n_new, expect,
            slots=4, flops_per_token=None, step_latency_s=0.0,
            **engine_kw):
    """One engine config over one prompt list; returns the metrics
    record (parity asserted, not reported on faith), including the
    MFU column (``flops_per_token`` × warm tokens/s over the
    platform's peak — ISSUE 7's the-gap-is-kernel-shaped metric) and,
    on ``attn_kernel`` legs, which attention path actually ran.

    SHARDED legs (ISSUE 8): ``tp=N`` runs the engine tensor-parallel
    over an N-device mesh; ``replicas=R`` builds R engines (each on
    its own device slice) behind the metrics-driven Router and the
    record gains per-replica routing/queue-depth facts plus
    ``mfu_per_device`` (MFU against the FLEET's peak — devices ×
    single-device peak).  A leg the host cannot seat (too few
    devices) returns a ``skipped`` record instead of crashing the
    bench: on CPU, ``--devices N`` forces an N-device dryrun host.

    The workload runs TWICE: the COLD pass supplies the prefill /
    prefix-cache accounting (what a first arrival of this traffic
    costs — the 7/8-hit acceptance shape), then metrics are reset and
    the WARM pass supplies wall/TTFT/dispatch numbers — non-chunked
    engines compile prompt-bucket programs lazily, and timing a
    steady-state serving claim through one-off compiles would hand the
    chunked legs an unearned 10x."""
    import jax
    from veles_tpu.serving import (LMEngine, Router, ServingMetrics,
                                   replica_device_slices)
    tp = int(engine_kw.pop("tp", 0) or 0)
    replicas = int(engine_kw.pop("replicas", 1) or 1)
    trace = bool(engine_kw.pop("trace", False))
    n_devices = max(1, replicas) * max(1, tp)
    features = {k: v for k, v in engine_kw.items() if v}
    if tp:
        features["tp"] = tp
    if replicas > 1:
        features["replicas"] = replicas
    tracer = None
    if trace:
        # the TRACED legs (ISSUE 12): one shared tracer across the
        # fleet, every request retained — after the run the span trees
        # must VERIFY (one root per request, no orphans, no unclosed
        # spans) or the leg fails; the ring is sized so closed-loop
        # admission retries cannot evict real requests
        from veles_tpu.serving import SpanTracer
        features["trace"] = True
        tracer = SpanTracer(mode="all",
                            last=8 * max(1, len(prompts)) + 64)
    if n_devices > 1 and jax.device_count() < n_devices:
        # recorded, never silent: a truncated matrix must say so
        return {"features": features,
                "skipped": "needs %d devices, have %d (CPU: rerun "
                           "with --devices %d under JAX_PLATFORMS="
                           "cpu)" % (n_devices, jax.device_count(),
                                     n_devices)}
    # the SAME replica→devices mapping serve_lm ships
    slices = (replica_device_slices(replicas, tp)
              if replicas > 1 else None)

    def build(idx=None, tag="lm_bench"):
        devices = None
        labels = None
        if idx is not None:
            devices = slices[idx]
            labels = {"replica": str(idx)}
        return LMEngine(params, n_heads=n_heads, max_len=max_len,
                        slots=slots, queue_depth=max(64, len(prompts)),
                        metrics=ServingMetrics(tag, labels=labels),
                        tp=tp, devices=devices, tracer=tracer,
                        name=tag if idx is None else "%s_r%d"
                        % (tag, idx), **engine_kw)

    if replicas > 1:
        engines = [build(i) for i in range(replicas)]
        server = Router(engines,
                        metrics=ServingMetrics("lm_bench_router"),
                        tracer=tracer)
    else:
        engines = [build()]
        server = engines[0]
    server.start()
    if step_latency_s:
        _emulate_device_latency(engines, step_latency_s)
        features["emulated_step_latency_s"] = step_latency_s

    def fresh_metrics(tag):
        for i, e in enumerate(engines):
            e.metrics = ServingMetrics(
                tag, labels={"replica": str(i)} if replicas > 1
                else None)
        if replicas > 1:
            server.metrics = ServingMetrics(tag + "_router")

    def combined_snapshot():
        """Aggregate the fleet: counters summed, histogram sums/counts
        summed (for the TTFT mean), peaks summed (aggregate
        concurrency), plus the raw per-replica snapshots."""
        snaps = [e.metrics.snapshot() for e in engines]
        counters = {}
        for s in snaps:
            for k, v in s["counters"].items():
                counters[k] = counters.get(k, 0) + v
        ttft_n = sum(s["ttft"]["count"] for s in snaps)
        return {
            "counters": counters,
            "ttft_mean": (sum(s["ttft"]["sum"] for s in snaps)
                          / ttft_n if ttft_n else 0.0),
            "slots_busy_peak": sum(
                int(s["gauges"].get("slots_busy_peak", 0))
                for s in snaps),
            "queue_depth_peaks": [
                int(s["gauges"].get("queue_depth_peak", 0))
                for s in snaps],
            "per_replica": snaps,
        }

    def submit_retrying(p):
        """Closed-loop admission: a 429 (queue or pool pressure) backs
        off per Retry-After and resubmits — large --requests against a
        small pool must measure throughput, not crash the leg (the
        single-lane paged pool admits ~3 requests' pages at a time)."""
        from veles_tpu.serving import Overloaded
        deadline = time.monotonic() + 600
        while True:
            try:
                return server.submit(p, n_new)
            except Overloaded as e:
                if time.monotonic() > deadline:
                    raise
                time.sleep(min(getattr(e, "retry_after", 0.05), 0.25))

    def one_pass():
        t0 = time.monotonic()
        futures = [submit_retrying(p) for p in prompts]
        rows = [f.result(timeout=600) for f in futures]
        wall = time.monotonic() - t0
        for p, row, exp in zip(prompts, rows, expect):
            got = numpy.concatenate([p, row])
            if not numpy.array_equal(got, exp):
                raise AssertionError(
                    "fast-path output diverged from greedy generate "
                    "for prompt of length %d under %r"
                    % (len(p), features))
        return wall, combined_snapshot()

    try:
        _, cold = one_pass()
        fresh_metrics("lm_bench_warm")
        wall, warm = one_pass()
        cc, c = cold["counters"], warm["counters"]
        tokens = c.get("tokens_out", 0)
        dispatches = c.get("decode_dispatches", 0)
        if features.get("attn_kernel"):
            from veles_tpu.ops.pallas_kernels import on_tpu
            if not on_tpu() and features["attn_kernel"] != "force" \
                    and not c.get("attn_kernel_fallbacks"):
                # the CPU acceptance criterion: the fallback path must
                # be EXERCISED and METERED, not silently absent
                raise AssertionError(
                    "attn_kernel leg on CPU did not increment the "
                    "fallback counter under %r" % (features,))
        megastep_cols = {}
        if features.get("megastep"):
            lane_iters = c.get("megastep_lane_iterations", 0)
            waste_frac = (
                round(c.get("megastep_wasted_iterations", 0)
                      / lane_iters, 4) if lane_iters else None)
            megastep_cols = {
                "megastep_dispatches": c.get("megastep_dispatches", 0),
                "megastep_tokens": c.get("megastep_tokens", 0),
                # tokens wasted to early-exit masking: the fraction of
                # lane-iterations the fused program ran frozen — the
                # measured cost side of the K tradeoff
                "megastep_waste_frac": waste_frac,
            }
            if slots == 1 and n_new >= 32 \
                    and int(features["megastep"]) >= 8:
                # THE acceptance criterion (ISSUE 13): single-lane
                # greedy at K >= 8 must measure < 0.1 dispatches per
                # token — asserted, not reported on faith
                dpt = (dispatches / tokens) if tokens else None
                if dpt is None or dpt >= 0.1:
                    raise AssertionError(
                        "megastep leg measured %s dispatches/token "
                        "(acceptance bound < 0.1) under %r"
                        % (dpt, features))
        tps = tokens / wall if wall else 0.0
        peak, peak_src = peak_flops_estimate()
        mfu = (tps * flops_per_token / peak
               if flops_per_token else None)
        record = {
            "features": features,
            "requests": len(prompts),
            "tokens_out": tokens,
            "wall_s": round(wall, 4),
            "tokens_per_sec": round(tps, 1),
            # the ISSUE 7 column: model FLOPs actually flowing over the
            # platform's advertised peak — the kernel-vs-XLA legs read
            # off against each other here.  ``mfu`` stays against ONE
            # device's peak (comparable across every leg);
            # ``mfu_per_device`` divides by the leg's device count —
            # the honest utilization of a sharded/replicated fleet
            "mfu": round(mfu, 6) if mfu is not None else None,
            "mfu_per_device": (round(mfu / n_devices, 6)
                               if mfu is not None else None),
            "devices": n_devices,
            "mfu_peak_source": peak_src,
            "attn_kernel_dispatches": c.get("attn_kernel_dispatches",
                                            0),
            "attn_kernel_fallbacks": c.get("attn_kernel_fallbacks", 0),
            "decode_dispatches": dispatches,
            "dispatches_per_token": (round(dispatches / tokens, 3)
                                     if tokens else None),
            # cold-pass facts: what FIRST arrivals of this traffic cost
            "prefill_tokens": cc.get("prefill_tokens", 0),
            "prefix_hit_tokens": cc.get("prefix_hit_tokens", 0),
            "draft_tokens": c.get("draft_tokens", 0),
            "draft_accepted": c.get("draft_accepted", 0),
            "draft_accept_rate": (
                round(c["draft_accepted"] / c["draft_tokens"], 3)
                if c.get("draft_tokens") else None),
            "ttft_mean_s": round(warm["ttft_mean"], 5),
            # KV memory facts: device KV footprint, pages served by
            # reference on prefix hits (cold pass), copy-on-write
            # count, and the peak concurrent lanes the pool sustained
            "kv_bytes_resident": sum(e.kv_bytes_resident()
                                     for e in engines),
            "kv_pages_referenced": cc.get("kv_pages_referenced", 0),
            "kv_cow_copies": (cc.get("kv_cow_copies", 0)
                              + c.get("kv_cow_copies", 0)),
            "slots_busy_peak": warm["slots_busy_peak"],
            "parity_vs_generate": True,     # asserted above, both passes
        }
        record.update(megastep_cols)
        if replicas > 1:
            # router evidence: server-side placement counts (includes
            # requeues), the queue-depth high-water spread across the
            # fleet, and per-replica warm tokens
            routed = server.routed_counts()
            record["replica_routed"] = routed
            record["replica_balance_ratio"] = (
                round(max(routed) / min(routed), 3)
                if min(routed) else None)
            record["replica_queue_depth_peak"] = \
                warm["queue_depth_peaks"]
            record["replica_queue_depth_spread"] = (
                max(warm["queue_depth_peaks"])
                - min(warm["queue_depth_peaks"]))
            record["replica_tokens_out"] = [
                s["counters"].get("tokens_out", 0)
                for s in warm["per_replica"]]
        if tracer is not None:
            # span-tree integrity is an ASSERTION, not a report: every
            # request rooted, no orphans, no unclosed spans — under
            # whatever fast-path combination this leg ran — and the
            # Chrome export must be strict-parseable JSON
            from veles_tpu.serving import cost_ledger, verify_integrity
            recs = tracer.requests()
            integrity = verify_integrity(recs)
            if integrity["requests"] < 2 * len(prompts):
                raise AssertionError(
                    "traced leg retained %d request traces for %d "
                    "requests x 2 passes under %r"
                    % (integrity["requests"], len(prompts), features))
            chrome = tracer.export_chrome()
            json.loads(json.dumps(chrome, allow_nan=False))
            ledger = cost_ledger(recs)
            if not ledger:
                raise AssertionError(
                    "traced leg produced an empty cost ledger "
                    "under %r" % (features,))
            record["trace"] = {
                "requests": integrity["requests"],
                "spans": integrity["spans"],
                "integrity": True,
                "chrome_events": len(chrome["traceEvents"]),
                "ledger_rows": len(ledger),
                "ledger_dispatches": int(sum(r["dispatches"]
                                             for r in ledger)),
            }
        return record
    finally:
        server.stop()


def replica_scaling_comparison(params, n_heads, max_len, chunk, n_new,
                               vocab, slots=4, requests=16,
                               step_latency_s=0.005):
    """ACCEPTANCE leg (ISSUE 8): the SAME mixed-length workload through
    (a) ONE paged engine and (b) 2 replicas behind the metrics router,
    both under the emulated device-bound regime
    (:func:`_emulate_device_latency` — per-dispatch idle wait, the
    regime real accelerators serve in and the one replica overlap
    exists for).  Reports the aggregate-throughput ratio and the
    router's balance evidence.  The RAW shared-core legs (tp2/
    replicas2 in the feature matrix) stay in the record for the honest
    side-by-side: on a dryrun box whose cores one engine already
    saturates, raw replication measures core contention, not the
    serving layer."""
    import jax
    if jax.device_count() < 2:
        # before the parity references: skipping must be free, not
        # cost `requests` full greedy generates first
        return {"skipped": "needs 2 devices, have %d"
                           % jax.device_count()}
    lo, hi = max(4, chunk // 2), max(chunk, (max_len - n_new) // 2)
    prompts = mixed_length_prompts(requests, vocab, lo, hi)
    expect = expected_rows(params, prompts, n_new, n_heads, max_len)
    fpt = decode_flops_per_token(
        vocab, params["embed"].shape[1], len(params["blocks"]),
        int(numpy.mean([len(p) for p in prompts])) + n_new // 2,
        n_heads=n_heads)
    single = run_leg(params, n_heads, max_len, prompts, n_new, expect,
                     slots=slots, paged_kv=True, prefill_chunk=chunk,
                     step_latency_s=step_latency_s,
                     flops_per_token=fpt)
    pair = run_leg(params, n_heads, max_len, prompts, n_new, expect,
                   slots=slots, replicas=2, paged_kv=True,
                   prefill_chunk=chunk, step_latency_s=step_latency_s,
                   flops_per_token=fpt)
    ratio = (pair["tokens_per_sec"]
             / max(single["tokens_per_sec"], 1e-9))
    return {
        "emulated_step_latency_s": step_latency_s,
        "tokens_per_sec_single": single["tokens_per_sec"],
        "tokens_per_sec_replicas2": pair["tokens_per_sec"],
        "replicas2_speedup": round(ratio, 2),
        "replica_routed": pair["replica_routed"],
        "replica_balance_ratio": pair["replica_balance_ratio"],
        "replica_queue_depth_spread":
            pair["replica_queue_depth_spread"],
        "single": single,
        "replicas2": pair,
    }


def run_lint_leg(results):
    """The dispatch-hygiene assertion leg (ISSUE 17): run every
    ``tools/veles_lint.py`` pass over the shipped tree BEFORE the
    serving legs — a hot path that regressed into an implicit host
    sync or a silently-compiled twin program would make every number
    below describe a slower engine than the one the repo ships, so
    the bench refuses to report on a dirty tree.  Streams the
    bench-schema ``lint_clean`` record (``check_stream_records.py
    --tool veles_lint`` validates the shape) and ASSERTS zero
    findings."""
    import veles_lint
    findings, _, stats = veles_lint.run_check()
    record = veles_lint.clean_record(findings, stats)[0]
    print(json.dumps(record), flush=True)
    assert not findings, (
        "lint_clean leg: %d finding(s) on the shipped tree — %s"
        % (len(findings), "; ".join(str(f) for f in findings[:5])))
    results["lint_clean"] = record["configs"]


def bench_max_len(smoke):
    """THE bench max_len — main()'s --chunk divisibility pre-check and
    run_bench() must read the same value, or the check validates a
    geometry the run doesn't use."""
    return 128 if smoke else 256


def run_bench(smoke=False, slots=4, chunk=16, cache=256, spec_k=4,
              n_new=32, requests=8, vocab=32, max_len=None):
    if max_len is None:
        max_len = bench_max_len(smoke)
    if smoke:
        n_new, requests = 8, 4
    params = build_params(vocab=vocab, max_len=max_len)
    n_heads = 4
    d_model = int(params["embed"].shape[1])
    n_layers = len(params["blocks"])
    feature_sets = {
        # the engine's defaults (its own page), alone and speculating
        "baseline": {},
        "spec": {"spec_k": spec_k},
        # ISSUE 6: pages of ``chunk`` tokens, alone, under the prefix
        # cache and under the full fast path — same workloads
        "paged": {"paged_kv": True, "prefill_chunk": chunk},
        "prefix_cache": {"prefix_cache": cache, "prefill_chunk": chunk},
        "paged_all": {"paged_kv": True, "prefix_cache": cache,
                      "prefill_chunk": chunk, "spec_k": spec_k},
        # ISSUE 7: the Pallas serving kernels against the same
        # workloads — the kernel-vs-XLA MFU comparison reads off the
        # 'paged' legs above.  On CPU these run the automatic XLA
        # fallback END TO END (no crash, attn_kernel_fallbacks
        # increments — asserted by run_leg); the kernel MFU numbers
        # are a TPU-session fact.
        "paged_kernel": {"paged_kv": True, "prefill_chunk": chunk,
                         "attn_kernel": "auto"},
        "paged_kernel_all": {"paged_kv": True, "prefix_cache": cache,
                             "prefill_chunk": chunk, "spec_k": spec_k,
                             "attn_kernel": "auto"},
        # ISSUE 8: sharded serving on the same workloads — tensor-
        # parallel decode (tp2, 2-device mesh), data-parallel replicas
        # behind the metrics router (replicas2, aggregate throughput +
        # balance evidence), and both stacked (tp2_replicas2, 4
        # devices).  Hosts without the devices bank a 'skipped' record
        # per leg (CPU dryrun: --devices N).
        "tp2": {"tp": 2, "paged_kv": True, "prefill_chunk": chunk},
        "replicas2": {"replicas": 2, "paged_kv": True,
                      "prefill_chunk": chunk},
        "tp2_replicas2": {"tp": 2, "replicas": 2, "paged_kv": True,
                          "prefill_chunk": chunk},
        # ISSUE 13: the fused decode megastep — K decode iterations
        # per device dispatch (lax.scan; spec_k folds its propose/
        # verify in-graph on the megastep_all leg).  The single-lane
        # greedy acceptance criterion rides run_leg: < 0.1
        # dispatches/token (vs 0.547 best single-lane before), plus
        # the megastep_waste_frac column so the K tradeoff (early-exit
        # masking wastes tail iterations) is measured, not guessed.
        "megastep": {"megastep": 16, "paged_kv": True,
                     "prefill_chunk": chunk},
        "megastep_all": {"megastep": 8, "paged_kv": True,
                         "prefix_cache": cache, "prefill_chunk": chunk,
                         "spec_k": spec_k},
        # ISSUE 12: the TRACED legs — the full fast-path stack with the
        # span tracer armed.  Parity still asserted (tracing must not
        # perturb output), span-tree integrity asserted per request,
        # and the record carries the cost-ledger shape (rows, deduped
        # dispatch count).  traced_tp2_all is the acceptance combo
        # (prefix_cache + prefill_chunk + spec_k + paged_kv + tp
        # dryrun); hosts without 2 devices bank a 'skipped' record.
        "traced_all": {"paged_kv": True, "prefix_cache": cache,
                       "prefill_chunk": chunk, "spec_k": spec_k,
                       "trace": True},
        "traced_tp2_all": {"tp": 2, "paged_kv": True,
                           "prefix_cache": cache,
                           "prefill_chunk": chunk, "spec_k": spec_k,
                           "trace": True},
    }
    # workload A: shared system prompt (load_gen's generator — one
    # request per "client", every prompt shares the prefix)
    mean_len = min(64, max_len - n_new - spec_k - 1)
    grid = lm_prompts(requests, 1, vocab=vocab, mean_len=mean_len,
                      shared_frac=0.6,
                      max_len=max_len - n_new - spec_k - 1, seed=11)
    shared = [grid[(ci, 0)] for ci in range(requests)]
    # workload B: repetitive text (prompt-lookup's home turf)
    rep = repetitive_prompts(requests, vocab,
                             min(48, max_len - n_new - spec_k - 1))
    # workload C: mixed lengths (where per-lane paging pays)
    mixed = mixed_length_prompts(
        requests, vocab, max(4, chunk // 2),
        max(chunk, (max_len - n_new - spec_k - 1) // 2))
    results = {"model": {"vocab": vocab, "d_model": d_model,
                         "n_layers": n_layers, "max_len": max_len},
               "slots": slots, "n_new": n_new,
               "workloads": {}}

    def stream_summary():
        """Bank everything completed so far as ONE stdout JSON line —
        an outer watchdog kill keeps the last one (the bench.py
        per-leg streaming discipline)."""
        record, _ = summary_record(results)
        print(json.dumps(record), flush=True)

    # the lint_clean assertion leg first (ISSUE 17): cheap (<1s, no
    # engine), and a dirty tree should refuse the run up front rather
    # than after minutes of legs
    run_lint_leg(results)
    # the single-lane repetitive workload ISOLATES speculation: with
    # one slot the baseline is exactly 1 dispatch/token, so any value
    # below 1 is the draft acceptance and nothing else (multi-slot
    # continuous batching is already sub-1 across lanes)
    for wname, prompts, wslots in (
            ("shared_prefix", shared, slots),
            ("mixed_length", mixed, slots),
            ("repetitive", rep, slots),
            ("repetitive_single_lane", rep[:max(2, requests // 2)], 1)):
        expect = expected_rows(params, prompts, n_new, n_heads, max_len)
        fpt = decode_flops_per_token(
            vocab, d_model, n_layers,
            int(numpy.mean([len(p) for p in prompts])) + n_new // 2,
            n_heads=n_heads)
        legs = results["workloads"].setdefault(wname, {})
        for fname, kw in feature_sets.items():
            legs[fname] = run_leg(params, n_heads, max_len, prompts,
                                  n_new, expect, slots=wslots,
                                  flops_per_token=fpt, **kw)
            print("%s/%s: %s" % (wname, fname, json.dumps(legs[fname])),
                  file=sys.stderr)
            stream_summary()
    # the replica-scaling acceptance leg (ISSUE 8): device-bound
    # regime, 1 engine vs 2 replicas on the same mixed-length traffic
    results["replica_scaling"] = replica_scaling_comparison(
        params, n_heads, max_len, chunk, n_new, vocab, slots=slots,
        requests=max(8, requests))
    stream_summary()
    # headline facts the acceptance criteria name
    lane1 = results["workloads"]["repetitive_single_lane"]
    sp_cache = results["workloads"]["shared_prefix"]["prefix_cache"]
    sp_paged = results["workloads"]["shared_prefix"]["paged_all"]
    sp_base = results["workloads"]["shared_prefix"]["baseline"]
    results["headline"] = {
        "dispatches_per_token_plain_single_lane":
            lane1["baseline"]["dispatches_per_token"],
        "dispatches_per_token_speculative_single_lane":
            lane1["spec"]["dispatches_per_token"],
        # ISSUE 13: the fused-megastep acceptance pair (run_leg already
        # ASSERTED < 0.1 on these legs) and the measured waste of
        # early-exit masking
        "dispatches_per_token_megastep_single_lane":
            lane1["megastep"]["dispatches_per_token"],
        "dispatches_per_token_megastep_all_single_lane":
            lane1["megastep_all"]["dispatches_per_token"],
        "megastep_waste_frac_single_lane":
            lane1["megastep"]["megastep_waste_frac"],
        "prefill_tokens_baseline": sp_base["prefill_tokens"],
        "prefill_tokens_prefix_cache": sp_cache["prefill_tokens"],
        "prefix_hit_tokens": sp_cache["prefix_hit_tokens"],
        "prefill_flops_saved_frac": round(
            1 - sp_cache["prefill_tokens"]
            / max(sp_base["prefill_tokens"], 1), 3),
        # ISSUE 6: zero-copy prefix sharing
        "kv_pages_referenced_shared_prefix":
            sp_paged["kv_pages_referenced"],
        # ISSUE 7: the kernel-vs-XLA MFU pair on the same workload
        # (identical on CPU where the kernel leg falls back — the
        # split is a TPU-session fact) plus the which-path evidence
        "mfu_paged_xla_shared_prefix":
            results["workloads"]["shared_prefix"]["paged"]["mfu"],
        "mfu_paged_kernel_shared_prefix":
            results["workloads"]["shared_prefix"]["paged_kernel"]
            ["mfu"],
        "attn_kernel_dispatches_shared_prefix":
            results["workloads"]["shared_prefix"]["paged_kernel"]
            ["attn_kernel_dispatches"],
        "attn_kernel_fallbacks_shared_prefix":
            results["workloads"]["shared_prefix"]["paged_kernel"]
            ["attn_kernel_fallbacks"],
    }
    # ISSUE 8 headline: replica scaling on the mixed-length workload
    # (the acceptance ratio) + client-relevant balance on shared_prefix
    ml = results["workloads"]["mixed_length"]
    if "skipped" not in ml["replicas2"]:
        # the RAW shared-core ratio against the SAME engine config
        # single-replica ('paged' == replicas2 minus the router) —
        # honest about core contention on a dryrun box; the
        # acceptance ratio is the device-bound replica_scaling leg's
        results["headline"]["replicas2_speedup_mixed_length_raw"] = \
            round(ml["replicas2"]["tokens_per_sec"]
                  / max(ml["paged"]["tokens_per_sec"], 1e-9), 2)
    scaling = results.get("replica_scaling", {})
    if "skipped" not in scaling:
        results["headline"]["replicas2_speedup_mixed_length"] = \
            scaling["replicas2_speedup"]
        results["headline"]["replica_balance_ratio_mixed_length"] = \
            scaling["replica_balance_ratio"]
    sp2 = results["workloads"]["shared_prefix"]["replicas2"]
    if "skipped" not in sp2:
        results["headline"]["replica_balance_ratio_shared_prefix"] = \
            sp2["replica_balance_ratio"]
    tp_leg = results["workloads"]["shared_prefix"]["tp2"]
    if "skipped" not in tp_leg:
        results["headline"]["tp2_tokens_per_sec_shared_prefix"] = \
            tp_leg["tokens_per_sec"]
        results["headline"]["tp2_parity_vs_generate"] = \
            tp_leg["parity_vs_generate"]
    return results


def _latest_mfu(results):
    """The newest completed leg's MFU — the per-line column the
    streamed partial records carry (a watchdog kill still banks an
    MFU reading for whatever finished last)."""
    mfu = None
    for legs in (results.get("workloads") or {}).values():
        for leg in legs.values():
            if leg.get("mfu") is not None:
                mfu = leg["mfu"]
    return mfu


def summary_record(results):
    """Build (record, exit_code) for the driver's summary JSON line —
    same shape as ``bench.py::summary_record`` (metric/value/unit/
    vs_baseline/configs), with the metric-selection priority in ONE
    place so the per-leg partial stream and the final emit can never
    disagree: the megastep's dispatches/token once the headline is
    made, tokens/s of the newest completed leg as the early-partial
    fallback.  EVERY line carries an ``mfu``
    column (ISSUE 7): the newest completed leg's model-FLOPs
    utilization, so a killed run still banks the kernel-vs-XLA
    reading."""
    mfu = _latest_mfu(results)
    headline = results.get("headline") or {}
    if headline.get("dispatches_per_token_megastep_single_lane") \
            is not None:
        # ISSUE 13 headline: the fused-decode dispatches/token against
        # the 0.547 single-lane record the megastep replaces
        return {
            "metric": "lm_megastep_dispatches_per_token",
            "mfu": mfu,
            "value":
                headline["dispatches_per_token_megastep_single_lane"],
            "unit": "dispatches/token",
            "vs_baseline": 0.547,
            "configs": results,
        }, 0
    workloads = results.get("workloads") or {}
    latest = None
    for legs in workloads.values():
        for leg in legs.values():
            if "tokens_per_sec" in leg:      # (a skipped leg has none)
                latest = leg
    if latest is not None:
        return {
            "metric": "lm_fastpath_tokens_per_sec",
            "mfu": mfu,
            "value": latest["tokens_per_sec"],
            "unit": "tokens/sec",
            "vs_baseline": None,
            "configs": results,
        }, 0
    return {
        "metric": "lm_fastpath_no_legs_completed",
        "mfu": mfu,
        "value": None,
        "unit": None,
        "vs_baseline": None,
        "configs": results,
    }, 1


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--smoke", action="store_true",
                        help="tiny sizes (CI validation)")
    parser.add_argument("--slots", type=int, default=4)
    parser.add_argument("--chunk", type=int, default=16,
                        help="prefill chunk size for the chunked legs")
    parser.add_argument("--cache", type=int, default=256,
                        help="prefix cache capacity (chunks)")
    parser.add_argument("--spec-k", type=int, default=4,
                        help="speculative draft length")
    parser.add_argument("--n-new", type=int, default=32)
    parser.add_argument("--requests", type=int, default=8)
    parser.add_argument("--json", default=None, metavar="FILE",
                        help="also write the record here")
    parser.add_argument("--devices", type=int, default=0, metavar="N",
                        help="force an N-device CPU host platform "
                             "(xla_force_host_platform_device_count) "
                             "so the sharded legs (tp2/replicas2/"
                             "tp2_replicas2) can seat on a laptop/CI "
                             "box — CPU dryrun only, set before jax "
                             "initializes; ignored on real TPU hosts")
    args = parser.parse_args(argv)
    if args.devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=%d"
                % args.devices).strip()
    from veles_tpu import compile_cache
    compile_cache.enable()
    max_len = bench_max_len(args.smoke)
    if args.chunk < 1 or max_len % args.chunk:
        # the paged legs run unconditionally and LMEngine requires the
        # page size (= chunk) to divide max_len — refuse up front
        # instead of crashing mid-run with the summary unwritten
        parser.error("--chunk %d must divide max_len %d (paged legs)"
                     % (args.chunk, max_len))
    if args.spec_k and args.spec_k + 1 > args.chunk:
        # same up-front rule for the combined legs: LMEngine requires
        # the verify span (spec_k + 1) to fit in one chunk
        parser.error("--spec-k %d + 1 must fit in --chunk %d "
                     "(the combined 'all'/'paged_all' legs)"
                     % (args.spec_k, args.chunk))
    results = run_bench(smoke=args.smoke, slots=args.slots,
                        chunk=args.chunk, cache=args.cache,
                        spec_k=args.spec_k, n_new=args.n_new,
                        requests=args.requests, max_len=max_len)
    record, rc = summary_record(results)
    line = json.dumps(record)
    print(line)                  # final full record — last line wins
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
