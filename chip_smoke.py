#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, which owns the chip from start to end and starts no child that
needs a device.  It drives the two main paths through the entry points a
user calls, at the full width of the models, and checks what comes out by
the repo's own means:

- *sync*: does ``jax.block_until_ready`` wait for the device?  (a 4096^3
  bf16 matmul chain timed under it and under a value fetch);
- *train*: full-width AlexNet (227x227 crops, 1000 classes, minibatch 128)
  from a records file through ``python -m veles_tpu``'s ``main``, graph
  loop and ``--epoch-scan``, fp32 policy and ``--precision bfloat16``;
  then the two forms' train programs from equal state with equal keys;
- *kernels*: every Pallas kernel compiled (``interpret=False``) at the
  sizes the models use, against its XLA twin;
- *serve*: the launcher's own two calls — the char_lm sample's ``train``
  at d_model 2048 / 16 heads x 128 / 4 layers / vocab 32768, then
  ``serve_lm`` with 8 slots, paged KV and ``attn_kernel='auto'`` — HTTP
  requests from ``tools/load_gen.py``, greedy output against
  ``ops/transformer.py::generate``.

``--chips 4`` runs ONLY the four-chip phase: ``ShardedTrainer`` on a
data 2 x model 2 mesh against a one-device trainer, and ``LMEngine(tp=4)``
plus a four-replica ``Router`` against a one-chip engine.

Data and weights come from ``--seed``; no network, no git metadata.  Sizes
are arguments of the phase functions (tests/test_chip_smoke.py calls them
tiny on the CPU); the CLI has no option for them.  Without a TPU the script
exits non-zero and prints no result line.  On success the LAST line of
stdout is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def say(tag, fmt, *args):
    print("[%s] %s" % (tag, fmt % args if args else fmt), flush=True)


class SmokeFailure(AssertionError):
    """A phase ran but what came out was wrong."""


def check(ok, fmt, *args):
    if not ok:
        raise SmokeFailure(fmt % args if args else fmt)


# ------------------------------------------------------------ compile meter
class _CompileMeter:
    """Seconds jax spent in backend compiles (or loading them from the
    persistent cache), programs compiled and persistent-cache hits, from
    jax's own monitoring events — so a phase that runs through ``main``
    can still split its wall time into compile and the rest."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.programs, self.hits = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **unused):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **unused):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def read(self):
        return self.seconds, self.programs, self.hits


_meter = None


def meter():
    global _meter
    if _meter is None:
        _meter = _CompileMeter()
    return _meter


@contextlib.contextmanager
def timed(tag, what):
    """Time a block; prints wall = compile + rest and yields the record."""
    c0, t0 = meter().read(), time.perf_counter()
    rec = {}
    yield rec
    wall = time.perf_counter() - t0
    c1 = meter().read()
    rec.update(wall_s=wall, compile_s=c1[0] - c0[0],
               programs=c1[1] - c0[1], cache_hits=c1[2] - c0[2])
    say(tag, "%s: wall %.2fs = compile %.2fs (%d programs, %d from the "
        "persistent cache) + steady %.2fs", what, wall, rec["compile_s"],
        rec["programs"], rec["cache_hits"], wall - rec["compile_s"])


def on_tpu():
    from veles_tpu.ops import pallas_kernels
    return pallas_kernels.on_tpu()


def device_record():
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


# --------------------------------------------------------------- sync check
def phase_sync(n=4096, chain=64, reps=5, peak_flops=None):
    """Time ``chain`` dependent n^3 bf16 matmuls under block_until_ready
    and under a value fetch.  The old access path returned from
    block_until_ready before the work was done (7000 TFLOP/s on a
    197-TFLOP chip); here the two must agree, and neither may beat the
    chip's published peak."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), jnp.bfloat16)
    b = (jax.random.normal(jax.random.fold_in(key, 1), (n, n), jnp.float32)
         / numpy.sqrt(n)).astype(jnp.bfloat16)

    @jax.jit
    def work(a, b):
        return jax.lax.fori_loop(
            0, chain, lambda i, x: jnp.matmul(x, b), a)

    with timed("sync", "compile %d x %d^3 bf16 matmul chain" % (chain, n)):
        jax.block_until_ready(work(a, b))

    # the two ways take turns, after one untimed round each (the fetch
    # compiles a slice): a busy spell on a shared host then slows both
    finishers = (jax.block_until_ready, lambda y: float(y[0, 0]))
    times = ([], [])
    for rep in range(reps + 1):
        for finish, record in zip(finishers, times):
            begin = time.perf_counter()
            finish(work(a, b))
            if rep:
                record.append(time.perf_counter() - begin)
    t_block, t_fetch = (float(numpy.median(t)) for t in times)
    flops = 2.0 * n ** 3 * chain
    say("sync", "block_until_ready %.3f ms (%.1f TFLOP/s) | value fetch "
        "%.3f ms (%.1f TFLOP/s)", t_block * 1e3, flops / t_block / 1e12,
        t_fetch * 1e3, flops / t_fetch / 1e12)
    # the peak first: it does not depend on how the two timings compare
    if peak_flops:
        check(flops / t_block <= 1.05 * peak_flops,
              "block_until_ready timing implies %.0f TFLOP/s, above the "
              "chip's %.0f peak: it returned early",
              flops / t_block / 1e12, peak_flops / 1e12)
    # an early return shows as a fetch that takes longer; 2 ms of slack
    # for host jitter (the chain itself takes ~47 ms on a v5e)
    agree = t_fetch - t_block <= 0.25 * t_fetch + 2e-3
    check(agree, "block_until_ready (%.3f ms) and a value fetch (%.3f ms) "
          "disagree: block_until_ready does not wait for the device",
          t_block * 1e3, t_fetch * 1e3)
    say("sync", "block_until_ready blocks: the two timings agree")
    return {"block_s": t_block, "fetch_s": t_fetch}


# -------------------------------------------------------------------- train
def write_imagenet_records(path, seed, n_train, n_valid, image_hw,
                           n_classes):
    """A records file of seeded uint8 noise images in the loader's
    [test | validation | train] layout."""
    from veles_tpu.loader.records import write_records
    rng = numpy.random.default_rng(seed)
    total = n_train + n_valid
    data = rng.integers(0, 256, (total,) + tuple(image_hw) + (3,),
                        dtype=numpy.uint8)
    labels = rng.integers(0, n_classes, total).astype(numpy.int32)
    write_records(path, data, labels, [0, n_valid, n_train])
    return data.nbytes


def _launch_imagenet(tag, records, result_file, seed, minibatch, extra,
                     layers):
    """One ``python -m veles_tpu samples/imagenet.py`` run, in process."""
    from veles_tpu import prng
    from veles_tpu.__main__ import main
    from veles_tpu.config import root
    prng.reset()
    root.__dict__.pop("imagenet", None)
    if layers is not None:       # a tiny topology for the CPU rehearsal
        root.imagenet.layers = layers
    argv = [os.path.join(HERE, "veles_tpu", "samples", "imagenet.py"),
            "--random-seed", str(seed), "--result-file", result_file,
            "--no-stats"] + list(extra) + [
        "root.imagenet.loader.records_path=%s" % records,
        "root.imagenet.loader.minibatch_size=%d" % minibatch,
        "root.imagenet.decision.max_epochs=1"]
    say(tag, "python -m veles_tpu %s", " ".join(argv))
    rc = main(argv)
    check(rc == 0, "launcher returned %r", rc)
    with open(result_file, encoding="utf-8") as f:
        return json.load(f)["last_epoch_metrics"]


def _updates_agree(tag, records, seed, minibatch, layers, steps=2):
    """The two forms' TRAIN programs from equal state with equal keys.

    The launcher's two runs draw their dropout and crop keys by different
    rules, so past the validation pass they cannot be held to each other.
    Here the same ``steps`` minibatches of the records file go through
    the program ``--epoch-scan`` dispatches (one ``window_scan_fn``
    window) and, one by one, through the program the graph loop
    dispatches (``FusedRunner._train``), keyed as the scan keys its
    steps.  The second minibatch's loss is taken on the weights the
    first one wrote (at full width that update moves it by 1.4 %, 700
    times the tolerance), so the loss is the exact check.  The state that
    comes out is held to 2 % of each leaf's largest entry only: a check
    against a wrong update rule, not roundoff — the two programs' conv
    weight gradients sum 128x55x55 terms in different orders and part by
    2e-3 of their largest entry already on the CPU, at equal losses."""
    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.loader.base import TRAIN
    from veles_tpu.samples import imagenet
    prng.reset()
    prng.seed_all(seed)
    root.__dict__.pop("imagenet", None)
    if layers is not None:
        root.imagenet.layers = layers
    root.imagenet.loader.records_path = records
    root.imagenet.loader.minibatch_size = minibatch
    wf = imagenet.build(fused=True)
    wf.initialize()
    runner, loader = wf._fused_runner, wf.loader
    # the whole train plan is the window and the scan takes its first
    # ``steps`` rows: with three train minibatches that is the very
    # dispatch the launcher's epoch-scan run ended on (its completion
    # replay), so both programs here come from the compile cache
    idx, mask = loader.plan_arrays(TRAIN)
    data, labels = loader.gather_window(idx.ravel())
    x, y, m = (jax.device_put(a) for a in (data, labels, mask))
    rows = jnp.arange(idx.size, dtype=jnp.int32).reshape(idx.shape)
    key = jax.random.PRNGKey(seed)
    state = runner.state
    with timed(tag, "%d minibatches: one scan window, then step by step"
               % steps):
        scan_state, totals = runner.window_scan_fn()(
            state, x, y, rows[:steps], m[:steps], key, 0)
        scan_loss = float(totals["loss_sum"])
        loop_state, loop_loss = state, 0.0
        for i in range(steps):
            loop_state, metrics = runner._train(
                loop_state, x[rows[i]], y[rows[i]], m[i],
                jnp.asarray(int(mask[i].sum()), jnp.int32),
                jax.random.fold_in(key, i), jnp.asarray(i, jnp.int32))
            loop_loss += float(metrics["loss_sum"])
    worst = 0.0
    for a, b in zip(jax.tree.leaves(scan_state),
                    jax.tree.leaves(loop_state)):
        err, scale = _max_err(a, b)
        worst = max(worst, err / scale if scale else err)
    rel = abs(scan_loss - loop_loss) / abs(loop_loss)
    say(tag, "train loss_sum over %d steps from equal state and keys: "
        "scan window %.8g, per-step program %.8g, rel diff %.2g "
        "(tolerance 2e-5); state after: worst leaf differs by %.2g of its "
        "largest entry (tolerance 2e-2)", steps, scan_loss, loop_loss, rel,
        worst)
    check(numpy.isfinite(scan_loss) and rel <= 2e-5,
          "%s: train loss differs between the scan window (%.8g) and the "
          "per-step program (%.8g)", tag, scan_loss, loop_loss)
    check(worst <= 2e-2, "%s: the state after %d updates differs by %.2g "
          "of a leaf's largest entry", tag, steps, worst)
    return rel


def _loss(metrics, split):
    found = [k for k in metrics if k.lower().startswith(split)]
    check(len(found) == 1, "no %r split in %r", split, sorted(metrics))
    return float(metrics[found[0]]["loss"])


def phase_train(seed, workdir, minibatch=128, train_minibatches=3,
                valid_minibatches=1, image_hw=(256, 256), n_classes=1000,
                layers=None, precisions=("float32", "bfloat16")):
    """AlexNet through the launcher: graph loop and one epoch-scan chunk
    per precision policy.  With a records file present the sample's
    ``default_config`` selects the full 227x227 1000-class topology.

    What is compared, at the tolerance of
    test_epoch_scan_matches_per_step_loop (rtol 2e-5): the two launcher
    runs' validation loss (that pass comes first and sees the seeded
    initial weights), and the two forms' train programs over two
    minibatches from equal state with equal keys (``_updates_agree``).
    The launcher runs' own training losses must be finite but are NOT
    equal: AlexNet has dropout and random crops, and the scan draws its
    keys by another rule than the graph loop (documented in
    epoch_driver.py)."""
    import jax
    from veles_tpu import native
    from veles_tpu.ops import functional as F
    records = os.path.join(workdir, "imagenet_smoke.records")
    n_train, n_valid = (minibatch * train_minibatches,
                        minibatch * valid_minibatches)
    nbytes = write_imagenet_records(records, seed, n_train, n_valid,
                                    image_hw, n_classes)
    say("train", "records %s: %d train + %d valid images %dx%dx3 uint8 "
        "(%.0f MB), %d classes, minibatch %d; native gather: %s; device "
        "%s", records, n_train, n_valid, image_hw[0], image_hw[1],
        nbytes / 1e6, n_classes, minibatch,
        "libdataio.so" if native.available() else "numpy fallback",
        jax.devices()[0].device_kind)
    check(native.available() or not on_tpu(),
          "libdataio.so did not build: the loader would run the numpy "
          "gather")
    out = {}
    try:
        for precision in precisions:
            flags = ([] if precision == "float32"
                     else ["--precision", precision])
            losses = {}
            for form, extra in (("graph", []),
                                ("epoch-scan", ["--epoch-scan", "1"])):
                tag = "train/%s/%s" % (precision, form)
                result = os.path.join(
                    workdir, "result_%s_%s.json" % (precision, form))
                with timed(tag, "%d steps" % train_minibatches) as rec:
                    metrics = _launch_imagenet(
                        tag, records, result, seed, minibatch,
                        flags + extra, layers)
                losses[form] = (_loss(metrics, "valid"),
                                _loss(metrics, "train"))
                say(tag, "validation loss %.8g, train loss %.8g",
                    *losses[form])
                check(all(numpy.isfinite(losses[form])),
                      "%s: loss is not finite: %r", tag, losses[form])
                out[(precision, form)] = dict(rec, valid=losses[form][0],
                                              train=losses[form][1])
            (gv, gt), (sv, st) = losses["graph"], losses["epoch-scan"]
            check(abs(gv - sv) <= 2e-5 * abs(gv) + 2e-6,
                  "%s: validation loss differs between graph loop "
                  "(%.8g) and epoch scan (%.8g)", precision, gv, sv)
            say("train/%s" % precision, "graph loop == epoch scan: "
                "validation loss rel diff %.2g (tolerance 2e-5); train "
                "losses %.8g and %.8g are both finite (different dropout "
                "keys by design)", abs(gv - sv) / abs(gv), gt, st)
            out[(precision, "update")] = _updates_agree(
                "train/%s/update" % precision, records, seed, minibatch,
                layers)
    finally:
        F.set_matmul_precision("float32")
        os.remove(records)
    return out


# ------------------------------------------------------------------ kernels
def _max_err(got, want):
    got, want = numpy.asarray(got, numpy.float64), numpy.asarray(
        want, numpy.float64)
    return float(numpy.abs(got - want).max()), float(
        numpy.abs(want).max())


def phase_kernels(seed, interpret=False,
                  sgd_shapes=((784, 100), (4096, 4096), (9216, 4096)),
                  lrn_shapes=((128, 55, 55, 96), (128, 27, 27, 256)),
                  dropout_shape=(128, 4096),
                  attn=dict(b=8, heads=16, kv=16, dh=128, page=32,
                            max_len=2048),
                  gmm_shapes=((4096, 4096, 3584, 1024, 64),
                              (4096, 4096, 1024, 3584, 64),
                              (1024, 128, 3072, 3072, 32)),
                  steady_reps=5):
    """Each Pallas kernel against its XLA twin.  ``interpret=False`` is the
    chip; the CPU test passes True and tiny shapes."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import functional as F, moe
    from veles_tpu.ops import pallas_kernels as PK
    from veles_tpu.ops.attention import (init_mha_params,
                                         mha_paged_chunk_step)
    check(interpret or on_tpu(), "interpret=False needs the TPU")
    check(F._SGD_BACKEND == "xla" and F._LRN_BACKEND == "xla",
          "the XLA twins need the xla backends selected")
    key = jax.random.PRNGKey(seed)
    failures = []

    def compare(name, fn_kernel, fn_xla, args, rtol, atol):
        kern, xla = jax.jit(fn_kernel), jax.jit(fn_xla)
        with timed("kernels", "%s compile+first run" % name):
            got = jax.block_until_ready(kern(*args))
        want = jax.block_until_ready(xla(*args))
        steady = {}
        for label, fn in (("pallas", kern), ("xla", xla)):
            times = []
            for _ in range(steady_reps):
                begin = time.perf_counter()
                jax.block_until_ready(fn(*args))
                times.append(time.perf_counter() - begin)
            steady[label] = float(numpy.median(times))
        worst = 0.0
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            err, scale = _max_err(g, w)
            worst = max(worst, err / (atol + rtol * scale))
            check(numpy.isfinite(numpy.asarray(g, numpy.float32)).all(),
                  "%s: non-finite output", name)
        say("kernels", "%s: max err %.3g of tolerance (rtol %g atol %g); "
            "steady pallas %.3f ms, xla %.3f ms", name, worst, rtol, atol,
            steady["pallas"] * 1e3, steady["xla"] * 1e3)
        if worst > 1.0:
            failures.append("%s: %.3gx over tolerance" % (name, worst))

    bs = jnp.asarray(128, jnp.int32)
    for shape in sgd_shapes:
        ks = jax.random.split(jax.random.fold_in(key, shape[0]), 3)
        p, v, g = (jax.random.normal(k, shape, jnp.float32) for k in ks)
        compare(
            "fused_sgd_update%r" % (shape,),
            lambda p, v, g: PK.fused_sgd_update(
                p, v, g, bs, 0.01, 0.9, 0.0005, 0.0, interpret=interpret),
            lambda p, v, g: F.sgd_update(
                p, v, g, bs, 0.01, 0.9, 0.0005, 0.0, None),
            (p, v, g), 1e-6, 1e-6)

    for shape in lrn_shapes:
        ks = jax.random.split(jax.random.fold_in(key, shape[1]), 2)
        x, dy = (jax.random.normal(k, shape, jnp.float32) for k in ks)

        def fwd_bwd(lrn):
            def run(x, dy):
                y, vjp = jax.vjp(lrn, x)
                return y, vjp(dy)[0]
            return run
        compare(
            "lrn_forward+vjp%r" % (shape,),
            fwd_bwd(lambda a: PK.lrn_forward(a, 1e-4, 0.75, 5, 2.0,
                                             interpret)),
            fwd_bwd(F.lrn_forward), (x, dy), 1e-5, 1e-5)

    rate = 0.5
    x = jnp.ones(dropout_shape, jnp.float32)
    with timed("kernels", "dropout%r compile+first run" % (dropout_shape,)):
        y = numpy.asarray(jax.jit(lambda a: PK.dropout(
            a, 1234, rate, interpret=interpret))(x))
    keep = float((y != 0).mean())
    sigma = numpy.sqrt(rate * (1 - rate) / y.size)
    say("kernels", "dropout%r: keep fraction %.5f (want %.2f +- %.5f), "
        "kept values %.3f", dropout_shape, keep, 1 - rate, 5 * sigma,
        float(y.max()))
    if abs(keep - (1 - rate)) > 5 * sigma or not numpy.allclose(
            y[y != 0], 1.0 / (1 - rate)):
        failures.append("dropout keep fraction %.5f" % keep)

    # the row-tiled grouped matmul at the chunk shapes of the benchmark's
    # two expert cells (rows, rows held, k, n, groups): bfloat16 operands,
    # float32 sums, ONE rounding, so it may part from the compiler's op by
    # one bfloat16 step of the largest value; rows behind the last group
    # are nobody's
    for rows, held, k, n, groups in gmm_shapes:
        ks = jax.random.split(jax.random.fold_in(key, k + rows), 2)
        xs = jax.random.normal(ks[0], (rows, k), jnp.bfloat16)
        w = (jax.random.normal(ks[1], (groups, k, n), jnp.float32)
             / k ** 0.5).astype(jnp.bfloat16)
        sizes = jnp.asarray(numpy.random.RandomState(seed).multinomial(
            held, [1.0 / groups] * groups), jnp.int32)
        compare(
            "grouped_matmul (%d rows, %d held, %d -> %d, %d groups)"
            % (rows, held, k, n, groups),
            lambda xs, w, sizes: PK.grouped_matmul(
                xs, w, sizes, interpret=interpret)[:held],
            lambda xs, w, sizes: moe._ragged_dot(xs, w, sizes)[:held],
            (xs, w, sizes), 2.0 ** -7, 0.0)
        del xs, w

    # the serving attention kernels through the route the engine takes:
    # mha_paged_chunk_step(attn_kernel=) follows on_tpu(), so on the chip
    # this is the compiled kernel
    b, heads, kv, dh, page = (attn[k] for k in
                              ("b", "heads", "kv", "dh", "page"))
    m = attn["max_len"] // page
    d_model = heads * dh
    from veles_tpu import prng
    prng.reset()
    prng.seed_all(seed)
    params = jax.tree.map(jnp.asarray, init_mha_params(
        prng.get("init"), d_model, heads, n_kv_heads=kv))
    rng = numpy.random.RandomState(seed)
    pool_shape = (b * m + 1, kv, page, dh)
    kp = jnp.asarray(rng.randn(*pool_shape) * 0.5, jnp.float32)
    vp = jnp.asarray(rng.randn(*pool_shape) * 0.5, jnp.float32)
    ptab = jnp.asarray(1 + rng.permutation(b * m).reshape(b, m), jnp.int32)
    for route, c, pos in (
            ("decode", 1, rng.randint(0, m * page - 1, b)),
            ("prefill", page, page * rng.randint(0, m, b))):
        xin = jnp.asarray(rng.randn(b, c, d_model) * 0.5, jnp.float32)
        pos = jnp.asarray(pos, jnp.int32)

        def step(kernel):
            # params as an argument: closed over, 64 MB of weights would
            # be baked into each executable (186 MB per cache entry)
            return lambda params, x, kp, vp, ptab, pos: (
                mha_paged_chunk_step(params, x, kp, vp, ptab, pos, heads,
                                     attn_kernel=kernel))
        compare("paged_flash_%s (b=%d, %dq/%dkv x %d, page %d, %d pages)"
                % (route, b, heads, kv, dh, page, m),
                step(route), step(None), (params, xin, kp, vp, ptab, pos),
                1e-4, 1e-5)
    check(not failures, "kernels disagree with their XLA twins: %s",
          "; ".join(failures))


# -------------------------------------------------------------------- serve
def _build_char_lm(seed, lm, run):
    """The char_lm sample at the given widths: ``train()`` (build,
    initialize, run) or just built and initialized."""
    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.samples import char_lm
    prng.reset()
    prng.seed_all(seed)
    root.__dict__.pop("char_lm", None)
    root.char_lm.update({
        "loader": {"minibatch_size": lm["minibatch"],
                   "n_train": lm["n_train"], "n_valid": lm["n_valid"],
                   "seq_len": lm["seq_len"], "vocab": lm["vocab"]},
        "trainer": {"vocab": lm["vocab"], "d_model": lm["d_model"],
                    "n_heads": lm["n_heads"], "n_layers": lm["n_layers"],
                    "max_len": lm["max_len"]},
        "decision": {"max_epochs": 1, "fail_iterations": 20},
    })
    if run:
        return char_lm.train()
    wf = char_lm.build()
    wf.initialize()
    return wf


FULL_LM = dict(d_model=2048, n_heads=16, n_layers=4, vocab=32768,
               max_len=2048, seq_len=256, minibatch=8, n_train=32,
               n_valid=8)


def _logits_fn(params, n_heads):
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops.transformer import transformer_forward
    fwd = jax.jit(lambda p, t: transformer_forward(p, t, n_heads)[0, -1])
    return lambda tokens: numpy.asarray(
        fwd(params, jnp.asarray([tokens], jnp.int32)), numpy.float64)


def compare_tokens(tag, what, got_rows, want_rows, prompts, logits_fn,
                   tol=1e-3):
    """Token for token.  Where a row differs, the two tokens at the first
    difference must be a tie in the reference logits to fp32 roundoff
    (``tol`` of the logit range): past a flipped argmax the
    continuations legitimately part ways.  Prints which rule was used."""
    ties = 0
    for i, (got, want, prompt) in enumerate(
            zip(got_rows, want_rows, prompts)):
        got, want = list(got), list(want)
        check(len(got) == len(want) and got[:len(prompt)] == list(prompt),
              "%s row %d: wrong shape or prompt not echoed", what, i)
        if got == want:
            continue
        at = next(j for j in range(len(want)) if got[j] != want[j])
        logits = logits_fn(want[:at])
        gap = abs(logits[got[at]] - logits[want[at]])
        span = float(logits.max() - logits.min())
        say(tag, "%s row %d differs at token %d: logit gap %.3g of range "
            "%.3g", what, i, at, gap, span)
        check(gap <= tol * span, "%s row %d: token %d is %d, reference "
              "%d — not a roundoff tie", what, i, at, got[at], want[at])
        ties += 1
    say(tag, "%s: %d rows %s", what, len(want_rows),
        "equal token for token" if not ties else
        "equal up to %d argmax ties accepted under a logits tolerance of "
        "%g" % (ties, tol))
    return ties


def _reference_rows(params, trainer, prompts, n_new, max_len):
    """``generate`` per prompt, right-padded to ONE bucket width with a
    traced ``true_len`` (bit-exact, one compile)."""
    import jax.numpy as jnp
    from veles_tpu.ops.transformer import generate
    width = 16
    while width < max(len(p) for p in prompts):
        width *= 2
    rows = []
    for prompt in prompts:
        padded = numpy.zeros((1, width), numpy.int32)
        padded[0, :len(prompt)] = prompt
        out = numpy.asarray(generate(
            params, jnp.asarray(padded), n_new, trainer.n_heads,
            temperature=0.0, max_len=max_len, true_len=len(prompt),
            rope=getattr(trainer, "rope", False)))
        rows.append(list(prompt) + out[0, width:width + n_new].tolist())
    return rows


def _say_page_steps(tag, engine, active):
    """The page steps the engine handed the attention kernels and the live
    ones among them (the others the kernels skip): both counted whenever
    the kernels served, and most of a table dead on these short requests."""
    given = int(engine.metrics.counter("attn_page_steps"))
    live = int(engine.metrics.counter("attn_page_steps_live"))
    say(tag, "attn_page_steps %d, attn_page_steps_live %d (%.1f %% dead)",
        given, live, 100.0 * (1.0 - live / given) if given else 0.0)
    check(not active or 0 < live < given,
          "the kernels served, and the page steps read %d live of %d",
          live, given)


def phase_serve(seed, lm=FULL_LM, slots=8, prefill_chunk=32, clients=4,
                requests_per_client=2, mean_len=96, n_new=16):
    """Train one short epoch, then serve over HTTP: the launcher's own
    two calls (``__main__.py``: ``module.run`` then ``serve_lm``)."""
    import jax
    from tools import load_gen
    from veles_tpu.restful_api import serve_lm
    say("serve", "char_lm d_model %d, %d heads x %d, %d layers, vocab %d, "
        "max_len %d; train %d sequences of %d; device %s",
        lm["d_model"], lm["n_heads"], lm["d_model"] // lm["n_heads"],
        lm["n_layers"], lm["vocab"], lm["max_len"], lm["n_train"],
        lm["seq_len"], jax.devices()[0].device_kind)
    with timed("serve", "train one epoch"):
        wf = _build_char_lm(seed, lm, run=True)
    last = wf.decision.epoch_metrics[-1]
    say("serve", "epoch metrics %s", {
        k: {m: round(float(v), 5) for m, v in row.items()
            if isinstance(v, (int, float))} for k, row in last.items()})
    trainer = wf.trainer
    with timed("serve", "serve_lm start (slots %d, paged KV, page %d, "
               "attn_kernel auto)" % (slots, prefill_chunk)):
        # every argument as __main__.py passes it, at the CLI's defaults
        # but for slots / chunk / paged KV / kernel / telemetry
        api = serve_lm(
            wf, port=0, slots=slots, prefix_cache=0,
            prefill_chunk=prefill_chunk, spec_k=0, paged_kv=True,
            attn_kernel="auto", megastep=0, tp=0, replicas=1,
            router="metrics", health=False, hedge=0.0, retries=0,
            fault_plan=None, model_dir=None, publish_interval_s=5.0,
            canary=1, canary_watch_s=2.0, trace="off", trace_last=256,
            telemetry=1.0, slo=None, auto_rollback=True)
    try:
        engine = api.lm_engine
        url = "http://127.0.0.1:%d/predict" % api.port
        prompts = load_gen.lm_prompts(
            clients, requests_per_client, vocab=lm["vocab"],
            mean_len=mean_len, shared_frac=0.25,
            max_len=lm["max_len"] - n_new - 1, seed=seed)
        order = sorted(prompts)
        say("serve", "%d prompts of %s tokens, n_new %d, over HTTP to %s",
            len(order), sorted(len(prompts[k]) for k in order), n_new, url)

        def programs():
            api.telemetry.sample_once()
            return int(engine.metrics.gauge("compile_programs"))

        def round_(name):
            with timed("serve", "%s: %d requests from %d clients"
                       % (name, len(order), clients)) as rec:
                summary = load_gen.run_load(
                    url, None, clients=clients,
                    requests_per_client=requests_per_client, timeout=600.0,
                    payload_fn=lambda ci, n: {"input": [prompts[(ci, n)]],
                                              "n_new": n_new})
            rows = {}
            for r, resp in zip(summary["records"], summary["responses"]):
                check(r["status"] == 200 and resp and "tokens" in resp,
                      "%s: request %r failed: %r", name, r, resp)
                rows[(r["client"], r["req"])] = resp["tokens"][0]
            rec["gauge"] = programs()
            say("serve", "%s: latency p50 %.3fs p95 %.3fs; "
                "compile_programs gauge %d", name,
                summary["latency_s"]["p50"], summary["latency_s"]["p95"],
                rec["gauge"])
            return [rows[k] for k in order], rec

        # the engine compiled every program family and table width in
        # start(): traffic should compile nothing at all
        first, rec1 = round_("round 1")
        second, rec2 = round_("round 2 (identical)")
        check(rec2["gauge"] == rec1["gauge"] and rec2["programs"] == 0,
              "the second identical round compiled: compile_programs %d "
              "-> %d, %d programs through the compiler", rec1["gauge"],
              rec2["gauge"], rec2["programs"])
        say("serve", "second identical round compiled nothing "
            "(compile_programs stays %d)", rec2["gauge"])

        active = int(engine.metrics.gauge("attn_kernel_active"))
        fallbacks = int(engine.metrics.counter("attn_kernel_fallbacks"))
        dispatches = int(engine.metrics.counter("attn_kernel_dispatches"))
        say("serve", "attn_kernel_active %d, attn_kernel_dispatches %d, "
            "attn_kernel_fallbacks %d", active, dispatches, fallbacks)
        if on_tpu():
            check(active == 1 and fallbacks == 0 and dispatches > 0,
                  "attn_kernel='auto' fell back to the XLA path on the "
                  "TPU: %s", engine._kernel_fallback_reason)
        _say_page_steps("serve", engine, active)

        in_place = int(engine.metrics.gauge("kv_storage_in_place"))
        rebuilds = int(engine.metrics.counter("kv_storage_rebuilds"))
        say("serve", "kv_storage_in_place %d, kv_storage_rebuilds %d",
            in_place, rebuilds)
        check(in_place == 1 and rebuilds == 0,
              "the KV storage is not updated in place (or was lost and "
              "rebuilt): kv_storage_in_place %d, kv_storage_rebuilds %d",
              in_place, rebuilds)

        params = trainer._to_portable(trainer.params)
        plist = [prompts[k] for k in order]
        with timed("serve", "reference generate x%d" % len(plist)):
            want = _reference_rows(params, trainer, plist, n_new,
                                   lm["max_len"])
        logits_fn = _logits_fn(params, trainer.n_heads)
        for name, rows in (("round 1", first), ("round 2", second)):
            compare_tokens("serve", "%s vs generate" % name, rows, want,
                           plist, logits_fn)
    finally:
        api.stop()


# --------------------------------------------------------------- four chips
#: the block of the benchmark's second configuration at its published
#: widths (heads of 128 under a hidden size of 3072, 48 query heads on 8 KV
#: heads, expert width 3072), cut to one layer of each kind and 4 held
#: experts of 256 routed, a window of two pages
KINDS_LM = {
    "model_type": "afmoe", "hidden_size": 3072, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 12288,
    "moe_intermediate_size": 3072, "vocab_size": 4096,
    "num_hidden_layers": 3, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "num_experts": 4, "router_width": 256, "held_experts": [0, 4],
    "num_experts_per_tok": 4, "sliding_window": 512, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "route_scale": 2.448, "route_norm": True,
    "score_func": "sigmoid", "num_shared_experts": 1,
    "initializer_std": 0.02, "max_position_embeddings": 2048,
}


def phase_kinds(seed, lm=KINDS_LM, slots=16, page=256, prompt_len=700,
                n_new=200, gap_limit=1.5, kernel="auto"):
    """The sandwich block with two kinds of layer on the serving path: one
    request through ``LMEngine`` (Pallas kernels on the chip: the fused
    prefill with its head-block grid axis, flash decode through a table
    per kind, the one-call row write of 16 lanes; expert layers through
    the grouped matmul), its context running past the window so that
    sliding pages are released, against the benchmark's plain reference
    (``benchmark/reference/afmoe.py``, float32): the served tokens are the
    reference's own but for bfloat16 roundoff, which a router's near-ties
    amplify (``gap_limit`` on the reference's logit scale, whose standard
    deviation is 1.1: a program that dropped a norm or a gate reads 3 and
    more); and against an engine on the XLA path (the
    kernels' twin), which serves the same request first."""
    import jax
    from benchmark.reference import afmoe
    from veles_tpu import model_config
    from veles_tpu.serving import LMEngine
    record = model_config.from_published(lm)
    say("kinds", "%d layers %s, %d of %d experts held, window %d, page %d, "
        "%s; %d lanes", lm["num_hidden_layers"], list(record.attn_kinds),
        lm["held_experts"][1], lm["router_width"], lm["sliding_window"],
        page, record.dtype, slots)
    weights = jax.tree.map(lambda a: a.astype(record.dtype),
                           afmoe.make_weights(seed, lm))
    prompt = numpy.random.RandomState(seed).randint(
        0, lm["vocab_size"], prompt_len)

    def serve(name, attn_kernel):
        engine = LMEngine(weights, record,
                          max_len=lm["max_position_embeddings"],
                          slots=slots, prefill_chunk=page, paged_kv=True,
                          attn_kernel=attn_kernel, deadline_s=600.0,
                          name=name)
        with timed("kinds", "%s: engine start (every program and table "
                   "width)" % name):
            engine.start()
        with timed("kinds", "%s: one request: prompt %d, n_new %d"
                   % (name, prompt_len, n_new)):
            return engine, engine.submit(prompt, n_new).result(timeout=600)

    # the XLA twin first (gather + dense softmax, update slices): the
    # kernels' engine must serve its tokens but for roundoff ties
    twin, want = serve("kinds_xla", 0)
    twin.stop()
    engine, out = serve("kinds", kernel)
    try:
        snap = engine.metrics.snapshot()
        gauges, counters = snap["gauges"], snap["counters"]
        released = counters.get("kv_pages_released_window", 0)
        say("kinds", "attn_kernel_active %d, kv_storage_in_place %d, "
            "kv_storage_rebuilds %d, window pages released %d, experts hit "
            "a step %.2f, assignments held %d elsewhere %d",
            gauges["attn_kernel_active"], gauges["kv_storage_in_place"],
            counters.get("kv_storage_rebuilds", 0), released,
            counters["moe_experts_hit"] / counters["decode_dispatches"],
            counters["moe_assignments_held"],
            counters["moe_assignments_elsewhere"])
        if on_tpu():
            check(gauges["attn_kernel_active"] == 1
                  and counters.get("attn_kernel_fallbacks", 0) == 0,
                  "attn_kernel='auto' fell back to the XLA path on the "
                  "TPU: %s", engine._kernel_fallback_reason)
        check(gauges["kv_storage_in_place"] == 1
              and counters.get("kv_storage_rebuilds", 0) == 0,
              "the pools of two kinds are not updated in place")
        check(released > 0, "no sliding-layer page was released")
        _say_page_steps("kinds", engine, gauges["attn_kernel_active"])
        check(engine.verify_pool_invariants()["used_pages"] == 0,
              "pages still held after the request")
    finally:
        engine.stop()
    rows = numpy.arange(prompt_len - 1, prompt_len + n_new - 1)
    for name, row in (("kernels", out), ("XLA twin", want)):
        # each engine against the reference over ITS OWN tokens: once a
        # near-tie parts the two (the chip, PR 29: at token 22 of 200, in
        # the parent's tree too), the rest of one engine's tokens says
        # nothing about a reference that read the other's
        with timed("kinds", "%s: reference over %d tokens"
                   % (name, prompt_len + n_new)):
            ref = numpy.asarray(afmoe.logits(
                weights, numpy.concatenate([prompt, row]), rows, lm))
        gap = ref.max(-1) - ref[numpy.arange(n_new), row]
        say("kinds", "%s: served tokens that are the reference's choice "
            "%d of %d (off at %s); widest gap below its best %.4f (limit "
            "%.3f)", name, int((gap == 0).sum()), n_new,
            numpy.nonzero(gap > 0)[0].tolist(), float(gap.max()), gap_limit)
        check(float(gap.max()) <= gap_limit,
              "%s: served tokens lie %.4f below the reference's best",
              name, float(gap.max()))
    same = int((numpy.cumsum(out != want) == 0).sum())
    say("kinds", "kernels and XLA twin serve the same first %d of %d "
        "tokens", same, n_new)


#: the latent kind at the benchmark configuration's published widths
#: (benchmark/configs/xing4.0-29b-a4b.json), cut in depth, experts and
#: vocabulary so that the phase is quick: a dense and two expert layers
LATENT_LM = {
    "model_type": "xing4_0", "hidden_size": 3584, "num_attention_heads": 32,
    "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "intermediate_size": 9216,
    "moe_intermediate_size": 1024, "vocab_size": 8192,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 16, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 2, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "rope_scaling": {"type": "yarn", "factor": 64, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096},
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "initializer_std": 0.02, "max_position_embeddings": 8192,
}


def phase_latent(seed, lm=LATENT_LM, slots=16, page=1024, prompt_len=5500,
                 n_new=120, gap_limit=1.5, kernel="auto"):
    """Latent attention under the n-stream residual on the serving path:
    one request through ``LMEngine`` (on the chip the expanded prefill
    kernel over five whole pages and a part of a sixth, the absorbed decode
    kernel through the table, the one-call row write of 16 lanes into the
    ONE pool a layer; expert layers through the grouped matmul) against the
    benchmark's plain reference (``benchmark/reference/xing4.py``, float32,
    expanded attention only), and against an engine on the XLA path, which
    serves the same request first.  The served tokens are the reference's
    own but for bfloat16 roundoff (``gap_limit`` on the reference's logit
    scale)."""
    import jax
    from benchmark.reference import xing4
    from veles_tpu import model_config
    from veles_tpu.serving import LMEngine
    record = model_config.from_published(lm)
    say("latent", "%d layers, ranks %d / %d, %d streams, %d experts top-%d, "
        "page %d, %s; %d lanes", lm["num_hidden_layers"],
        lm["q_lora_rank"], lm["kv_lora_rank"], record.streams,
        lm["n_routed_experts"], lm["num_experts_per_tok"], page,
        record.dtype, slots)
    weights = jax.tree.map(lambda a: a.astype(record.dtype),
                           xing4.make_weights(seed, lm))
    prompt = numpy.random.RandomState(seed).randint(
        0, lm["vocab_size"], prompt_len)

    def serve(name, attn_kernel):
        engine = LMEngine(weights, record,
                          max_len=lm["max_position_embeddings"],
                          slots=slots, prefill_chunk=page, paged_kv=True,
                          attn_kernel=attn_kernel, deadline_s=600.0,
                          name=name)
        with timed("latent", "%s: engine start (every program and table "
                   "width)" % name):
            engine.start()
        with timed("latent", "%s: one request: prompt %d, n_new %d"
                   % (name, prompt_len, n_new)):
            return engine, engine.submit(prompt, n_new).result(timeout=600)

    twin, want = serve("latent_xla", 0)
    twin.stop()
    engine, out = serve("latent", kernel)
    try:
        snap = engine.metrics.snapshot()
        gauges, counters = snap["gauges"], snap["counters"]
        say("latent", "attn_kernel_active %d, kv_storage_in_place %d, "
            "kv_storage_rebuilds %d, kv_bytes_per_token %d, experts hit a "
            "step %.2f", gauges["attn_kernel_active"],
            gauges["kv_storage_in_place"],
            counters.get("kv_storage_rebuilds", 0),
            gauges["kv_bytes_per_token"],
            counters["moe_experts_hit"] / counters["decode_dispatches"])
        if on_tpu():
            check(gauges["attn_kernel_active"] == 1
                  and counters.get("attn_kernel_fallbacks", 0) == 0,
                  "attn_kernel='auto' fell back to the XLA path on the "
                  "TPU: %s", engine._kernel_fallback_reason)
        check(gauges["kv_storage_in_place"] == 1
              and counters.get("kv_storage_rebuilds", 0) == 0,
              "the latent pools are not updated in place")
        check(len(engine._storage()[0]) == 1, "more than one pool a layer")
        _say_page_steps("latent", engine, gauges["attn_kernel_active"])
        check(engine.verify_pool_invariants()["used_pages"] == 0,
              "pages still held after the request")
    finally:
        engine.stop()
    rows = numpy.arange(prompt_len - 1, prompt_len + n_new - 1)
    for name, row in (("kernels", out), ("XLA twin", want)):
        with timed("latent", "%s: reference over %d tokens"
                   % (name, prompt_len + n_new)):
            ref = numpy.asarray(xing4.logits(
                weights, numpy.concatenate([prompt, row]), rows, lm))
        gap = ref.max(-1) - ref[numpy.arange(n_new), row]
        say("latent", "%s: served tokens that are the reference's choice "
            "%d of %d (off at %s); widest gap below its best %.4f (limit "
            "%.3f)", name, int((gap == 0).sum()), n_new,
            numpy.nonzero(gap > 0)[0].tolist(), float(gap.max()), gap_limit)
        check(float(gap.max()) <= gap_limit,
              "%s: served tokens lie %.4f below the reference's best",
              name, float(gap.max()))
    same = int((numpy.cumsum(out != want) == 0).sum())
    say("latent", "kernels and XLA twin serve the same first %d of %d "
        "tokens", same, n_new)


#: a latent stack that drafts with its own module, at the benchmark
#: configuration's published widths (``joyai-llm-flash-ep8``), cut in depth,
#: vocabulary and experts so that two engines and the reference fit
MTP_LM = {
    "model_type": "joyai_llm_flash", "hidden_size": 2048,
    "num_attention_heads": 32, "q_lora_rank": 1536, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "intermediate_size": 7168, "moe_intermediate_size": 768,
    "vocab_size": 8192, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 8, "router_width": 64, "held_experts": [0, 8],
    "num_experts_per_tok": 8, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "rope_theta": 32000000, "rope_scaling": None,
    "rms_norm_eps": 1e-6, "num_nextn_predict_layers": 1,
    "initializer_std": 0.02,
    "mtp_init": {"residual_std": 2e-06, "h_mix": 0.01},
    "max_position_embeddings": 8192,
}


def phase_mtp(seed, lm=MTP_LM, slots=16, page=1024, prompt_len=2500,
              n_new=161, gap_limit=1.5, kernel="auto"):
    """Self-speculation on the serving path (ISSUE 40): requests through an
    ``LMEngine`` whose model drafts with its own multi-token-prediction
    module (the chunk program with the module's rows behind the stack's; one
    decode dispatch a turn that verifies two rows a lane through the
    absorbed kernel, decides acceptance and makes the next draft in the
    graph; two dispatches in flight) against the SAME engine without
    ``spec_k``, which must serve the same tokens up to bfloat16 near-ties
    (each engine is held to the plain reference over its own tokens), and
    the program's acceptance against the reference's module's."""
    import jax
    from benchmark.reference import joyai
    from veles_tpu import model_config
    from veles_tpu.serving import LMEngine
    record = model_config.from_published(lm)
    say("mtp", "%d layers and the module's, ranks %d / %d, %d of %d experts "
        "held top-%d, page %d, %s; %d lanes", lm["num_hidden_layers"],
        lm["q_lora_rank"], lm["kv_lora_rank"], lm["n_routed_experts"],
        lm["router_width"], lm["num_experts_per_tok"], page, record.dtype,
        slots)
    weights = jax.tree.map(lambda a: a.astype(record.dtype),
                           joyai.make_weights(seed, lm))
    rng = numpy.random.RandomState(seed)
    prompts = [rng.randint(0, lm["vocab_size"], n)
               for n in (prompt_len, prompt_len // 3, prompt_len // 35 + 5)]

    def serve(name, spec_k):
        engine = LMEngine(weights, record,
                          max_len=lm["max_position_embeddings"],
                          slots=slots, prefill_chunk=page, paged_kv=True,
                          attn_kernel=kernel, spec_k=spec_k,
                          deadline_s=600.0, name=name)
        with timed("mtp", "%s: engine start (every program and table "
                   "width)" % name):
            engine.start()
        try:
            with timed("mtp", "%s: %d requests, n_new %d"
                       % (name, len(prompts), n_new)):
                outs = [f.result(timeout=600) for f in
                        [engine.submit(p, n_new) for p in prompts]]
            snap = engine.metrics.snapshot()
            check(engine.verify_pool_invariants()["used_pages"] == 0,
                  "%s: pages still held after the requests", name)
            check(snap["gauges"]["kv_storage_in_place"] == 1
                  and snap["counters"].get("kv_storage_rebuilds", 0) == 0,
                  "%s: the latent pools are not updated in place", name)
            if on_tpu():
                check(snap["gauges"]["attn_kernel_active"] == 1,
                      "%s: attn_kernel='auto' fell back to the XLA path on "
                      "the TPU: %s", name, engine._kernel_fallback_reason)
            return outs, snap["counters"]
        finally:
            engine.stop()

    plain, c0 = serve("mtp_plain", 0)
    spec, c1 = serve("mtp_spec", 1)
    drafts, accepted = c1["draft_tokens"], c1.get("draft_accepted", 0)
    say("mtp", "decode dispatches %d -> %d; drafts accepted %d of %d; tokens "
        "discarded %d; sent ahead %d, drains %d", c0["decode_dispatches"],
        c1["decode_dispatches"], accepted, drafts,
        c1.get("spec_tokens_discarded", 0),
        c1.get("dispatches_sent_ahead", 0), c1.get("pipeline_drains", 0))
    check(c1["spec_dispatches"] == c1["decode_dispatches"],
          "decode dispatches that verified no draft")
    check(c1["tokens_out"] == len(prompts) * n_new == c0["tokens_out"],
          "tokens_out %d", c1["tokens_out"])
    hits = positions = 0
    for prompt, a, b in zip(prompts, plain, spec):
        rows = numpy.arange(len(prompt) - 1, len(prompt) + n_new - 1)
        for name, row in (("with the module", b), ("without", a)):
            seq = numpy.concatenate([prompt, row])
            ref = numpy.asarray(joyai.logits(weights, seq, rows, lm))
            gap = ref.max(-1) - ref[numpy.arange(n_new), row]
            check(float(gap.max()) <= gap_limit,
                  "%s: served tokens lie %.4f below the reference's best",
                  name, float(gap.max()))
            if name == "with the module":
                got = joyai.draft_hits(weights, seq, len(prompt), lm)
                hits, positions = hits + got[0], positions + got[1]
        same = int((numpy.cumsum(a != b) == 0).sum())
        say("mtp", "prompt %d: with and without the module the same first "
            "%d of %d tokens; widest gap below the reference's best %.4f",
            len(prompt), same, n_new, float(gap.max()))
    say("mtp", "acceptance %.1f %% (the reference's module, at every "
        "position: %.1f %%)", 100.0 * accepted / max(drafts, 1),
        100.0 * hits / max(positions, 1))
    check(abs(accepted / max(drafts, 1) - hits / max(positions, 1)) <= 0.25,
          "the program's acceptance is not the reference's module's")


#: a stack of linear and full layers at the benchmark configuration's
#: published widths (benchmark/configs/qwen3-next-80b-a3b-ep4.json), cut in
#: depth, experts and vocabulary so that the phase is quick: one period
LINEAR_LM = {
    "model_type": "qwen3_next", "hidden_size": 2048,
    "num_attention_heads": 16, "num_key_value_heads": 2, "head_dim": 256,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "rope_scaling": None, "rms_norm_eps": 1e-6,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "num_experts": 16,
    "router_width": 64, "held_experts": [0, 16], "num_experts_per_tok": 10,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "vocab_size": 8192, "num_hidden_layers": 4, "full_attention_interval": 4,
    "initializer_std": 0.02, "max_position_embeddings": 4096,
}


def phase_linear(seed, lm=LINEAR_LM, slots=16, page=1024, prompt_len=2500,
                 n_new=60, rows=1024, gap_limit=1.5, kernel="auto",
                 interpret=False):
    """The gated delta rule (``ops/linear_attn.py``) at the published head
    sizes: the CHUNKED order (``chunk_terms`` and the sequential pass, on
    the chip ``pallas_kernels.gdn_chunk``) against the recurrent rule row
    by row over ``rows`` rows from a state that is not zero; the recurrent
    STEP (``gdn_decode``) with half the lanes masked, whose states must
    come back bit for bit; then one request through ``LMEngine`` (state
    slots beside pages, the prefill kernel at a head of 256 in query
    blocks, the row-tiled grouped matmul in the decode step) against the
    benchmark's plain reference (``benchmark/reference/qwen3_next.py``,
    float32, the recurrent rule token by token)."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import qwen3_next
    from veles_tpu import model_config
    from veles_tpu.ops import linear_attn
    from veles_tpu.ops import pallas_kernels as PK
    from veles_tpu.serving import LMEngine
    record = model_config.from_published(lm)
    lin = record.linear
    h, dk, dv = lin.v_heads, lin.k_dim, lin.v_dim
    say("linear", "%d layers %s, %d value heads of %d x %d, %d experts held "
        "of %d top-%d, page %d, %s; %d lanes", lm["num_hidden_layers"],
        "".join(k[0] for k in record.attn_kinds), h, dk, dv,
        lm["num_experts"], lm["router_width"], lm["num_experts_per_tok"],
        page, record.dtype, slots)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (1, rows, h, dk)) for key in keys[:2])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    v = jax.random.normal(keys[2], (1, rows, h, dv))
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (1, rows, h)))
    g = -jnp.exp(jax.random.uniform(keys[4], (h,), minval=-6.0, maxval=2.0)) \
        * jax.nn.softplus(jax.random.normal(keys[3], (1, rows, h)) + 1.0)
    state = jax.random.normal(keys[5], (slots, h, dk, dv))
    use_kernel = interpret or on_tpu()

    def rel(got, want):
        err, largest = _max_err(got, want)
        return err / max(largest, 1e-30)

    @jax.jit
    def chunked(state):
        terms = linear_attn.chunk_terms(q, k, v, beta, g)
        if use_kernel:
            o, s = PK.gdn_chunk(state, jnp.asarray([1]), jnp.asarray([False]),
                                *terms, interpret=interpret)
            return o, s[1:2]
        return linear_attn.chunk_pass(state[1:2], terms)

    @jax.jit
    def by_row(state):
        def row(s, t):
            o, s = linear_attn.recurrent_step(s, *t)
            return s, o
        s, o = jax.lax.scan(row, state[1:2], tuple(
            jnp.moveaxis(y, 1, 0) for y in (q, k, v, beta, g)))
        return jnp.moveaxis(o, 0, 1), s

    with timed("linear", "chunked rule over %d rows (%s)"
               % (rows, "kernel" if use_kernel else "scan")):
        o, s = jax.block_until_ready(chunked(state))
    with timed("linear", "recurrent rule over %d rows" % rows):
        o2, s2 = jax.block_until_ready(by_row(state))
    o = jnp.moveaxis(o, 1, 3).reshape(o2.shape)
    err = (rel(o, o2), rel(s, s2))
    say("linear", "chunked against recurrent: outputs %.3g, state %.3g "
        "(largest error over largest entry)", *err)
    check(max(err) < 2e-3, "the chunked rule differs from the recurrent "
          "one: %r", err)
    active = jnp.arange(slots) % 2 == 0
    # (the first rows as one row of each lane)
    row = [y[0, :slots] for y in (q, k, v, beta, g)]
    if use_kernel:
        step = jax.jit(lambda s: PK.gdn_decode(s, *row, active,
                                               interpret=interpret))
        with timed("linear", "the step with half of %d lanes masked"
                   % slots):
            o, s = jax.block_until_ready(step(state))
        o2, s2 = linear_attn.recurrent_step(state, *row)
        err = (rel(o[active], o2[active]), rel(s[active], s2[active]))
        kept = bool((s[~active] == state[~active]).all())
        say("linear", "step kernel against the rule: outputs %.3g, state "
            "%.3g; masked lanes bit for bit: %s", *err, kept)
        check(max(err) < 1e-4 and kept, "the step kernel: %r, masked lanes "
              "kept %s", err, kept)
    weights = jax.tree.map(lambda a: a.astype(record.dtype),
                           qwen3_next.make_weights(seed, lm))
    prompt = numpy.random.RandomState(seed).randint(
        0, lm["vocab_size"], prompt_len)
    engine = LMEngine(weights, record, max_len=lm["max_position_embeddings"],
                      slots=slots, prefill_chunk=page, paged_kv=True,
                      attn_kernel=kernel, deadline_s=600.0, name="linear")
    with timed("linear", "engine start (every program and table width)"):
        engine.start()
    try:
        with timed("linear", "one request: prompt %d, n_new %d"
                   % (prompt_len, n_new)):
            out = engine.submit(prompt, n_new).result(timeout=600)
        snap = engine.metrics.snapshot()
        gauges, counters = snap["gauges"], snap["counters"]
        say("linear", "attn_kernel_active %d, kv_storage_in_place %d, "
            "kv_storage_rebuilds %d, state_bytes_per_lane %d, "
            "kv_bytes_per_token %d, state_resets %d",
            gauges["attn_kernel_active"], gauges["kv_storage_in_place"],
            counters.get("kv_storage_rebuilds", 0),
            gauges["state_bytes_per_lane"], gauges["kv_bytes_per_token"],
            counters["state_resets"])
        if on_tpu():
            check(gauges["attn_kernel_active"] == 1
                  and counters.get("attn_kernel_fallbacks", 0) == 0,
                  "attn_kernel='auto' fell back to the XLA path on the "
                  "TPU: %s", engine._kernel_fallback_reason)
        check(gauges["kv_storage_in_place"] == 1
              and counters.get("kv_storage_rebuilds", 0) == 0,
              "state and pools are not updated in place")
        check(engine.verify_pool_invariants()["used_pages"] == 0
              and gauges["state_slots_free"] == slots,
              "pages or state slots still held after the request")
    finally:
        engine.stop()
    steps = numpy.arange(prompt_len - 1, prompt_len + n_new - 1)
    with timed("linear", "reference over %d tokens" % (prompt_len + n_new)):
        ref = numpy.asarray(qwen3_next.logits(
            weights, numpy.concatenate([prompt, out]), steps, lm))
    gap = ref.max(-1) - ref[numpy.arange(n_new), out]
    say("linear", "served tokens that are the reference's choice %d of %d "
        "(off at %s); widest gap below its best %.4f (limit %.3f)",
        int((gap == 0).sum()), n_new, numpy.nonzero(gap > 0)[0].tolist(),
        float(gap.max()), gap_limit)
    check(float(gap.max()) <= gap_limit,
          "served tokens lie %.4f below the reference's best",
          float(gap.max()))


def _sharding_line(name, arr):
    return "%s %s on %d device(s), shard %s" % (
        name, tuple(arr.shape), len(arr.sharding.device_set),
        tuple(arr.sharding.shard_shape(arr.shape)))


def phase_four_chips(seed, devices=None, minibatch=128, steps=3,
                     image_hw=(256, 256), n_classes=1000, layers=None,
                     tp_min_width=4096, lm=FULL_LM, slots=8,
                     prefill_chunk=32, n_prompts=8, mean_len=96, n_new=16):
    """What exists only across chips: a data 2 x model 2 AlexNet step
    against the one-device step, and a tp=4 engine and four one-chip
    replicas behind the Router against a one-chip engine."""
    import jax
    import jax.numpy as jnp
    from tools import load_gen
    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.parallel import (ShardedTrainer, make_mesh,
                                    model_shard_candidates)
    from veles_tpu.samples import imagenet
    from veles_tpu.serving import LMEngine, Router, replica_device_slices
    devices = list(devices if devices is not None else jax.devices())
    check(len(devices) == 4, "the four-chip phase needs 4 devices, got %d",
          len(devices))

    # ---- train: mesh step == one-device step
    def build():
        prng.reset()
        prng.seed_all(seed)
        root.__dict__.pop("imagenet", None)
        root.imagenet.update({
            "loader": {"minibatch_size": minibatch, "n_train": minibatch,
                       "n_valid": minibatch, "image_hw": tuple(image_hw),
                       "n_classes": n_classes},
            "decision": {"max_epochs": 1, "fail_iterations": 5},
            "layers": (layers if layers is not None
                       else imagenet.alexnet_layers(n_classes=n_classes)),
        })
        wf = imagenet.build(fused=True)
        wf.initialize()
        return wf

    rng = numpy.random.RandomState(seed)
    batches = [(rng.uniform(-1, 1, (minibatch,) + tuple(image_hw) + (3,))
                .astype(numpy.float32),
                rng.randint(0, n_classes, minibatch).astype(numpy.int32))
               for _ in range(steps)]
    mask = numpy.ones(minibatch, numpy.float32)
    key = jax.random.PRNGKey(seed)
    say("mesh", "AlexNet minibatch %d, %dx%d, %d classes, %d steps",
        minibatch, image_hw[0], image_hw[1], n_classes, steps)

    runner = build()._fused_runner
    mesh = make_mesh(4, model_parallel=2, devices=devices)
    shard = model_shard_candidates(runner, min_width=tp_min_width)
    trainer = ShardedTrainer(runner, mesh, model_shard_layers=shard)
    say("mesh", "mesh %s, model-sharded layers %s", dict(mesh.shape),
        list(shard))
    for i, entry in enumerate(trainer.state):
        if entry and entry.get("w") is not None:
            say("mesh", _sharding_line("layer %d w" % i, entry["w"]))
    x0, y0, m0 = trainer.put_batch(batches[0][0], batches[0][1], mask)
    say("mesh", _sharding_line("batch x", x0))
    batch_size = jnp.asarray(minibatch, jnp.int32)
    with timed("mesh", "lower+compile the mesh step for its text"):
        text = trainer._train.lower(
            trainer.state, x0, y0, m0, batch_size, key,
            jnp.asarray(0, jnp.int32)).compile().as_text()
    n_allreduce = text.count("all-reduce(") + text.count("all-reduce-start(")
    say("mesh", "compiled mesh step: %d all-reduce ops", n_allreduce)
    check(n_allreduce > 0, "no all-reduce in the compiled mesh step")
    # Each step is compared from EQUAL state: the one-device step starts
    # from the mesh's own gathered pre-step state.  Two free-running
    # trajectories part ways on seeded noise (6.6e-8, 2.7e-7, then 5e-5
    # at the third step on four v5e chips): roundoff amplified by
    # training, not a difference between the two steps.
    one_step = jax.jit(runner._train_step)
    with timed("mesh", "%d steps, mesh and one device" % steps):
        for i, (x, y) in enumerate(batches):
            before = trainer.fetch(trainer.state)
            step_key = jax.random.fold_in(key, i)
            metrics = trainer.train_step(x, y, mask, minibatch,
                                         rng=step_key, step=i)
            b = float(trainer.fetch(metrics)["loss_sum"])
            _, ref = one_step(before, x, y, mask, batch_size, step_key,
                              jnp.asarray(i, jnp.int32))
            a = float(ref["loss_sum"])
            say("mesh", "step %d loss_sum: one device %.8g, mesh %.8g, "
                "rel diff %.2g", i, a, b, abs(a - b) / abs(a))
            check(numpy.isfinite(b) and abs(a - b) <= 2e-5 * abs(a) + 2e-6,
                  "step %d: mesh loss %.8g != one-device loss %.8g (rtol "
                  "2e-5)", i, b, a)
    del trainer, runner, before

    # ---- serve: tp=4 and four replicas == one chip
    wf = _build_char_lm(seed, lm, run=False)
    lm_trainer = wf.trainer
    params = lm_trainer._to_portable(lm_trainer.params)
    grid = load_gen.lm_prompts(
        1, n_prompts, vocab=lm["vocab"], mean_len=mean_len,
        shared_frac=0.25, max_len=lm["max_len"] - n_new - 1, seed=seed)
    plist = [grid[k] for k in sorted(grid)]
    say("serve4", "char_lm d_model %d, %d heads, %d layers, vocab %d; %d "
        "prompts of %s tokens, n_new %d", lm["d_model"], lm["n_heads"],
        lm["n_layers"], lm["vocab"], len(plist),
        sorted(len(p) for p in plist), n_new)

    def engine(name, **kw):
        return LMEngine(params, n_heads=lm_trainer.n_heads,
                        max_len=lm["max_len"], slots=slots,
                        prefill_chunk=prefill_chunk, paged_kv=True,
                        attn_kernel="auto", name=name, **kw)

    def answers(server):
        futures = [server.submit(numpy.asarray(p, numpy.int32), n_new)
                   for p in plist]
        return futures, [list(p) + numpy.asarray(f.result(600)).tolist()
                         for p, f in zip(plist, futures)]

    def where(eng):
        leaf = jax.tree.leaves(eng.params)[0]
        return sorted(d.id for d in leaf.sharding.device_set)

    logits_fn = _logits_fn(params, lm_trainer.n_heads)
    one = engine("one_chip", devices=[devices[0]]).start()
    try:
        with timed("serve4", "one-chip engine on device %s" % where(one)):
            _, want = answers(one)
    finally:
        one.stop()
    tp = engine("tp4", tp=4, devices=devices).start()
    try:
        wq = tp.params["blocks"][0]["attn"]["wq"]
        say("serve4", _sharding_line("tp=4 wq", wq))
        say("serve4", _sharding_line("tp=4 kv pool", tp._kv_pools[0][0]))
        check(len(wq.sharding.device_set) == 4
              and not wq.sharding.is_fully_replicated,
              "tp=4 weights are not sharded over four devices")
        with timed("serve4", "LMEngine(tp=4) on devices %s" % where(tp)):
            _, got = answers(tp)
    finally:
        tp.stop()
    compare_tokens("serve4", "tp=4 vs one chip", got, want, plist,
                   logits_fn)
    replicas = [engine("replica%d" % i, devices=slice_)
                for i, slice_ in enumerate(
                    replica_device_slices(4, 0, devices))]
    router = Router(replicas, policy="round_robin").start()
    try:
        homes = [where(r) for r in replicas]
        say("serve4", "replica devices %s", homes)
        check(len({tuple(h) for h in homes}) == 4,
              "the four replicas do not sit on four distinct devices")
        with timed("serve4", "four-replica Router"):
            futures, got = answers(router)
        served_by = sorted({f.job.replica for f in futures})
        say("serve4", "requests served by replicas %s", served_by)
        check(len(served_by) == 4, "round robin reached only replicas %s",
              served_by)
    finally:
        router.stop()
    compare_tokens("serve4", "four replicas vs one chip", got, want, plist,
                   logits_fn)


# ---------------------------------------------------------------------- CLI
def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs only the four-chip phase")
    parser.add_argument("--seed", type=int, default=22)
    parser.add_argument("--only", default=None, metavar="PHASE",
                        help="run one phase of the one-chip list")
    args = parser.parse_args(argv)

    import jax
    from veles_tpu import compile_cache
    device = device_record()
    if device["platform"] != "tpu":
        print("chip_smoke: jax found no TPU (platform %r): this check "
              "runs on the chip only" % device["platform"],
              file=sys.stderr)
        return 2
    if args.chips == 4 and device["count"] != 4:
        print("chip_smoke: --chips 4 needs four TPU devices, jax has %d"
              % device["count"], file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()
    meter()
    from veles_tpu.serving.timeseries import tpu_peak_flops
    peak, _ = tpu_peak_flops(device["kind"])
    say("smoke", "jax %s on %s x%d (%s); compile cache %s; seed %d",
        jax.__version__, device["kind"], device["count"],
        device["platform"], cache_dir, args.seed)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    if args.chips == 4:
        phases = [("four-chips", lambda: phase_four_chips(args.seed))]
    else:
        phases = [("sync", lambda: phase_sync(peak_flops=peak)),
                  ("train", lambda: phase_train(args.seed, workdir)),
                  ("kernels", lambda: phase_kernels(args.seed)),
                  ("serve", lambda: phase_serve(args.seed)),
                  ("kinds", lambda: phase_kinds(args.seed)),
                  ("latent", lambda: phase_latent(args.seed)),
                  ("linear", lambda: phase_linear(args.seed)),
                  ("mtp", lambda: phase_mtp(args.seed))]
    if args.only:
        phases = [(name, run) for name, run in phases if name == args.only]
    failed = []
    begin = time.perf_counter()
    try:
        for name, run in phases:
            try:
                with timed("smoke", "phase %s" % name):
                    run()
            except Exception:   # noqa: BLE001 — later phases still run
                traceback.print_exc()
                say("smoke", "phase %s FAILED", name)
                failed.append(name)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if failed:
        say("smoke", "failed phases: %s", ", ".join(failed))
        return 1
    say("smoke", "all phases passed in %.1fs", time.perf_counter() - begin)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
