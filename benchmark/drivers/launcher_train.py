"""Driver for a training job run by the launcher: ``veles_tpu.__main__.main``
with ``--epoch-scan`` on a records file the benchmark writes from the seed
(on a records loader that is the streaming windowed epoch scan).

Set-up writes the records, then lets the launcher build the workflow; before
the launcher boots it, the benchmark's first steps run on the very objects
the window will drive (``lib/handoff.py``, ``drivers/imagenet_workflow.py``):
the workflow's fused runner gets weights made from the seed, and its window
program (``FusedRunner.window_scan_fn``) is driven through the epoch driver's
own feed (``_WindowStager.stage``) for one minibatch and then two whole
windows, on rows that all differ.  What they produced is kept for the
comparison; the state they left is the state the launcher trains on.

The window opens once the launcher has completed ``warm_windows`` windows of
its own.  Completions are observed from outside, by polling the counts the
epoch driver keeps on the workflow (``_stream_stats``); nothing is patched."""

from __future__ import annotations

import os
import threading
import time

import numpy

from benchmark.lib import compare, handoff
from benchmark.lib.files import HERE, load_module

POLL_S = 0.002


def make_records(seed, cfg):
    """(images uint8 (N, H, W, 3), labels int32 (N,)) from the seed, laid out
    [validation | train] as the records loader expects."""
    data = cfg["data"]
    n = data["n_valid"] + data["n_train"]
    rng = numpy.random.default_rng([int(seed), 11])
    images = rng.integers(0, 256, size=(n,) + tuple(cfg["image"]),
                          dtype=numpy.uint8)
    labels = rng.integers(0, cfg["n_classes"], size=n).astype(numpy.int32)
    return images, labels


def as_tree(state, which):
    """The program's per-layer state as the reference's tree: ``which`` is
    ("w", "b") for the parameters, ("vw", "vb") for the velocities."""
    return {i: {"w": e[which[0]], "b": e[which[1]]}
            for i, e in enumerate(state) if e}


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.traffic = ctx.traffic
        self.reference = load_module("reference", self.cfg["reference"])
        self.first = {}
        self.thread = None
        self.error = None

    # ------------------------------------------------------------- set-up
    def call_rows(self):
        """Row ranges (in minibatches of the train split, file order) of the
        first steps' three calls: one minibatch, then two whole windows."""
        w = int(self.traffic["window_minibatches"])
        return [(0, 1), (1, 1 + w), (1 + w, 1 + 2 * w)]

    def first_steps(self, wf):
        """On the launcher's thread, after the workflow is built and before
        it is booted."""
        import jax
        import jax.numpy as jnp
        from veles_tpu.epoch_driver import _WindowStager
        seed, cfg = self.ctx.seed, self.cfg
        wf.initialize()
        runner, loader = wf._fused_runner, wf.loader
        weights = self.reference.make_weights(seed, cfg)
        state = []
        for i, entry in enumerate(runner.state):
            if not entry:
                state.append({})
                continue
            w, b = weights[i]["w"], weights[i]["b"]
            if w.shape != entry["w"].shape or b.shape != entry["b"].shape:
                raise RuntimeError(
                    "layer %d: the configuration file says %s, the program "
                    "built %s" % (i, w.shape, entry["w"].shape))
            state.append({"w": w, "vw": jnp.zeros_like(w),
                          "b": b, "vb": jnp.zeros_like(b)})
        if set(weights) != {i for i, e in enumerate(state) if e}:
            raise RuntimeError("parameterised layers differ from the "
                               "configuration file's")
        mb = int(cfg["minibatch"])
        offset = int(cfg["data"]["n_valid"])
        window_fn = runner.window_scan_fn()
        stager = _WindowStager(loader, True, 1, name="bench_first_steps")
        params0 = as_tree(state, ("w", "b"))
        losses = []
        try:
            for call, (lo, hi) in enumerate(self.call_rows()):
                gidx = (offset + numpy.arange(lo * mb, hi * mb)
                        ).reshape(hi - lo, mb)
                x, y, lidx, m = stager.stage(
                    gidx, numpy.ones(gidx.shape, numpy.float32))
                state, totals = window_fn(
                    state, x, y, lidx, m,
                    self.reference.window_key(seed, call), lo)
                losses.append(float(totals["loss_sum"]))
                if call == 0:
                    gradient = compare.first_gradient_norms(
                        params0, as_tree(state, ("vw", "vb")), cfg["sgd"])
        finally:
            stager.shutdown()
        self.first = {
            "loss": losses, "gradient": gradient,
            "change": compare.change_norms(params0,
                                           as_tree(state, ("w", "b")))}
        del params0
        # the same object, with the state these steps left, goes to the window
        runner.state = state
        runner.sync_to_units()
        jax.block_until_ready(jax.tree.leaves(state))
        self.workflow = wf

    def launch(self, records):
        from veles_tpu.__main__ import main
        cfg = self.cfg
        argv = [os.path.join(HERE, "drivers", "imagenet_workflow.py"),
                "--epoch-scan", "1",
                "--random-seed", str(int(self.ctx.seed) % (2 ** 31)),
                "root.imagenet.loader.records_path=%s" % records,
                "root.imagenet.loader.minibatch_size=%d" % cfg["minibatch"],
                "root.imagenet.decision.max_epochs=1000000000",
                "root.imagenet.decision.fail_iterations=1000000000"]
        for flag in ("stream_window", "stage_ahead"):
            # the launcher's own defaults unless the configuration says
            if cfg.get(flag):
                argv[1:1] = ["--" + flag.replace("_", "-"), str(cfg[flag])]

        def body():
            try:
                main(argv)
            except BaseException as e:   # noqa: BLE001 — reported by wait()
                self.error = e
        self.thread = threading.Thread(target=body, name="bench-launcher",
                                       daemon=True)
        self.thread.start()

    def stats(self):
        wf = getattr(self, "workflow", None)
        return getattr(wf, "_stream_stats", None) if wf is not None else None

    def wait_windows(self, n, timeout):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.error is not None:
                raise self.error
            stats = self.stats()
            if stats is not None and stats["windows"] >= n:
                return
            time.sleep(0.01)
        raise RuntimeError("the launcher completed no %d windows in %ds"
                           % (n, timeout))

    def setup(self):
        from veles_tpu.loader.records import write_records
        cfg = self.cfg
        images, labels = make_records(self.ctx.seed, cfg)
        records = os.path.join(self.ctx.tmp, "imagenet.records")
        write_records(records, images, labels,
                      [0, cfg["data"]["n_valid"], cfg["data"]["n_train"]])
        # the reference follows the first steps' rows, and no others
        lo = cfg["data"]["n_valid"]
        hi = lo + self.call_rows()[-1][1] * cfg["minibatch"]
        self.rows = (images[lo:hi].copy(), labels[lo:hi].copy())
        del images, labels
        handoff.SLOT["first_steps"] = self.first_steps
        self.launch(records)
        self.wait_windows(int(self.traffic["warm_windows"]),
                          float(self.traffic["setup_timeout_s"]))

    # ------------------------------------------------------------- window
    def measure(self):
        ctx = self.ctx
        stats = self.stats()
        per_window = int(stats["window_minibatches"]) * int(
            self.cfg["minibatch"])
        compiles0 = ctx.compiles()
        t_open = time.monotonic()
        t_close = t_open + ctx.seconds
        trace_at = (t_open + min(float(self.traffic["trace_offset_s"]),
                                 ctx.seconds / 4) if ctx.trace else None)
        trace_until = trace_window_s = None
        seen = stats["windows"]
        epochs = stats["epochs"]
        completions = []              # (host time, windows completed so far)
        epoch_ends = []               # (host time, stall seconds so far)
        while True:
            now = time.monotonic()
            if now >= t_close:
                break
            if self.error is not None:
                raise self.error
            if stats["windows"] != seen:
                seen = stats["windows"]
                completions.append((now, seen))
            if stats["epochs"] != epochs:
                epochs = stats["epochs"]
                epoch_ends.append((now, stats["staging_stall_s"]))
            if trace_at is not None and now >= trace_at:
                ctx.start_trace()
                trace_at = None
                trace_until = now + min(float(self.traffic["trace_s"]),
                                        max(t_close - now - 0.5, 0.25))
            if trace_until is not None and now >= trace_until:
                trace_window_s = ctx.stop_trace()
                trace_until = None
            time.sleep(POLL_S)
        if trace_until is not None:
            trace_window_s = ctx.stop_trace()
        compiles = ctx.compiles() - compiles0
        # the launcher ends after the epoch it is in
        self.workflow.decision.max_epochs = 1
        self.thread.join(float(self.traffic["setup_timeout_s"]))
        if self.thread.is_alive():
            raise RuntimeError("the launcher did not end")
        if self.error is not None:
            raise self.error

        rate = span_share = None
        if len(completions) >= 2:
            (t_a, n_a), (t_b, n_b) = completions[0], completions[-1]
            rate = (n_b - n_a) * per_window / (t_b - t_a)
            span_share = (t_b - t_a) / ctx.seconds
        stall_share = None
        if len(epoch_ends) >= 2:
            (t_a, s_a), (t_b, s_b) = epoch_ends[0], epoch_ends[-1]
            stall_share = 100.0 * (s_b - s_a) / (t_b - t_a)
        minibatches = ((completions[-1][1] - completions[0][1])
                       * int(stats["window_minibatches"])
                       if len(completions) >= 2 else 0)
        return {
            "t_open": t_open, "window_s": ctx.seconds,
            "trace_window_s": trace_window_s,
            "attempted": len(completions), "failed": 0,
            "end_to_end": {"train_samples_s": rate},
            "completions": completions,
            "counters": {"compiles": compiles, "span_share": span_share,
                         "stage_stall_share": stall_share,
                         "minibatches": minibatches,
                         "samples_per_window": per_window},
        }

    # ------------------------------------------------- release and compare
    def release(self):
        import gc
        import jax
        self.workflow = None
        handoff.SLOT.clear()
        gc.collect()
        for a in jax.live_arrays():
            a.delete()

    def check(self, art, control=None):
        """The reference follows the first steps: the same rows, the same
        keys, weights from the same seed.  Compared: each call's summed loss,
        the first gradient's norm by the worst leaf, the parameters' change
        after the three calls by the worst leaf, and that completions span
        the window."""
        import jax.numpy as jnp
        ref, cfg, seed = self.reference, self.cfg, self.ctx.seed
        limits = cfg["limits"]
        mb = int(cfg["minibatch"])
        images, labels = self.rows

        def follow(how):
            # ``how``: a precision name, or the fault ``half_batch`` (half of
            # every minibatch left out, the mean taken over the rest)
            half = how == "half_batch"
            step = ref.make_step(cfg, "highest" if half else how)
            params = params0 = ref.make_weights(seed, cfg)
            velocity = ref.zeros_like(params)
            losses, gradient = [], None
            for call, (lo, hi) in enumerate(self.call_rows()):
                x = (jnp.asarray(images[lo * mb:hi * mb], jnp.float32)
                     * jnp.float32(1.0 / 127.5) - 1.0).reshape((hi - lo, mb) + images.shape[1:])
                y = jnp.asarray(labels[lo * mb:hi * mb]).reshape(hi - lo, mb)
                if half:
                    x, y = x[:, :mb // 2], y[:, :mb // 2]
                params, velocity, loss = ref.window(
                    step, params, velocity, x, y, ref.window_key(seed, call))
                losses.append(float(loss))
                if call == 0:
                    gradient = compare.first_gradient_norms(
                        params0, velocity, cfg["sgd"])
            return {"loss": losses, "gradient": gradient,
                    "change": compare.change_norms(params0, params)}

        def gaps(got, want):
            out = {}
            for i, (a, b) in enumerate(zip(got["loss"], want["loss"])):
                out["loss_gap_call%d" % (i + 1)] = abs(a - b) / abs(b)
            out["first_gradient_gap"], where_g = compare.worst_gap(
                got["gradient"], want["gradient"])
            out["change_gap"], where_c = compare.worst_gap(
                got["change"], want["change"],
                leave_out=compare.all_but_zero(want["gradient"]))
            seen = {"worst_leaves": {"first_gradient_gap": where_g,
                                     "change_gap": where_c},
                    "first_gradient_gap_median": compare.median_gap(
                        got["gradient"], want["gradient"]),
                    "change_gap_median": compare.median_gap(
                        got["change"], want["change"])}
            return out, seen

        want = follow("highest")
        program, seen = gaps(self.first, want)
        art["checked"] = dict(seen, reference_loss=want["loss"],
                              program_loss=self.first["loss"])
        if control is not None:
            readings, seen = gaps(follow(control), want)
            art["control"] = dict(readings, **seen)
        compared = {name: {"value": value, "limit": limits[name]}
                    for name, value in program.items()}
        span = art["counters"]["span_share"]
        compared["completions_span_short"] = {
            "value": None if span is None else max(0.0, 1.0 - span),
            "limit": limits["completions_span_short"]}
        return compared
