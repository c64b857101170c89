"""Benchmarks: MNIST-FC, CIFAR-10-conv, AlexNet (BASELINE configs 0-2).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "configs"}.
The headline metric stays MNIST-FC samples/sec/chip (config[0]); the
``configs`` field carries the full per-config methodology record — step
time, analytic model FLOPs, achieved TFLOP/s, and MFU — for every bench.

Measurement protocol (BASELINE.md):
- steady-state samples/sec/chip after a warm-up epoch (compile excluded),
  timed over enough epochs to dominate host<->device latency;
- SYNCHRONIZATION: every timing window ends in ``jax.block_until_ready``
  (``_sync``); chip_smoke.py's sync phase checks on the chip that it
  waits for the device (it agrees with a value fetch there).
- MFU = achieved TFLOP/s / bf16 peak of the chip.  Matmul precision is
  fp32 HIGHEST (convergence parity — SURVEY §7).  A bf16 variant of the
  AlexNet bench is also recorded (the TPU-idiomatic fast path).
- ``vs_baseline`` is the speedup over the reference's numpy backend FLOOR
  measured in-process (the reference itself is unrecoverable — SURVEY
  §0/§6): per-minibatch python loop, numpy GEMMs, same topology.

FLOPs convention: analytic per-sample model FLOPs — dense fwd = 2*in*out,
conv fwd = 2*ky*kx*cin*cout*oh*ow; training = 3x fwd per parameterized
layer, minus the dX term of the first parameterized layer (its err_input
is never formed).  Activations/pools/LRN/softmax are excluded (memory-
bound, <2% of conv/dense FLOPs at these shapes).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy


def _peak_tflops():
    """(device_kind, bf16 peak TFLOP/s) from the package's one peaks
    table; no peak (and so no MFU) off the TPU, an error for a TPU kind
    the table does not hold."""
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        return device.device_kind, None
    from veles_tpu.serving.timeseries import tpu_peak_flops
    return device.device_kind, tpu_peak_flops(device.device_kind)[0] / 1e12


def _sync(tree):
    """End a timing window: wait for the device."""
    import jax
    return jax.block_until_ready(tree)


# --------------------------------------------------------------- workflows
def build_mnist(n_train, n_valid, mb, seed=1):
    from veles_tpu import prng
    from veles_tpu.config import root
    prng.reset()
    prng.seed_all(seed)
    root.mnist.update({
        "loader": {"minibatch_size": mb, "n_train": n_train,
                   "n_valid": n_valid},
        "decision": {"max_epochs": 1000, "fail_iterations": 1000},
        "layers": [
            {"type": "all2all_tanh", "output_sample_shape": 100,
             "learning_rate": 0.03, "momentum": 0.9},
            {"type": "softmax", "output_sample_shape": 10,
             "learning_rate": 0.03, "momentum": 0.9},
        ],
    })
    from veles_tpu.samples import mnist
    wf = mnist.build(fused=True)
    wf.initialize()
    return wf


def build_cifar(n_train, n_valid, mb, seed=1):
    from veles_tpu import prng
    from veles_tpu.config import root
    prng.reset()
    prng.seed_all(seed)
    root.__dict__.pop("cifar", None)
    root.cifar.update({
        "loader": {"minibatch_size": mb, "n_train": n_train,
                   "n_valid": n_valid},
        "decision": {"max_epochs": 1000, "fail_iterations": 1000},
    })
    from veles_tpu.samples import cifar
    wf = cifar.build(fused=True)   # default small-conv topology (config[1])
    wf.initialize()
    return wf


def build_alexnet(n_train, n_valid, mb, image_hw=(256, 256), n_classes=1000,
                  crop=(227, 227)):
    """Full-size AlexNet (BASELINE config[2]) on random 256x256 images with
    the real random-crop+flip augmentation and dropout FC trunk."""
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.samples.imagenet import ImagenetWorkflow, alexnet_layers
    prng.reset()
    prng.seed_all(1)

    class _RandomImages(FullBatchLoader):
        def load_data(self):
            rng = numpy.random.RandomState(12345)
            h, w = image_hw
            total = n_train + n_valid
            self.original_data.reset(
                rng.uniform(-1.0, 1.0, (total, h, w, 3))
                .astype(numpy.float32))
            self.original_labels.reset(
                rng.randint(0, n_classes, total).astype(numpy.int32))
            self.class_lengths = [0, n_valid, n_train]

    wf = ImagenetWorkflow(
        None, name="alexnet_bench", loader_factory=_RandomImages,
        loader_config={"minibatch_size": mb},
        layers=alexnet_layers(n_classes=n_classes, crop=crop),
        decision_config={"max_epochs": 1000, "fail_iterations": 1000},
        loss_function="softmax", fused=True)
    wf.initialize()
    return wf


# ------------------------------------------------------------------- flops
def model_train_flops_per_sample(runner):
    """Analytic training FLOPs per sample (convention in module docstring)."""
    total = 0.0
    first = True
    for fwd in runner.forwards:
        if not getattr(fwd, "has_params", False) or fwd.weights.is_empty:
            continue
        w_shape = tuple(fwd.weights.shape)
        if len(w_shape) == 4:         # conv (ky, kx, cin, cout)
            oh, ow = fwd.output_sample_shape[:2]
            f = 2.0 * numpy.prod(w_shape) * oh * ow
        else:                         # dense (n_in, n_out)
            f = 2.0 * numpy.prod(w_shape)
        total += 3.0 * f - (f if first else 0.0)
        first = False
    return float(total)


# ------------------------------------------------------------------ timing
def epoch_plan_arrays(loader, wanted_cls=None):
    """(idx, mask) matrices of one set for the epoch-scan fast path,
    from a FRESH plan (train by default; pass loader.base.VALID for the
    validation set).  Extraction lives on the Loader (plan_arrays)."""
    loader._plan_epoch()
    return loader.plan_arrays(wanted_cls)


def best_time(fn, reps=3):
    """Best-of-``reps`` wall time of ``fn()``, each run ended by
    ``_sync`` — the shared core of every K-vs-1 microbench."""
    best = float("inf")
    for _ in range(reps):
        begin = time.perf_counter()
        out = fn()
        _sync(out)
        best = min(best, time.perf_counter() - begin)
    return best


def timed_window(dispatch, target_seconds, initial=1):
    """Grow the work window until it reaches ``target_seconds``:
    ``dispatch(n, start)`` issues ``n`` work units beginning at offset
    ``start`` and must end in ``_sync``.  Returns
    (n_in_final_window, elapsed_seconds)."""
    n, done = initial, 0
    while True:
        begin = time.perf_counter()
        dispatch(n, done)
        elapsed = time.perf_counter() - begin
        done += n
        if elapsed >= target_seconds:
            return n, elapsed
        n = max(n * 2, int(n * 1.3 * target_seconds / max(elapsed, 1e-3)))


#: epochs folded into ONE device program by the timing path, so that
#: per-epoch dispatch does not dominate small models' timings
#: (compiled.epoch_chunk_fn).  On CPU (--smoke) fp32-HIGHEST convs are
#: slow, so chunking would only multiply the warm-up cost — use 1 there.
CHUNK_EPOCHS = 8


def _chunk_epochs():
    import jax
    return 1 if jax.default_backend() == "cpu" else CHUNK_EPOCHS


def bench_epoch_scan(wf, target_seconds=4.0):
    """Steady-state samples/sec via the epoch-scan path, dispatched in
    chunks of epochs so the per-execute round-trip amortizes.

    Returns (samples_per_sec, steps_per_epoch, step_time_us)."""
    runner = wf._fused_runner
    chunk_epochs = _chunk_epochs()
    chunk = runner.epoch_chunk_fn(chunk_epochs)
    loader = wf.loader
    data = loader.original_data.devmem
    labels = loader.original_labels.devmem
    idx, mask = epoch_plan_arrays(loader)
    n_samples = int(mask.sum())
    steps_per_epoch = idx.shape[0]
    from veles_tpu import prng
    rng = prng.get("dropout").key() if runner._has_stochastic else None

    def run_chunks(state, n, step0):
        for c in range(n):
            state, totals = chunk(state, data, labels, idx, mask, rng=rng,
                                  step0=step0 + c * chunk_epochs
                                  * steps_per_epoch)
        return state, totals

    # warm-up chunk (compile)
    holder = {"state": runner.state}
    state, totals = run_chunks(holder["state"], 1, 0)
    _sync(totals)
    holder["state"] = state

    def dispatch(n, done):
        state, totals = run_chunks(holder["state"], n,
                                   (done + 1) * chunk_epochs
                                   * steps_per_epoch)
        _sync(totals)
        holder["state"] = state

    chunks, elapsed = timed_window(dispatch, target_seconds)
    runner.state = holder["state"]
    epochs = chunks * chunk_epochs
    sps = epochs * n_samples / elapsed
    step_us = elapsed / (epochs * steps_per_epoch) * 1e6
    return sps, steps_per_epoch, step_us


def bench_config(name, wf, target_seconds, device_kind, peak_tflops,
                 precision):
    sps, steps, step_us = bench_epoch_scan(wf, target_seconds)
    flops = model_train_flops_per_sample(wf._fused_runner)
    achieved = sps * flops / 1e12
    rec = {
        "samples_per_sec": round(sps, 1),
        "minibatch": int(wf.loader.max_minibatch_size),
        "steps_per_epoch": int(steps),
        "step_time_us": round(step_us, 2),
        "model_train_mflops_per_sample": round(flops / 1e6, 3),
        "achieved_tflops": round(achieved, 2),
        "mfu_pct_of_bf16_peak": (round(100.0 * achieved / peak_tflops, 2)
                                 if peak_tflops else None),
        "precision": precision,
        "device": device_kind,
    }
    print("%-16s %12.0f samples/s  %8.1f us/step  %7.2f TF/s  MFU %s%%"
          % (name, sps, step_us, achieved,
             rec["mfu_pct_of_bf16_peak"]), file=sys.stderr)
    return rec


# ------------------------------------------------ alexnet from records
def bench_alexnet_records(wf, target_seconds=4.0, smoke=False):
    """End-to-end AlexNet training throughput fed from a RECORDS FILE:
    per minibatch, the native C++ gather+convert reads uint8 images from
    the memory-mapped record file and the jitted train step consumes
    them — the real input path a disk-resident ImageNet epoch uses
    (the HBM-resident bench excludes input cost).

    Dispatches pipeline: jax returns on dispatch, so host-side gather of
    batch i+1 overlaps device compute of batch i; the timing window ends
    in one ``_sync``.  emit_summary adds
    ``pipeline_ratio_vs_hbm`` = this number / the HBM-resident
    samples/sec — 1.0 means the input path is fully hidden.
    """
    import tempfile
    import jax
    import jax.numpy as jnp
    from veles_tpu import native, prng

    runner = wf._fused_runner
    mb = int(wf.loader.max_minibatch_size)
    shape = tuple(wf.loader.original_data.shape[1:])      # (H, W, 3)
    n_classes = int(numpy.prod(wf.forwards[-1].output_sample_shape))
    n = 256 if smoke else 1024
    rs = numpy.random.RandomState(7)
    data = rs.randint(0, 256, (n,) + shape, numpy.uint8)
    labels = (numpy.arange(n) % n_classes).astype(numpy.int32)
    mask = numpy.ones(mb, numpy.float32)

    with tempfile.TemporaryDirectory() as tmp:
        src, lab = records_fixture(tmp, data, labels, mb)
        rng0 = (prng.get("dropout").key()
                if runner._has_stochastic else None)
        state = runner.state

        def dispatch(state, step):
            idx = ((numpy.arange(mb) + step * mb) % n).astype(numpy.int32)
            x = native.gather_convert(src, idx, scale=1.0 / 127.5,
                                      offset=-1.0)
            y = native.gather_labels(lab, idx)
            r = (jax.random.fold_in(rng0, step)
                 if rng0 is not None else None)
            return runner._train(state, x, y, mask,
                                 jnp.asarray(mb, jnp.int32), r,
                                 jnp.asarray(step, jnp.int32))

        holder = {"state": state}
        _, metrics = dispatch(holder["state"], 0)
        _sync(metrics)          # per-minibatch train-step compile + warm

        def window(n, done):
            st = holder["state"]
            for i in range(n):
                st, metrics = dispatch(st, 1 + done + i)
            _sync(metrics)
            holder["state"] = st

        steps, elapsed = timed_window(window, target_seconds, initial=8)
    sps = steps * mb / elapsed
    rec = {
        "samples_per_sec": round(sps, 1),
        "step_time_ms": round(elapsed / steps * 1e3, 3),
        "minibatch": mb,
        "images_in_file": n,
        "native_gather": native.available(),
    }
    return rec


# ------------------------------------------------------------- convergence
def bench_convergence(build_fn, max_epochs=15, patience=5):
    """Train to the stopping criterion (no val improvement for ``patience``
    epochs) via the epoch-scan path and record the final val metric — the
    convergence half of the BASELINE acceptance (val-acc at throughput),
    which throughput-only benches never measured.
    The metric follows the workflow's evaluator: classification records
    n_err, MSE/autoencoder workflows record the mean per-sample squared
    reconstruction error (BASELINE config[3]) — one source of truth, the
    same flag that routes the scan's target.

    Runs the SAME pure step functions the Decision-driven graph runs
    (compiled.py composes one set of fns for both), with a fresh shuffle
    per epoch, seed pinned by build_fn.
    """
    import jax
    from veles_tpu import prng
    from veles_tpu.loader.base import VALID

    wf = build_fn()
    runner = wf._fused_runner
    metric = "mse" if runner._is_mse else "n_err"
    loader = wf.loader
    data = loader.original_data.devmem
    # MSE/AE workflows reconstruct the input: the scan's target is the
    # data itself (labels=None), matching the evaluator's target aliasing
    labels = (None if runner._is_mse
              else loader.original_labels.devmem)
    vidx, vmask = epoch_plan_arrays(loader, wanted_cls=VALID)
    n_valid = int(vmask.sum())
    rng = prng.get("dropout").key() if runner._has_stochastic else None

    # train-k-epochs + per-epoch eval in ONE program: the per-epoch loop
    # pays 2k dispatches where this pays 1 per chunk; per-epoch val
    # metrics come back stacked so the early-stop decisions are
    # IDENTICAL, just evaluated in k-epoch batches (at most k-1 extra
    # epochs trained past the stopping point, never a different best)
    k = _chunk_epochs()
    chunk_eval = runner.epoch_chunk_eval_fn(k)

    state = runner.state
    best, best_epoch, since = None, 0, 0
    begin = time.perf_counter()
    epoch = 0
    stop = False
    while not stop and epoch < max_epochs:
        plans = [epoch_plan_arrays(loader) for _ in range(k)]  # fresh
        idx = numpy.stack([p[0] for p in plans])   # shuffle per epoch
        mask = numpy.stack([p[1] for p in plans])
        steps_per_epoch = idx.shape[-2]
        # base key: _epoch_chunk_eval folds per epoch by global step
        state, _, val_stack, _ = chunk_eval(
            state, data, labels, idx, mask, vidx, vmask, rng=rng,
            step0=epoch * steps_per_epoch)
        if metric == "n_err":
            vals = numpy.asarray(val_stack["n_err"])        # sync point
        else:
            vals = (numpy.asarray(val_stack["mse_sum"])
                    / max(n_valid, 1))
        for row in range(k):
            epoch += 1
            val = (int(vals[row]) if metric == "n_err"
                   else float(vals[row]))
            if best is None or val < best:
                best, best_epoch, since = val, epoch, 0
            else:
                since += 1
            if since >= patience or epoch >= max_epochs:
                stop = True
                break
    wall = time.perf_counter() - begin
    runner.state = state
    rec = {
        "val_count": n_valid,
        "best_epoch": best_epoch,
        "epochs_run": epoch,
        "wall_s": round(wall, 1),
    }
    if metric == "n_err":
        rec["best_val_err"] = best
        rec["best_val_err_pct"] = round(100.0 * best / max(n_valid, 1), 2)
    else:
        rec["best_val_mse"] = round(best, 6)
    return rec


# -------------------------------------------------------- transformer LM
def bench_lm(smoke=False, iters=None, publish=None):
    """Char-LM transformer training throughput (the beyond-parity
    long-context family): tokens/sec of THE product train step
    (transformer.make_adam_train_step — the same function
    TransformerTrainer jits), measured by in-jit K-vs-1 repetition
    (lax.scan) so per-dispatch latency cancels.  TFLOP/s
    uses the standard 6·N·T convention (N = param count, T = tokens;
    attention term excluded) — approximate but comparable across rounds.

    ``publish`` (optional) is called with the partial record after each
    sub-leg (train / remat / flash / decode) so the orchestrator keeps
    completed legs if a later leg's compile hangs the worker.
    """
    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.ops.transformer import (init_transformer_params,
                                           lm_loss, make_adam_train_step)

    if smoke:
        vocab, d, heads, layers, seq, mb = 64, 32, 2, 2, 64, 8
        iters = 2 if iters is None else iters
    else:
        vocab, d, heads, layers, seq, mb = 256, 512, 8, 8, 512, 32
        iters = 6 if iters is None else iters
    host = init_transformer_params(prng.get("init"), vocab, d, heads,
                                   layers, max_len=seq + 1)
    params = jax.tree.map(jnp.asarray, host)
    n_params = sum(int(numpy.prod(a.shape))
                   for a in jax.tree.leaves(params))
    opt = (jax.tree.map(jnp.zeros_like, params),
           jax.tree.map(jnp.zeros_like, params))
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (mb, seq + 1), 0, vocab, jnp.int32)
    mask = jnp.ones((mb,), jnp.float32)
    def measure(remat):
        train_step = make_adam_train_step(
            lambda p, toks, msk: lm_loss(p, toks, msk, heads,
                                         remat=remat), 1e-3)

        def step(carry, _):
            p, opt_state, t = carry
            p, opt_state, metrics = train_step(p, opt_state, tokens,
                                               mask, t)
            return (p, opt_state, t + 1), metrics["loss_sum"]

        def chain(k):
            def fn(p, opt):
                carry, losses = jax.lax.scan(
                    step, (p, opt, jnp.asarray(0, jnp.int32)), None,
                    length=k)
                return losses[-1]
            return jax.jit(fn)

        f1, fk = chain(1), chain(1 + iters)
        _sync(f1(params, opt)); _sync(fk(params, opt))    # compile
        return (best_time(lambda: fk(params, opt))
                - best_time(lambda: f1(params, opt))) / iters

    step_s = measure(remat=False)
    toks = mb * seq
    rec = {
        "tokens_per_sec": round(toks / step_s, 1),
        "step_time_ms": round(step_s * 1e3, 3),
        "seq_len": seq, "minibatch": mb, "d_model": d,
        "n_layers": layers, "n_params": n_params,
        "approx_tflops": round(6.0 * n_params * toks / step_s / 1e12, 2),
        "flops_convention": "6*N*T, attention excluded",
    }
    if publish:
        publish(rec)
    # the HBM-for-FLOPs trade, priced: same step with per-block
    # jax.checkpoint (recompute ~1 extra fwd in the bwd pass)
    remat_s = measure(remat=True)
    rec["tokens_per_sec_remat"] = round(toks / remat_s, 1)
    rec["remat_overhead_pct"] = round(100.0 * (remat_s / step_s - 1.0), 1)
    if publish:
        publish(rec)

    # attention-backend comparison: the bundled TPU Pallas flash kernel
    # vs XLA's fused attention on the SAME train step (TPU only — the
    # kernel has no CPU lowering); the winner would keep the default
    from veles_tpu.ops.pallas_kernels import on_tpu
    if not on_tpu():
        pass                                  # kernel has no CPU lowering
    elif seq % 128:
        # the bundled kernel's default blocks are 128-wide; a short
        # smoke sequence is "not applicable", not "kernel broke"
        rec["flash_pallas_skipped"] = "seq %d not divisible by 128" % seq
    else:
        from veles_tpu.ops import attention as A
        A.set_attention_backend("flash_pallas")
        try:
            flash_s = measure(remat=False)
            rec["tokens_per_sec_flash_pallas"] = round(toks / flash_s, 1)
            rec["flash_vs_xla_speedup"] = round(step_s / flash_s, 2)
        except Exception as exc:   # noqa: BLE001 — recorded, not fatal
            rec["flash_pallas_error"] = repr(exc)[-300:]
        finally:
            A.set_attention_backend("xla")
    if publish:
        publish(rec)

    # serving side: KV-cached greedy decode throughput.  generate() is
    # one jit call (prefill + scan); both timings PIN the same max_len
    # (cache shape) so the n_long-vs-n_short subtraction isolates step
    # count alone — prefill, dispatch, and cache size all cancel
    from veles_tpu.ops.transformer import generate
    key = jax.random.PRNGKey(3)
    n_short, n_long = (2, 10) if smoke else (8, 64)
    dec_mb = 1 if smoke else 8
    dprompt = jax.random.randint(key, (dec_mb, 8), 0, vocab, jnp.int32)
    cache_len = 8 + n_long

    def decode_time(n):
        run = lambda: generate(params, dprompt, n, heads, temperature=0,
                               max_len=cache_len)
        _sync(run())   # compile
        return best_time(run)

    per_tok = (decode_time(n_long) - decode_time(n_short)) \
        / (n_long - n_short)
    rec["decode_tokens_per_sec"] = round(dec_mb / per_tok, 1)
    rec["decode_ms_per_token"] = round(per_tok * 1e3, 3)
    rec["decode_batch"] = dec_mb
    if publish:
        publish(rec)

    # GQA serving lever: same model shape with 1 kv head — the decode
    # delta vs the record above is what grouped-query attention buys
    # (smaller cache reads per token) on this hardware
    gqa_host = init_transformer_params(prng.get("init"), vocab, d, heads,
                                       layers, max_len=seq + 1,
                                       n_kv_heads=1, rope=True)
    gqa_params = jax.tree.map(jnp.asarray, gqa_host)

    def gqa_decode_time(n):
        run = lambda: generate(gqa_params, dprompt, n, heads,
                               temperature=0, max_len=cache_len,
                               rope=True)
        _sync(run())   # compile
        return best_time(run)

    gqa_per_tok = (gqa_decode_time(n_long) - gqa_decode_time(n_short)) \
        / (n_long - n_short)
    rec["decode_tokens_per_sec_gqa1_rope"] = round(dec_mb / gqa_per_tok,
                                                   1)
    rec["gqa_decode_speedup"] = round(per_tok / gqa_per_tok, 2)
    return rec


# ------------------------------------------------------------ DP scaling
def bench_scaling(smoke=False, seconds=2.0):
    """DP scaling-efficiency hook (BASELINE config[4]): MNIST-FC
    epoch-scan samples/sec on ONE device vs ALL local devices via
    ShardedTrainer.  Recorded as skipped on single-device hosts (this
    container's TPU is one chip); the measurement runs unchanged the
    round the driver offers a multi-chip mesh.
    """
    import jax
    from veles_tpu.parallel import make_mesh, ShardedTrainer

    n = len(jax.devices())
    if n < 2:
        return {"skipped": "single device — scaling unmeasurable here"}
    sizes = (4000, 800, 200) if smoke else (60000, 10000, 512)

    def measure(n_dev):
        wf = build_mnist(*sizes)
        trainer = ShardedTrainer(wf._fused_runner, make_mesh(n_dev))
        loader = wf.loader
        trainer.place_dataset(numpy.asarray(loader.original_data.mem),
                              numpy.asarray(loader.original_labels.mem))
        idx, mask = epoch_plan_arrays(loader)
        n_samples = int(mask.sum())
        _sync(trainer.train_epoch(idx, mask))          # compile + warm
        epochs, elapsed = 1, 0.0
        while elapsed < seconds:
            begin = time.perf_counter()
            for _ in range(epochs):
                totals = trainer.train_epoch(idx, mask)
            _sync(totals)
            elapsed = time.perf_counter() - begin
            if elapsed < seconds:
                epochs *= 2
        return epochs * n_samples / elapsed

    sps_1, sps_n = measure(1), measure(n)
    return {
        "devices": n,
        "samples_per_sec_1dev": round(sps_1, 1),
        "samples_per_sec_ndev": round(sps_n, 1),
        "scaling_efficiency": round(sps_n / (n * sps_1), 3),
    }


# ------------------------------------------------- sgd backend (XLA/Pallas)
def bench_sgd_backends(n=4 * 1024 * 1024, iters=20, smoke=False,
                       publish=None):
    """XLA-vs-Pallas fused-SGD-update comparison (SURVEY §2.4 custom-kernel
    row): per-update device time on an AlexNet-FC-sized fp32 tensor,
    measured by in-jit repetition (K-vs-1 difference — dispatch overhead
    cancels).  The winner keeps the default (functional._SGD_BACKEND).
    ``publish`` streams the partial record after each backend so a hang
    in the pallas leg cannot discard the measured xla number."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import functional as F

    if smoke:
        n, iters = 64 * 1024, 4   # interpret-mode pallas is slow off-TPU
    key = jax.random.PRNGKey(0)
    p0 = jax.random.normal(key, (n,), jnp.float32)
    v0 = jnp.zeros((n,), jnp.float32)
    g0 = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)
    bs = jnp.asarray(128, jnp.int32)
    record = {"elements": n}
    for backend in ("xla", "pallas"):
        F.set_sgd_backend(backend)
        try:
            def chain(p, v, g, k):
                def body(i, pv):
                    return F.sgd_update(pv[0], pv[1], g, bs, 0.01, 0.9,
                                        0.0005, 0.0, None)
                return jax.lax.fori_loop(0, k, body, (p, v))

            f1 = jax.jit(lambda p, v, g: chain(p, v, g, 1))
            fk = jax.jit(lambda p, v, g: chain(p, v, g, 1 + iters))
            _sync(f1(p0, v0, g0)); _sync(fk(p0, v0, g0))  # compile
            record[backend + "_us"] = round(
                (best_time(lambda: fk(p0, v0, g0))
                 - best_time(lambda: f1(p0, v0, g0))) / iters * 1e6, 2)
            if publish:
                publish(record)
        finally:
            F.set_sgd_backend("xla")
    if "xla_us" in record and "pallas_us" in record:
        record["winner"] = ("pallas" if record["pallas_us"] <
                            record["xla_us"] else "xla")
    return record


# ------------------------------------------------ native PJRT runner (C++)
def bench_native_runner(smoke=False):
    """End-to-end proof of the standalone C++ PJRT runner (libVeles
    parity): train tiny MNIST on CPU, export a native bundle, run
    native/artifact_runner against a PJRT plugin .so, and parity-check
    its output against the in-framework forward.  The worker's own jax
    is cpu-pinned by orchestrate(), so the C++ binary is the only
    process on the chip; without a chip the record still proves
    build+selfcheck and reports the execute error."""
    import subprocess
    import tempfile

    import jax
    jax.config.update("jax_platforms", "cpu")

    nat_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "veles_tpu", "native")
    record = {}
    try:
        subprocess.run(["make", "artifact_runner"], cwd=nat_dir,
                       check=True, stdout=subprocess.PIPE,
                       stderr=subprocess.STDOUT, timeout=180)
    except Exception as e:   # noqa: BLE001 — recorded
        return {"error": "build failed: %r" % (e,)}
    runner_bin = os.path.join(nat_dir, "artifact_runner")

    from veles_tpu.native import find_pjrt_plugin
    plugin = find_pjrt_plugin()
    if plugin is None:
        return {"error": "no PJRT plugin .so found"}
    record["plugin"] = plugin

    out = subprocess.run([runner_bin, "--selfcheck", plugin],
                         stdout=subprocess.PIPE, timeout=120)
    record["selfcheck"] = ("ok" if b"SELFCHECK OK" in out.stdout
                           else "failed rc=%d" % out.returncode)
    from veles_tpu import export, prng
    from veles_tpu.config import root
    prng.reset()
    prng.seed_all(1)
    root.__dict__.pop("mnist", None)
    root.mnist.update({
        "loader": {"minibatch_size": 50, "n_train": 500, "n_valid": 100},
        "decision": {"max_epochs": 1, "fail_iterations": 5},
        "layers": [
            {"type": "all2all_tanh", "output_sample_shape": 64,
             "learning_rate": 0.03, "momentum": 0.9},
            {"type": "softmax", "output_sample_shape": 10,
             "learning_rate": 0.03, "momentum": 0.9},
        ],
    })
    from veles_tpu.samples import mnist
    wf = mnist.train()
    if smoke:
        # CI contract run: no device behind the plugin here — prove
        # build + selfcheck + export only (the TPU-marked test and the
        # real bench run cover execute)
        import tempfile as _tf
        export.export_native_bundle(
            wf, os.path.join(_tf.mkdtemp(prefix="native_smoke_"), "nb"),
            batch=8)
        record["execute"] = "skipped (smoke: no device)"
        record["bundle_export"] = "ok"
        return record
    tmp = tempfile.mkdtemp(prefix="native_bench_")
    bundle = export.export_native_bundle(wf, os.path.join(tmp, "nb"),
                                         batch=8)
    x = numpy.random.RandomState(0).uniform(
        -1, 1, (8, 784)).astype(numpy.float32)
    in_bin = os.path.join(tmp, "in.bin")
    out_bin = os.path.join(tmp, "out.bin")
    with open(in_bin, "wb") as f:
        f.write(x.tobytes())
    begin = time.perf_counter()
    proc = subprocess.run([runner_bin, bundle, plugin, in_bin, out_bin],
                          stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, timeout=600)
    wall = time.perf_counter() - begin
    tail = proc.stdout.decode(errors="replace")[-400:]
    if proc.returncode != 0 or b"EXECUTE OK" not in proc.stdout:
        record["execute"] = "failed: %s" % tail.strip()
        return record
    got = numpy.fromfile(out_bin, numpy.float32).reshape(8, -1)
    want = numpy.asarray(wf._fused_runner.eval_forward()(
        wf._fused_runner.state, x))
    record.update({
        "execute": "ok",
        "compile_plus_infer_wall_s": round(wall, 2),
        "max_abs_diff_vs_framework": float(numpy.abs(got - want).max()),
        "parity": bool(numpy.allclose(got, want, rtol=1e-3, atol=1e-3)),
    })
    return record


# --------------------------------------------------- lrn backend (XLA/Pallas)
def bench_lrn_backends(iters=8, smoke=False, publish=None):
    """XLA-vs-Pallas LRN comparison at the AlexNet-LRN1 train shape
    (fwd+bwd — the top memory-bound item of the post-bf16 step,
    docs/PERF.md): per-application device time by
    in-jit K-vs-1 repetition.  The winner keeps the default
    (functional._LRN_BACKEND).  ``publish`` streams the partial record
    after each backend (see bench_sgd_backends)."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import functional as F

    shape = (8, 28, 28, 32) if smoke else (128, 55, 55, 96)
    if smoke:
        iters = 2                 # interpret-mode pallas is slow off-TPU
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, shape, jnp.float32)
    dy0 = jax.random.normal(jax.random.fold_in(key, 1), shape,
                            jnp.float32)
    record = {"shape": list(shape)}
    for backend in ("xla", "pallas"):
        F.set_lrn_backend(backend)
        try:
            def fwd_bwd(x, dy, k):
                def body(i, acc):
                    y, vjp = jax.vjp(F.lrn_forward, acc)
                    (dx,) = vjp(dy)
                    return dx
                return jax.lax.fori_loop(0, k, body, x)

            f1 = jax.jit(lambda x, dy: fwd_bwd(x, dy, 1))
            fk = jax.jit(lambda x, dy: fwd_bwd(x, dy, 1 + iters))
            _sync(f1(x0, dy0)); _sync(fk(x0, dy0))       # compile
            record[backend + "_us"] = round(
                (best_time(lambda: fk(x0, dy0))
                 - best_time(lambda: f1(x0, dy0))) / iters * 1e6, 2)
            if publish:
                publish(record)
        finally:
            F.set_lrn_backend("xla")
    if "xla_us" in record and "pallas_us" in record:
        record["winner"] = ("pallas" if record["pallas_us"] <
                            record["xla_us"] else "xla")
    return record


# --------------------------------------------------- records input pipeline
def records_fixture(tmpdir, data, labels, mb):
    """Write a record file and open it through RecordsLoader — the shared
    fixture for the records-path benches.  Returns (memmap_src, labels)."""
    from veles_tpu.loader.records import write_records, RecordsLoader
    path = write_records(tmpdir + "/bench.rec", data, labels,
                         [0, 0, len(data)])
    loader = RecordsLoader(None, path=path, minibatch_size=mb,
                           name="recloader")
    loader.initialize()
    return loader._data, numpy.asarray(loader._labels)


def bench_records(smoke=False, seconds=2.0):
    """Throughput of the record-file input pipeline (the streaming path
    a real ImageNet epoch needs):
    memmap gather + uint8→[-1,1] float32 convert per minibatch, native
    C++ (loader hot path) vs the numpy fallback.  Host-side — the number
    is platform-independent and bounds the achievable samples/s of any
    records-fed training run."""
    import tempfile
    from veles_tpu import native

    n, hw, mb = (256, 32, 32) if smoke else (2048, 128, 128)
    rng = numpy.random.RandomState(0)
    data = rng.randint(0, 256, (n, hw, hw, 3), numpy.uint8)
    labels = (numpy.arange(n) % 100).astype(numpy.int32)
    record = {"images": n, "hw": hw, "minibatch": mb,
              "native_available": native.available()}
    with tempfile.TemporaryDirectory() as tmp:
        src, lab = records_fixture(tmp, data, labels, mb)

        def timed(gather):
            idx = rng.randint(0, n, mb).astype(numpy.int32)
            gather(idx)  # warm (page in the mmap, build the .so)
            done, begin = 0, time.perf_counter()
            while time.perf_counter() - begin < seconds:
                idx = rng.randint(0, n, mb).astype(numpy.int32)
                gather(idx)
                done += mb
            return done / (time.perf_counter() - begin)

        sps_native = timed(lambda idx: (
            native.gather_convert(src, idx, scale=1.0 / 127.5, offset=-1.0),
            native.gather_labels(numpy.asarray(lab), idx)))
        out = numpy.empty((mb,) + src.shape[1:], numpy.float32)
        sps_numpy = timed(lambda idx: native._numpy_gather(
            src, idx, 1.0 / 127.5, -1.0, out))
        sample_mb = data[0].nbytes / 1e6
        record["samples_per_sec"] = round(sps_native, 1)
        record["numpy_fallback_samples_per_sec"] = round(sps_numpy, 1)
        record["read_mb_per_sec"] = round(sps_native * sample_mb, 1)
    return record


# ------------------------------------------------------------- numpy floor
def bench_numpy_floor(wf, min_seconds=3.0):
    """The reference's numpy backend, reproduced: python minibatch loop with
    numpy GEMMs, same 784->100(tanh)->10(softmax) + momentum SGD."""
    loader = wf.loader
    data = numpy.asarray(loader.original_data.mem)
    labels = numpy.asarray(loader.original_labels.mem)
    idx, mask = epoch_plan_arrays(loader)
    rng = numpy.random.RandomState(1)
    w1 = rng.uniform(-0.1, 0.1, (784, 100)).astype(numpy.float32)
    b1 = numpy.zeros(100, numpy.float32)
    w2 = rng.uniform(-0.1, 0.1, (100, 10)).astype(numpy.float32)
    b2 = numpy.zeros(10, numpy.float32)
    vw1 = numpy.zeros_like(w1); vb1 = numpy.zeros_like(b1)
    vw2 = numpy.zeros_like(w2); vb2 = numpy.zeros_like(b2)
    lr, mom = 0.03, 0.9
    a, bconst = 1.7159, 0.6666

    done_samples = 0
    begin = time.perf_counter()
    while time.perf_counter() - begin < min_seconds:
        for mb_idx, mb_mask in zip(idx, mask):
            x = data[mb_idx]
            lab = labels[mb_idx]
            n = int(mb_mask.sum())
            y1 = a * numpy.tanh(bconst * (x @ w1 + b1))
            z2 = y1 @ w2 + b2
            e = numpy.exp(z2 - z2.max(axis=1, keepdims=True))
            probs = e / e.sum(axis=1, keepdims=True)
            onehot = numpy.eye(10, dtype=numpy.float32)[lab]
            err2 = (probs - onehot) * mb_mask[:, None]
            gw2 = y1.T @ err2 / n
            gb2 = err2.sum(0) / n
            err1 = (err2 @ w2.T) * (bconst * (a - y1 * y1 / a))
            gw1 = x.T @ err1 / n
            gb1 = err1.sum(0) / n
            vw2 = mom * vw2 - lr * gw2; w2 += vw2
            vb2 = mom * vb2 - lr * gb2; b2 += vb2
            vw1 = mom * vw1 - lr * gw1; w1 += vw1
            vb1 = mom * vb1 - lr * gb1; b1 += vb1
            done_samples += n
    return done_samples / (time.perf_counter() - begin)


KNOWN_CONFIGS = ("mnist", "cifar", "alexnet", "alexnet_records", "sgd",
                 "lrn", "records", "convergence", "lm", "scaling",
                 "native")
#: record name -> the worker config that produces it (the config whose
#: ``<name>_error`` explains the record's absence)
RECORD_WORKERS = {"mnist_fc": "mnist", "cifar_conv": "cifar",
                  "cifar_conv_bf16": "cifar", "alexnet": "alexnet",
                  "alexnet_bf16": "alexnet", "alexnet_fast": "alexnet",
                  "alexnet_records": "alexnet_records",
                  "char_lm": "lm", "sgd_update": "sgd",
                  "lrn_fwd_bwd": "lrn", "records_pipeline": "records",
                  "dp_scaling": "scaling", "native_runner": "native"}
#: "convergence" expands to one watchdog worker per sub-bench, so a hang
#: in one cannot discard the others
CONVERGENCE_SUBS = ("kohonen", "mnist_fc", "cifar_conv",
                    "cifar_conv_bf16", "mnist_ae")


def expand_configs(wanted):
    out = []
    for c in wanted:
        if c == "convergence":
            out.extend("convergence:" + s for s in CONVERGENCE_SUBS)
        else:
            out.append(c)
    return out


#: configs that never touch the device (host pipeline; the native
#: runner pins its worker to the CPU so that the C++ binary is the one
#: process on the chip): they run wherever there is a host
HOST_ONLY = {"records", "native"}


class _StreamingResults(dict):
    """Worker-side results dict that (when VELES_BENCH_STREAM=1, set by
    the orchestrator) emits each completed record to stdout the moment it
    lands, as a ``{"partial": {...}}`` JSON line: a worker killed at its
    time limit keeps everything it measured before the kill."""

    def _stream(self, payload):
        if os.environ.get("VELES_BENCH_STREAM") == "1":
            print(json.dumps({"partial": payload}), flush=True)

    def __setitem__(self, key, value):
        super().__setitem__(key, value)
        self._stream({key: value})

    def stream_all(self):
        self._stream(dict(self))


def run_configs(wanted, args):
    """Run the wanted bench configs in THIS process; returns the results
    dict (per-config records and/or ``<name>_error`` entries)."""
    if args.smoke:
        import jax
        jax.config.update("jax_platforms", "cpu")
        sizes = {"mnist": (2000, 500, 100), "cifar": (500, 100, 50),
                 "alexnet": (64, 16, 16)}
        alex_kwargs = dict(image_hw=(64, 64), n_classes=10, crop=(56, 56))
        target, floor_seconds = args.seconds or 0.5, 0.5
    else:
        sizes = {"mnist": (60000, 10000, 100), "cifar": (50000, 10000, 100),
                 "alexnet": (1024, 128, 128)}
        alex_kwargs = {}
        target, floor_seconds = args.seconds or 4.0, 3.0

    import jax
    platform = jax.devices()[0].platform
    if (not args.smoke and platform != "tpu"
            and any(c.split(":")[0] not in HOST_ONLY for c in wanted)):
        # a measurement path that finds no chip fails: a CPU run is
        # --smoke, and its numbers are not device numbers
        return {"error": "no TPU device (jax platform %r): device "
                         "configs are measured on the chip only"
                         % platform}
    from veles_tpu import compile_cache
    compile_cache.enable()

    device_kind, peak = _peak_tflops()
    results = _StreamingResults()

    def guarded(section, fn):
        """One config blowing up must not zero the whole bench record."""
        import traceback
        try:
            fn()
            # re-stream the whole dict: records grow in place after their
            # first assignment (floors, parity sub-records), and the
            # orchestrator's partial collection must see the final shape
            results.stream_all()
        except Exception:
            traceback.print_exc()
            results[section + "_error"] = traceback.format_exc()[-800:]

    def _bench_mnist():
        wf = build_mnist(*sizes["mnist"])
        results["mnist_fc"] = bench_config(
            "mnist_fc", wf, target, device_kind, peak, "fp32_highest")
        floor = bench_numpy_floor(wf, min_seconds=floor_seconds)
        results["mnist_fc"]["numpy_floor_samples_per_sec"] = round(floor, 1)
        results["mnist_fc"]["vs_numpy_floor"] = round(
            results["mnist_fc"]["samples_per_sec"] / floor, 2)
        # int8-artifact predict parity ON THIS DEVICE (the CPU-side test
        # exists; this puts the TPU number in the bench record):
        # quantized vs fp32 artifact outputs
        import tempfile
        from veles_tpu import export
        d = tempfile.mkdtemp()
        fp = export.export_model(wf, os.path.join(d, "m.veles"))
        qp = export.export_model(wf, os.path.join(d, "m8.veles"),
                                 quantize="int8")
        ref, qm = export.load_model(fp), export.load_model(qp)
        x = numpy.random.RandomState(0).uniform(
            -1, 1, (256, 784)).astype(numpy.float32)
        a, b = ref.predict(x), qm.predict(x)
        results["mnist_fc"]["artifact_int8_parity"] = {
            "argmax_agreement": float(
                (a.argmax(1) == b.argmax(1)).mean()),
            "max_abs_diff": float(numpy.abs(a - b).max()),
        }

    if "mnist" in wanted:
        guarded("mnist", _bench_mnist)

    def bench_bf16_variant(name, build_fn):
        """The TPU-idiomatic fast path: bf16 operand casts inside the
        step, then restore parity precision."""
        from veles_tpu.ops import functional as F
        with F.matmul_precision("bfloat16"):
            results[name] = bench_config(
                name, build_fn(), target, device_kind, peak, "bf16_cast")

    def _bench_cifar():
        wf = build_cifar(*sizes["cifar"])
        results["cifar_conv"] = bench_config(
            "cifar_conv", wf, target, device_kind, peak, "fp32_highest")
        bench_bf16_variant("cifar_conv_bf16",
                           lambda: build_cifar(*sizes["cifar"]))

    if "cifar" in wanted:
        guarded("cifar", _bench_cifar)

    def _bench_alexnet():
        wf = build_alexnet(*sizes["alexnet"], **alex_kwargs)
        results["alexnet"] = bench_config(
            "alexnet", wf, target, device_kind, peak, "fp32_highest")
        bench_bf16_variant(
            "alexnet_bf16",
            lambda: build_alexnet(*sizes["alexnet"], **alex_kwargs))
        # the full fast path: bf16 convs + the fused Pallas LRN — shown
        # NEXT TO alexnet_bf16 so the LRN kernel's end-to-end effect is
        # a diff between two records, win or lose (docs/PERF.md r5)
        from veles_tpu.ops import functional as F
        F.set_lrn_backend("pallas")
        try:
            bench_bf16_variant(
                "alexnet_fast",
                lambda: build_alexnet(*sizes["alexnet"], **alex_kwargs))
        finally:
            F.set_lrn_backend("xla")

    if "alexnet" in wanted:
        guarded("alexnet", _bench_alexnet)

    def _bench_alexnet_records():
        # end-to-end: the training step fed from a real records file
        # through the native gather path (the HBM-resident bench never
        # includes input-pipeline cost).  Own
        # worker: the per-minibatch step is a FRESH compile, and a hang
        # here must not discard the HBM numbers
        wf = build_alexnet(*sizes["alexnet"], **alex_kwargs)
        results["alexnet_records"] = bench_alexnet_records(
            wf, target_seconds=target, smoke=args.smoke)
        print("alexnet_records: %s" % results["alexnet_records"],
              file=sys.stderr)

    if "alexnet_records" in wanted:
        guarded("alexnet_records", _bench_alexnet_records)

    conv_sel = set()
    for c in wanted:
        if c == "convergence":
            conv_sel.update(CONVERGENCE_SUBS)
        elif c.startswith("convergence:"):
            conv_sel.add(c.split(":", 1)[1])
    if conv_sel:
        # small-but-real convergence runs (val-acc is the OTHER half of the
        # BASELINE acceptance); sizes keep the wall time in minutes on TPU
        # (and seconds in --smoke: fp32-HIGHEST convs on CPU are SLOW)
        if args.smoke:
            conv_sizes = {"mnist": (2000, 500, 100),
                          "cifar": (200, 100, 50),
                          "ae": (500, 200, 50)}
            conv_epochs = {"mnist": (8, 4), "cifar": (4, 2), "ae": (4, 2)}
        else:
            conv_sizes = {"mnist": (60000, 10000, 100),
                          "cifar": (10000, 2000, 100),
                          "ae": (10000, 2000, 100)}
            conv_epochs = {"mnist": (15, 5), "cifar": (15, 5),
                           "ae": (10, 4)}

        def build_ae():
            """MNIST conv autoencoder (BASELINE config[3]) at bench sizes;
            metric = mean per-sample squared reconstruction error."""
            from veles_tpu import prng
            from veles_tpu.config import root
            prng.reset()
            prng.seed_all(1)
            n_train, n_valid, mb = conv_sizes["ae"]
            root.__dict__.pop("mnist_ae", None)
            root.mnist_ae.update({
                "loader": {"minibatch_size": mb, "n_train": n_train,
                           "n_valid": n_valid},
                "decision": {"max_epochs": 1000, "fail_iterations": 1000},
            })
            from veles_tpu.samples import mnist_ae
            wf = mnist_ae.build(fused=True)
            wf.initialize()
            return wf

        def _bench_kohonen():
            """SOM quantization error to Decision-complete (row 3's
            unsupervised half).  Non-SGD graph path — the trainer
            dispatches per minibatch, so sizes stay small."""
            from veles_tpu import prng
            from veles_tpu.config import root
            prng.reset()
            prng.seed_all(1)
            root.__dict__.pop("kohonen", None)
            from veles_tpu.samples import kohonen
            kohonen.default_config()
            root.kohonen.update({
                "loader": {"minibatch_size": 100,
                           "n_train": 500 if args.smoke else 2000},
                "decision": {"max_epochs": 4 if args.smoke else 10,
                             "fail_iterations": 20},
            })
            begin = time.perf_counter()
            wf = kohonen.train()
            qerrs = [m["train"]["qerr"]
                     for m in wf.decision.epoch_metrics]
            results["convergence_kohonen"] = {
                "first_epoch_qerr": round(qerrs[0], 4),
                "best_qerr": round(min(qerrs), 4),
                "epochs_run": len(qerrs),
                "wall_s": round(time.perf_counter() - begin, 1),
            }
            print("convergence kohonen: %s"
                  % results["convergence_kohonen"], file=sys.stderr)

        if "kohonen" in conv_sel:
            guarded("convergence_kohonen", _bench_kohonen)

        for name, build_fn in (
                ("mnist_fc", lambda: build_mnist(*conv_sizes["mnist"])),
                ("cifar_conv", lambda: build_cifar(*conv_sizes["cifar"])),
                # bf16 operand casts on the SAME topology/seed/data: the
                # val-err delta vs cifar_conv is the convergence-parity
                # evidence the bf16 conv-net default rests on (PERF.md)
                ("cifar_conv_bf16",
                 lambda: build_cifar(*conv_sizes["cifar"])),
                ("mnist_ae", build_ae)):
            if name not in conv_sel:
                continue
            def _bench_conv(name=name, build_fn=build_fn):
                key = {"mnist_fc": "mnist", "cifar_conv": "cifar",
                       "cifar_conv_bf16": "cifar", "mnist_ae": "ae"}[name]
                epochs, patience = conv_epochs[key]
                from veles_tpu.ops import functional as F
                with F.matmul_precision("bfloat16" if name.endswith("_bf16")
                                        else "float32"):
                    results["convergence_" + name] = bench_convergence(
                        build_fn, max_epochs=epochs, patience=patience)
                print("convergence %s: %s"
                      % (name, results["convergence_" + name]),
                      file=sys.stderr)
            guarded("convergence_" + name, _bench_conv)

    def _publisher(key):
        """Stream a copy of a growing record under ``key`` (partials
        survive a later-leg hang; copies keep streamed snapshots
        immune to in-place mutation)."""
        return lambda r: results.__setitem__(key, dict(r))

    def _bench_lm():
        results["char_lm"] = bench_lm(
            smoke=args.smoke, publish=_publisher("char_lm"))
        print("char_lm: %s" % results["char_lm"], file=sys.stderr)

    if "lm" in wanted:
        guarded("lm", _bench_lm)

    def _bench_scaling():
        results["dp_scaling"] = bench_scaling(smoke=args.smoke)
        print("dp_scaling: %s" % results["dp_scaling"], file=sys.stderr)

    if "scaling" in wanted:
        guarded("scaling", _bench_scaling)

    def _bench_sgd():
        results["sgd_update"] = bench_sgd_backends(
            smoke=args.smoke, publish=_publisher("sgd_update"))
        print("sgd_update: %s" % results["sgd_update"], file=sys.stderr)

    if "sgd" in wanted:
        guarded("sgd", _bench_sgd)

    def _bench_lrn():
        results["lrn_fwd_bwd"] = bench_lrn_backends(
            smoke=args.smoke, publish=_publisher("lrn_fwd_bwd"))
        print("lrn_fwd_bwd: %s" % results["lrn_fwd_bwd"],
              file=sys.stderr)

    if "lrn" in wanted:
        guarded("lrn", _bench_lrn)

    def _bench_native():
        if args.in_process and not args.smoke:
            # bench_native_runner pins THIS process's jax to cpu (the
            # chip must belong to the C++ client alone) — under
            # --in-process that would poison sibling configs' device
            # numbers; the watchdog-worker path is the supported one
            results["native_runner"] = {
                "skipped": "needs its own worker process — run without "
                           "--in-process"}
            return
        results["native_runner"] = bench_native_runner(smoke=args.smoke)
        print("native_runner: %s" % results["native_runner"],
              file=sys.stderr)

    if "native" in wanted:
        guarded("native", _bench_native)

    def _bench_recs():
        results["records_pipeline"] = bench_records(
            smoke=args.smoke, seconds=min(target, 4.0))
        print("records_pipeline: %s" % results["records_pipeline"],
              file=sys.stderr)

    if "records" in wanted:
        guarded("records", _bench_recs)

    return results


def summary_record(results):
    """Build (record, exit_code) for the driver's summary JSON line —
    the metric-selection priority lives HERE so the final emit and the
    per-leg partial stream (``orchestrate``) can never disagree on
    shape."""
    hbm = results.get("alexnet", {})
    rec = results.get("alexnet_records", {})
    if isinstance(rec, dict) and rec.get("samples_per_sec") and \
            isinstance(hbm, dict) and hbm.get("samples_per_sec"):
        # 1.0 = the records input path is fully hidden behind compute
        rec["pipeline_ratio_vs_hbm"] = round(
            rec["samples_per_sec"] / hbm["samples_per_sec"], 3)
    model_results = [k for k in results
                     if isinstance(results[k], dict)
                     and "samples_per_sec" in results[k]
                     and k != "records_pipeline"]  # host-side, not a model
    if model_results:
        headline_name = ("mnist_fc" if "mnist_fc" in results
                         else model_results[0])
        headline = results[headline_name]
        return {
            "metric": "%s_train_samples_per_sec_per_chip" % headline_name,
            "value": headline["samples_per_sec"],
            "unit": "samples/sec",
            "vs_baseline": headline.get("vs_numpy_floor"),
            "configs": results,
        }, 0
    if "sgd_update" in results:   # aux-only invocation
        return {
            "metric": "sgd_update_device_us",
            "value": results["sgd_update"].get("xla_us"),
            "unit": "us",
            "vs_baseline": None,
            "configs": results,
        }, 0
    if "lrn_fwd_bwd" in results:
        return {
            "metric": "lrn_fwd_bwd_device_us",
            "value": results["lrn_fwd_bwd"].get("xla_us"),
            "unit": "us",
            "vs_baseline": None,
            "configs": results,
        }, 0
    if "records_pipeline" in results:
        # preferred over native_runner: always carries a real value
        # (the native record may be selfcheck-only without a chip)
        return {
            "metric": "records_pipeline_samples_per_sec",
            "value": results["records_pipeline"]["samples_per_sec"],
            "unit": "samples/sec",
            "vs_baseline": None,
            "configs": results,
        }, 0
    if "native_runner" in results:
        return {
            "metric": "native_runner_compile_plus_infer_wall_s",
            "value": results["native_runner"].get(
                "compile_plus_infer_wall_s"),
            "unit": "s",
            "vs_baseline": None,
            "configs": results,
        }, 0
    if "char_lm" in results:
        return {
            "metric": "char_lm_train_tokens_per_sec",
            "value": results["char_lm"]["tokens_per_sec"],
            "unit": "tokens/sec",
            "vs_baseline": None,
            "configs": results,
        }, 0
    if results.get("dp_scaling", {}).get("scaling_efficiency") \
            is not None:
        return {
            "metric": "dp_scaling_efficiency",
            "value": results["dp_scaling"].get("scaling_efficiency"),
            "unit": "fraction",
            "vs_baseline": None,
            "configs": results,
        }, 0
    if "skipped" in results.get("dp_scaling", {}):
        # a skipped scaling probe on a single-device host is a SUCCESS
        # (the record documents why), not a bench failure
        return {
            "metric": "dp_scaling_skipped",
            "value": None,
            "unit": "",
            "vs_baseline": None,
            "configs": results,
        }, 0
    if any(k.startswith("convergence_") and isinstance(results[k], dict)
           for k in results):   # convergence-only invocation
        keys = [k for k in ("convergence_mnist_fc", "convergence_cifar_conv",
                            "convergence_mnist_ae", "convergence_kohonen")
                if isinstance(results.get(k), dict)]
        keys += [k for k in results if k.startswith("convergence_")
                 and isinstance(results[k], dict) and k not in keys]
        units = {"best_val_err_pct": "percent", "best_val_mse": "mse",
                 "best_qerr": "qe"}
        key, suffix, value, unit = None, None, None, ""
        for k in keys:
            hit = next((sfx for sfx in units if sfx in results[k]), None)
            if hit is not None:
                key, suffix = k, hit
                value, unit = results[k][hit], units[hit]
                break
        if key is None:   # convergence dicts with no known metric key
            key, suffix = keys[0], "record"
            value = None
        return {
            "metric": "%s_%s" % (key, suffix),
            "value": value,
            "unit": unit,
            "vs_baseline": None,
            "configs": results,
        }, 0
    # everything failed: still emit the one JSON line with errors
    return {
        "metric": "bench_failed",
        "value": None,
        "unit": "",
        "vs_baseline": None,
        "configs": results,
    }, 1


def emit_summary(results):
    """Print the FINAL summary JSON line the driver records (the last
    parseable line of stdout wins); returns the exit code."""
    rec, code = summary_record(results)
    print(json.dumps(rec), flush=True)
    return code


def collect_worker_output(stdout_bytes):
    """Merge every parseable worker stdout line: ``partial`` lines stream
    in as records complete (kept even when the worker is later killed);
    the final ``results`` line, when present, wins.  Returns
    (records_dict, saw_final_line)."""
    got = {}
    final = None
    for raw in (stdout_bytes or b"").decode(errors="replace").splitlines():
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        try:
            obj = json.loads(raw)
        except ValueError:
            continue
        if "partial" in obj:
            got.update(obj["partial"])
        elif "results" in obj:
            final = obj["results"]
    if final is not None:
        got.update(final)
    return got, final is not None


class OuterTimeout(BaseException):
    """Raised by the SIGTERM handler: an outer watchdog fired.
    BaseException so no blanket per-config `except Exception` can eat
    it on the way out."""


def total_deadline():
    """Monotonic deadline for the WHOLE bench run (VELES_BENCH_TOTAL_S,
    0 disables): configs that would start past it are recorded as
    skipped, so the summary line gets out while the process still owns
    its stdout."""
    total = float(os.environ.get("VELES_BENCH_TOTAL_S", 1680))
    return (time.monotonic() + total) if total > 0 else None


def orchestrate(wanted, args, argv, results=None, deadline=None):
    """Run each config in its own subprocess under a hard deadline.

    A chip belongs to one process at a time: workers run STRICTLY
    sequentially and the parent never imports jax (a parent that has
    touched jax holds the chip, and a child that needs it then fails or
    hangs).  Per-config workers also bound the damage of a hang: a hung
    config is killed and recorded as an error, the rest still run, and
    the one-line contract always holds.

    ``results`` (when given) is mutated IN PLACE so the caller's SIGTERM
    handler can emit whatever was measured if an outer watchdog fires
    mid-config; ``deadline`` (time.monotonic()) bounds the whole run —
    configs that would start too close to it are recorded as skipped so
    the summary line still gets out in time.
    """
    import subprocess
    per_config = float(os.environ.get(
        "VELES_BENCH_CONFIG_TIMEOUT_S", 300 if args.smoke else 1500))
    if results is None:
        results = {}

    def stream_summary():
        """One full summary line after EVERY completed leg — not only on
        SIGTERM: `timeout -k` follows TERM with KILL, and a KILLed
        process runs no handler.  The driver takes the LAST parseable
        stdout line, so any kill, however rude, still leaves every
        completed leg in the output JSON."""
        rec, _ = summary_record(results)
        print(json.dumps(rec), flush=True)

    def time_left():
        return (float("inf") if deadline is None
                else deadline - time.monotonic())

    for name in wanted:
        if time_left() < 60:
            # too close to the outer watchdog to start another config:
            # record the skip and keep going (cheap) so the summary
            # emits while we still own the process
            results[name + "_error"] = (
                "skipped: total bench deadline reached "
                "(VELES_BENCH_TOTAL_S) — partial results emitted")
            stream_summary()
            continue
        cmd = [sys.executable, os.path.abspath(__file__),
               "--worker", name] + argv
        env = dict(os.environ, VELES_BENCH_STREAM="1")
        if name in HOST_ONLY:
            # cpu-pinned worker: a host-side config must not take the
            # chip — for 'native' specifically, the C++ runner must be
            # the only process on it
            env["JAX_PLATFORMS"] = "cpu"
        # a worker may not outlive the total deadline either — cap its
        # watchdog so ITS kill (and partial collection) happens while
        # the parent can still print the summary line
        worker_timeout = min(per_config, max(time_left() - 60, 30))
        try:
            proc = subprocess.run(cmd, stdout=subprocess.PIPE,
                                  timeout=worker_timeout, env=env)
            got, complete = collect_worker_output(proc.stdout)
            if not got and not complete:
                got = {name + "_error":
                       "worker produced no output (rc=%s)"
                       % proc.returncode}
            if "error" in got:   # the worker found no device
                got = {name + "_error": got.pop("error"), **got}
            results.update(got)
        except subprocess.TimeoutExpired as exc:
            got, _ = collect_worker_output(exc.stdout)  # keep pre-hang records
            results.update(got)
            results[name + "_error"] = ("killed after %.0fs (hung device "
                                        "dispatch/compile)"
                                        % worker_timeout)
        except Exception as exc:   # worker crash / bad output
            results[name + "_error"] = "worker failed: %r" % (exc,)
        stream_summary()
    return results


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true",
                        help="tiny sizes on CPU for CI validation")
    parser.add_argument("--configs",
                        # most-valuable-first: the headline alexnet
                        # records run before cifar, and the cheap
                        # sgd/lrn/lm kernels before the long convergence
                        # legs.  The order applies to the orchestrated
                        # (watchdog-subprocess) path; run_configs
                        # (--in-process / --smoke) keeps its fixed
                        # source order
                        default="mnist,alexnet,cifar,sgd,lrn,lm,"
                                "convergence,alexnet_records,records,"
                                "scaling,native",
                        help="comma list: " + ",".join(KNOWN_CONFIGS))
    parser.add_argument("--seconds", type=float, default=None,
                        help="target seconds per timing window")
    parser.add_argument("--in-process", action="store_true",
                        help="run all configs in this process (no "
                             "per-config watchdog subprocesses)")
    parser.add_argument("--worker", default=None, metavar="CONFIG",
                        help=argparse.SUPPRESS)   # internal: one config
    args = parser.parse_args()

    if args.worker is not None:
        results = run_configs([args.worker], args)
        print(json.dumps({"worker": args.worker, "results": results}))
        return 0

    wanted = [c.strip() for c in args.configs.split(",") if c.strip()]
    known = set(KNOWN_CONFIGS) | {
        "convergence:" + s for s in CONVERGENCE_SUBS}
    unknown = [c for c in wanted if c not in known]
    if unknown or not wanted:
        parser.error("unknown configs %r (choose from %s)"
                     % (unknown, ", ".join(sorted(known))))

    # The driver runs the bench under an outer `timeout`: on TERM emit
    # whatever was measured (the one-line contract) before exiting
    # non-zero
    import signal
    partial = {}

    def _on_term(signum, frame):
        raise OuterTimeout()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:       # non-main thread (embedded use): skip
        pass

    try:
        # --smoke forces CPU: run in process, skip one python+jax cold
        # start per config
        if args.in_process or args.smoke:
            results = run_configs(wanted, args)
        else:
            argv = (["--seconds", str(args.seconds)]
                    if args.seconds else [])
            results = orchestrate(expand_configs(wanted), args, argv,
                                  results=partial,
                                  deadline=total_deadline())
    except OuterTimeout:
        partial["bench_error"] = (
            "terminated by the outer watchdog (SIGTERM) mid-run — "
            "partial results emitted")
        emit_summary(partial)
        return 1
    rc = emit_summary(results)
    # a config that failed, timed out, was skipped or found no device
    # fails the run, whatever else was measured
    failed = any(k == "error" or k.endswith("_error") for k in results)
    return 1 if failed else rc


if __name__ == "__main__":
    sys.exit(main())
