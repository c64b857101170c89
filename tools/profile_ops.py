"""Microbenchmark train-step AND serving-attention components on the
real chip — the per-op cost table.

Dispatch overhead would swamp a single op, so per-op cost is measured
by repeating the op K times INSIDE one jit (fori_loop with a scalar
data dependency that defeats CSE), then differencing K vs 1
repetitions.  Timing windows end in ``jax.block_until_ready``.

The round-3 patch-materializing pooling / cumsum LRN are kept here as
local copies so the current native implementations can always be
re-compared against them (the r3→r4 rewrite rationale: docs/PERF.md).

ISSUE 7 adds the serving attention rows (decode step / chunked
prefill, contiguous / paged, Pallas kernel vs XLA — the inputs the
ROADMAP autotuning item will select between) and the bench.py
streaming discipline: after EVERY completed row one summary_record
JSON line goes to stdout (metric/value/unit/vs_baseline/configs,
last-line-wins), so an outer watchdog kill still leaves a parseable
record of everything measured so far.
"""
import argparse
import json
import os
import sys
import time

import numpy
import jax
import jax.numpy as jnp

# run as a script, tools/ is on sys.path but the repo root (veles_tpu/)
# is not
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from veles_tpu.ops import functional as F  # noqa: E402

K = 20

#: accumulated {row name: per-op ms} — the ``configs`` payload of every
#: streamed summary line
RESULTS = {}


def stream_summary():
    """Bank everything measured so far as ONE stdout JSON line in the
    bench.py summary_record shape — a watchdog kill keeps the last."""
    last = next(reversed(RESULTS)) if RESULTS else None
    print(json.dumps({
        "metric": "profile_ops_row_ms",
        "value": RESULTS.get(last),
        "unit": "ms/op",
        "vs_baseline": None,
        "configs": {"last_row": last, "rows_ms": dict(RESULTS)},
    }), flush=True)


_sync = jax.block_until_ready


def bench_op(name, op, x, n_timed=3, reps=K):
    """op: x -> y (any shape).  Reports per-application device time."""
    def chain(x, k):
        def body(i, carry):
            y = op(carry)
            s = jnp.asarray(jax.tree.leaves(y)[0], jnp.float32).ravel()[0]
            return carry + (s * 1e-30).astype(carry.dtype)
        return jax.lax.fori_loop(0, k, body, x)

    f0 = jax.jit(lambda x: chain(x, 1))
    fk = jax.jit(lambda x: chain(x, 1 + reps))
    _sync(f0(x)); _sync(fk(x))  # compile both
    ts = []
    for variant in (f0, fk):
        best = float("inf")
        for _ in range(n_timed):
            begin = time.perf_counter()
            out = variant(x)
            _sync(out)
            best = min(best, time.perf_counter() - begin)
        ts.append(best)
    per_op = (ts[1] - ts[0]) / reps
    print("%-44s %10.3f ms" % (name, per_op * 1e3), flush=True,
          file=sys.stderr)
    RESULTS[name] = round(per_op * 1e3, 4)
    stream_summary()
    return per_op


# ---- round-3 implementations, kept for A/B comparison -----------------
def _r3_patch_maxpool(x, window=(3, 3), stride=(2, 2)):
    """The replaced patch-materializing max pooling (kh*kw HBM blowup)."""
    lowest = float(jnp.finfo(x.dtype).min) / 2
    patches, _, _ = F._pool_patches(x, window, stride, lowest)
    idx = jnp.argmax(patches, axis=3, keepdims=True)
    return jnp.take_along_axis(patches, idx, axis=3)[:, :, :, 0, :]


def _r3_cumsum_lrn(x, alpha=1e-4, beta=0.75, n=5, k=2.0):
    """The replaced cumsum-based LRN (prefix-scan lowering)."""
    c = x.shape[-1]
    sq = x * x
    half = n // 2
    padded = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(half, half)])
    csum = jnp.cumsum(padded, axis=-1)
    csum = jnp.pad(csum, [(0, 0)] * (x.ndim - 1) + [(1, 0)])
    window_sums = jax.lax.slice_in_dim(csum, n, n + c, axis=-1) - \
        jax.lax.slice_in_dim(csum, 0, c, axis=-1)
    return x / (k + (alpha / n) * window_sums) ** beta


# ---- serving attention rows (ISSUE 7) --------------------------------
def attention_rows(kernels="auto"):
    """Per-op cost of the serving hot loop's attention programs at the
    lm-bench geometry: decode step (c=1) and chunked prefill (c=page)
    over paged storage, XLA vs the Pallas serving kernels, isolated
    here per dispatch (autotuning seed data).

    ``kernels``: 'auto' rows the Pallas kernels only on real TPU
    hardware (off-TPU they would run in interpret mode — minutes per
    timing rep, useless numbers); 'force' insists (parity spelunking);
    'off' skips them."""
    from veles_tpu import prng
    from veles_tpu.ops import attention as A
    from veles_tpu.ops.pallas_kernels import on_tpu

    d_model, n_heads, max_len, page, b = 64, 4, 256, 16, 4
    params = jax.tree.map(jnp.asarray, A.init_mha_params(
        prng.get("profile_attn"), d_model, n_heads))
    rng = numpy.random.RandomState(11)
    kv = A.kv_heads_of(params, n_heads, d_model)
    dh = d_model // n_heads
    m = max_len // page                       # pages per lane
    n_pages = b * m + 1                       # + reserved scratch page
    kp = jnp.asarray(rng.randn(n_pages, kv, page, dh), jnp.float32)
    vp = jnp.asarray(rng.randn(n_pages, kv, page, dh), jnp.float32)
    ptab = jnp.asarray(
        1 + numpy.arange(b * m).reshape(b, m), jnp.int32)
    pos_mid = jnp.full((b,), max_len // 2, jnp.int32)  # page-aligned

    x1 = jnp.asarray(rng.randn(b, 1, d_model), jnp.float32)
    xc = jnp.asarray(rng.randn(b, page, d_model), jnp.float32)

    def paged(kern=None):
        return lambda a: A.mha_paged_chunk_step(
            params, a, kp, vp, ptab, pos_mid, n_heads, rope=True,
            attn_kernel=kern)[0]

    bench_op("attn decode step c=1 (paged, xla)", paged(), x1)
    bench_op("attn chunk prefill c=%d (paged, xla)" % page, paged(),
             xc)
    run_kernels = (kernels == "force"
                   or (kernels == "auto" and on_tpu()))
    if run_kernels:
        bench_op("attn decode step c=1 (paged, pallas kernel)",
                 paged("decode"), x1, reps=5)
        bench_op("attn chunk prefill c=%d (paged, pallas kernel)"
                 % page, paged("prefill"), xc, reps=5)
    elif kernels == "auto":
        print("(pallas kernel rows skipped off-TPU — interpret mode "
              "measures the interpreter, not the kernel; pass "
              "--attn-kernels force to insist)", file=sys.stderr)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="all",
                    choices=("all", "alexnet", "attention"),
                    help="which section of the cost table to run")
    ap.add_argument("--attn-kernels", default="auto",
                    choices=("auto", "force", "off"),
                    help="Pallas serving-kernel rows: auto = only on "
                         "real TPU hardware; force = interpret mode "
                         "off-TPU (slow, parity gear); off = skip")
    args = ap.parse_args(argv)
    if args.only in ("all", "attention"):
        attention_rows(kernels=args.attn_kernels)
    if args.only in ("all", "alexnet"):
        alexnet_rows()
    stream_summary()


def alexnet_rows():
    key = jax.random.PRNGKey(0)
    B = 128

    # ---- crop at alexnet shape
    x_raw = jax.random.normal(key, (B, 256, 256, 3), jnp.float32)
    bench_op("crop 256->227 train (pad back to 256)",
             lambda x: jnp.pad(F.random_crop_flip(
                 x, jax.random.PRNGKey(1), (227, 227), True, True),
                 [(0, 0), (14, 15), (14, 15), (0, 0)]), x_raw)

    # ---- conv1 11x11 s4 fwd
    x227 = jax.random.normal(key, (B, 227, 227, 3), jnp.float32)
    w1 = jax.random.normal(key, (11, 11, 3, 96), jnp.float32) * 0.01
    b1 = jnp.zeros((96,))
    bench_op("conv1 fwd (current precision mode)",
             lambda x: F.conv2d_forward(x, w1, b1, (4, 4), "VALID",
                                        "strict_relu"), x227)

    # ---- LRN at conv1 output shape: current slice-sum vs r3 cumsum
    y1 = jax.random.normal(key, (B, 55, 55, 96), jnp.float32)
    bench_op("lrn fwd (current slice-sum)", F.lrn_forward, y1)
    bench_op("lrn fwd (r3 cumsum)", _r3_cumsum_lrn, y1)

    def lrn_vjp(x):
        _, vjp = jax.vjp(F.lrn_forward, x)
        return vjp(x)[0]
    bench_op("lrn fwd+vjp (current)", lrn_vjp, y1)

    # ---- max pooling 3x3 s2: current reduce_window vs r3 patches
    bench_op("maxpool fwd (current reduce_window)",
             lambda x: F.max_pooling(x, (3, 3), (2, 2)), y1)
    bench_op("maxpool fwd (r3 patches)", _r3_patch_maxpool, y1)

    def pool_vjp(x):
        y, vjp = jax.vjp(lambda a: F.max_pooling(a, (3, 3), (2, 2)), x)
        return vjp(y)[0]
    bench_op("maxpool fwd+vjp (current)", pool_vjp, y1)

    # ---- conv2 5x5 pad2 96->256 under both precision modes
    x2 = jax.random.normal(key, (B, 27, 27, 96), jnp.float32)
    w2 = jax.random.normal(key, (5, 5, 96, 256), jnp.float32) * 0.01
    b2 = jnp.zeros((256,))
    for mode in ("float32", "bfloat16"):
        with F.matmul_precision(mode):
            bench_op("conv2 fwd (%s)" % mode,
                     lambda x: F.conv2d_forward(x, w2, b2, (1, 1), 2,
                                                "strict_relu"), x2)

    # ---- FC trunk 9216->4096->4096->1000
    xf = jax.random.normal(key, (B, 9216), jnp.float32)
    wf1 = jax.random.normal(key, (9216, 4096), jnp.float32) * 0.01
    wf2 = jax.random.normal(key, (4096, 4096), jnp.float32) * 0.01
    wf3 = jax.random.normal(key, (4096, 1000), jnp.float32) * 0.01

    def fc_fwd(x):
        h = jnp.maximum(F.matmul(x, wf1), 0.0)
        h = jnp.maximum(F.matmul(h, wf2), 0.0)
        return F.matmul(h, wf3)
    bench_op("fc trunk fwd", fc_fwd, xf)

    def fc_vjp(x):
        y, vjp = jax.vjp(fc_fwd, x)
        return vjp(y)[0]
    bench_op("fc trunk fwd+vjp", fc_vjp, xf)

    # ---- roofline sanity
    xm = jax.random.normal(key, (4096, 4096), jnp.float32)
    t = bench_op("matmul 4096^3 HIGHEST", lambda x: F.matmul(x, x), xm)
    print("   -> %.1f TF/s fp32-HIGHEST" % (2 * 4096**3 / t / 1e12),
          file=sys.stderr)

    def mm_bf16(x):
        return jnp.matmul(x.astype(jnp.bfloat16),
                          x.astype(jnp.bfloat16)).astype(jnp.float32)
    t = bench_op("matmul 4096^3 bf16-cast", mm_bf16, xm)
    print("   -> %.1f TF/s bf16" % (2 * 4096**3 / t / 1e12),
          file=sys.stderr)


if __name__ == "__main__":
    main()
