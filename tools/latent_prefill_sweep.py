"""Time the expanded latent-attention prefill kernel alone on the chip
(``pallas_kernels.paged_latent_prefill``), at the one shape all three latent
configurations give it (1 lane x 32 heads x a chunk of 1024 rows in bfloat16,
128 unrotated + 64 rotated, 128 value, ``kv_lora_rank`` 512, pool rows of
640): ``xing4.0-29b-a4b.longdoc``'s table of 33 pages with the chunk at page
0, 4, 16 and 31, and ``joyai-llm-flash-ep8.reason``'s table of 9 with the
chunk at page 0, 4 and 8.  The table fixes ``pallas_kernels._LATENT_HEADS``,
``_LATENT_Q_ROWS`` and ``_LATENT_CHAINS`` (PERF.md section 6, PR 44) and
shows what the step's matmuls and its softmax cost apart.

    python tools/latent_prefill_sweep.py [--parent PATH/pallas_kernels.py]
                                         [--out chiprun_out/latent_prefill_sweep.json]

A call's time is the device time of the Pallas call in a profiler trace, as
the benchmark's rooflines read it (``benchmark/lib/trace.py``), median of
``--calls``; *us a head and history page* is the slope between the chunk at
page 4 and at the table's last page.  ``--parent`` names another checkout's
kernel file to time beside this one's.  Every variant's largest difference
from the first is printed.  *matmuls alone* replaces the softmax by a cast of
the scores, *softmax alone* every dot by a broadcast (``_latent_softmax``,
``_latent_dot``).  One process, one chip; a CPU run has no meaning and is
refused.
"""

import argparse
import contextlib
import importlib.util
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy  # noqa: E402

from benchmark.lib import peaks, trace  # noqa: E402
from benchmark.rooflines import mla_prefill  # noqa: E402
from tools.latent_decode_sweep import patched  # noqa: E402
from veles_tpu.ops import pallas_kernels as PK  # noqa: E402

BF16 = jnp.bfloat16
HEADS, CHUNK, NOPE, ROPE, VDIM, RANK, ROW = 32, 1024, 128, 64, 128, 512, 640
SCALE = 192 ** -0.5

#: cell -> (table width, the pages the chunk starts at)
CELLS = {"longdoc": (33, (0, 4, 16, 31)), "reason": (9, (0, 4, 8))}

#: fast memory a variant may take (the shipped constants are compiled under
#: the kernel's own limit by ``tests/test_chip_compile.py``)
VARIANT_VMEM = 100 << 20


def no_softmax(s, m_prev):
    """*matmuls alone*: the scores go to ``p . v`` as they are."""
    return m_prev, m_prev, s, m_prev


def no_dots(x, y, transposed, precision):
    """*softmax alone*: a broadcast where a matmul stood."""
    n = y.shape[0 if transposed else 1]
    return (x[:, :1] * y[:1, :1]).astype(jnp.float32) * jnp.ones(
        (1, n), jnp.float32)


def order(heads, rows, chains, **more):
    """A context under which this file's kernel takes ``heads`` heads a
    grid step, units of ``rows`` query rows, ``chains`` of them a body."""
    consts = dict(_LATENT_HEADS=heads, _LATENT_Q_ROWS=rows,
                  _LATENT_CHAINS=chains, _LATENT_VMEM=VARIANT_VMEM, **more)

    @contextlib.contextmanager
    def context():
        with contextlib.ExitStack() as stack:
            for name, value in consts.items():
                stack.enter_context(patched(PK, name, value))
            yield
    return context


def variants(parent):
    """name -> (module, context under which its kernel is traced)."""
    out = {}
    if parent is not None:
        out["parent"] = (parent, contextlib.nullcontext)
    out["shipped (%d heads, %d rows, %d chains)" % (
        PK._LATENT_HEADS, PK._LATENT_Q_ROWS, PK._LATENT_CHAINS)] = (
            PK, contextlib.nullcontext)
    for rows in (256, 512, 1024):
        for chains in (1, 2):
            out["4 heads, %d rows, %d chains" % (rows, chains)] = (
                PK, order(4, rows, chains))
    for heads in (2, 8):
        for rows in (512, 1024):
            out["%d heads, %d rows, 2 chains" % (heads, rows)] = (
                PK, order(heads, rows, 2))
    out["4 heads, 256 rows, 4 chains"] = (PK, order(4, 256, 4))
    for rows in (128, 512):
        out["4 heads, 1024 rows, 2 chains, own page by %d" % rows] = (
            PK, order(4, 1024, 2, _LATENT_DIAGONAL_ROWS=rows))
    # the step's two halves apart, in the parent's order and in the widest
    for rows, chains in ((256, 1), (1024, 2)):
        name = "4 heads, %d rows, %d chains" % (rows, chains)
        out[name + ", matmuls alone"] = (
            PK, order(4, rows, chains, _latent_softmax=no_softmax))
        out[name + ", softmax alone"] = (
            PK, order(4, rows, chains, _latent_dot=no_dots))
    return out


def call_times(fn, args_by_start, calls):
    """Device microseconds of the Pallas call ``attn ...`` of ``fn`` for
    each of ``args_by_start`` (start -> arguments), ``calls`` runs each,
    from one trace of them all."""
    for args in args_by_start.values():
        jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            for args in args_by_start.values():
                for _ in range(calls):
                    out = fn(*args)
                jax.block_until_ready(out)
        ops = trace.read(logdir)["devices"][0]["ops"]
    times = [op.dur / 1e3 for op in ops if op.name.startswith("attn ")]
    if len(times) != calls * len(args_by_start):
        raise SystemExit("latent_prefill_sweep: %d calls traced, %d made"
                         % (len(times), calls * len(args_by_start)))
    return {start: times[n * calls:(n + 1) * calls]
            for n, start in enumerate(args_by_start)}


def cell(name, parent, calls, rng, only):
    width, starts = CELLS[name]
    keys = jax.random.split(jax.random.PRNGKey(44), 5)
    q_nope = jax.random.normal(keys[0], (1, HEADS, CHUNK, NOPE), BF16)
    q_rope = jax.random.normal(keys[1], (1, HEADS, CHUNK, ROPE), BF16)
    wk = (jax.random.normal(keys[2], (HEADS, RANK, NOPE), jnp.float32)
          * RANK ** -0.5).astype(BF16)
    wv = (jax.random.normal(keys[3], (HEADS, RANK, VDIM), jnp.float32)
          * RANK ** -0.5).astype(BF16)
    pool = jax.random.normal(keys[4], (width + 1, 1, CHUNK, ROW), BF16)
    pool = pool.at[..., RANK + ROPE:].set(0)
    tab = jnp.asarray(1 + rng.permutation(width)[None], jnp.int32)
    args = {start: (q_nope, q_rope, wk, wv, pool, tab,
                    jnp.asarray([start * CHUNK], jnp.int32))
            for start in starts}
    device = peaks.peaks(jax.devices()[0].device_kind)
    # what ``mla_prefill_roofline`` holds a call to
    shapes = {"num_attention_heads": HEADS, "qk_nope_head_dim": NOPE,
              "qk_rope_head_dim": ROPE, "v_head_dim": VDIM,
              "kv_lora_rank": RANK}
    least = {start: mla_prefill.roofline_seconds(
        shapes, start * CHUNK, CHUNK, device) * 1e6 for start in starts}
    row = {"cell": name, "table": width, "starts": list(starts),
           "least_us": least, "us": {}, "us_head_page": {}, "share": {},
           "off": {}}
    ref = None
    for label, (module, context) in variants(parent).items():
        if only and not any(word in label for word in only):
            continue
        with context():
            fn = jax.jit(lambda qn, qr, wk, wv, pool, tab, pos, m=module:
                         jax.named_scope("attn.latent")(
                             m.paged_latent_prefill)(
                                 qn, qr, wk, wv, pool, tab, pos, SCALE, RANK))
            got = {start: numpy.asarray(fn(*a).astype(jnp.float32))
                   for start, a in args.items()}
            times = call_times(fn, args, calls)
        if ref is None:
            ref = got
        us = {start: statistics.median(t) for start, t in times.items()}
        row["us"][label] = us
        row["us_head_page"][label] = (us[starts[-1]] - us[starts[1]]) / (
            (starts[-1] - starts[1]) * HEADS)
        row["share"][label] = {start: 100.0 * least[start] / us[start]
                               for start in starts}
        if "alone" not in label:
            row["off"][label] = max(
                float(numpy.abs(got[start] - ref[start]).max())
                for start in starts)
        print("%-8s %-44s %s us; %.2f us a head and history page; %s %% of "
              "the roofline; off %s" % (
                  name, label,
                  " | ".join("%.0f" % us[start] for start in starts),
                  row["us_head_page"][label],
                  " | ".join("%.1f" % row["share"][label][start]
                             for start in starts),
                  row["off"].get(label)), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="another checkout's pallas_kernels.py")
    ap.add_argument("--out", default="chiprun_out/latent_prefill_sweep.json")
    ap.add_argument("--calls", type=int, default=12)
    ap.add_argument("--cells", nargs="*", default=list(CELLS))
    ap.add_argument("--only", nargs="*",
                    help="variants whose name holds one of these words")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("latent_prefill_sweep: needs the chip, found %s"
                         % jax.default_backend())
    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location("parent_kernels",
                                                      args.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    rng = numpy.random.default_rng(44)
    rows = [cell(name, parent, args.calls, rng, args.only)
            for name in args.cells]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f,
                  indent=1)


if __name__ == "__main__":
    main()
