"""Time the absorbed latent-attention kernel alone on the chip
(``pallas_kernels.paged_latent_decode``), at the two benchmark cells' shapes:
``joyai-llm-flash-ep8.reason`` (32 lanes, two query rows a head, a table of
9 pages) and ``xing4.0-29b-a4b.longdoc`` (16 lanes, one row, a table of 33),
lanes as deep as the cells' traffic tables leave them.  The table fixes
``pallas_kernels._LATENT_BLOCK`` (PERF.md section 6, PR 41) and shows what
the walk's copies and its matmuls cost apart.

    python tools/latent_decode_sweep.py [--parent PATH/pallas_kernels.py]
                                        [--out chiprun_out/latent_sweep.json]

A call's time is the device time of the Pallas call in a profiler trace, as
the benchmark's rooflines read it (``benchmark/lib/trace.py``), median of
``--calls``.  ``--parent`` names another checkout's kernel file to time
beside this one's (also at a table twice as wide, whose added entries no
lane sees: what a dead table entry costs it).  One process, one chip; a CPU
run has no meaning and is refused.
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
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from benchmark.lib import peaks, trace  # noqa: E402
from veles_tpu.ops import pallas_kernels as PK  # noqa: E402

BF16 = jnp.bfloat16
PAGE, ROW, WIDTH = 1024, 640, 576     # a pool row, and the numbers it needs

#: cell -> (lanes, heads, query rows a head, table width, traffic table)
CELLS = {
    "reason": (32, 32, 2, 9, "benchmark/traffic/reason.json"),
    "longdoc": (16, 32, 1, 33, "benchmark/traffic/longdoc.json"),
}


def draw_positions(rng, lanes, table):
    """A position a lane: a request of the cell's table, a uniform share of
    its ``n_new`` tokens made."""
    picks = rng.integers(0, len(table), lanes)
    return numpy.array([table[k][0] + int(rng.random() * table[k][1])
                        for k in picks], numpy.int32)


def call_times(fn, args, calls):
    """Device microseconds of each Pallas call ``attn ...`` over ``calls``
    runs of ``fn``, from a trace of them."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        ops = trace.read(logdir)["devices"][0]["ops"]
    return [op.dur / 1e3 for op in ops if op.name.startswith("attn ")]


@contextlib.contextmanager
def patched(owner, name, value):
    before = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, before)


class _NoCopy:
    def start(self):
        pass

    wait = start


def variants(parent):
    """name -> (module, context under which its kernel is traced, table
    widths as a multiple of the cell's)."""
    plain = contextlib.nullcontext
    out = {}
    if parent is not None:
        out["parent"] = (parent, plain, 1)
        out["parent, table x 2"] = (parent, plain, 2)
    for block in (1024, 512, 256):
        out["walk %d" % block] = (
            PK, lambda b=block: patched(PK, "_LATENT_BLOCK", b), 1)
    out["walk, table x 2"] = (PK, plain, 2)
    # the walk's two halves apart: what overlaps what
    out["walk, copies alone"] = (
        PK, lambda: patched(PK, "_flash_step", lambda *a, **k: None), 1)
    out["walk, matmuls alone"] = (
        PK, lambda: patched(pltpu, "make_async_copy",
                            lambda *a, **k: _NoCopy()), 1)
    return out


def cell(name, parent, calls, rng):
    lanes, heads, c, width, traffic = CELLS[name]
    with open(traffic) as f:
        table = json.load(f)["table"]
    pos = draw_positions(rng, lanes, table)
    tokens = int((pos + c).sum())
    pool = (jax.random.normal(jax.random.PRNGKey(41),
                              (2 * lanes * width + 1, 1, PAGE, ROW), BF16)
            * 0.3).astype(BF16)
    q = jax.random.normal(jax.random.PRNGKey(42), (lanes, heads, c, ROW),
                          BF16)
    tab = 1 + rng.permutation(2 * lanes * width).reshape(lanes, 2 * width)
    device = peaks.peaks(jax.devices()[0].device_kind)
    least = tokens * WIDTH * 2 / device["hbm_bytes_s"] * 1e6
    row = {"cell": name, "lanes": lanes, "rows": heads * c, "table": width,
           "positions": pos.tolist(), "tokens": tokens,
           "live_pages": int((-(-(pos + c) // PAGE)).sum()),
           "least_us": least, "us": {}, "share": {}, "off": {}}
    ref = None
    for label, (module, context, wide) in variants(parent).items():
        with context():
            fn = jax.jit(lambda q, pool, tab, pos, m=module: jax.named_scope(
                "attn.latent")(m.paged_latent_decode)(
                    q, pool, tab, pos, 192 ** -0.5))
            args = (q, pool, jnp.asarray(tab[:, :wide * width], jnp.int32),
                    jnp.asarray(pos))
            got = numpy.asarray(fn(*args)[..., :512].astype(jnp.float32))
            times = call_times(fn, args, calls)
        if ref is None:
            ref = got
        row["us"][label] = statistics.median(times)
        row["share"][label] = 100.0 * least / row["us"][label]
        if "alone" not in label:
            row["off"][label] = float(numpy.abs(got - ref).max())
        print("%-8s %-20s %8.1f us (%5.1f %% of %6.1f; %d calls read, "
              "min %.1f)" % (name, label, row["us"][label],
                             row["share"][label], least, len(times),
                             min(times)), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="another checkout's pallas_kernels.py")
    ap.add_argument("--out", default="chiprun_out/latent_sweep.json")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--cells", nargs="*", default=list(CELLS))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("latent_decode_sweep: needs the chip, found %s"
                         % jax.default_backend())
    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location("parent_kernels",
                                                      args.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    rng = numpy.random.default_rng(41)
    rows = [cell(name, parent, args.calls, rng) for name in args.cells]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f,
                  indent=1)


if __name__ == "__main__":
    main()
