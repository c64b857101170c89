"""Time the flash-decode kernel alone on the chip
(``pallas_kernels.paged_flash_decode``), at the shapes of the three benchmark
cells that call it: ``opt-1.3b.chat`` (8 lanes, 32 heads of 64 packed by two
in float32, pages of 32, a table of 40), ``trinity-large-ep8.longmix`` (32
lanes, 48 query on 8 KV heads of 128 in bfloat16, pages of 256: a sliding
layer's short table of 18 behind a window of 4096, and the full layer's of
32) and ``qwen3-next-80b-a3b-ep4.longchat`` (64 lanes, 16 on 2 heads of 256,
pages of 1024, a table of 17), lanes as deep as a decode step of the cells'
traffic finds them (``draw_positions``).  The table fixes ``pallas_kernels._FLASH_BLOCK_BYTES`` (PERF.md
section 6, PR 43) and shows what the walk's copies and its matmuls cost
apart.

    python tools/flash_decode_sweep.py [--parent PATH/pallas_kernels.py]
                                       [--out chiprun_out/flash_sweep.json]

A call's time is the device time of the Pallas call in a profiler trace, as
the benchmark's rooflines read it (``benchmark/lib/trace.py``), median of
``--calls``.  ``--parent`` names another checkout's kernel file to time
beside this one's.  Every variant's largest difference from the first is
printed, and once a cell the kernel runs behind a call that leaves NaN in
the chip's fast memory, over lanes of ONE live page (a short block: what the
slot holds beside it must not reach the sums).  One process, one chip; a CPU
run has no meaning and is refused.
"""

import argparse
import contextlib
import importlib.util
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from benchmark.lib import peaks  # noqa: E402
from tools.latent_decode_sweep import (_NoCopy, call_times,  # noqa: E402
                                       patched)
from veles_tpu.ops import pallas_kernels as PK  # noqa: E402

#: cell -> lanes, query heads, KV heads, head size, page, table width,
#: window, dtype, traffic table (the shapes ``tests/test_chip_compile.py``
#: and ``tools/aot_compile.py flash`` compile)
CELLS = {
    "chat": dict(lanes=8, heads=32, kv=32, dh=64, page=32, width=40,
                 window=None, dtype="float32",
                 traffic="benchmark/traffic/chat.json"),
    "longmix.window": dict(lanes=32, heads=48, kv=8, dh=128, page=256,
                           width=18, window=4096, dtype="bfloat16",
                           traffic="benchmark/traffic/longmix.json"),
    "longmix.full": dict(lanes=32, heads=48, kv=8, dh=128, page=256,
                         width=32, window=None, dtype="bfloat16",
                         traffic="benchmark/traffic/longmix.json"),
    "longchat": dict(lanes=64, heads=16, kv=2, dh=256, page=1024, width=17,
                     window=None, dtype="bfloat16",
                     traffic="benchmark/traffic/longchat.json"),
}

#: fast memory the three slots of both pools may take in a variant
SLOTS_BYTES = 40 << 20


def shapes(cell, c=1):
    """``(q, pool, table, positions)`` as (shape, dtype) pairs of a cell's
    call, the pool packed as the engine packs it."""
    lanes = cell["lanes"]
    r = PK.pool_pack(cell["kv"], cell["dh"])
    dtype = jnp.dtype(cell["dtype"])
    return (((lanes, cell["heads"], c, cell["dh"]), dtype),
            ((lanes * cell["width"] + 1, cell["kv"] // r, cell["page"],
              r * cell["dh"]), dtype),
            ((lanes, cell["width"]), jnp.int32), ((lanes,), jnp.int32))


def draw_positions(rng, lanes, table):
    """A position a lane as a decode step finds them: a request of the
    cell's table drawn by the steps it takes (its ``n_new``), a uniform
    share of them made."""
    steps = numpy.array([n_new for _, n_new in table], float)
    picks = rng.choice(len(table), lanes, p=steps / steps.sum())
    return numpy.array([table[k][0] + int(rng.random() * table[k][1])
                        for k in picks], numpy.int32)


def blocks(pages):
    """A context under which the kernel's walk takes ``pages`` a block."""
    return lambda: patched(PK, "flash_block_pages",
                           lambda shape, itemsize, m: min(pages, m))


def variants(parent, cell, pool_shape, itemsize):
    """name -> (module, context under which its kernel is traced)."""
    plain = contextlib.nullcontext
    out = {}
    if parent is not None:
        out["parent"] = (parent, plain)
    own = PK.flash_block_pages(pool_shape, itemsize, cell["width"])
    out["walk (%d pages)" % own] = (PK, plain)
    page_bytes = 2 * int(numpy.prod(pool_shape[1:])) * itemsize
    for pages in (1, 2, 4, 8, 16):
        if (pages != own and pages <= cell["width"]
                and 3 * pages * page_bytes <= SLOTS_BYTES):
            out["walk %d pages" % pages] = (PK, blocks(pages))
    out["walk, one block ahead"] = (
        PK, lambda: patched(PK, "_FLASH_AHEAD", 1))
    # the walk's two halves apart: what overlaps what
    out["walk, copies alone"] = (
        PK, lambda: patched(PK, "_flash_step", lambda *a, **k: None))
    out["walk, matmuls alone"] = (PK, no_copies)
    return out


@contextlib.contextmanager
def no_copies():
    """The kernel traced with copies that do nothing (its call is kept by
    its sizes, which this does not change: forget the kept ones)."""
    with patched(pltpu, "make_async_copy", lambda *a, **k: _NoCopy()):
        PK._flash_walk_call.cache_clear()
        yield
    PK._flash_walk_call.cache_clear()


def leave_nan_behind():
    """A call that fills 40 MB of the chip's fast memory with NaN."""
    def kernel(o_ref, buf_ref):
        buf_ref[...] = jnp.full(buf_ref.shape, jnp.nan, jnp.float32)
        o_ref[...] = buf_ref[:8]

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((SLOTS_BYTES // 512, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SLOTS_BYTES + (8 << 20)),
        interpret=PK._interpret(None))()


def short_blocks_stay_clean(cell, q, k_pool, v_pool, tab):
    """Lanes of one live page each, behind a call that left NaN where the
    slots will lie: the largest difference from the same lanes' result on
    the plain XLA path (NaN if a masked row reached the sums)."""
    from veles_tpu.ops import attention as A
    pos = jnp.arange(q.shape[0], dtype=jnp.int32) % cell["page"]
    jax.block_until_ready(leave_nan_behind())
    got = PK.paged_flash_decode(q, k_pool, v_pool, tab, pos,
                                window=cell["window"])
    r = k_pool.shape[-1] // cell["dh"]

    def plain(pool):          # (pages, kv/r, page, r dh) -> (b, kv, page, dh)
        rows = pool[tab[:, 0]]
        b, kvp, page, _ = rows.shape
        return rows.reshape(b, kvp, page, r, cell["dh"]).transpose(
            0, 1, 3, 2, 4).reshape(b, kvp * r, page, cell["dh"])
    kx, vx = (A._repeat_kv(plain(p), cell["heads"]).astype(jnp.float32)
              for p in (k_pool, v_pool))
    s = jnp.einsum("bhcd,bhld->bhcl", q.astype(jnp.float32), kx,
                   precision="highest") / cell["dh"] ** 0.5
    live = jnp.arange(cell["page"])[None, :] <= pos[:, None]
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    want = jnp.einsum("bhcl,bhld->bhcd", jax.nn.softmax(s, axis=-1), vx,
                      precision="highest")
    return float(jnp.abs(got.astype(jnp.float32) - want).max())


def run_cell(name, parent, calls, rng):
    cell = CELLS[name]
    with open(cell["traffic"]) as f:
        table = json.load(f)["table"]
    lanes, page, width, window = (cell[k] for k in
                                  ("lanes", "page", "width", "window"))
    pos = draw_positions(rng, lanes, table)
    if window:
        # the short table begins at the lane's first live page, and the
        # kernel is handed ``pos - base``
        pos = pos - numpy.maximum(pos - window + 1, 0) // page * page
    (q_s, q_t), (pool_s, pool_t), _, _ = shapes(cell)
    keys = jax.random.split(jax.random.PRNGKey(43), 3)
    q = jax.random.normal(keys[0], q_s, q_t)
    k_pool = (jax.random.normal(keys[1], pool_s, jnp.float32) * 0.3
              ).astype(pool_t)
    v_pool = (jax.random.normal(keys[2], pool_s, jnp.float32) * 0.3
              ).astype(pool_t)
    tab = jnp.asarray(1 + rng.permutation(lanes * width).reshape(
        lanes, width), jnp.int32)
    seen = numpy.minimum(pos + 1, window) if window else pos + 1
    token_bytes = 2 * cell["kv"] * cell["dh"] * pool_t.itemsize
    device = peaks.peaks(jax.devices()[0].device_kind)
    least = int(seen.sum()) * token_bytes / device["hbm_bytes_s"] * 1e6
    live = PK.live_page_count(*PK.live_pages(pos, 1, page, width, window,
                                             xp=numpy))
    row = {"cell": name, "lanes": lanes, "table": width,
           "positions": pos.tolist(), "tokens_seen": int(seen.sum()),
           "live_pages": int(live.sum()),
           "copied_bytes": int(live.sum()) * page * token_bytes,
           "least_us": least, "us": {}, "share": {}, "off": {}}
    row["copies_least_us"] = (row["copied_bytes"]
                              / device["hbm_bytes_s"] * 1e6)
    ref = None
    for label, (module, context) in variants(
            parent, cell, pool_s, pool_t.itemsize).items():
        with context():
            fn = jax.jit(lambda q, k, v, tab, pos, m=module: jax.named_scope(
                "attn.full")(m.paged_flash_decode)(q, k, v, tab, pos,
                                                   window=window))
            args = (q, k_pool, v_pool, tab, jnp.asarray(pos, jnp.int32))
            got = numpy.asarray(fn(*args).astype(jnp.float32))
            times = call_times(fn, args, calls)
        if ref is None:
            ref = got
        row["us"][label] = statistics.median(times)
        row["share"][label] = 100.0 * least / row["us"][label]
        if "alone" not in label:
            row["off"][label] = float(numpy.abs(got - ref).max())
        print("%-14s %-22s %8.1f us (%5.1f %% of %6.1f; %d calls read, "
              "min %.1f; off %s)" % (
                  name, label, row["us"][label], row["share"][label], least,
                  len(times), min(times), row["off"].get(label)), flush=True)
    row["short_blocks_off"] = short_blocks_stay_clean(cell, q, k_pool,
                                                      v_pool, tab)
    print("%-14s live pages %d (%.1f us of copies at the peak), short "
          "blocks behind NaN: off %g" % (
              name, row["live_pages"], row["copies_least_us"],
              row["short_blocks_off"]), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="another checkout's pallas_kernels.py")
    ap.add_argument("--out", default="chiprun_out/flash_sweep.json")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--cells", nargs="*", default=list(CELLS))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("flash_decode_sweep: needs the chip, found %s"
                         % jax.default_backend())
    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location("parent_kernels",
                                                      args.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    rng = numpy.random.default_rng(43)
    rows = [run_cell(name, parent, args.calls, rng) for name in args.cells]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f,
                  indent=1)


if __name__ == "__main__":
    main()
