"""Compile the chip smoke's step programs for a DESCRIBED TPU, no chip attached.

The third rehearsal before a chip call (README "Verify"): the TPU compiler
is installed here and compiles for a ``v5e:2x2`` that is described, not
attached — what it refuses here costs no chip time.  tests/
test_chip_compile.py keeps the two-second kernel compiles in tier-1; this
script holds the whole-program ones, which take up to minutes and are run
by hand::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python tools/aot_compile.py [alexnet] [lm] [flash] [latent] [linear] [kda] [ssd] [mtp] [mesh] [tp]

- ``alexnet``: the graph loop's train step and the epoch-scan window
  program at minibatch 128, 227x227 crops, 1000 classes, fp32 and bf16;
- ``lm``: the char_lm train step and the engine's prefill-chunk and decode
  programs at d_model 2048 / 16 heads / 4 layers / vocab 32768, Pallas
  serving kernels active; then the same two programs at the benchmark's
  head size (32 heads of 64, 2 layers, 320 pages), where the chip's
  default layout for the pool is not the kernels'; then the same two
  programs for the sandwich block with two kinds of layer and two kinds
  of pool (``chip_smoke.KINDS_LM``: heads of 128 in bfloat16, 32 lanes,
  pages of 256, expert layers through the grouped matmul); then (``latent``,
  also on its own) the same two programs for the latent kind at the
  benchmark cell's own configuration and geometry
  (``benchmark/configs/xing4.0-29b-a4b.json``: 6 layers, 16 lanes, pages of
  1024, a table of 33; one pool a layer), and before them the expanded
  prefill kernel alone at the latent cells' one shape (``tools/
  latent_prefill_sweep.py``; tables of 33 and 9): its compile seconds and
  the least fast memory it compiles under (ISSUE 44).  For each engine
  program it prints the copies of a whole KV pool and the pool leaves
  updated in place (``compiled_storage_report``) and the copies with a
  weight matrix's shape (``compiled_param_copies``), and exits non-zero
  when a copy of either kind is there or a leaf is not aliased; for a
  program with expert layers also how their grouped matmuls are carried
  out (``compiled_grouped_matmuls``: the row-tiled kernel from
  ``ops/moe.py::ROW_KERNEL_MIN`` assignment rows on, else ``ragged-dot``);
- ``flash``: the flash-decode kernel alone at the three benchmark cells'
  own shapes (``tools/flash_decode_sweep.py::CELLS``: the pages a block its
  own rule gives each, one query row a lane and two);
- ``mesh``: the ``ShardedTrainer`` AlexNet step on a data 2 x model 2 mesh
  (checks for an all-reduce);
- ``tp``: the ``LMEngine(tp=4)`` decode program over four chips.

Nothing runs, so this says nothing about results or times.  Code under
trace that asks ``on_tpu()`` sees the CPU; the script steers it (the
program has no option for that).
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

import chip_smoke  # noqa: E402

MB, HW, CLASSES = 128, (256, 256), 1000
LM = chip_smoke.FULL_LM


def abstract(tree, sharding):
    """Shapes of ``tree`` placed by ``sharding`` (one sharding, or a
    matching tree of them)."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, sharding)
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def compile_(name, jitted, *args):
    begin = time.time()
    compiled = jitted.lower(*args).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print("%-34s OK %6.1fs  args %.2f GB  temp %.2f GB  out %.2f GB  "
          "tpu_custom_call x%d  all-reduce x%d"
          % (name, time.time() - begin,
             mem.argument_size_in_bytes / 1e9, mem.temp_size_in_bytes / 1e9,
             mem.output_size_in_bytes / 1e9, text.count("tpu_custom_call"),
             text.count("all-reduce(") + text.count("all-reduce-start(")),
          flush=True)
    return text


def build_alexnet():
    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.samples import imagenet
    prng.reset()
    prng.seed_all(1)
    root.__dict__.pop("imagenet", None)
    root.imagenet.update({
        "loader": {"minibatch_size": MB, "n_train": MB, "n_valid": MB,
                   "image_hw": HW, "n_classes": CLASSES},
        "decision": {"max_epochs": 1, "fail_iterations": 5},
        "layers": imagenet.alexnet_layers(),
    })
    wf = imagenet.build(fused=True)
    wf.initialize()
    return wf._fused_runner


def step_args(sharding, batch_sharding=None):
    s = lambda shape, dt, sh=sharding: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=sh)
    b = batch_sharding or sharding
    return (s((MB,) + HW + (3,), jnp.float32, b), s((MB,), jnp.int32, b),
            s((MB,), jnp.float32, b), s((), jnp.int32),
            s((2,), jnp.uint32), s((), jnp.int32))


def alexnet(one_chip):
    from veles_tpu.ops import functional as F
    window = 3
    for precision in ("float32", "bfloat16"):
        with F.matmul_precision(precision):
            runner = build_alexnet()
            state = abstract(runner.state, one_chip)
            compile_("alexnet step %s" % precision,
                     jax.jit(runner._step_fn), state, *step_args(one_chip))
            s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
                shape, dt, sharding=one_chip)
            compile_("alexnet epoch-scan window %s" % precision,
                     jax.jit(runner._epoch_train), state,
                     s((window * MB,) + HW + (3,), jnp.float32),
                     s((window * MB,), jnp.int32),
                     s((window, MB), jnp.int32),
                     s((window, MB), jnp.float32), s((2,), jnp.uint32),
                     s((), jnp.int32))


def mesh(topo):
    from veles_tpu.parallel import (ShardedTrainer, make_mesh,
                                    model_shard_candidates)

    class AbstractTrainer(ShardedTrainer):
        def _put(self, arr, sharding):      # shapes, not arrays
            return None if arr is None else jax.ShapeDtypeStruct(
                arr.shape, arr.dtype, sharding=sharding)

    runner = build_alexnet()
    trainer = AbstractTrainer(
        runner, make_mesh(4, model_parallel=2, devices=topo.devices),
        model_shard_layers=model_shard_candidates(runner, min_width=4096))
    text = compile_("alexnet step data2 x model2", trainer._train,
                    trainer.state,
                    *step_args(trainer._repl, trainer._batch))
    if "all-reduce" not in text:
        raise SystemExit("no all-reduce in the compiled mesh step")


def build_lm(lm=LM):
    wf = chip_smoke._build_char_lm(1, lm, run=False)
    trainer = wf.trainer
    return trainer, trainer._to_portable(trainer.params)


def kernel_engine(params, n_heads, **kwargs):
    """An ``LMEngine`` with the Pallas serving kernels active."""
    from veles_tpu.ops import pallas_kernels as PK
    from veles_tpu.serving import LMEngine
    PK.on_tpu = lambda: True        # trace the compiled-kernel branch
    eng = LMEngine(params, n_heads=n_heads, attn_kernel="auto", **kwargs)
    if not eng._kernel_active:
        raise SystemExit("the engine did not select the Pallas kernels")
    return eng


def storage_in_place(name, text, eng):
    """Print what the compiled program does to the KV storage and to its
    weights; exit non-zero on a whole-pool copy, a pool leaf not updated
    in place or a copy with a weight matrix's shape."""
    from veles_tpu.serving.lm_engine import (compiled_param_copies,
                                             compiled_storage_report)
    leaves = jax.tree.leaves(eng._kv_pools)
    # one report per kind of pool (a stack of two kinds has two shapes)
    kinds = {leaf.shape: leaf for leaf in leaves}.values()
    copies = sum(compiled_storage_report(text, leaf)[0] for leaf in kinds)
    _, aliased = compiled_storage_report(text, leaves[0])
    weights = compiled_param_copies(text, eng.params)
    print("%-34s pool copies x%d  pool leaves in place %d of %d  "
          "weight-shaped copies x%d"
          % (name, copies, aliased, len(leaves), weights), flush=True)
    if copies or aliased < len(leaves):
        raise SystemExit("%s: the KV storage is not updated in place"
                         % name)
    if weights:
        raise SystemExit("%s: a weight is copied whole before it is read"
                         % name)


def grouped_matmuls(name, text, eng, tokens):
    """Print how a compiled program carries out the expert layers' grouped
    matmuls (ISSUE 35) and exit non-zero unless it is the row-tiled kernel
    for a program of ``ROW_KERNEL_MIN`` assignment rows or more (``tokens``
    x top-k) and the compiler's ``ragged-dot`` under that, three a layer
    of experts either way."""
    from veles_tpu import model_config
    from veles_tpu.ops import moe
    from veles_tpu.serving.lm_engine import compiled_grouped_matmuls
    layers = sum(eng.cfg.ffn_kind(i, blk) == model_config.MOE
                 for i, blk in enumerate(eng.params["blocks"])) \
        + (eng.cfg.nextn if eng._mtp else 0)
    if not layers:
        return
    rows = tokens * eng.cfg.moe.top_k
    ragged, kernel = compiled_grouped_matmuls(text)
    print("%-34s %d assignment rows: ragged-dot x%d  row kernel x%d"
          % (name, rows, ragged, kernel), flush=True)
    want = (0, 3 * layers) if rows >= moe.ROW_KERNEL_MIN else (3 * layers, 0)
    if (ragged, kernel) != want:
        raise SystemExit("%s: grouped matmuls %r, expected %r"
                         % (name, (ragged, kernel), want))


def state_space_calls(name, text, eng):
    """Print the Pallas calls of a compiled program's state-space layers by
    innermost scope (ISSUE 47) and exit non-zero unless each layer has ONE
    under ``ssd.`` (the benchmark's two rooflines count the operations named
    ``ssd ...`` as one a layer and dispatch) and one under ``norm.gated``."""
    from veles_tpu import model_config
    from veles_tpu.serving.lm_engine import compiled_kernel_scopes
    lin = eng.cfg.linear
    if lin is None or lin.rule != "ssd":
        return
    layers = sum(eng.cfg.kind(i) == model_config.LINEAR
                 for i in range(len(eng.params["blocks"])))
    scopes = compiled_kernel_scopes(text)
    rule = sum(scope.startswith("ssd.") for scope in scopes)
    norm = scopes.count("norm.gated")
    print("%-34s %d state-space layers: ssd.* calls x%d  norm.gated x%d"
          % (name, layers, rule, norm), flush=True)
    if (rule, norm) != (layers, layers):
        raise SystemExit("%s: %d ssd.* and %d norm.gated calls, expected %d "
                         "of each" % (name, rule, norm, layers))


def engine_programs(tag, eng, one_chip, widths):
    """Compile ``eng``'s chunk program and its decode program at
    ``widths`` of the page table; each must hold a Pallas kernel and
    update the pools in place."""
    s = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    a_params = abstract(eng.params, one_chip)
    pools = abstract(eng._kv_pools, one_chip)
    slots, page = eng.slots, eng.prefill_chunk

    def tables(*lead):
        """The page-table argument at width ``lead[-1]``
        (``LMEngine._table_args``): one table, or for a stack of two
        kinds of layer one per kind and where the sliding kind's begins."""
        if eng._state_shapes is not None:
            # linear layers beside one table: the chunk's lane's slot, or
            # the lanes that decode
            return s(lead), (s(()) if len(lead) == 1
                             else s(lead[:-1], jnp.bool_))
        if eng._wt is None:
            return s(lead)
        narrow = lead[:-1] + (min(lead[-1], eng._wt.width),)
        return {"full": s(lead), "sliding": s(narrow)}, s(lead[:-1])

    # the lanes' state as it passes from dispatch to dispatch: their last
    # tokens, or (last, draft, position) where the model's module drafts
    state = (s((slots,)),) * 3 if eng._mtp else s((slots,))
    name = "%s prefill chunk (kernel)" % tag
    text = compile_(name, eng._chunk_jit, a_params, pools,
                    tables(eng._max_pages), s((page + int(eng._mtp),)),
                    s(()), s(()), s(()), state)
    storage_in_place(name, text, eng)
    grouped_matmuls(name, text, eng, page)
    state_space_calls(name, text, eng)
    for width in widths:
        name = "%s decode step width %d" % (tag, width)
        text = compile_(name, eng._step_jit, a_params, pools,
                        tables(slots, width), state,
                        *(() if eng._mtp else (s((slots,)),)),
                        s((slots,), jnp.bool_))
        if "tpu_custom_call" not in text:
            raise SystemExit("no Pallas kernel in the decode program")
        storage_in_place(name, text, eng)
        grouped_matmuls(name, text, eng, slots * (eng.spec_k + 1
                                                  if eng._mtp else 1))
        state_space_calls(name, text, eng)


def lm(one_chip):
    trainer, params = build_lm()
    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    compile_("char_lm train step", trainer._train,
             abstract(trainer.params, one_chip),
             abstract(trainer.opt_state, one_chip),
             s((LM["minibatch"], LM["seq_len"]), jnp.int32),
             s((LM["minibatch"],), jnp.float32), s((), jnp.int32))
    eng = kernel_engine(params, trainer.n_heads, max_len=LM["max_len"],
                        slots=8, prefill_chunk=32, paged_kv=True)
    engine_programs("engine", eng, one_chip,
                    (1, 8, LM["max_len"] // 32))
    # the benchmark's geometry (OPT-1.3B: 32 heads of 64, 8 lanes, 320 pages
    # of 32), cut to 2 layers: under 128 lanes of head size the chip's own
    # layout for a pool is not row-major, which is where the copies were
    _, params = build_lm(dict(LM, n_heads=32, n_layers=2))
    eng = kernel_engine(params, 32, max_len=LM["max_len"], slots=8,
                        prefill_chunk=32, paged_kv=320)
    engine_programs("engine dh64", eng, one_chip, (1, 64))
    # ISSUE 28: the sandwich block, two kinds of layer and of pool
    from benchmark.reference import afmoe
    from veles_tpu import model_config
    kinds = chip_smoke.KINDS_LM
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: afmoe.make_weights(1, kinds)))
    eng = kernel_engine(params, model_config.from_published(kinds),
                        max_len=kinds["max_position_embeddings"], slots=32,
                        prefill_chunk=256, paged_kv=True)
    engine_programs("engine kinds", eng, one_chip, (1, 8))
    latent(one_chip)


def flash(one_chip):
    """The flash-decode kernel at the cells' shapes (ISSUE 43): a decode
    step's one query row a lane and a verify step's two."""
    from tools.flash_decode_sweep import CELLS, shapes
    from veles_tpu.ops import pallas_kernels as PK
    for name, cell in CELLS.items():
        for c in (1, 2):
            args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                    for shape, dtype in shapes(cell, c=c)]
            pool = args[1]
            compile_(
                "flash decode %s, %d row(s) a lane, %d pages a block"
                % (name, c, PK.flash_block_pages(
                    pool.shape, pool.dtype.itemsize, cell["width"])),
                jax.jit(lambda q, k, tab, pos, w=cell["window"]:
                        PK.paged_flash_decode(q, k, k, tab, pos, window=w,
                                              interpret=False)), *args)


def latent_prefill_kernel(one_chip):
    """ISSUE 44: the expanded prefill kernel alone at the shape the three
    latent cells give it, under its own constants: seconds to lower and
    compile, and the least limit of fast memory (whole MiB) it compiles
    under, found by halving between nothing and the kernel's own limit."""
    from tools import latent_prefill_sweep as sweep
    from veles_tpu.ops import pallas_kernels as PK
    bf16, own = jnp.bfloat16, PK._LATENT_VMEM

    def compiles(width, limit):
        shapes = (((1, sweep.HEADS, sweep.CHUNK, sweep.NOPE), bf16),
                  ((1, sweep.HEADS, sweep.CHUNK, sweep.ROPE), bf16),
                  ((sweep.HEADS, sweep.RANK, sweep.NOPE), bf16),
                  ((sweep.HEADS, sweep.RANK, sweep.VDIM), bf16),
                  ((width + 1, 1, sweep.CHUNK, sweep.ROW), bf16),
                  ((1, width), jnp.int32), ((1,), jnp.int32))
        try:
            with sweep.patched(PK, "_LATENT_VMEM", limit):
                jax.jit(lambda qn, qr, wk, wv, pool, tab, pos:
                        PK.paged_latent_prefill(
                            qn, qr, wk, wv, pool, tab, pos, sweep.SCALE,
                            sweep.RANK, interpret=False)).lower(*(
                                jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
                                for shape, dtype in shapes)).compile()
            return True
        except Exception as e:  # noqa: BLE001 — the compiler's refusal
            if "vmem" not in str(e):
                raise
            return False

    for width, _ in sweep.CELLS.values():
        begin = time.time()
        if not compiles(width, own):
            raise SystemExit("the latent prefill kernel does not compile "
                             "under its own limit of %d MiB" % (own >> 20))
        seconds = time.time() - begin
        low, high = 0, own >> 20        # MiB: refused, accepted
        while high - low > 1:
            mid = (low + high) // 2
            low, high = (low, mid) if compiles(width, mid << 20) else (
                mid, high)
        print("latent prefill kernel, table %-3d      OK %6.1fs  (%d heads "
              "a step, units of %d rows, %d a body)  fast memory %d of %d "
              "MiB" % (width, seconds, PK._LATENT_HEADS, PK._LATENT_Q_ROWS,
                       PK._LATENT_CHAINS, high, own >> 20), flush=True)


def latent(one_chip):
    """ISSUE 34: the latent kind at the benchmark cell's configuration and
    geometry: the chunk program (expanded attention) and the decode program
    (absorbed) at the narrowest and the widest table; before them the
    prefill kernel alone (``latent_prefill_kernel``)."""
    import json
    latent_prefill_kernel(one_chip)
    from benchmark.reference import xing4
    from veles_tpu import model_config
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "xing4.0-29b-a4b.json")) as f:
        cfg = json.load(f)
    dep = cfg["deployment"]
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: xing4.make_weights(1, cfg)))
    eng = kernel_engine(params, model_config.from_published(cfg),
                        max_len=cfg["max_position_embeddings"],
                        slots=dep["slots"],
                        prefill_chunk=dep["prefill_chunk"],
                        paged_kv=dep["paged_kv"])
    engine_programs("engine latent", eng, one_chip, (1, eng._max_pages))


def mtp(one_chip):
    """ISSUE 40: a latent stack that drafts with its own module, at the
    benchmark cell's configuration and geometry: the chunk program with the
    module's rows of the prompt behind the stack's, and the verify-and-draft
    step (two rows a lane through the absorbed kernel, the row-tiled grouped
    matmul at 512 assignment rows, acceptance and the next draft in the
    graph) at the narrowest and the widest table."""
    import json
    from benchmark.reference import joyai
    from veles_tpu import model_config
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "joyai-llm-flash-ep8.json")) as f:
        cfg = json.load(f)
    dep = cfg["deployment"]
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: joyai.make_weights(1, cfg)))
    eng = kernel_engine(params, model_config.from_published(cfg),
                        max_len=cfg["max_position_embeddings"],
                        slots=dep["slots"],
                        prefill_chunk=dep["prefill_chunk"],
                        paged_kv=dep["paged_kv"], spec_k=dep["spec_k"])
    engine_programs("engine mtp", eng, one_chip, (1, eng._max_pages))


def linear(one_chip, config="qwen3-next-80b-a3b-ep4", tag="engine linear"):
    """ISSUE 36: a stack of linear (gated delta rule) and full layers at
    the benchmark cell's configuration and geometry: the chunk program (the
    chunked rule, the prefill kernel at a head of 256) and the decode
    program (the recurrent rule on the decoding lanes' state, the row-tiled
    grouped matmul at 640 assignment rows) at the narrowest and the widest
    table.  ``kda`` (ISSUE 42) is the same for ``ling-3.0-flash-vl-ep4``:
    a decay a key channel, the full layer latent (one pool), 512 assignment
    rows a decode step.  ``ssd`` (ISSUE 46) for ``granite-4.0-h-micro``: all
    40 layers, 36 of them the state-space rule on a packed state, no expert
    layer, a tied head over 100352 rows; each program's ``ssd.*`` and
    ``norm.gated`` calls are counted against the 36 (ISSUE 47)."""
    import importlib
    import json
    from veles_tpu import model_config
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            config + ".json")) as f:
        cfg = json.load(f)
    reference = importlib.import_module(
        "benchmark.reference." + cfg["reference"])
    dep = cfg["deployment"]
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: reference.make_weights(1, cfg)))
    eng = kernel_engine(params, model_config.from_published(cfg),
                        max_len=cfg["max_position_embeddings"],
                        slots=dep["slots"],
                        prefill_chunk=dep["prefill_chunk"],
                        paged_kv=dep["paged_kv"])
    engine_programs(tag, eng, one_chip, (1, eng._max_pages))


def tp(topo):
    from veles_tpu.ops.transformer import lm_param_specs
    from veles_tpu.parallel import make_tp_mesh
    from veles_tpu.serving import LMEngine
    trainer, params = build_lm()
    slots, page = 8, 32
    eng = LMEngine(params, n_heads=trainer.n_heads, max_len=LM["max_len"],
                   slots=slots, prefill_chunk=page, paged_kv=True,
                   attn_kernel="auto", tp=4, devices=jax.devices()[:4])
    tmesh = make_tp_mesh(4, devices=topo.devices)
    on = lambda spec: NamedSharding(tmesh, spec)  # noqa: E731
    a_params = abstract(eng.params, jax.tree.map(
        on, lm_param_specs(eng.params)))
    kv = on(eng._kv_shard.spec)
    pools = abstract(eng._kv_pools, kv)
    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=on(P()))
    # the engine pinned its out_shardings to the CPU mesh: re-jit the same
    # function with the described mesh's, the pools donated as the engine's
    step = jax.jit(eng._step_jit.__wrapped__, donate_argnums=(1,),
                   out_shardings=([(kv, kv)] * len(eng.params["blocks"]),
                                  on(P())))
    text = compile_("engine decode step tp=4", step, a_params, pools,
                    s((slots, 8), jnp.int32), s((slots,), jnp.int32),
                    s((slots,), jnp.int32), s((slots,), jnp.bool_))
    from veles_tpu.serving.lm_engine import compiled_storage_report
    leaves = jax.tree.leaves(pools)
    _, aliased = compiled_storage_report(text, leaves[0])
    print("%-34s pool leaves in place %d of %d"
          % ("engine decode step tp=4", aliased, len(leaves)), flush=True)
    if aliased < len(leaves):
        raise SystemExit("tp=4: a pool leaf is not updated in place")


def main(argv):
    from jax.experimental import topologies
    want = argv or ["alexnet", "lm", "mesh", "tp"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    print("compiling for %s (%d described devices)"
          % (topo.devices[0].device_kind, len(topo.devices)), flush=True)
    if "alexnet" in want:
        alexnet(one_chip)
    if "lm" in want:
        lm(one_chip)
    elif "latent" in want:
        latent(one_chip)
    if "flash" in want:
        flash(one_chip)
    if "linear" in want:
        linear(one_chip)
    if "kda" in want:
        linear(one_chip, "ling-3.0-flash-vl-ep4", "engine kda")
    if "ssd" in want:
        linear(one_chip, "granite-4.0-h-micro", "engine ssd")
    if "mtp" in want:
        mtp(one_chip)
    if "mesh" in want:
        mesh(topo)
    if "tp" in want:
        tp(topo)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
