"""The main path's Pallas kernels compile for the chip — without the chip.

The TPU compiler is installed here and compiles for a ``v5e:2x2`` that is
DESCRIBED, not attached, so these catch what interpret mode cannot: a
kernel with no grid whose operands do not fit VMEM (``fused_sgd_update``
at AlexNet's fc6/fc7 before it got its row grid), a slice not aligned to
the tiling, a dot precision Mosaic refuses.  About two seconds each, at
the real widths of the models chip_smoke.py runs.  The whole-program
compiles (AlexNet step, LM engine programs; up to minutes) live in
tools/aot_compile.py and are run by hand.

ALL of these stay in this ONE file, and the topology is described inside
a fixture: only one process may load the TPU library, so under xdist only
the worker that is handed this file may touch it — never at import, in a
``skipif`` or in ``parametrize``.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from veles_tpu.ops import pallas_kernels as PK


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described device is written to jax's persistent
    cache but cannot be read back without the chip; keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def compile_for(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; returns the compiled text
    (raises what the chip's compiler would raise)."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("shape", [(784, 100), (4096, 4096), (9216, 4096)],
                         ids=["mnist", "alexnet_fc7", "alexnet_fc6"])
def test_fused_sgd_update_compiles(one_chip, shape):
    def update(p, v, g):
        return PK.fused_sgd_update(p, v, g, jnp.int32(128), 0.01, 0.9,
                                   0.0005, 0.0, interpret=False)
    text = compile_for(one_chip, update, *[(shape, F32)] * 3)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [(128, 55, 55, 96), (128, 27, 27, 256)],
                         ids=["alexnet_lrn1", "alexnet_lrn2"])
def test_lrn_forward_and_vjp_compile(one_chip, shape):
    def lrn(a):
        return PK.lrn_forward(a, 1e-4, 0.75, 5, 2.0, False)

    def backward(x, dy):
        return jax.vjp(lrn, x)[1](dy)[0]
    assert "tpu_custom_call" in compile_for(one_chip, lrn, (shape, F32))
    text = compile_for(one_chip, backward, (shape, F32), (shape, F32))
    assert text.count("tpu_custom_call") == 2      # forward + backward


def test_dropout_compiles(one_chip):
    text = compile_for(
        one_chip, lambda x: PK.dropout(x, 7, 0.5, interpret=False),
        ((128, 4096), F32))
    assert "tpu_custom_call" in text


def paged_shapes(heads, kv, page, dtype, chunk, slots=8, dh=128,
                 max_len=2048, window=None):
    """The serving kernels' operands, the pool packed as an engine with
    the kernels active packs it (``pool_pack``: two heads of 64 to a
    row)."""
    m = max_len // page
    r = PK.pool_pack(kv, dh)
    pool = ((slots * m + 1, kv // r, page, r * dh), dtype)
    return dict(q=((slots, heads, chunk, dh), dtype),
                new=((slots, kv, chunk, dh), dtype), pool=pool,
                ptab=((slots, m), I32), pos=((slots,), I32), window=window)


# (heads, kv heads, page, dtype[, what else ``paged_shapes`` takes]); the
# last three are the benchmark cells' own shapes (ISSUE 43): OPT-1.3B's 32
# heads of 64 packed by two over 8 lanes and a table of 40,
# ``trinity-large-ep8``'s 48 on 8 of 128 behind its window over 32 lanes,
# ``qwen3-next-80b-a3b-ep4``'s 16 on 2 of 256 over 64 lanes and 17 pages
PAGED = [(16, 16, 16, F32), (16, 16, 32, F32), (16, 16, 128, F32),
         (16, 16, 16, BF16), (16, 16, 32, BF16), (32, 4, 16, F32),
         (32, 32, 32, F32, dict(dh=64, max_len=1280)),
         (48, 8, 256, BF16, dict(slots=32, max_len=8192, window=4096)),
         (16, 2, 1024, BF16, dict(slots=64, dh=256, max_len=17408))]
PAGED_IDS = ["mha_p16_f32", "mha_p32_f32", "mha_p128_f32", "mha_p16_bf16",
             "mha_p32_bf16", "gqa32x4_p16_f32", "chat", "longmix",
             "longchat"]


@pytest.mark.parametrize("case", PAGED, ids=PAGED_IDS)
def test_paged_flash_decode_compiles(one_chip, case):
    s = paged_shapes(*case[:4], chunk=1, **dict(*case[4:]))
    text = compile_for(
        one_chip,
        lambda q, k, v, pt, ps: PK.paged_flash_decode(
            q, k, v, pt, ps, window=s["window"], interpret=False),
        s["q"], s["pool"], s["pool"], s["ptab"], s["pos"])
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("case", PAGED, ids=PAGED_IDS)
def test_paged_flash_prefill_compiles(one_chip, case):
    s = paged_shapes(*case[:4], chunk=case[2], **dict(*case[4:], slots=8))
    text = compile_for(
        one_chip,
        lambda q, kn, vn, k, v, pt, ps: PK.paged_flash_prefill(
            q, kn, vn, k, v, pt, ps, window=s["window"], interpret=False),
        s["q"], s["new"], s["new"], s["pool"], s["pool"], s["ptab"],
        s["pos"])
    assert "tpu_custom_call" in text


def test_paged_flash_prefill_compiles_with_a_head_block_axis(one_chip):
    """ISSUE 28: 48 query heads of 128 on 8 KV heads over a 256-token
    chunk are 1536 query rows a KV head; all heads in one grid step ran
    the kernel out of its 16 MiB (28.6 asked), so the grid takes them a
    block at a time."""
    assert PK._heads_per_step(8, 1536, 256) == 1
    assert PK._heads_per_step(16, 64, 32) == 16       # OPT-1.3B: as it was
    pool = ((65, 8, 256, 128), BF16)
    text = compile_for(
        one_chip,
        lambda q, kn, vn, k, v, pt, ps: PK.paged_flash_prefill(
            q, kn, vn, k, v, pt, ps, window=4096, interpret=False),
        ((1, 48, 256, 128), BF16), ((1, 8, 256, 128), BF16),
        ((1, 8, 256, 128), BF16), pool, pool, ((1, 18), I32), ((1,), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
@pytest.mark.parametrize("width,window", [(32, None), (18, 4096)],
                         ids=["full", "window_base"])
def test_kernels_that_skip_dead_pages_compile(one_chip, kernel, width,
                                              window):
    """ISSUE 29: the index maps clamp the page index into the lane's live
    range (scalar arithmetic on the prefetched positions) and the step's
    body sits under ``pl.when``; interpret mode runs grid steps in order
    and cannot show what the chip's pipelined grid makes of that, the
    chip's compiler at least must take it: at ``trinity-large-ep8``'s
    shapes, a full layer's table and a sliding layer's short one that
    begins at a ``base`` (the caller hands the kernel ``pos - base``)."""
    lanes = 32 if kernel == "decode" else 1
    pool = ((lanes * width + 1, 8, 256, 128), BF16)
    table, ints = ((lanes, width), I32), ((lanes,), I32)
    if kernel == "decode":
        def run(q, k, v, pt, ps, base):
            return PK.paged_flash_decode(q, k, v, pt, ps - base,
                                         window=window, interpret=False)
        shapes = [((lanes, 48, 1, 128), BF16), pool, pool]
    else:
        def run(q, kn, vn, k, v, pt, ps, base):
            return PK.paged_flash_prefill(q, kn, vn, k, v, pt, ps - base,
                                          window=window, interpret=False)
        new = ((lanes, 8, 256, 128), BF16)
        shapes = [((lanes, 48, 256, 128), BF16), new, new, pool, pool]
    text = compile_for(one_chip, run, *shapes, table, ints, ints)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_paged_row_write_compiles(one_chip, dtype):
    text = compile_for(
        one_chip,
        lambda pool, new, pid, off: PK.paged_row_write(
            pool, new, pid, off, interpret=False),
        ((65, 8, 256, 128), dtype), ((32, 8, 128), dtype), ((32,), I32),
        ((32,), I32))
    assert "tpu_custom_call" in text


def test_flash_attention_tpu_traces_at_policy_precision(one_chip,
                                                        monkeypatch):
    """The bundled flash-attention kernel names no dot precision; under
    the fp32 policy every dot, forward and backward, must be traced at
    HIGHEST (Mosaic's default is bf16 passes: 1e-2 off on the chip), and
    the chip's compiler must take them."""
    from veles_tpu.ops import attention as A
    monkeypatch.setattr(PK, "on_tpu", lambda: True)

    def grads(q, k, v):
        return jax.grad(lambda *a: A.flash_attention_tpu(*a).sum(),
                        (0, 1, 2))(q, k, v)
    shape = ((2, 4, 256, 64), F32)
    jaxpr = str(jax.make_jaxpr(grads)(
        *[jax.ShapeDtypeStruct(*shape)] * 3))
    assert jaxpr.count("dot_general") == jaxpr.count(
        "precision=(Precision.HIGHEST, Precision.HIGHEST)") == 9
    text = compile_for(one_chip, grads, shape, shape, shape)
    assert text.count("tpu_custom_call") == 3      # forward, dkv, dq


def described(engine, one_chip):
    """(engine, its params' and its pools' shapes on the DESCRIBED chip,
    a maker of int32 shapes there): what ``program_text`` lowers with."""
    import numpy
    shapes = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    return (engine, shapes(engine.params), shapes(engine._kv_pools),
            lambda *shape: jax.ShapeDtypeStruct(shape, numpy.int32,
                                                sharding=one_chip))


@pytest.fixture(scope="module")
def kernel_engine(one_chip):
    """A tiny ``LMEngine`` with the Pallas serving kernels active and
    the shapes of its arguments on the DESCRIBED chip: the head size is
    64, under the chip's 128 lanes, where the chip's own layout for a
    pool of such rows is not the kernels' (the geometry of the
    benchmark's OPT-1.3B)."""
    from veles_tpu import prng
    from veles_tpu.ops.transformer import init_transformer_params
    from veles_tpu.serving import LMEngine
    params = init_transformer_params(
        prng.get("init"), 256, d_model=128, n_heads=2, n_layers=2,
        max_len=256)
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setattr(PK, "on_tpu", lambda: True)
    try:
        engine = LMEngine(params, n_heads=2, max_len=256, slots=4,
                          prefill_chunk=32, paged_kv=16,
                          attn_kernel="auto", name="aot_tiny")
        assert engine._kernel_active
        yield described(engine, one_chip)
    finally:
        monkeypatch.undo()


PROGRAMS = ["chunk", "decode_w1", "decode_w8"]
_TEXTS = {}


def program_text(fixture, program):
    """The compiled text of one of ``PROGRAMS`` of a fixture's engine for
    the described chip, compiled once a module: the chunk program, or the
    decode program at a page table 1 or 8 wide."""
    engine, params, pools, ints = fixture
    if (engine.name, program) in _TEXTS:
        return _TEXTS[engine.name, program]
    wide = engine._wt.width if engine._wt is not None else None

    def tables(*lead):
        """``LMEngine._table_args`` at width ``lead[-1]``."""
        if engine._state_shapes is not None:
            # linear layers beside one table: the chunk's lane's slot, or
            # the lanes that decode
            return ints(*lead), (ints() if len(lead) == 1 else
                                 jax.ShapeDtypeStruct(
                                     lead[:-1], jnp.bool_,
                                     sharding=ints().sharding))
        if wide is None:
            return ints(*lead)
        return ({"full": ints(*lead),
                 "sliding": ints(*lead[:-1], min(lead[-1], wide))},
                ints(*lead[:-1]))

    # the lanes' state between dispatches: their last tokens, or (last,
    # draft, position) where the model's own module drafts (ISSUE 40)
    state = (ints(engine.slots),) * 3 if engine._mtp else ints(engine.slots)
    if program == "chunk":
        lowered = engine._chunk_jit.lower(
            params, pools, tables(engine._max_pages),
            ints(engine.prefill_chunk + int(engine._mtp)), ints(), ints(),
            ints(), state)
    else:
        width = int(program.rsplit("w", 1)[1])
        lowered = engine._step_jit.lower(
            params, pools, tables(engine.slots, width), state,
            *(() if engine._mtp else (ints(engine.slots),)),
            jax.ShapeDtypeStruct(
                (engine.slots,), jnp.bool_, sharding=ints().sharding))
    text = _TEXTS[engine.name, program] = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("program", PROGRAMS)
def test_engine_programs_update_the_pool_in_place(kernel_engine, program):
    """ISSUE 27: compiled for the chip, the decode and the chunk program
    hold no copy with a pool's shape — not of the arguments (they are
    donated), not around the row writes (update slices, not a scatter),
    not into or out of the kernel (a pool row packs two heads of 64 into
    the chip's 128 lanes, so the pool lies the way the kernel reads it)
    — and list every pool leaf under ``input_output_alias``."""
    from veles_tpu.serving.lm_engine import compiled_storage_report
    engine = kernel_engine[0]
    text = program_text(kernel_engine, program)
    leaves = jax.tree.leaves(engine._kv_pools)
    copies, aliased = compiled_storage_report(text, leaves[0])
    assert copies == 0, "%d whole-pool copies in %s" % (copies, program)
    assert aliased == len(leaves) == 4


@pytest.fixture(scope="module")
def kinds_engine(one_chip):
    """A tiny ``LMEngine`` for the sandwich block with two kinds of layer
    (and so two kinds of pool), the Pallas serving kernels active, 16
    lanes (the one-call row write) of heads of 128 in bfloat16."""
    from benchmark.reference import afmoe
    from veles_tpu import model_config
    from veles_tpu.serving import LMEngine
    cfg = {
        "model_type": "afmoe", "hidden_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
        "intermediate_size": 512, "moe_intermediate_size": 256,
        "vocab_size": 512, "num_hidden_layers": 3, "num_dense_layers": 1,
        "layer_types": ["sliding_attention", "sliding_attention",
                        "full_attention"],
        "num_experts": 4, "router_width": 16, "held_experts": [0, 4],
        "num_experts_per_tok": 4, "sliding_window": 64, "rope_theta": 10000,
        "rms_norm_eps": 1e-5, "route_scale": 2.448, "route_norm": True,
        "score_func": "sigmoid", "num_shared_experts": 1,
        "initializer_std": 0.02, "max_position_embeddings": 256}
    params = afmoe.make_weights(1, cfg)
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setattr(PK, "on_tpu", lambda: True)
    try:
        engine = LMEngine(params, model_config.from_published(cfg),
                          max_len=256, slots=16, prefill_chunk=32,
                          paged_kv=96, attn_kernel="auto", name="aot_kinds")
        assert engine._kernel_active and engine._wt is not None
        yield described(engine, one_chip)
    finally:
        monkeypatch.undo()


@pytest.mark.parametrize("program", PROGRAMS)
def test_programs_of_two_kinds_update_both_pools_in_place(kinds_engine,
                                                          program):
    """ISSUE 28: the check of ISSUE 27 for the new block: compiled for
    the chip, the chunk and the decode program of a stack with two kinds
    of layer hold no copy with either pool's shape and list every leaf of
    both kinds under ``input_output_alias``."""
    from veles_tpu.serving.lm_engine import compiled_storage_report
    engine = kinds_engine[0]
    text = program_text(kinds_engine, program)
    leaves = jax.tree.leaves(engine._kv_pools)
    kinds = {leaf.shape: leaf for leaf in leaves}
    assert len(kinds) == 2
    for leaf in kinds.values():
        copies, aliased = compiled_storage_report(text, leaf)
        assert copies == 0, "%d whole-pool copies in %s" % (copies, program)
    assert aliased == len(leaves) == 6


#: what the chip's compiler gives a kernel that asks for no more
KERNEL_VMEM = 16 << 20


def latent_decode_vmem(q, pool, table):
    """Bytes of fast memory ``paged_latent_decode`` asks for at these
    shapes, read off its traced call: the scratch it names (three slots of
    a block, the float32 accumulators), its blocked operands twice (the
    pipeline double-buffers the queries and the outputs; the pool stays
    where it lies and costs nothing) and one block's float32 scores with
    their exponentials.  The call must ask for no limit of its own: the
    compiler then holds it to ``KERNEL_VMEM``."""
    import math
    jaxpr = jax.make_jaxpr(lambda q, k, pt, ps: PK.paged_latent_decode(
        q, k, pt, ps, 0.1447, interpret=False))(
            *(jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in
              (q, pool, table, (table[0][:1], I32))))
    call, = (e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call")
    assert not call.params["compiler_params"]
    total = 0
    for ref in (v.aval for v in call.params["jaxpr"].invars):
        space = str(getattr(ref, "memory_space", None) or "blocked")
        if space in ("blocked", "vmem"):
            total += (math.prod(ref.shape) * ref.dtype.itemsize
                      * (2 if space == "blocked" else 1))
        else:
            assert space in ("smem", "any", "semaphore_mem"), space
    rows = q[0][1] * q[0][2]
    return total + 2 * rows * PK._latent_block(pool[0][2]) * 4


@pytest.mark.parametrize("page", [1024, 512])
def test_latent_kernels_compile_at_the_cells_widths(one_chip, page):
    """ISSUE 34: the absorbed decode kernel (16 lanes, 32 heads, rows of
    640 lanes, a table over 33,792 positions) and the expanded prefill
    kernel (a chunk of one page, 4 heads a grid step, keys and values
    rebuilt in fast memory) at ``xing4.0-29b-a4b``'s published widths, for
    both page sizes the configuration may take."""
    m = 33792 // page
    pool = ((16 * m + 1, 1, page, 640), BF16)
    text = compile_for(
        one_chip,
        lambda q, k, pt, ps: PK.paged_latent_decode(
            q, k, pt, ps, 0.1447, interpret=False),
        ((16, 32, 1, 640), BF16), pool, ((16, m), I32), ((16,), I32))
    assert "tpu_custom_call" in text
    assert latent_decode_vmem(((16, 32, 1, 640), BF16), pool,
                              ((16, m), I32)) < KERNEL_VMEM // 2
    w = ((32, 512, 128), BF16)
    text = compile_for(
        one_chip,
        lambda qn, qr, wk, wv, k, pt, ps: PK.paged_latent_prefill(
            qn, qr, wk, wv, k, pt, ps, 0.1447, 512, interpret=False),
        ((1, 32, page, 128), BF16), ((1, 32, page, 64), BF16), w, w, pool,
        ((1, m), I32), ((1,), I32))
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def latent_engine(one_chip):
    """A small ``LMEngine`` for the latent kind (ONE pool a layer), the
    Pallas serving kernels active, 16 lanes (the one-call row write), the
    published head sizes and ranks in bfloat16 under a 4-stream residual.
    (No weight's leading size is the chunk's 256 rows: the census of
    weight-shaped copies goes by shape, and an activation of chunk x
    width must not pass for one.)"""
    from benchmark.reference import xing4
    from veles_tpu import model_config
    from veles_tpu.serving import LMEngine
    cfg = {
        "model_type": "xing4_0", "hidden_size": 384,
        "num_attention_heads": 4, "q_lora_rank": 128, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "intermediate_size": 512, "moe_intermediate_size": 128,
        "vocab_size": 512, "num_hidden_layers": 2,
        "first_k_dense_replace": 1, "n_routed_experts": 4,
        "num_experts_per_tok": 2, "n_shared_experts": 1,
        "routed_scaling_factor": 2, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "rope_theta": 10000,
        "rms_norm_eps": 1e-6, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30,
        "rope_scaling": {"type": "yarn", "factor": 64, "beta_fast": 32,
                         "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096},
        "initializer_std": 0.02}
    params = xing4.make_weights(1, cfg)
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setattr(PK, "on_tpu", lambda: True)
    try:
        engine = LMEngine(params, model_config.from_published(cfg),
                          max_len=2048, slots=16, prefill_chunk=256,
                          paged_kv=128, attn_kernel="auto",
                          name="aot_latent")
        assert engine._kernel_active and engine.cfg.latent.row == 640
        yield described(engine, one_chip)
    finally:
        monkeypatch.undo()


@pytest.mark.parametrize("program", PROGRAMS)
def test_latent_programs_update_the_one_pool_in_place(latent_engine,
                                                      program):
    """ISSUE 34: the check of ISSUE 27 for the latent kind: compiled for
    the chip, the chunk program (the chunk's page written with one update
    slice, then the expanded kernel) and the decode program (the one-call
    row write, then the absorbed kernel) hold no copy with the pool's
    shape and list the ONE pool of every layer under
    ``input_output_alias``."""
    from veles_tpu.serving.lm_engine import compiled_storage_report
    engine = latent_engine[0]
    text = program_text(latent_engine, program)
    leaves = jax.tree.leaves(engine._kv_pools)
    assert len(leaves) == 2 and leaves[0].shape == (129, 1, 256, 640)
    copies, aliased = compiled_storage_report(text, leaves[0])
    assert copies == 0, "%d whole-pool copies in %s" % (copies, program)
    assert aliased == len(leaves)


def test_delta_rule_kernels_compile_at_the_cells_widths(one_chip):
    """ISSUE 36: the recurrent step over 64 lanes' states (32 heads of 128
    x 128 float32, 2 MiB a lane in fast memory twice each way) and the
    chunked rule's sequential pass over the 16 inner chunks of a 1024-row
    chunk, at the published head sizes."""
    lanes, h, d = 64, 32, 128
    state = ((lanes, h, d, d), F32)
    vec, one = ((lanes, h, d), F32), ((lanes, h), F32)
    text = compile_for(
        one_chip,
        lambda s, q, k, v, b, g, a: PK.gdn_decode(s, q, k, v, b, g, a,
                                                  interpret=False),
        state, vec, vec, vec, one, one, ((lanes,), jnp.bool_))
    assert "tpu_custom_call" in text
    rows = lambda *tail: ((1, h, 16) + tail, F32)  # noqa: E731
    text = compile_for(
        one_chip,
        lambda s, sl, fr, *terms: PK.gdn_chunk(s, sl, fr, *terms,
                                               interpret=False),
        state, ((1,), I32), ((1,), jnp.bool_), rows(64, d), rows(64, d),
        rows(64, d), rows(64, 64), rows(d, 64), rows())
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page", [512, 1024, 2048])
def test_flash_kernels_compile_at_a_head_of_256(one_chip, page):
    """ISSUE 36: 16 query heads of 256 on 2 KV heads: a chunk's 8 x page
    query rows a KV head overrun the prefill kernel's memory at any page
    worth serving, so it takes them in blocks (``_query_rows``: a whole
    number of blocks, scores under the limit); the decode kernel at 64
    lanes."""
    slots, m = 64, 17408 // page
    pool = ((slots * m + 1, 2, page, 256), BF16)
    text = compile_for(
        one_chip,
        lambda q, kn, vn, k, v, pt, ps: PK.paged_flash_prefill(
            q, kn, vn, k, v, pt, ps, interpret=False),
        ((1, 16, page, 256), BF16), ((1, 2, page, 256), BF16),
        ((1, 2, page, 256), BF16), pool, pool, ((1, m), I32), ((1,), I32))
    assert "tpu_custom_call" in text
    qr = PK._query_rows(8 * page, page)
    assert (8 * page) % qr == 0 and qr * page * 4 <= PK._Q_BLOCK_BYTES
    text = compile_for(
        one_chip,
        lambda q, k, v, pt, ps: PK.paged_flash_decode(
            q, k, v, pt, ps, interpret=False),
        ((slots, 16, 1, 256), BF16), pool, pool, ((slots, m), I32),
        ((slots,), I32))
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def linear_engine(one_chip):
    """A small ``LMEngine`` for a stack of linear and full layers, the
    Pallas serving kernels active, 64 lanes (10 of 64 routed: 640
    assignment rows a decode step), the published head sizes (128 x 128
    states; full heads of 256) in bfloat16.  (No weight's leading size is
    the chunk's 256 rows or the 64 lanes.)"""
    from benchmark.reference import qwen3_next
    from veles_tpu import model_config
    from veles_tpu.serving import LMEngine
    cfg = {
        "model_type": "qwen3_next", "hidden_size": 384,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 256,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "rope_scaling": None, "rms_norm_eps": 1e-6,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "linear_conv_kernel_dim": 4, "moe_intermediate_size": 128,
        "shared_expert_intermediate_size": 128, "num_experts": 16,
        "router_width": 64, "held_experts": [0, 16],
        "num_experts_per_tok": 10, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [], "vocab_size": 512,
        "num_hidden_layers": 4, "full_attention_interval": 4,
        "initializer_std": 0.02}
    params = qwen3_next.make_weights(1, cfg)
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setattr(PK, "on_tpu", lambda: True)
    try:
        engine = LMEngine(params, model_config.from_published(cfg),
                          max_len=2048, slots=64, prefill_chunk=256,
                          paged_kv=128, attn_kernel="auto",
                          name="aot_linear")
        assert engine._kernel_active
        yield described(engine, one_chip)
    finally:
        monkeypatch.undo()


@pytest.mark.parametrize("program", PROGRAMS)
def test_linear_programs_update_state_and_pools_in_place(linear_engine,
                                                         program):
    """ISSUE 36: the check of ISSUE 27 for two kinds of cache in one
    manager: compiled for the chip, the chunk program (the chunked rule on
    one lane's slot, the prefill kernel) and the decode program (the
    recurrent rule on the decoding lanes' slots, the row write and the
    decode kernel) hold no copy with the shape of a state, of a
    convolution tail or of a pool, and list every leaf of all three under
    ``input_output_alias``."""
    from veles_tpu.serving.lm_engine import compiled_storage_report
    engine = linear_engine[0]
    text = program_text(linear_engine, program)
    leaves = jax.tree.leaves(engine._kv_pools)
    kinds = {leaf.shape: leaf for leaf in leaves}
    assert set(kinds) == {(64, 4, 128, 128), (64, 3, 1024),
                          (129, 2, 256, 256)}
    for leaf in kinds.values():
        copies, aliased = compiled_storage_report(text, leaf)
        assert copies == 0, "%d copies of %s in %s" % (copies, leaf.shape,
                                                       program)
    assert aliased == len(leaves) == 8


@pytest.mark.parametrize("program", PROGRAMS)
def test_a_decode_step_of_640_rows_takes_the_row_kernel(linear_engine,
                                                        program):
    """ISSUE 36: 64 lanes x 10 experts a token are 640 assignment rows, at
    or above ``ROW_KERNEL_MIN``: the first configuration whose DECODE step
    runs the row-tiled grouped matmul, three calls an expert layer, as its
    chunk does; no ``ragged-dot`` in either."""
    from veles_tpu.ops import moe
    from veles_tpu.serving.lm_engine import compiled_grouped_matmuls
    engine = linear_engine[0]
    assert engine.slots * engine.cfg.moe.top_k >= moe.ROW_KERNEL_MIN
    text = program_text(linear_engine, program)
    assert compiled_grouped_matmuls(text) == (0, 3 * 4)


def test_kda_kernels_compile_at_the_cells_widths(one_chip):
    """ISSUE 42: the delta rule's two kernels with a decay a KEY CHANNEL
    (the decay a column beside q and k in the recurrent step; a row turned
    into a column in the sequential pass), at 64 lanes and 32 heads of 128
    x 128; and the chunked rule's terms over a 1024-row chunk (the pairwise
    decays about reference rows)."""
    from veles_tpu.ops import linear_attn
    lanes, h, d = 64, 32, 128
    state = ((lanes, h, d, d), F32)
    vec, one = ((lanes, h, d), F32), ((lanes, h), F32)
    text = compile_for(
        one_chip,
        lambda s, q, k, v, b, g, a: PK.gdn_decode(s, q, k, v, b, g, a,
                                                  interpret=False),
        state, vec, vec, vec, one, vec, ((lanes,), jnp.bool_))
    assert "tpu_custom_call" in text
    rows = lambda *tail: ((1, h, 16) + tail, F32)  # noqa: E731
    text = compile_for(
        one_chip,
        lambda s, sl, fr, *terms: PK.gdn_chunk(s, sl, fr, *terms,
                                               interpret=False),
        state, ((1,), I32), ((1,), jnp.bool_), rows(64, d), rows(64, d),
        rows(64, d), rows(64, 64), rows(d, 64), rows(d))
    assert "tpu_custom_call" in text
    row = lambda *tail: ((1, 1024, h) + tail, F32)  # noqa: E731
    compile_for(one_chip, linear_attn.chunk_terms, row(d), row(d), row(d),
                row(), row(d))


@pytest.fixture(scope="module")
def kda_engine(one_chip):
    """A small ``LMEngine`` for a stack of Kimi-delta-attention layers and
    one latent layer, the Pallas serving kernels active, 64 lanes (8 of 64
    routed in 8 groups: 512 assignment rows a decode step), the published
    head sizes (128 x 128 states; latent rows of 640 lanes) in bfloat16."""
    from benchmark.reference import ling3
    from veles_tpu import model_config
    from veles_tpu.serving import LMEngine
    cfg = {
        "model_type": "ling3_flash", "hidden_size": 384,
        "num_attention_heads": 4, "head_dim": 128, "q_lora_rank": None,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "rope_theta": 6000000,
        "rms_norm_eps": 1e-6, "intermediate_size": 256,
        "moe_intermediate_size": 128,
        "moe_shared_expert_intermediate_size": 128, "num_experts": 16,
        "router_width": 64, "held_experts": [0, 16],
        "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "first_k_dense_replace": 1, "short_conv_kernel_size": 4,
        "kda_lower_bound": -5, "layer_group_size": 3,
        "num_hidden_layers": 3, "vocab_size": 512, "initializer_std": 0.02}
    params = ling3.make_weights(1, cfg)
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setattr(PK, "on_tpu", lambda: True)
    try:
        engine = LMEngine(params, model_config.from_published(cfg),
                          max_len=2048, slots=64, prefill_chunk=256,
                          paged_kv=128, attn_kernel="auto", name="aot_kda")
        assert engine._kernel_active
        yield described(engine, one_chip)
    finally:
        monkeypatch.undo()


@pytest.mark.parametrize("program", ["chunk", "decode_w8"])
def test_kda_programs_update_state_and_pool_in_place(kda_engine, program):
    """ISSUE 42: two kinds of cache in one lane, one of them latent rows:
    compiled for the chip, neither program copies a state, a convolution
    tail or the pool, every leaf is listed under ``input_output_alias``,
    and the expert layers' grouped matmuls are the row kernel's (512
    assignment rows a decode step)."""
    from veles_tpu.serving.lm_engine import (compiled_grouped_matmuls,
                                             compiled_storage_report)
    engine = kda_engine[0]
    text = program_text(kda_engine, program)
    leaves = jax.tree.leaves(engine._kv_pools)
    kinds = {leaf.shape: leaf for leaf in leaves}
    assert set(kinds) == {(64, 4, 128, 128), (64, 3, 1536),
                          (129, 1, 256, 640)}
    for leaf in kinds.values():
        copies, aliased = compiled_storage_report(text, leaf)
        assert copies == 0, "%d copies of %s in %s" % (copies, leaf.shape,
                                                       program)
    assert aliased == len(leaves) == 5
    assert compiled_grouped_matmuls(text) == (0, 3 * 2)


def test_ssd_kernels_compile_at_the_cells_widths(one_chip):
    """ISSUE 46: the state-space rule's two kernels at 64 lanes and 64
    heads of 128 x 64 in ONE group: the recurrent step without the delta
    correction on the packed state (32 rows of two heads, 128 x 128, C and B
    one a group), and the chunk kernel over a 1024-row chunk in inner chunks
    of 128."""
    from veles_tpu.ops import linear_attn
    lanes, h, dk, dv = 64, 64, 128, 64
    state = ((lanes, h // 2, dk, 2 * dv), F32)
    text = compile_for(
        one_chip,
        lambda s, q, k, v, b, g, a: PK.gdn_decode(
            s, q, k, v, b, g, a, correct=False, interpret=False),
        state, ((lanes, 1, dk), F32), ((lanes, 1, dk), F32),
        ((lanes, h, dv), F32), ((lanes, h), F32), ((lanes, h), F32),
        ((lanes,), jnp.bool_))
    assert "tpu_custom_call" in text
    row = lambda *tail: ((1, 1024) + tail, F32)  # noqa: E731
    text = compile_for(
        one_chip,
        lambda s, sl, fr, *rows: PK.ssd_chunk(
            s, sl, fr, *rows, chunk=linear_attn.SSD_CHUNK, interpret=False),
        state, ((1,), I32), ((1,), jnp.bool_), row(1, dk), row(1, dk),
        row(h, dv), row(h), row(h))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [64, 1024], ids=["step", "chunk"])
def test_gated_norm_kernel_compiles_at_the_cells_widths(one_chip, rows):
    """ISSUE 47: the state-space layer's gated norm over 4096 channels, the
    heads' inputs read out of the convolution's 4352: a decode step's 64
    rows (one block) and a chunk's 1024 (blocks of ``_NORM_ROWS``, both
    buffers of every operand under the kernel's own limit)."""
    text = compile_for(
        one_chip,
        lambda o, x, z, d, w: PK.gated_rms_norm(o, x, z, d, w, 1e-5,
                                                interpret=False),
        ((rows, 4096), F32), ((rows, 4352), F32), ((rows, 4096), BF16),
        ((1, 4096), F32), ((1, 4096), F32))
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def ssd_engine(one_chip):
    """A small ``LMEngine`` for two state-space layers around one plain
    attention layer, the Pallas serving kernels active, 64 lanes, the
    published head sizes (one group, heads of 128 x 64 packed two a row;
    attention heads of 64) in bfloat16."""
    from benchmark.reference import granite_hybrid
    from veles_tpu import model_config
    from veles_tpu.serving import LMEngine
    cfg = {
        "model_type": "granitemoehybrid", "hidden_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 512, "shared_intermediate_size": 512,
        "mamba_n_heads": 8, "mamba_d_head": 64, "mamba_n_groups": 1,
        "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "embedding_multiplier": 12, "attention_multiplier": 0.015625,
        "residual_multiplier": 0.22, "logits_scaling": 8,
        "position_embedding_type": "nope", "tie_word_embeddings": True,
        "num_local_experts": 0, "num_experts_per_tok": 0,
        "rms_norm_eps": 1e-5, "num_hidden_layers": 3,
        "layer_types": ["mamba", "attention", "mamba"], "vocab_size": 512,
        "initializer_std": 0.02}
    params = granite_hybrid.make_weights(1, cfg)
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setattr(PK, "on_tpu", lambda: True)
    try:
        engine = LMEngine(params, model_config.from_published(cfg),
                          max_len=2048, slots=64, prefill_chunk=256,
                          paged_kv=128, attn_kernel="auto", name="aot_ssd")
        assert engine._kernel_active
        yield described(engine, one_chip)
    finally:
        monkeypatch.undo()


@pytest.mark.parametrize("program", ["chunk", "decode_w8"])
def test_ssd_programs_update_state_and_pool_in_place(ssd_engine, program):
    """ISSUE 46: compiled for the chip, neither program copies a packed
    state, a convolution tail or a pool, every leaf is listed under
    ``input_output_alias``, and each state-space layer's kernel stands in
    the program under the scope the benchmark's readers find it by
    (``ssd.chunk`` in the chunk program, ``ssd.decode`` in the step)."""
    import re
    from veles_tpu.serving.lm_engine import compiled_storage_report
    engine = ssd_engine[0]
    text = program_text(ssd_engine, program)
    leaves = jax.tree.leaves(engine._kv_pools)
    kinds = {leaf.shape: leaf for leaf in leaves}
    assert set(kinds) == {(64, 4, 128, 128), (64, 3, 768),
                          (129, 1, 256, 128)}
    for leaf in kinds.values():
        copies, aliased = compiled_storage_report(text, leaf)
        assert copies == 0, "%d copies of %s in %s" % (copies, leaf.shape,
                                                       program)
    assert aliased == len(leaves) == 6
    scope = "ssd.chunk" if program == "chunk" else "ssd.decode"
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*'
                       r'op_name="[^"]*attn\.linear/%s/pallas_call'
                       % re.escape(scope), text)
    assert len(calls) == 2


@pytest.mark.parametrize("program", ["chunk", "decode_w8"])
def test_ssd_programs_call_one_rule_kernel_and_one_norm_a_layer(ssd_engine,
                                                                program):
    """ISSUE 47: the benchmark's two rooflines count the operations named
    ``ssd ...`` as ONE call a Mamba layer and dispatch, so the gated norm's
    kernel runs under a scope of its own: a program holds one custom call
    under ``ssd.chunk`` (the step: ``ssd.decode``) and one under
    ``norm.gated`` for each of the two Mamba layers, and no other whose
    innermost scope starts with ``ssd.``."""
    from veles_tpu.serving.lm_engine import compiled_kernel_scopes
    scopes = compiled_kernel_scopes(program_text(ssd_engine, program))
    rule = "ssd.chunk" if program == "chunk" else "ssd.decode"
    assert sorted(s for s in scopes if s.startswith(("ssd.", "norm."))) \
        == ["norm.gated"] * 2 + [rule] * 2


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("fixture", ["kernel_engine", "kinds_engine",
                                     "latent_engine", "linear_engine",
                                     "mtp_engine", "kda_engine",
                                     "ssd_engine"])
def test_engine_programs_read_the_weights_where_they_lie(request, fixture,
                                                         program):
    """ISSUE 31: compiled for the chip, no engine program holds a copy
    with the shape of a weight matrix: the head split and the pool's head
    packing are applied to a projection's small output, never folded into
    ``wq``, ``wk``, ``wv`` (three weight-sized transpositions a layer and
    dispatch before ``ops/attention.py::_qkv_cached`` barred the fold)."""
    from veles_tpu.serving.lm_engine import compiled_param_copies
    fixture = request.getfixturevalue(fixture)
    copies = compiled_param_copies(program_text(fixture, program),
                                   fixture[0].params)
    assert copies == 0, "%d weight-shaped copies in %s" % (copies, program)


@pytest.fixture(scope="module")
def mtp_engine(one_chip):
    """A small ``LMEngine`` for a latent stack that DRAFTS WITH ITS OWN
    MODULE (ISSUE 40; ``joyai_llm_flash``): the published head sizes and
    ranks in bfloat16, a dense and an expert layer and the module's layer
    behind them (three pools), 16 lanes, 4 held of 8 routed experts, top-4:
    a verify step carries 16 x 2 x 4 = 128 assignment rows."""
    from benchmark.reference import joyai
    from veles_tpu import model_config
    from veles_tpu.serving import LMEngine
    cfg = {
        "model_type": "joyai_llm_flash", "hidden_size": 384,
        "num_attention_heads": 4, "q_lora_rank": 128, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "intermediate_size": 512, "moe_intermediate_size": 128,
        "vocab_size": 512, "num_hidden_layers": 2,
        "first_k_dense_replace": 1, "n_routed_experts": 4,
        "router_width": 8, "held_experts": [0, 4],
        "num_experts_per_tok": 4, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "rope_theta": 32000000,
        "rope_scaling": None, "rms_norm_eps": 1e-6,
        "num_nextn_predict_layers": 1, "initializer_std": 0.02}
    params = joyai.make_weights(1, cfg)
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setattr(PK, "on_tpu", lambda: True)
    try:
        engine = LMEngine(params, model_config.from_published(cfg),
                          max_len=2048, slots=16, prefill_chunk=256,
                          paged_kv=128, attn_kernel="auto", spec_k=1,
                          name="aot_mtp")
        assert engine._kernel_active and engine._mtp
        yield described(engine, one_chip)
    finally:
        monkeypatch.undo()


@pytest.mark.parametrize("program", PROGRAMS)
def test_programs_that_draft_update_every_pool_in_place(mtp_engine, program):
    """ISSUE 40: the chunk program with the module's rows of the prompt
    behind the stack's, and the verify-and-draft step (two rows a lane: a
    row-kernel call a row, then the absorbed kernel at two query rows a
    head; acceptance and the next draft in the graph) compile for the chip,
    hold no copy with the pool's shape and list the stack's pools AND the
    module's under ``input_output_alias``."""
    from veles_tpu.serving.lm_engine import (compiled_grouped_matmuls,
                                             compiled_storage_report)
    engine = mtp_engine[0]
    text = program_text(mtp_engine, program)
    leaves = jax.tree.leaves(engine._kv_pools)
    assert len(leaves) == 3 and leaves[0].shape == (129, 1, 256, 640)
    copies, aliased = compiled_storage_report(text, leaves[0])
    assert copies == 0, "%d whole-pool copies in %s" % (copies, program)
    assert aliased == len(leaves)
    # the module's attention's kernels are named by its own scope, so that
    # the trace can tell where the module's part of a program begins
    assert "%mtp.draft" in text and "%attn.latent" in text
    # two expert layers (the stack's one and the module's): the chunk's 1024
    # assignment rows take the row kernel, a step's 128 the compiler's op
    assert compiled_grouped_matmuls(text) \
        == ((0, 6) if program == "chunk" else (6, 0))


@pytest.mark.parametrize("rows", [1, 2], ids=["decode", "verify"])
def test_latent_decode_kernel_compiles_at_the_verify_cells_width(one_chip,
                                                                 rows):
    """The absorbed kernel at ``joyai-llm-flash-ep8.reason``'s shapes: 32
    lanes, 32 heads, pages of 1024 over a table of 9, one query row a head
    (a plain step) and two (a verify step)."""
    text = compile_for(
        one_chip,
        lambda q, k, pt, ps: PK.paged_latent_decode(
            q, k, pt, ps, 192 ** -0.5, interpret=False),
        ((32, 32, rows, 640), BF16), ((289, 1, 1024, 640), BF16),
        ((32, 9), I32), ((32,), I32))
    assert "tpu_custom_call" in text
    assert latent_decode_vmem(((32, 32, rows, 640), BF16),
                              ((289, 1, 1024, 640), BF16),
                              ((32, 9), I32)) < KERNEL_VMEM // 2


def test_latent_prefill_kernel_compiles_inside_its_limit(one_chip):
    """ISSUE 44: the expanded kernel at the one shape the three latent
    cells give it (1 lane x 32 heads x a chunk of 1024 rows in bfloat16, a
    table of 33 and of 9 pages) compiles for the chip under the limit it
    asks for itself, which is under half the chip's fast memory: units of
    the whole chunk, two heads' units a loop body (float32 scores of 1024
    x 1024 twice, their exponentials and casts beside 14 MB of blocked
    operands and accumulators)."""
    assert PK._LATENT_VMEM <= 64 << 20
    shapes = lambda m: (  # noqa: E731
        ((1, 32, 1024, 128), BF16), ((1, 32, 1024, 64), BF16),
        ((32, 512, 128), BF16), ((32, 512, 128), BF16),
        ((16 * m + 1, 1, 1024, 640), BF16), ((1, m), I32), ((1,), I32))
    run = lambda qn, qr, wk, wv, k, pt, ps: PK.paged_latent_prefill(  # noqa: E731
        qn, qr, wk, wv, k, pt, ps, 0.1447, 512, interpret=False)
    for m in (33, 9):
        text = compile_for(one_chip, run, *shapes(m))
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert "bf16[1,32,1024,128]" in text
    jaxpr = jax.make_jaxpr(run)(*(jax.ShapeDtypeStruct(shape, dtype)
                                  for shape, dtype in shapes(33)))
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert calls[0].params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes \
        == PK._LATENT_VMEM


@pytest.mark.parametrize("fixture,latent_layers", [
    ("latent_engine", 2), ("mtp_engine", 2), ("kda_engine", 1)])
def test_a_chunk_program_calls_the_prefill_kernel_once_a_latent_layer(
        request, fixture, latent_layers):
    """ISSUE 44: compiled for the chip, each latent configuration's chunk
    program holds exactly one Pallas call under ``attn.latent`` a latent
    layer whose result is ``[1, heads, chunk, v]``: the expanded prefill
    kernel, by the name and shape ``benchmark/lib/latent.py::
    is_prefill_kernel`` finds it by (``[1,32,1024,128]`` in the cells).
    The kernel's call is kept by its sizes and shared by the layers; each
    layer's call still stands in the program under its own place's name."""
    import re
    engine = request.getfixturevalue(fixture)[0]
    lat = engine.cfg.latent
    text = program_text(request.getfixturevalue(fixture), "chunk")
    shape = "bf16[1,%d,%d,%d]" % (engine.cfg.n_heads, engine.prefill_chunk,
                                  lat.v)
    calls = re.findall(
        r"= %s\S* custom-call\([^\n]*"
        r'custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="[^"]*attn\.latent/pallas_call' % re.escape(shape), text)
    assert len(calls) == latent_layers


@pytest.mark.parametrize("rows,k,n,groups", [
    (4096, 3584, 1024, 64), (4096, 1024, 3584, 64), (1024, 3072, 3072, 32),
    (1000, 3584, 1024, 64)],
    ids=["longdoc_gate_up", "longdoc_down", "longmix", "rows_off_a_tile"])
def test_grouped_matmul_compiles_at_the_cells_chunk_shapes(one_chip, rows,
                                                           k, n, groups):
    """ISSUE 35: the row-tiled grouped matmul at the chunk shapes of
    ``xing4.0-29b-a4b.longdoc`` (1024 tokens x top-4 over 64 experts) and
    ``trinity-large-ep8.longmix`` (256 x top-4 over 32 held experts): a
    group's whole ``k x tn`` block twice in fast memory, under the limit
    the kernel asks for; and at a row count that is no whole number of
    tiles (a whole-sequence forward)."""
    text = compile_for(
        one_chip,
        lambda xs, w, sizes: PK.grouped_matmul(xs, w, sizes,
                                               interpret=False),
        ((rows, k), BF16), ((groups, k, n), BF16), ((groups,), I32))
    assert "tpu_custom_call" in text
    assert k * PK._gmm_columns(k, n, 2) * 2 <= PK._GMM_BLOCK


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("fixture, expert_layers, chunk_rows", [
    ("kinds_engine", 2, 32 * 4), ("latent_engine", 1, 256 * 2)])
def test_chunks_of_many_rows_take_the_row_kernel(request, fixture,
                                                 expert_layers, chunk_rows,
                                                 program):
    """ISSUE 35: how ``ops/moe.py::grouped_matmul`` is carried out is
    chosen from a call's STATIC rows (tokens x top-k), so it is counted
    here: a chunk program whose calls carry ``ROW_KERNEL_MIN`` rows or
    more holds 3 calls of the row-tiled kernel an expert layer and no
    ``ragged-dot``; a chunk under it, and every decode program (16 lanes),
    3 ``ragged-dot`` an expert layer and no kernel call."""
    from veles_tpu.ops import moe
    from veles_tpu.serving.lm_engine import compiled_grouped_matmuls
    text = program_text(request.getfixturevalue(fixture), program)
    kernel = program == "chunk" and chunk_rows >= moe.ROW_KERNEL_MIN
    assert (fixture == "latent_engine") == (chunk_rows >= moe.ROW_KERNEL_MIN)
    want = (0, 3 * expert_layers) if kernel else (3 * expert_layers, 0)
    assert compiled_grouped_matmuls(text) == want
