"""ISSUE 28: the ``sandwich`` block (RMSNorm x4, q/k norm, gated attention,
rotary positions on sliding layers only, gated-SiLU feed forward, sigmoid-
routed experts beside a shared expert of which a chip HOLDS a share) against
the benchmark's plain reference ``benchmark/reference/afmoe.py`` (float32,
no cache, no kernel, imports nothing of veles_tpu), and the engine's two
kinds of KV cache: a page table and an allocator per kind, a sliding layer's
pages released as they leave the window.

Tolerances: the program in float32 and the reference compute the same
sums in another order, so logits agree to float32 roundoff (1e-4 on logits
of magnitude 3; the greedy tokens are then the reference's own, gap 0)."""

import numpy
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import afmoe
from lm_cases import SMALL, record
from veles_tpu import model_config
from veles_tpu.serving.kv_pool import KVPagePool, WindowTables

PAGE = 4


@pytest.fixture(scope="module")
def weights():
    """(the reference's bfloat16-valued tree, the same raised to float32)."""
    w = afmoe.make_weights(3, SMALL)
    return w, jax.tree.map(lambda a: a.astype(jnp.float32), w)


def tokens(n, seed=0):
    return numpy.random.default_rng(seed).integers(0, SMALL["vocab_size"], n)


def test_whole_forward_matches_the_reference(weights):
    from veles_tpu.ops.transformer import transformer_forward
    w, wf = weights
    toks = tokens(40)
    ref = afmoe.logits(w, toks, numpy.arange(40), SMALL)
    got = transformer_forward(wf, jnp.asarray(toks)[None], record())[0]
    numpy.testing.assert_allclose(got, ref, atol=1e-4)


def test_contiguous_decode_picks_the_references_tokens(weights):
    from veles_tpu.ops.transformer import generate
    w, wf = weights
    toks = tokens(20, 1)
    out = numpy.asarray(generate(wf, jnp.asarray(toks)[None], 14, record(),
                                 temperature=0.0, max_len=40)[0])
    ref = afmoe.logits(w, out, numpy.arange(19, 33), SMALL)
    numpy.testing.assert_array_equal(ref.argmax(-1), out[20:])


@pytest.mark.parametrize("kernel", [None, "kernel"])
def test_paged_prefill_then_decode_matches_the_reference(weights, kernel):
    """Prefill chunks, then single steps, through the two kinds of pool
    with the sliding kind's table sliding as ``WindowTables`` says: the
    logits of every decoded position are the reference's over the whole
    sequence.  The context (36) is 4.5 windows, so pages are released
    mid-request, in prefill and in decode."""
    from veles_tpu.ops.transformer import head_logits, paged_chunk_apply
    w, wf = weights
    cfg = record()
    full, sliding = model_config.FULL, model_config.SLIDING
    seq = tokens(36, 2)
    prompt_len, max_pages = 20, 10
    wt = WindowTables(KVPagePool(8, PAGE), 1, SMALL["sliding_window"])
    wt.admit(0, 9)
    shape = {full: (max_pages + 1, 2, PAGE, 16), sliding: (9, 2, PAGE, 16)}
    pools = [(jnp.zeros(shape[cfg.kind(i)]), jnp.zeros(shape[cfg.kind(i)]))
             for i in range(4)]
    table = numpy.arange(1, max_pages + 1, dtype=numpy.int32)

    @jax.jit
    def apply(pools, chunk, tables, pos, base):
        h, new = paged_chunk_apply(
            wf, chunk[None], pools, tables, pos, cfg,
            attn_kernel=({1: "decode", PAGE: "prefill"}[chunk.shape[0]]
                         if kernel else None),
            base={full: None, sliding: base})
        return head_logits(wf, h, cfg)[0], new

    def run(chunk, pos):
        wt.advance(0, pos, pos + len(chunk))
        wt.verify()
        assert wt.count[0] <= wt.width
        # the row as a numpy array of its own: a put may alias host memory
        # until the dispatch has run, and the next ``advance`` shifts the
        # row in place (the engine's next shift comes after its fetch)
        return apply(pools, jnp.asarray(chunk),
                     {full: jnp.asarray(table)[None],
                      sliding: jnp.asarray(wt.tables[:1].copy())},
                     jnp.asarray([pos]), jnp.asarray(wt.base[:1] * PAGE))

    for pos in range(0, prompt_len, PAGE):
        logits, pools = run(seq[pos:pos + PAGE], pos)
    got = [logits[-1]]
    for pos in range(prompt_len, 35):
        logits, pools = run(seq[pos:pos + 1], pos)
        got.append(logits[0])
    assert wt.released > 0
    ref = afmoe.logits(w, seq, numpy.arange(prompt_len - 1, 35), SMALL)
    numpy.testing.assert_allclose(jnp.stack(got), ref, atol=1e-4)


@pytest.mark.parametrize("features", [
    {"slots": 3}, {"slots": 16, "attn_kernel": "force", "prefill_chunk": 8}],
    ids=["xla", "kernels_row_write"])
def test_engine_serves_the_references_tokens(weights, features,
                                             page_step_census):
    """Through ``LMEngine`` (admission, chunked prefill interleaved with
    decode, the live-width ladder, window pages released per lane): every
    served token is the reference's choice, the allocators of both kinds
    come home whole, and no lane ever held more than W/page + 2 pages of
    the sliding kind.  16 lanes on pages of 8 take the kernels' one-call
    row write (it moves whole tiles of 8 float32 rows)."""
    from veles_tpu.serving import LMEngine
    w, wf = weights
    page = features.get("prefill_chunk", PAGE)
    eng = LMEngine(wf, record(), max_len=48, **dict(
        {"paged_kv": 48, "prefill_chunk": PAGE}, **features)).start()
    count = page_step_census(eng)
    held, loads = [], []
    note = eng._note_moe

    def noted(counts):
        loads.append(int(counts[3]))    # ``ops/moe.py::held_part``'s order
        return note(counts)
    eng._note_moe = noted
    if eng._wt is not None:
        real = eng._wt.advance

        def watched(slot, lo, hi):
            out = real(slot, lo, hi)
            held.append(int(eng._wt.count[slot]))
            return out
        eng._wt.advance = watched
    try:
        prompts = [tokens(n, 10 + n) for n in (5, 17, 26, 9)]
        outs = [f.result(timeout=300)
                for f in [eng.submit(p, 22) for p in prompts]]
        for p, o in zip(prompts, outs):
            seq = numpy.concatenate([p, o])
            ref = afmoe.logits(w, seq, numpy.arange(len(p) - 1, len(seq) - 1),
                               SMALL)
            gap = ref.max(-1) - ref[numpy.arange(len(o)), o]
            assert float(gap.max()) <= 1e-4
        assert eng.verify_pool_invariants()["used_pages"] == 0
        assert eng._wt.verify()["held"] == 0
        assert max(held) <= SMALL["sliding_window"] // page + 2
        snap = eng.metrics.snapshot()
        assert snap["counters"]["kv_pages_released_window"] > 0
        assert snap["gauges"]["kv_pages_free.window"] \
            == snap["gauges"]["kv_pages_total.window"]
        assert snap["gauges"]["kv_pages_free.full"] == 48
        assert snap["gauges"]["kv_storage_in_place"] == 1
        assert snap["counters"].get("kv_storage_rebuilds", 0) == 0
        # the step's counts, fetched with its tokens: counters, and
        # the recorder's per-turn columns
        c = snap["counters"]
        steps = c["decode_dispatches"]
        assert c["moe_assignments_held"] + c["moe_assignments_elsewhere"] \
            == steps * eng.slots * 3 * 3      # lanes x top_k x layers
        from veles_tpu.serving import tracing
        turns = eng.recorder.turns()
        assert int(turns[:, tracing.COL_MOE_HIT].sum()) \
            == c["moe_experts_hit"]
        # the largest load of any expert in any step is a gauge alone
        # (ISSUE 38: the turn row holds what a reader reads): the
        # largest of the loads the steps fetched with their tokens.
        # (Idle lanes' rows are routed too, so the reference's routing
        # of the requests alone does not give a step's load.)
        assert len(loads) == steps
        assert snap["gauges"]["moe_max_expert_load"] == max(loads)
        assert 1 <= max(loads) <= eng.slots
        # ISSUE 29: the page steps handed to the kernels and the live
        # ones, over both kinds of table (the sliding kind's relative
        # to its base); nothing is counted without the kernels
        steps = (c.get("attn_page_steps"), c.get("attn_page_steps_live"))
        assert steps == (count() if eng._kernel_active
                         else (None, None))
        assert int(turns[:, tracing.COL_ATTN_STEPS].sum()) \
            == (steps[0] or 0)
        assert int(turns[:, tracing.COL_ATTN_LIVE].sum()) \
            == (steps[1] or 0)
        if eng._kernel_active:
            # ISSUE 43: a decode step is handed its live pages alone,
            # and these pools' pages are so small that a lane's walk
            # is ONE block a layer, whatever it holds
            given, live, blocks = count(per=1 << 20)
            assert given == live
            assert c["attn_walk_blocks"] == blocks \
                == c["decode_dispatches"] * eng.slots \
                * len(eng.params["blocks"])
    finally:
        eng.stop()


def test_dispatches_consume_both_kinds_of_pool(weights):
    """ISSUE 27's rule for the new block: the step program takes the pools
    of BOTH kinds donated (every leaf that went in is consumed, none is
    copied or held twice), and the tokens are what they were."""
    from veles_tpu.serving import LMEngine
    _, wf = weights
    eng = LMEngine(wf, record(), max_len=64, slots=2, paged_kv=24,
                   prefill_chunk=PAGE)
    leaves = lambda: [a for pair in eng._storage() for a in pair]  # noqa
    made = leaves()
    assert len({a.shape for a in made}) == 2       # two kinds of pool
    eng.start()
    try:
        assert all(a.is_deleted() for a in made)
        warm, handed, real = leaves(), [], eng._step_jit

        def watched(p, storage, *args):
            handed.append([a for pair in storage for a in pair])
            return real(p, storage, *args)
        eng._step_jit = watched
        assert len(eng.submit(tokens(11, 5), 9).result(timeout=120)) == 9
        assert handed and all(a.is_deleted() for a in warm)
        assert all(a.is_deleted() for ls in handed for a in ls)
        assert not any(a.is_deleted() for a in leaves())
        assert eng.metrics.counter("kv_storage_rebuilds") == 0
    finally:
        eng.stop()


def test_eight_shares_and_the_shared_expert_make_the_uncut_layer():
    """The share test: a layer that holds ALL 16 experts equals the shared
    expert once plus the routed parts of its 8 shares of 2 (each computed
    by the layer that is told it holds only those), to float32 roundoff."""
    from veles_tpu.ops import moe
    rng = numpy.random.default_rng(4)
    d, f, e = 32, 24, 16
    mk = lambda *s: jnp.asarray(rng.normal(0, 0.2, s), jnp.float32)  # noqa
    p = {"router": mk(d, e), "bias": mk(e) * 0.05, "w_gate": mk(e, d, f),
         "w_up": mk(e, d, f), "w_down": mk(e, f, d),
         "shared": {"w_gate": mk(d, f), "w_up": mk(d, f),
                    "w_down": mk(f, d)}}
    x = mk(2, 9, d)
    base = dict(router_width=e, top_k=4, score="sigmoid", route_norm=True,
                route_scale=2.448)
    mm = lambda a, b: jnp.matmul(a, b, precision="highest")  # noqa: E731
    whole, stats = moe.routed_ffn(
        p, x, model_config.MoEConfig(shared=True, **base), mm)
    assert int(stats[0]) == 18 * 4 and int(stats[1]) == 0
    parts = moe.gated_ffn(p["shared"], x.reshape(-1, d), mm).reshape(x.shape)
    held = 0
    for lo in range(0, e, 2):
        share = dict(p, **{k: p[k][lo:lo + 2]
                           for k in ("w_gate", "w_up", "w_down")})
        out, st = moe.routed_ffn(
            share, x, model_config.MoEConfig(held=(lo, 2), **base), mm)
        parts = parts + out
        held += int(st[0])
        assert int(st[0]) + int(st[1]) == 18 * 4
    assert held == 18 * 4
    numpy.testing.assert_allclose(parts, whole, atol=2e-5)


def test_routing_properties():
    """top_k DISTINCT experts; weights sum to route_scale; the selection
    bias changes the choice and not the weight of an expert."""
    from veles_tpu.ops import moe
    rng = numpy.random.default_rng(5)
    d, e = 16, 12
    p = {"router": jnp.asarray(rng.normal(0, 1, (d, e)), jnp.float32)}
    x = jnp.asarray(rng.normal(0, 1, (50, d)), jnp.float32)
    cfg = model_config.MoEConfig(router_width=e, top_k=4, score="sigmoid",
                                 route_norm=True, route_scale=2.448)
    scores, idx, w = moe.route(p, x, cfg)
    assert all(len(set(row)) == 4 for row in numpy.asarray(idx).tolist())
    numpy.testing.assert_allclose(w.sum(-1), 2.448, rtol=1e-5)
    bias = jnp.zeros(e).at[7].set(10.0)          # expert 7 always chosen
    _, idx_b, w_b = moe.route(dict(p, bias=bias), x, cfg)
    assert (numpy.asarray(idx_b) == 7).any(axis=1).all()
    assert not (numpy.asarray(idx) == 7).any(axis=1).all()
    # its weight is its score over the chosen scores' sum: the bias is
    # nowhere in it
    chosen = numpy.take_along_axis(numpy.asarray(scores),
                                   numpy.asarray(idx_b), axis=1)
    numpy.testing.assert_allclose(
        w_b, 2.448 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


def test_window_tables_accounting():
    """``KVPagePool.verify`` per kind through a long request: a lane never
    holds more than W/page + 2 pages, every key its next query can see is
    on a held page, admission commits against the pool, and a vacated lane
    gives everything back."""
    pool = KVPagePool(7, 4)
    wt = WindowTables(pool, 3, 8)
    assert wt.width == 8 // 4 + 2
    wt.admit(0, 25)                       # commits min(25, 4) = 4
    assert wt.can_admit(3) and not wt.can_admit(4)
    wt.admit(1, 3)
    assert not wt.can_admit(1)
    for start in range(0, 40, 4):         # prefill chunks of one page
        wt.advance(0, start, start + 4)
        assert wt.verify()["held"] <= 4
    pos = numpy.zeros(3, numpy.int64)
    for p in range(40, 100):              # decode
        pos[0] = p
        if wt.due(pos)[0]:
            wt.advance(0, p, p + 1)
        assert wt.count[0] <= wt.width
        assert wt.base[0] * 4 <= max(0, p - 8 + 1)
        assert p // 4 < wt.base[0] + wt.count[0]
    assert wt.released == 100 // 4 - wt.count[0]
    with pytest.raises(RuntimeError, match="committed"):
        wt.advance(1, 0, 16)              # 4 pages, committed 3
    wt.vacate(0)
    wt.vacate(1)
    assert wt.verify() == {"held": 0, "committed": 0,
                           "released": wt.released}
    assert pool.free_pages == 7


@pytest.mark.parametrize("option,match", [
    ({"prefix_cache": 8}, "prefix_cache"), ({"spec_k": 2}, "spec_k"),
    ({"megastep": 4}, "megastep"),
    ({"tp": 2}, "tp >= 2")])
def test_what_was_not_widened_says_so(weights, option, match):
    from veles_tpu.serving import LMEngine
    with pytest.raises(ValueError, match=match):
        LMEngine(weights[1], record(), max_len=64, slots=2,
                 **dict({"paged_kv": 24, "prefill_chunk": PAGE}, **option))


def test_the_default_pool_is_every_lanes_whole_table(weights):
    """``paged_kv`` 0 (the default) names no other layout: the pool then
    holds every lane's whole table, ``slots x max_len / page`` pages."""
    from veles_tpu.serving import LMEngine
    eng = LMEngine(weights[1], record(), max_len=64, slots=2,
                   prefill_chunk=PAGE)
    assert eng._pool.num_pages == 2 * 64 // PAGE
    assert eng._page_tables.shape == (2, 64 // PAGE)
    # the sliding layers' pool: the window's pages a lane, no more
    assert eng._wt.pool.num_pages \
        == 2 * eng.cfg.window_pages(PAGE) < eng._pool.num_pages


def test_pipeline_stages_refuse_the_block():
    from veles_tpu.ops.nn_units import NNWorkflow
    from veles_tpu.ops.transformer import TransformerTrainer
    with pytest.raises(ValueError, match="pipeline"):
        TransformerTrainer(NNWorkflow(None, name="t"), config=record(),
                           pipeline_stages=2)


def test_record_from_the_published_keys():
    cfg = record(dtype="bfloat16")
    assert cfg.kinds == (model_config.FULL, model_config.SLIDING)
    assert [cfg.layer_rope(i) for i in range(4)] == [True, True, False, True]
    assert [cfg.layer_window(i) for i in range(4)] == [8, 8, None, 8]
    assert cfg.ffn_kinds == ("dense", "moe", "moe", "moe")
    assert cfg.moe.held == (4, 4) and cfg.moe.router_width == 16
    assert cfg.window_pages(4) == 4 and cfg.head_size(64) == 16
    assert model_config.of(4, rope=True, window=16) \
        == model_config.classic(4, True, 16)
    with pytest.raises(ValueError, match="record"):
        model_config.of(cfg, rope=True)


def test_the_controls_float8_rounding_is_the_types_own():
    """``afmoe.round_to_e4m3`` (float32 arithmetic, the same on every
    backend) gives ``float8_e4m3fn``'s own values for bfloat16 weights of
    every scale the configuration draws, subnormals and ties included."""
    rng = numpy.random.default_rng(0)
    w = numpy.concatenate([
        rng.normal(0, 0.02, 20000), rng.normal(0, 1, 5000),
        [0.0, 2 ** -6, 2 ** -9, 2 ** -10, 1.5 * 2 ** -9, 448.0, -0.0156,
         0.017578125]]).astype(numpy.float32)
    w = jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32)
    numpy.testing.assert_array_equal(
        afmoe.round_to_e4m3(w),
        w.astype(jnp.float8_e4m3fn).astype(jnp.float32))


@pytest.mark.parametrize("c, taken", [(1, True), (2, False)])
def test_row_kernel_only_for_one_row_a_lane(monkeypatch, c, taken):
    """``paged_write(kernel=True)`` hands ``pallas_kernels.paged_row_write``
    only a decode write (one row a lane, 16 lanes or more): that kernel
    rewrites a whole tile per row, and c adjacent positions of a lane (the
    speculative verify) share tiles, where the chip's pipelined grid would
    keep only the last row of a tile.  Either way the pool reads the same
    as with the update slices."""
    from veles_tpu.ops import attention as A, pallas_kernels as PK
    lanes, kv, page, dh = 16, 2, 8, 16
    rng = numpy.random.default_rng(c)
    pool = jnp.asarray(rng.normal(size=(lanes * 2 + 1, kv, page, dh)),
                       jnp.float32)
    ptab = jnp.asarray(1 + numpy.arange(lanes * 2).reshape(lanes, 2),
                       jnp.int32)
    pos = jnp.asarray(rng.integers(0, 2 * page - c, lanes), jnp.int32)
    rows = jnp.asarray(rng.normal(size=(lanes, kv, c, dh)), jnp.float32)
    calls = []
    real = PK.paged_row_write
    monkeypatch.setattr(
        PK, "paged_row_write",
        lambda *a, **k: calls.append(1) or real(*a, **k))
    got = A.paged_write(pool, ptab, pos, rows, kernel=True)
    want = A.paged_write(pool, ptab, pos, rows)
    assert bool(calls) == taken
    numpy.testing.assert_array_equal(got, want)


def test_selection_bias_is_balanced_across_shares(weights):
    """``make_weights`` shifts each chip's share of experts together so
    that every share has the same mean selection bias (bfloat16 rounding
    of values near 0.01 moves a mean of 4 by under 4e-5), and keeps the
    spread inside a share: the share of assignments this chip receives
    does not depend on the seed through the draw of the bias."""
    w, _ = weights
    share = SMALL["held_experts"][1]
    for blk in w["blocks"][SMALL["num_dense_layers"]:]:
        bias = numpy.asarray(blk["moe"]["bias"].astype(jnp.float32))
        means = bias.reshape(-1, share).mean(1)
        assert numpy.abs(means - means.mean()).max() < 4e-5
        assert bias.reshape(-1, share).std(1).min() > 1e-3


def _expert_layer(rng, d, f, e, dtype):
    mk = lambda *s: jnp.asarray(rng.normal(0, 0.3, s), dtype)  # noqa: E731
    return {"w_gate": mk(e, d, f), "w_up": mk(e, d, f), "w_down": mk(e, f, d)}


@pytest.fixture
def row_kernel_here(monkeypatch):
    """``ops/moe.py``'s choice as on the chip (the rule itself stays: rows,
    dtype), with the kernel in interpret mode."""
    from veles_tpu.ops import pallas_kernels as PK
    monkeypatch.setattr(PK, "on_tpu", lambda: True)
    monkeypatch.setattr(PK, "_interpret", lambda flag: True)
    return monkeypatch


def _nan_behind_the_last_group(patch, calls):
    """What the chip may leave in rows the kernel never writes."""
    from veles_tpu.ops import pallas_kernels as PK
    real = PK.grouped_matmul

    def kernel(xs, w, sizes, **kw):
        calls.append(1)
        behind = jnp.arange(xs.shape[0])[:, None] >= sizes.sum()
        return jnp.where(behind, jnp.nan, real(xs, w, sizes, **kw))
    patch.setattr(PK, "grouped_matmul", kernel)


@pytest.mark.parametrize("lo, n, dtype", [
    (0, 8, "bfloat16"), (2, 4, "bfloat16"), (6, 2, "bfloat16"),
    (0, 8, "float16")], ids=["all_held", "a_share", "the_last_share",
                             "float16"])
def test_held_part_on_the_row_kernel_is_held_part_on_ragged_dot(
        row_kernel_here, lo, n, dtype):
    """ISSUE 35: from ``ROW_KERNEL_MIN`` assignment rows on the grouped
    matmuls of ``held_part`` are the row-tiled kernel; the part it
    computes equals the one on ``jax.lax.ragged_dot`` within one rounding
    of the float32 result to the model's type (the intermediate
    ``hidden`` is rounded once on both paths), with the same ``stats``,
    and rows of experts held elsewhere (behind the last group, where the
    kernel writes nothing: here NaN) add nothing."""
    from veles_tpu.ops import moe, pallas_kernels as PK
    rng = numpy.random.default_rng(n)
    d, f, e, k = 64, 48, 8, 2
    tokens = moe.ROW_KERNEL_MIN // k
    p = _expert_layer(rng, d, f, n, dtype)
    flat = jnp.asarray(rng.normal(0, 1, (tokens, d)), dtype)
    idx = jnp.asarray(numpy.stack([rng.permutation(e)[:k]
                                   for _ in range(tokens)]), jnp.int32)
    w = jnp.asarray(rng.uniform(0.2, 1.0, (tokens, k)), jnp.float32)
    calls = []
    _nan_behind_the_last_group(row_kernel_here, calls)
    got, stats = moe.held_part(p, flat, idx, w, lo, n)
    assert len(calls) == 3
    # a row fewer than the constant: the compiler's op
    moe.held_part(p, flat[1:], idx[1:], w[1:], lo, n)
    assert len(calls) == 3
    row_kernel_here.setattr(PK, "on_tpu", lambda: False)
    want, stats_ragged = moe.held_part(p, flat, idx, w, lo, n)
    numpy.testing.assert_array_equal(stats, stats_ragged)
    assert int(stats[0]) == int(((idx >= lo) & (idx < lo + n)).sum())
    want = numpy.asarray(want.astype(jnp.float32))
    step = 2.0 ** -8 if dtype == "bfloat16" else 2.0 ** -11
    numpy.testing.assert_allclose(numpy.asarray(got.astype(jnp.float32)),
                                  want, atol=2 * step * numpy.abs(want).max())


def test_float32_never_takes_the_row_kernel(row_kernel_here):
    """The trained top-1 layer, ``moe_ffn_ep`` and a float32 serving model
    keep ``ragged_dot`` at ``functional``'s precision at any row count;
    a narrower type takes the kernel from ``ROW_KERNEL_MIN`` rows on."""
    from veles_tpu.ops import moe
    calls = []
    _nan_behind_the_last_group(row_kernel_here, calls)
    rows = moe.ROW_KERNEL_MIN
    w = jnp.ones((2, 8, 8), jnp.float32)
    sizes = jnp.asarray([rows // 2, rows // 2 - 1], jnp.int32)
    for dtype, n, taken in (("float32", 2 * rows, 0), ("bfloat16", rows, 1),
                            ("bfloat16", rows - 1, 0)):
        out = moe.grouped_matmul(jnp.ones((n, 8), dtype), w.astype(dtype),
                                 sizes)
        assert out.dtype == dtype and len(calls) == taken
        numpy.testing.assert_array_equal(out[:rows - 1], 8)
        del calls[:]


def test_gradient_through_held_part_on_the_row_kernel(row_kernel_here):
    """Differentiating through the kernel path gives ``ragged_dot``'s own
    gradients (``_kernel_matmul`` carries its vjp): for the rows, the
    three weight tensors and the routing weights, in bfloat16 at a row
    count above the constant; and what the kernel leaves behind the last
    group (here NaN) reaches no gradient."""
    from veles_tpu.ops import moe, pallas_kernels as PK
    rng = numpy.random.default_rng(7)
    d, f, e, k = 32, 24, 4, 2
    tokens = moe.ROW_KERNEL_MIN // k + 5
    p = _expert_layer(rng, d, f, e, jnp.bfloat16)
    flat = jnp.asarray(rng.normal(0, 1, (tokens, d)), jnp.bfloat16)
    idx = jnp.asarray(numpy.stack([rng.permutation(e + 2)[:k]
                                   for _ in range(tokens)]), jnp.int32)
    w = jnp.asarray(rng.uniform(0.2, 1.0, (tokens, k)), jnp.float32)
    tilt = jnp.asarray(rng.normal(0, 1, (tokens, d)), jnp.float32)

    def loss(p, flat, w):
        out, _ = moe.held_part(p, flat, idx, w, 1, e)
        return (out.astype(jnp.float32) * tilt).sum()
    calls = []
    _nan_behind_the_last_group(row_kernel_here, calls)
    got = jax.grad(loss, (0, 1, 2))(p, flat, w)
    assert len(calls) == 3
    row_kernel_here.setattr(PK, "on_tpu", lambda: False)
    want = jax.grad(loss, (0, 1, 2))(p, flat, w)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == r.dtype and g.shape == r.shape
        r = numpy.asarray(r.astype(jnp.float32))
        assert numpy.isfinite(numpy.asarray(g.astype(jnp.float32))).all()
        numpy.testing.assert_allclose(
            numpy.asarray(g.astype(jnp.float32)), r,
            atol=2.0 ** -6 * numpy.abs(r).max())


@pytest.mark.parametrize("name", ["ragged-dot-none f32[4096,1024]",
                                  "moe bf16[4096,3584]"])
def test_chunk_matmul_roofline_reads_either_name(name):
    """ISSUE 35: ``moe_chunk_matmul_roofline`` divides the chunks' least
    time (every expert read once a layer a chunk, unedited
    ``rooflines/moe_grouped_matmul.py``) by the time, inside
    ``jit_chunk_slot``, of the compiler's ``ragged-dot`` and of the Pallas
    call under ``moe.experts`` alike; the decode program's ``ragged-dot``
    is not its business, a trace without either reads None, and so does a
    configuration that holds a share of the experts (a chunk does not hit
    them all, and the program does not count those it hits)."""
    import os
    import types
    from benchmark.lib import peaks, trace as T
    from benchmark.lib.files import load_json, load_module
    assert T.short_name(
        "%moe.experts.3 = bf16[4096,3584]{1,0:T(8,128)(2,1)} custom-call("
        "%a, %b)") == "moe bf16[4096,3584]"
    configs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs")
    cfg = load_json(os.path.join(configs, "xing4.0-29b-a4b.json"))
    v5e = peaks.PEAKS["TPU v5 lite"]
    ctx = types.SimpleNamespace(config=cfg, peaks=lambda: v5e)
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    ops = [T.Op(name, 0, 2_000_000, 2_000_000, "jit_chunk_slot")
           for _ in range(3 * layers * 2)]
    ops += [T.Op("ragged-dot-none f32[64,1024]", 0, 9e9, 9e9, "jit_step_all"),
            T.Op("fusion f32[4096]", 0, 9e9, 9e9, "jit_chunk_slot")]
    modules = [T.Module("jit_chunk_slot", 0, 1),
               T.Module("jit_chunk_slot", 2, 1), T.Module("jit_step_all", 4, 1)]
    art = {"trace": {"devices": [{"ops": ops, "modules": modules}]}}
    read = load_module("layer_metrics", "moe_chunk_matmul_roofline").read
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    least = (64 * 3 * d * f * 2 + 2 * 4096 * d * 2) / v5e["hbm_bytes_s"]
    assert read(art, ctx) == pytest.approx(
        100.0 * least / (3 * 2_000_000 / 1e9))
    ctx.config = load_json(os.path.join(configs, "trinity-large-ep8.json"))
    assert read(art, ctx) is None
    ctx.config = cfg
    art["trace"]["devices"][0]["ops"] = ops[-2:]
    assert read(art, ctx) is None
