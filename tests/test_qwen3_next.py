"""ISSUE 36: a ``pre_rms`` stack of LINEAR layers (Gated DeltaNet:
``ops/linear_attn.py``) and gated full-attention layers, every layer routed
(``ops/moe.py``, softmax scores, a sigmoid-gated shared expert), against the
benchmark's plain reference ``benchmark/reference/qwen3_next.py`` (float32,
the RECURRENT rule token by token, no chunking, cache or kernel, imports
nothing of veles_tpu); and the engine's two kinds of cache in one manager: a
slot of recurrent state and convolution tail a lane for every linear layer
beside ONE page table for the full layers.

Tolerances: the program in float32 and the reference compute the same sums in
another order (the chunked rule against the recurrent one), so logits agree to
float32 roundoff (1e-4 on logits of magnitude 3; the greedy tokens are then
the reference's own, gap 0)."""

import json
import os

import numpy
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import qwen3_next
from veles_tpu import model_config
from veles_tpu.ops import linear_attn
from veles_tpu.ops import pallas_kernels as PK

PAGE = 8
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMALL = {
    "model_type": "qwen3_next", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "rope_scaling": None, "rms_norm_eps": 1e-6,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 4,
    "router_width": 16, "held_experts": [4, 4], "num_experts_per_tok": 3,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "vocab_size": 96, "num_hidden_layers": 4, "full_attention_interval": 4,
    "max_position_embeddings": 128, "initializer_std": 0.1,
    "dtype": "float32",
}


def record(**over):
    return model_config.from_published(dict(SMALL, **over))


@pytest.fixture(scope="module")
def weights():
    """(the reference's bfloat16-valued tree, the same raised to float32)."""
    w = qwen3_next.make_weights(3, SMALL)
    return w, jax.tree.map(lambda a: a.astype(jnp.float32), w)


def tokens(n, seed=0):
    return numpy.random.default_rng(seed).integers(0, SMALL["vocab_size"], n)


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def rule_inputs(seed, b, length, h=4, dk=16, dv=16):
    """q, k (unit), v, beta, g of ``length`` rows of ``b`` sequences."""
    rng = numpy.random.default_rng(seed)
    q, k = normal(rng, b, length, h, dk), normal(rng, b, length, h, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return (q, k, normal(rng, b, length, h, dv),
            jax.nn.sigmoid(normal(rng, b, length, h)),
            -0.3 * jnp.abs(normal(rng, b, length, h)))


# ------------------------------------------------------------ the forward
def test_whole_forward_matches_the_reference(weights):
    """75 tokens: two inner chunks of the chunked rule, the second padded,
    against the reference's token-by-token rule."""
    from veles_tpu.ops.transformer import transformer_forward
    w, wf = weights
    toks = tokens(75)
    ref = qwen3_next.logits(w, toks, numpy.arange(75), SMALL)
    got = transformer_forward(wf, jnp.asarray(toks)[None], record())[0]
    numpy.testing.assert_allclose(got, ref, atol=1e-4)


def test_the_reference_in_blocks_is_the_reference_whole(weights, monkeypatch):
    w, _ = weights
    toks = tokens(40, 7)
    whole = qwen3_next.logits(w, toks, numpy.arange(40), SMALL)
    monkeypatch.setattr(qwen3_next, "ROWS", 8)
    jax.clear_caches()
    blocks = qwen3_next.logits(w, toks, numpy.arange(40), SMALL)
    jax.clear_caches()
    numpy.testing.assert_allclose(blocks, whole, atol=2e-5)


def empty_storage(cfg, lanes, pages, packed):
    """What the engine keeps a layer: (state, tail) of ``lanes`` slots for a
    linear layer, (k pool, v pool) for a full one."""
    state, tail = cfg.linear.state_shapes(lanes)
    r = PK.pool_pack(2, 16) if packed else 1
    pool = (pages + 1, 2 // r, PAGE, 16 * r)
    return [(jnp.zeros(state), jnp.zeros(tail))
            if cfg.kind(i) == model_config.LINEAR
            else (jnp.zeros(pool), jnp.zeros(pool)) for i in range(4)]


@pytest.mark.parametrize("kernel", [None, "kernel"])
def test_paged_prefill_then_decode_matches_the_reference(weights, kernel):
    """Prefill by chunks of a page (the chunked rule, the state and the
    convolution tail carried from chunk to chunk; the last chunk PADDED: the
    prompt is no multiple of the chunk), then single steps (the recurrent
    rule) through state and pages: the logits of every decoded position are
    the reference's over the whole sequence.  The lane is slot 1 of two;
    slot 0 rides the steps without decoding and keeps its bits."""
    from veles_tpu.ops.transformer import head_logits, paged_chunk_apply
    w, wf = weights
    cfg = record()
    seq = tokens(44, 2)
    prompt_len, max_pages = 21, 6
    pools = empty_storage(cfg, 2, 2 * max_pages, bool(kernel))
    # what slot 1 held before must not show: its first chunk starts at 0
    pools = [tuple(a + 1 if cfg.kind(i) == model_config.LINEAR else a
                   for a in layer) for i, layer in enumerate(pools)]
    table = jnp.arange(1, 2 * max_pages + 1, dtype=jnp.int32).reshape(2, -1)

    @jax.jit
    def chunk(pools, toks, pos, rows):
        h, new = paged_chunk_apply(
            wf, toks[None], pools, table[1:], pos[None], cfg,
            attn_kernel="prefill" if kernel else None, rows=rows[None],
            slots=jnp.asarray([1]))
        return head_logits(wf, h, cfg)[0], new

    @jax.jit
    def step(pools, tok, pos):
        h, new = paged_chunk_apply(
            wf, jnp.stack([tok * 0, tok])[:, None], pools, table,
            jnp.stack([pos * 0, pos]), cfg,
            attn_kernel="decode" if kernel else None,
            rows=jnp.asarray([0, 1]))
        return head_logits(wf, h, cfg)[1, 0], new

    for pos in range(0, prompt_len, PAGE):
        rows = min(PAGE, prompt_len - pos)
        toks = numpy.zeros(PAGE, numpy.int32)
        toks[:rows] = seq[pos:pos + rows]
        toks[rows:] = 5                      # padding that is a real id
        logits, pools = chunk(pools, jnp.asarray(toks), jnp.asarray(pos),
                              jnp.asarray(rows))
    got = [logits[rows - 1]]
    idle = [layer for i, layer in enumerate(pools)
            if cfg.kind(i) == model_config.LINEAR]
    for pos in range(prompt_len, 43):
        logits, pools = step(pools, jnp.asarray(seq[pos]), jnp.asarray(pos))
        got.append(logits)
    ref = qwen3_next.logits(w, seq, numpy.arange(prompt_len - 1, 43), SMALL)
    numpy.testing.assert_allclose(jnp.stack(got), ref, atol=1e-4)
    after = [layer for i, layer in enumerate(pools)
             if cfg.kind(i) == model_config.LINEAR]
    for (s0, t0), (s1, t1) in zip(idle, after):
        assert bool((s0[0] == s1[0]).all()) and bool((t0[0] == t1[0]).all())
        assert not bool((s0[1] == s1[1]).all())


# ------------------------------------------------------- the rule's orders
def test_the_chunked_rule_equals_the_recurrent_one():
    """130 rows from a state that is not zero: three inner chunks, the last
    padded with rows whose beta and g are 0, against the rule row by row."""
    q, k, v, beta, g = rule_inputs(1, 2, 130)
    s0 = normal(numpy.random.default_rng(2), 2, 4, 16, 16)
    s, outs = s0, []
    for t in range(130):
        o, s = linear_attn.recurrent_step(s, q[:, t], k[:, t], v[:, t],
                                          beta[:, t], g[:, t])
        outs.append(o)
    pad = [(0, 0), (0, -130 % linear_attn.CHUNK)]
    padded = [jnp.pad(y, pad + [(0, 0)] * (y.ndim - 2))
              for y in (q, k, v, beta, g)]
    o, s1 = linear_attn.chunk_pass(s0, linear_attn.chunk_terms(*padded))
    o = jnp.moveaxis(o, 1, 3).reshape(2, -1, 4, 16)[:, :130]
    numpy.testing.assert_allclose(o, jnp.stack(outs, 1), atol=2e-5)
    numpy.testing.assert_allclose(s1, s, atol=2e-5)


def test_the_kernels_equal_their_twins():
    """``gdn_decode`` against ``recurrent_step`` on the active lanes (the
    others' state bit for bit, their outputs 0; no lane active: nothing
    moves), ``gdn_chunk`` against ``chunk_pass`` (a fresh lane from zeros,
    the slots not named untouched)."""
    q, k, v, beta, g = rule_inputs(3, 5, 128)
    state = normal(numpy.random.default_rng(4), 5, 4, 16, 16)
    active = jnp.asarray([True, False, True, False, False])
    row = [y[:, 0] for y in (q, k, v, beta, g)]
    o, s = PK.gdn_decode(state, *row, active, interpret=True)
    o2, s2 = linear_attn.recurrent_step(state, *row)
    numpy.testing.assert_allclose(o[active], o2[active], atol=1e-5)
    numpy.testing.assert_allclose(s[active], s2[active], atol=1e-6)
    assert bool((s[~active] == state[~active]).all())
    assert not bool(o[~active].any())
    _, s = PK.gdn_decode(state, *row, jnp.zeros(5, bool), interpret=True)
    assert bool((s == state).all())
    terms = linear_attn.chunk_terms(*(y[:2] for y in (q, k, v, beta, g)))
    slots, fresh = jnp.asarray([3, 1]), jnp.asarray([False, True])
    o, s = PK.gdn_chunk(state, slots, fresh, *terms, interpret=True)
    o2, s2 = linear_attn.chunk_pass(
        jnp.where(fresh[:, None, None, None], 0.0, state[slots]), terms)
    numpy.testing.assert_allclose(o, o2, atol=1e-5)
    numpy.testing.assert_allclose(s[slots], s2, atol=1e-5)
    rest = jnp.asarray([0, 2, 4])
    assert bool((s[rest] == state[rest]).all())


def layer_inputs(weights, lanes, c, seed):
    rng = numpy.random.default_rng(seed)
    cfg = record()
    p = weights[1]["blocks"][0]["attn"]
    state_shape, tail_shape = cfg.linear.state_shapes(lanes)
    return (cfg, p, normal(rng, lanes, c, 64), normal(rng, *state_shape),
            normal(rng, *tail_shape))


@pytest.mark.parametrize("kernel", [None, "decode"])
def test_a_step_leaves_the_lanes_that_do_not_decode_bit_for_bit(weights,
                                                                kernel):
    """Lanes still in prefill and empty lanes ride ``step_all``; a
    recurrent state has no scratch page to divert them to."""
    cfg, p, x, state, tail = layer_inputs(weights, 4, 1, 5)
    rows = jnp.asarray([1, 0, 1, 0])
    out, s, t = linear_attn.linear_paged_chunk_step(
        p, x, state, tail, cfg, rows, attn_kernel=kernel)
    full, s_all, t_all = linear_attn.linear_paged_chunk_step(
        p, x, state, tail, cfg, jnp.ones(4, jnp.int32))
    for lane in (1, 3):
        assert bool((s[lane] == state[lane]).all())
        assert bool((t[lane] == tail[lane]).all())
    for lane in (0, 2):
        numpy.testing.assert_allclose(s[lane], s_all[lane], atol=1e-6)
        assert bool((t[lane] == t_all[lane]).all())
        assert bool((t[lane, -1] != tail[lane, -1]).any())
        numpy.testing.assert_allclose(out[lane], full[lane], atol=1e-5)


def test_a_padded_row_moves_neither_the_state_nor_the_tail(weights):
    """A prompt's last chunk of 5 real rows in 8: the state and the tail are
    what the 5 rows alone leave, whatever the padding holds."""
    cfg, p, x, state, tail = layer_inputs(weights, 1, 8, 6)
    rows = jnp.asarray([5])
    out, s, t = linear_attn.linear_paged_chunk_step(
        p, x, state, tail, cfg, rows)
    out5, s5, t5 = linear_attn.linear_paged_chunk_step(
        p, x[:, :5], state, tail, cfg, rows)
    numpy.testing.assert_allclose(s, s5, atol=1e-5)
    numpy.testing.assert_allclose(out[:, :5], out5, atol=1e-5)
    assert bool((t == t5).all())
    # the tail: the three rows of [q | k | v] before the convolution that
    # end at the TRUE length
    qkv = jnp.matmul(x[0], p["w_qkv"], precision="highest")
    numpy.testing.assert_allclose(t[0], qkv[2:5], atol=1e-5)
    # and a lane whose chunk starts its sequence reads its slot as zeros
    _, s0, _ = linear_attn.linear_paged_chunk_step(
        p, x, state, tail, cfg, rows, fresh=jnp.asarray([True]))
    _, z0, _ = linear_attn.linear_paged_chunk_step(
        p, x, 0 * state, 0 * tail, cfg, rows)
    numpy.testing.assert_allclose(s0, z0, atol=1e-6)


def test_partial_rotation_against_a_hand_computed_table():
    """16 dimensions a head, factor 0.25: the first 4 are rotated half-split
    (pairs 0-2 and 1-3, frequencies 1 and theta^-1/2), the other 12 left."""
    from veles_tpu.ops.attention import cfg_rotate
    cfg = record(rope_theta=100.0)
    assert cfg.rotary_dims(16) == 4 and cfg.rotary_dims(256) == 64
    x = jnp.arange(1.0, 33.0).reshape(1, 2, 16)       # (heads, seq, dh)
    got = numpy.asarray(cfg_rotate(x, jnp.asarray([0, 3]), cfg))
    numpy.testing.assert_allclose(got[0, 0], x[0, 0])        # position 0
    row = numpy.asarray(x[0, 1])
    want = row.copy()
    for i, freq in enumerate((1.0, 0.1)):
        c, s = numpy.cos(3 * freq), numpy.sin(3 * freq)
        want[i] = row[i] * c - row[i + 2] * s
        want[i + 2] = row[i + 2] * c + row[i] * s
    numpy.testing.assert_allclose(got[0, 1], want, rtol=1e-5)
    batched = cfg_rotate(x[None], jnp.asarray([[0, 3]]), cfg, batched=True)
    numpy.testing.assert_allclose(batched[0], got, rtol=1e-6)
    # the reference rotates the same way on its own
    ref = qwen3_next.rotate(jnp.swapaxes(x, 0, 1), jnp.asarray([0, 3]),
                            qwen3_next.sizes(dict(SMALL, rope_theta=100.0)))
    numpy.testing.assert_allclose(jnp.swapaxes(ref, 0, 1), got, rtol=1e-5)


def test_the_prefill_kernel_in_query_blocks_is_the_kernel_whole(monkeypatch):
    """A kv head whose query rows overrun the kernel's memory is taken a
    block of rows at a time (16 query heads of 256 on 2 kv heads over a
    1024-token chunk): the same outputs, the same page installed."""
    rng = numpy.random.default_rng(8)
    page, m = 16, 3
    q = normal(rng, 1, 4, page, 128)
    kn, vn = normal(rng, 1, 1, page, 128), normal(rng, 1, 1, page, 128)
    pool_k, pool_v = normal(rng, m + 1, 1, page, 128), \
        normal(rng, m + 1, 1, page, 128)
    table = jnp.asarray([[2, 3, 1]], jnp.int32)
    args = (q, kn, vn, pool_k, pool_v, table, jnp.asarray([2 * page]))
    whole = PK.paged_flash_prefill(*args, interpret=True)
    monkeypatch.setattr(PK, "_SCORES_BYTES", 4 * page * 4 * 16 - 1)
    monkeypatch.setattr(PK, "_Q_BLOCK_BYTES", 16 * page * 4)
    assert PK._query_rows(4 * page, page) == 16
    blocks = PK.paged_flash_prefill(*args, interpret=True)
    for a, b in zip(whole, blocks):
        numpy.testing.assert_allclose(a, b, atol=1e-6)


# ------------------------------------------------------------- the shares
def test_the_shares_add_up_to_the_uncut_layer(weights):
    """Four chips' shares of 4 held experts each, the gated shared expert
    counted ONCE, add up to the uncut reference's layer over all 16."""
    from veles_tpu.ops.attention import cfg_matmul
    from veles_tpu.ops.moe import gated_ffn, routed_ffn
    uncut = dict(SMALL, num_experts=16, held_experts=[0, 16])
    whole = jax.tree.map(lambda a: a.astype(jnp.float32),
                         qwen3_next.make_weights(5, uncut))["blocks"][1]["moe"]
    m = normal(numpy.random.default_rng(9), 23, 64)
    with jax.default_matmul_precision("highest"):
        want = qwen3_next.expert_layer(m, whole, qwen3_next.sizes(uncut),
                                       None)
    cfg = record()
    total, held = 0.0, 0
    for lo in range(0, 16, 4):
        share = dict(whole, **{k: whole[k][lo:lo + 4]
                               for k in ("w_gate", "w_up", "w_down")})
        part, stats = routed_ffn(share, m, model_config.MoEConfig(
            router_width=16, top_k=3, route_norm=True, held=(lo, 4)))
        total, held = total + part, held + int(stats[0])
    assert held == 23 * 3                  # every assignment held once
    mm = lambda a, b: cfg_matmul(cfg, a, b)  # noqa: E731
    shared = gated_ffn(whole["shared"], m, mm) * jax.nn.sigmoid(
        jnp.matmul(m, whole["shared_gate"], precision="highest"))
    numpy.testing.assert_allclose(total + shared, want, atol=2e-5)


# -------------------------------------------------------------- the engine
def engine(wf, **over):
    from veles_tpu.serving import LMEngine
    return LMEngine(wf, record(), **dict(
        {"max_len": 128, "slots": 4, "paged_kv": 32, "prefill_chunk": 16},
        **over))


def assert_served_the_references(w, prompts, outs):
    for p, o in zip(prompts, outs):
        seq = numpy.concatenate([p, o])
        ref = qwen3_next.logits(w, seq, numpy.arange(len(p) - 1, len(seq) - 1),
                                SMALL)
        gap = ref.max(-1) - ref[numpy.arange(len(o)), o]
        assert float(gap.max()) <= 1e-4


@pytest.mark.parametrize("features", [
    {}, {"slots": 16, "attn_kernel": "force", "prefill_chunk": 8,
         "paged_kv": 96, "max_len": 96}],
    ids=["xla", "kernels"])
def test_engine_serves_the_references_tokens(weights, features):
    """Through ``LMEngine`` (admission, chunked prefill interleaved with
    decode, lanes re-admitted, the live-width ladder): every served token
    is the reference's choice; state slots and pages come home; the storage
    is updated in place; the step's counts reach counters and recorder."""
    from veles_tpu.serving import tracing
    w, wf = weights
    eng = engine(wf, **features).start()
    try:
        prompts = [tokens(n, 10 + n) for n in (5, 21, 40, 16, 33, 70)]
        outs = [f.result(timeout=600)
                for f in [eng.submit(p, 12) for p in prompts]]
        assert_served_the_references(w, prompts, outs)
        assert eng.verify_pool_invariants()["used_pages"] == 0
        snap = eng.metrics.snapshot()
        g, c = snap["gauges"], snap["counters"]
        assert g["kv_pages_free"] == g["kv_pages_total"]
        assert g["state_slots_free"] == g["state_slots_total"] == eng.slots
        # three linear layers: 4 x 16 x 16 float32 and 3 x (2 x 32 + 64)
        assert g["state_bytes_per_lane"] == 3 * (4 * 1024 + 4 * 3 * 128)
        assert g["kv_bytes_per_token"] == 2 * 2 * 16 * 4    # one full layer
        assert g["kv_storage_in_place"] == 1
        assert c.get("kv_storage_rebuilds", 0) == 0
        assert c["state_resets"] == len(prompts)
        steps = c["decode_dispatches"]
        held = c["moe_assignments_held"]
        assert held + c["moe_assignments_elsewhere"] \
            == steps * eng.slots * 3 * 4
        turns = eng.recorder.turns()
        assert int(turns[:, tracing.COL_MOE_HIT].sum()) == c["moe_experts_hit"]
        if eng._kernel_active:
            pages = c["attn_page_steps"], c["attn_page_steps_live"]
            assert 0 < pages[1] < pages[0]
            assert int(turns[:, tracing.COL_ATTN_STEPS].sum()) == pages[0]
            assert int(turns[:, tracing.COL_ATTN_LIVE].sum()) == pages[1]
            # ISSUE 43: a decode step of the full layers is handed their
            # live pages alone, one block a lane a layer at these sizes
            alone = (turns[:, tracing.COL_STEP_PROGRAM] != 0) \
                & (turns[:, tracing.COL_PREFILL_PROGRAM] == 0)
            assert alone.any() and (turns[alone, tracing.COL_ATTN_STEPS]
                                    == turns[alone, tracing.COL_ATTN_LIVE]
                                    ).all()
            assert c["attn_walk_blocks"] == steps * eng.slots   # one layer
    finally:
        eng.stop()


def test_a_lane_readmitted_after_a_longer_request_answers_as_a_fresh_one(
        weights):
    """One lane: whatever a longer request left in its slot, the next
    request's first chunk starts from zeros (no dispatch resets it)."""
    _, wf = weights
    long, short = tokens(61, 20), tokens(13, 21)
    one = engine(wf, slots=1).start()
    try:
        one.submit(long, 20).result(timeout=300)
        again = one.submit(short, 10).result(timeout=300)
        assert one.metrics.counter("state_resets") == 2
    finally:
        one.stop()
    fresh = engine(wf, slots=1).start()
    try:
        first = fresh.submit(short, 10).result(timeout=300)
    finally:
        fresh.stop()
    assert again.tolist() == first.tolist()


def test_dispatches_consume_state_and_pools(weights):
    """ISSUE 27's rule for both kinds of cache: every leaf that goes into a
    dispatch is consumed (none copied or held twice), the states too."""
    _, wf = weights
    eng = engine(wf, slots=2)
    leaves = lambda: [a for layer in eng._storage() for a in layer]  # noqa
    made = leaves()
    assert len(made) == 8
    assert [a.shape for a in made[:2]] == [(2, 4, 16, 16), (2, 3, 128)]
    assert made[0].dtype == jnp.float32 and made[6].shape == (33, 2, 16, 16)
    assert eng.kv_bytes_resident() == sum(a.nbytes for a in made)
    eng.start()
    try:
        assert all(a.is_deleted() for a in made)
        warm, handed, real = leaves(), [], eng._step_jit

        def watched(p, storage, *args):
            handed.append([a for layer in storage for a in layer])
            return real(p, storage, *args)
        eng._step_jit = watched
        assert len(eng.submit(tokens(19, 5), 9).result(timeout=120)) == 9
        assert handed and all(a.is_deleted() for a in warm)
        assert all(a.is_deleted() for ls in handed for a in ls)
        assert not any(a.is_deleted() for a in leaves())
        assert eng.metrics.counter("kv_storage_rebuilds") == 0
    finally:
        eng.stop()


def test_the_invariants_cover_the_state_slots(weights):
    eng = engine(weights[1], slots=2)
    assert eng.verify_pool_invariants()["used_pages"] == 0
    eng._free.remove(1)
    with pytest.raises(RuntimeError, match="state slot 1"):
        eng.verify_pool_invariants()
    eng._free.append(1)
    eng._pos[0] = 7
    with pytest.raises(RuntimeError, match="parks at position 7"):
        eng.verify_pool_invariants()


def test_checkpoint_and_restore_rederive_the_state(weights):
    """A checkpoint carries no tensors: the restored engine prefills again,
    and the state with it (works as it stands for this kind)."""
    w, wf = weights
    prompt = tokens(37, 30)
    first = engine(wf, slots=2).start()
    try:
        first.submit(prompt, 60)
        saved = first.checkpoint()       # taken while the request is served
    finally:
        first.stop()
    assert len(saved["requests"]) == 1
    second = engine(wf, slots=2).start()
    try:
        futures = second.restore(json.loads(json.dumps(saved)))
        outs = [f.result(timeout=300) for f in futures.values()]
    finally:
        second.stop()
    assert_served_the_references(w, [prompt], outs)


@pytest.mark.parametrize("option,match", [
    ({"prefix_cache": 16}, "prefix_cache"), ({"spec_k": 2}, "spec_k"),
    ({"megastep": 4}, "megastep"), ({"tp": 2}, "tp >= 2")])
def test_what_was_not_widened_says_so(weights, option, match):
    with pytest.raises(ValueError, match=match):
        engine(weights[1], **option)


def test_the_default_pool_is_every_lanes_whole_table(weights):
    """``paged_kv`` 0 (the default) names no other layout: the pool then
    holds every lane's whole table, ``slots x max_len / page`` pages."""
    eng = engine(weights[1], paged_kv=0)
    assert eng._pool.num_pages == 4 * 128 // 16
    assert eng._page_tables.shape == (4, 128 // 16)
    # and a slot of state a lane beside it
    assert eng.metrics.snapshot()["gauges"]["state_slots_total"] == 4


def test_the_contiguous_cached_path_refuses_linear_layers(weights):
    from veles_tpu.ops.transformer import generate
    with pytest.raises(ValueError, match="no contiguous cache"):
        generate(weights[1], jnp.asarray(tokens(8))[None], 4, record(),
                 temperature=0.0, max_len=16)


# -------------------------------------------------------------- the record
def test_record_from_the_published_keys():
    cfg = record(dtype="bfloat16")
    assert cfg.block == "pre_rms" and cfg.latent is None and cfg.hyper is None
    assert cfg.attn_kinds == ("linear", "linear", "linear", "full")
    assert cfg.kinds == (model_config.FULL,) and cfg.by_kind
    assert cfg.state_layers == (0, 1, 2)
    assert cfg.linear == model_config.LinearConfig(2, 4, 16, 16, 4)
    assert cfg.linear.conv_width == 128
    assert cfg.linear.state_shapes(3) == ((3, 4, 16, 16), (3, 3, 128))
    assert [cfg.layer_rope(i) for i in range(4)] == [False] * 3 + [True]
    assert cfg.norm_centred and cfg.partial_rotary == 0.25 and cfg.wide
    assert cfg.moe.score == "softmax" and cfg.moe.shared_gate
    assert cfg.moe.held == (4, 4) and cfg.moe.router_width == 16
    assert cfg.ffn_kinds == ("moe",) * 4
    written = record(num_hidden_layers=3, layer_types=[
        "linear_attention", "full_attention", "linear_attention"])
    assert written.state_layers == (0, 2)
    published = model_config.LinearConfig(16, 32, 128, 128, 4)
    assert published.state_shapes(64) == ((64, 32, 128, 128), (64, 3, 8192))
    with pytest.raises(ValueError, match="model_type"):
        model_config.from_published(dict(SMALL, model_type="qwen9"))
    with pytest.raises(ValueError, match="come together"):
        model_config.ModelConfig(n_heads=4, block="pre_rms",
                                 attn_kinds=("linear", "full"))
    with pytest.raises(ValueError, match="every layer routed"):
        record(mlp_only_layers=[1])


def test_the_configuration_file_carries_the_published_widths():
    """``benchmark/configs/qwen3-next-80b-a3b-ep4.json``: every published
    width as published, the four reduced keys, the stated deployment, and a
    record can be made of it; the traffic file is the issue's table."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b-ep4.json")) as f:
        cfg = json.load(f)
    want = {"hidden_size": 2048, "linear_num_key_heads": 16,
            "linear_num_value_heads": 32, "linear_key_head_dim": 128,
            "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
            "num_attention_heads": 16, "num_key_value_heads": 2,
            "head_dim": 256, "partial_rotary_factor": 0.25,
            "rope_theta": 10000000, "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512, "router_width": 512,
            "num_experts_per_tok": 10, "full_attention_interval": 4,
            "intermediate_size": 5120, "rms_norm_eps": 1e-6}
    assert {k: cfg[k] for k in want} == want
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size",
         "max_position_embeddings"])
    assert set(cfg["reduced_note"]) == set(cfg["reduced"])
    assert cfg["held_experts"] == [0, cfg["num_experts"]] == [0, 128]
    rec = model_config.from_published(cfg)
    assert rec.attn_kinds == ("linear", "linear", "linear", "full") * 2
    assert rec.linear.state_shapes(64) == ((64, 32, 128, 128), (64, 3, 8192))
    assert rec.moe.held == (0, 128) and rec.dtype == "bfloat16"
    dep = cfg["deployment"]
    assert cfg["max_position_embeddings"] % dep["prefill_chunk"] == 0
    assert dep["paged_kv"] == dep["slots"] * (
        cfg["max_position_embeddings"] // dep["prefill_chunk"])
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longchat.json")) as f:
        traffic = json.load(f)
    table = traffic["table"]
    assert traffic["clients"] == dep["slots"] == 64 and len(table) == 16
    assert sum(p for p, _ in table) == 95488
    assert sum(n for _, n in table) == 10752
    assert max(p + n for p, n in table) == 16640 \
        <= cfg["max_position_embeddings"]
    assert max(n for _, n in table) == dep["max_new"]
    assert table[:3] == [[512, 1024], [8192, 512], [1024, 1024]]
