"""ISSUE 42: a ``pre_rms`` stack of Kimi-delta-attention layers (a delta rule
whose state decays by a vector over the key dimension: ``ops/linear_attn.py``
with ``decay == "channel"``) and ONE latent-attention layer without a query
bottleneck under a head-wise output gate (``ops/latent.py``), group-limited
routing (``ops/moe.py::route``), against the benchmark's plain reference
``benchmark/reference/ling3.py`` (float32, the RECURRENT rule token by token,
attention expanded, imports nothing of veles_tpu); and the engine's lane: a
slot of state for six layers beside a page table over one pool of latent rows.

Tolerances: the program in float32 and the reference compute the same sums in
another order (the chunked rule against the recurrent one, absorbed attention
against expanded), so logits agree to float32 roundoff (1e-4 on logits of
magnitude 3; the greedy tokens are then the reference's own, gap 0)."""

import dataclasses
import json
import os

import numpy
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import ling3
from veles_tpu import model_config
from veles_tpu.ops import linear_attn, moe
from veles_tpu.ops import pallas_kernels as PK

PAGE = 8
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: one period (five KDA layers to one MLA) behind one leading dense layer, as
#: the cell's cut has it; 2 of 8 groups held (experts 8..15 of 32)
SMALL = {
    "model_type": "ling3_flash", "hidden_size": 64, "num_attention_heads": 4,
    "head_dim": 16, "q_lora_rank": None, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rotary_dim": 8, "rope_theta": 6000000, "rms_norm_eps": 1e-6,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "num_experts": 8,
    "router_width": 32, "held_experts": [8, 8], "num_experts_per_tok": 4,
    "n_group": 8, "topk_group": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "score_function": "sigmoid",
    "moe_router_enable_expert_bias": True, "first_k_dense_replace": 1,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
    "linear_silu": True, "group_norm_size": 1, "use_mla_nope": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "layer_group_size": 6, "num_hidden_layers": 7,
    "layer_types": ["linear_attention"] * 4 + ["full_attention"]
    + ["linear_attention"] * 2,
    "expert_swiglu_limit_list": [0] * 7,
    "share_expert_swiglu_limit_list": [0] * 7,
    "vocab_size": 96, "max_position_embeddings": 128,
    "initializer_std": 0.1, "dtype": "float32",
}
MLA_LAYER = 4


def record(**over):
    return model_config.from_published(dict(SMALL, **over))


@pytest.fixture(scope="module")
def weights():
    """(the reference's bfloat16-valued tree, the same raised to float32)."""
    w = ling3.make_weights(3, SMALL)
    return w, jax.tree.map(lambda a: a.astype(jnp.float32), w)


def tokens(n, seed=0):
    return numpy.random.default_rng(seed).integers(0, SMALL["vocab_size"], n)


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def rule_inputs(seed, b, length, decay, h=4, dk=16, dv=16):
    """q, k (unit), v, beta and a decay per channel ``g`` of ``length`` rows
    of ``b`` sequences: ``floor``: -5 on EVERY row and channel; ``near0``:
    about -1e-3; ``mixed``: channels of both ends and between."""
    rng = numpy.random.default_rng(seed)
    q, k = normal(rng, b, length, h, dk), normal(rng, b, length, h, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    u = jnp.abs(normal(rng, b, length, h, dk))
    g = {"floor": jnp.full(u.shape, -5.0), "near0": -1e-3 * u,
         "mixed": -5.0 * jax.nn.sigmoid(
             8.0 * normal(rng, 1, 1, h, dk) + normal(rng, b, length, h, dk))
         }[decay]
    return (q, k, normal(rng, b, length, h, dv),
            jax.nn.sigmoid(normal(rng, b, length, h)), g)


# ------------------------------------------------------------ the forward
def test_whole_forward_matches_the_reference(weights):
    """75 tokens: two inner chunks of the chunked rule, the second padded,
    against the reference's token-by-token rule; expanded attention."""
    from veles_tpu.ops.transformer import transformer_forward
    w, wf = weights
    toks = tokens(75)
    ref = ling3.logits(w, toks, numpy.arange(75), SMALL)
    got = transformer_forward(wf, jnp.asarray(toks)[None], record())[0]
    numpy.testing.assert_allclose(got, ref, atol=1e-4)


def empty_storage(cfg, lanes, pages):
    """What the engine keeps a layer: (state, tail) of ``lanes`` slots for a
    KDA layer, (the pool of latent rows,) for the MLA layer."""
    state, tail = cfg.linear.state_shapes(lanes)
    pool = (pages + 1, 1, PAGE, cfg.latent.row)
    return [(jnp.zeros(state), jnp.zeros(tail))
            if cfg.kind(i) == model_config.LINEAR else (jnp.zeros(pool),)
            for i in range(7)]


@pytest.mark.parametrize("kernel", [None, "kernel"])
def test_paged_prefill_then_decode_matches_the_reference(weights, kernel):
    """Prefill by chunks of a page (the chunked rule, state and tail carried
    from chunk to chunk; expanded attention over the pool; the last chunk
    PADDED: the prompt is no multiple of the chunk), then single steps (the
    recurrent rule; absorbed attention) through state and pages: the logits
    of every decoded position are the reference's over the whole sequence.
    The lane is slot 1 of two; slot 0 rides the steps without decoding and
    keeps its bits."""
    from veles_tpu.ops.transformer import head_logits, paged_chunk_apply
    w, wf = weights
    cfg = record()
    seq = tokens(44, 2)
    prompt_len, max_pages = 21, 6
    pools = empty_storage(cfg, 2, 2 * max_pages)
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
    ref = ling3.logits(w, seq, numpy.arange(prompt_len - 1, 43), SMALL)
    numpy.testing.assert_allclose(jnp.stack(got), ref, atol=1e-4)
    after = [layer for i, layer in enumerate(pools)
             if cfg.kind(i) == model_config.LINEAR]
    for (s0, t0), (s1, t1) in zip(idle, after):
        assert bool((s0[0] == s1[0]).all()) and bool((t0[0] == t1[0]).all())
        assert not bool((s0[1] == s1[1]).all())


# ------------------------------------------------------- the rule's orders
@pytest.mark.parametrize("decay", ["floor", "near0", "mixed"])
def test_the_chunked_rule_equals_the_recurrent_one(decay):
    """200 rows from a state that is not zero: four inner chunks, the last
    padded with rows whose beta and g are 0, against the rule row by row.
    With ``g`` = -5 on EVERY row the cumulative decay of a chunk reaches
    -320 and ``exp(-gam)`` alone would overflow float32: every term is
    finite, and the two orders agree within 1e-5."""
    q, k, v, beta, g = rule_inputs(1, 2, 200, decay)
    s0 = normal(numpy.random.default_rng(2), 2, 4, 16, 16)
    s, outs = s0, []
    for t in range(200):
        o, s = linear_attn.recurrent_step(s, q[:, t], k[:, t], v[:, t],
                                          beta[:, t], g[:, t])
        outs.append(o)
    pad = [(0, 0), (0, -200 % linear_attn.CHUNK)]
    padded = [jnp.pad(y, pad + [(0, 0)] * (y.ndim - 2))
              for y in (q, k, v, beta, g)]
    terms = linear_attn.chunk_terms(*padded)
    assert terms[-1].shape == (2, 4, 4, 16)        # a decay a key channel
    assert all(bool(jnp.isfinite(t).all()) for t in terms)
    o, s1 = linear_attn.chunk_pass(s0, terms)
    o = jnp.moveaxis(o, 1, 3).reshape(2, -1, 4, 16)[:, :200]
    numpy.testing.assert_allclose(o, jnp.stack(outs, 1), atol=1e-5)
    numpy.testing.assert_allclose(s1, s, atol=1e-5)


def test_one_decay_a_head_is_the_same_decay_on_every_channel():
    """The two forms of ``g`` meet: a decay per channel that is equal over a
    head's channels gives what the decay per head gives."""
    q, k, v, beta, _ = rule_inputs(5, 2, 128, "near0")
    g = -0.3 * jnp.abs(normal(numpy.random.default_rng(6), 2, 128, 4))
    wide = jnp.broadcast_to(g[..., None], g.shape + (16,))
    s0 = jnp.zeros((2, 4, 16, 16))
    o1, s1 = linear_attn.chunk_pass(
        s0, linear_attn.chunk_terms(q, k, v, beta, g))
    o2, s2 = linear_attn.chunk_pass(
        s0, linear_attn.chunk_terms(q, k, v, beta, wide))
    numpy.testing.assert_allclose(o2, o1, atol=1e-5)
    numpy.testing.assert_allclose(s2, s1, atol=1e-5)


def test_the_kernels_equal_their_twins():
    """With a decay per channel: ``gdn_decode`` against ``recurrent_step`` on
    the active lanes (the others' state bit for bit, their outputs 0),
    ``gdn_chunk`` against ``chunk_pass`` (a fresh lane from zeros, the slots
    not named untouched), at ``g`` of both ends."""
    q, k, v, beta, g = rule_inputs(3, 5, 128, "mixed")
    state = normal(numpy.random.default_rng(4), 5, 4, 16, 16)
    active = jnp.asarray([True, False, True, False, False])
    row = [y[:, 0] for y in (q, k, v, beta, g)]
    o, s = PK.gdn_decode(state, *row, active, interpret=True)
    o2, s2 = linear_attn.recurrent_step(state, *row)
    numpy.testing.assert_allclose(o[active], o2[active], atol=1e-5)
    numpy.testing.assert_allclose(s[active], s2[active], atol=1e-6)
    assert bool((s[~active] == state[~active]).all())
    assert not bool(o[~active].any())
    terms = linear_attn.chunk_terms(*(y[:2] for y in (q, k, v, beta, g)))
    slots, fresh = jnp.asarray([3, 1]), jnp.asarray([False, True])
    o, s = PK.gdn_chunk(state, slots, fresh, *terms, interpret=True)
    o2, s2 = linear_attn.chunk_pass(
        jnp.where(fresh[:, None, None, None], 0.0, state[slots]), terms)
    numpy.testing.assert_allclose(o, o2, atol=1e-5)
    numpy.testing.assert_allclose(s[slots], s2, atol=1e-5)
    rest = jnp.asarray([0, 2, 4])
    assert bool((s[rest] == state[rest]).all())


def test_the_gate_keeps_the_decay_above_its_lower_bound(weights):
    """``g = lower_bound * sigmoid(...)``: in (-5, 0) whatever the input,
    and the seeded heads reach both ends of it."""
    cfg = record()
    p = weights[1]["blocks"][0]["attn"]
    x = 3.0 * normal(numpy.random.default_rng(7), 2, 9, 64)
    _, _, beta, g = linear_attn._inputs(p, x, cfg, cached=False)
    assert g.shape == (2, 9, 4, 16) and beta.shape == (2, 9, 4)
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
    assert float(g.min()) < -4.5 and float(g.max()) > -0.05


# -------------------------------------------------------------- the router
def test_grouped_routing_against_the_reference_with_a_bias_that_flips():
    """8 groups of 4, the best 4 kept, 4 experts chosen: the program's choice
    and weights are the reference's; a bias that lifts a group into the kept
    ones changes the choice and never the weight of an expert."""
    rng = numpy.random.default_rng(11)
    m = normal(rng, 29, 64)
    p = {"router": 0.5 * normal(rng, 64, 32),
         "bias": 0.01 * normal(rng, 32)}
    cfg = record().moe
    z = ling3.sizes(SMALL)

    def dense(params):
        scores, idx, w = moe.route(params, m, cfg)
        assert idx.shape == (29, 4)
        got = jnp.zeros((29, 32)).at[jnp.arange(29)[:, None], idx].set(w)
        with jax.default_matmul_precision("highest"):
            want = ling3.route_all(m, params, z, None)
        numpy.testing.assert_allclose(got, want, atol=1e-6)
        return scores, idx

    scores, idx = dense(p)
    groups = numpy.asarray(idx) // 4
    assert all(len(set(row)) <= 4 for row in groups)
    # without groups the choice differs somewhere: the limit binds
    _, free, _ = moe.route(p, m, model_config.MoEConfig(
        router_width=32, top_k=4, score="sigmoid", route_norm=True,
        route_scale=2.5))
    assert not bool((jnp.sort(free, -1) == jnp.sort(idx, -1)).all())
    # a group most tokens dropped, lifted by its bias: kept by every token
    lost = [g for g in range(8) if (groups != g).all(1).sum() > 20][0]
    lifted = dict(p, bias=p["bias"].at[4 * lost:4 * lost + 4].add(1.0))
    scores2, idx2 = dense(lifted)
    numpy.testing.assert_array_equal(scores2, scores)
    assert bool(((numpy.asarray(idx2) // 4) == lost).any(1).all())


def test_the_selection_bias_comes_to_rest_on_an_even_load():
    """Rows with a component common to all tokens make every token's router
    favour the same experts; ``noaux_tc``'s update, run to rest by the
    reference's ``even_bias``, evens every expert's load on those rows and
    nearly on fresh ones of the same kind, and the chip's share of the
    assignments comes to its share of the experts."""
    z = ling3.sizes(SMALL)
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    common = jax.random.normal(keys[0], (64,))

    def rows(key, n):
        x = 0.7 * common + 0.7 * jax.random.normal(key, (n, 64))
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True))

    p = {"router": (0.125 * jax.random.normal(keys[1], (64, 32))),
         "bias": 0.01 * jax.random.normal(keys[2], (32,))}

    def load(bias, m):
        chosen = ling3.choose(jax.nn.sigmoid(m @ p["router"]) + bias, z)
        return jnp.zeros(32).at[chosen.reshape(-1)].add(1.0)

    seen, fresh = rows(keys[3], 2048), rows(keys[4], 2048)
    with jax.default_matmul_precision("highest"):
        before = load(p["bias"], seen)
        bias = ling3.even_bias(seen, p, z).astype(jnp.float32)
        after, after_fresh = load(bias, seen), load(bias, fresh)
    assert float(before.std() / before.mean()) > 0.5
    assert float(after.std() / after.mean()) < 0.02
    assert float(after_fresh.std() / after_fresh.mean()) < 0.1
    assert abs(float(after_fresh[8:16].sum() / after_fresh.sum()) - 0.25) \
        < 0.01
    assert abs(float(bias.mean())) < 1e-3


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of 8 held experts each (two groups of the eight),
    the shared expert counted ONCE, add up to the uncut reference's layer
    over all 32."""
    from veles_tpu.ops.attention import cfg_matmul
    from veles_tpu.ops.moe import gated_ffn, routed_ffn
    uncut = dict(SMALL, num_experts=32, held_experts=[0, 32])
    whole = jax.tree.map(lambda a: a.astype(jnp.float32),
                         ling3.make_weights(5, uncut))["blocks"][1]["moe"]
    m = normal(numpy.random.default_rng(9), 23, 64)
    with jax.default_matmul_precision("highest"):
        want = ling3.expert_layer(m, whole, ling3.sizes(uncut), None)
    cfg = record()
    total, held, seen = 0.0, 0, []
    for lo in range(0, 32, 8):
        share = dict(whole, **{k: whole[k][lo:lo + 8]
                               for k in ("w_gate", "w_up", "w_down")})
        share.pop("shared")
        part, stats = routed_ffn(share, m, dataclasses.replace(
            cfg.moe, held=(lo, 8), shared=False))
        total, held = total + part, held + int(stats[0])
        seen.append(int(stats[0]))
    assert held == 23 * 4                  # every assignment held once
    assert len(set(seen)) > 1              # a chip's part is none or several
    mm = lambda a, b: cfg_matmul(cfg, a, b)  # noqa: E731
    shared = gated_ffn(whole["shared"], m, mm)
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
        ref = ling3.logits(w, seq, numpy.arange(len(p) - 1, len(seq) - 1),
                           SMALL)
        gap = ref.max(-1) - ref[numpy.arange(len(o)), o]
        assert float(gap.max()) <= 1e-4


@pytest.mark.parametrize("features", [
    {}, {"slots": 16, "attn_kernel": "force", "prefill_chunk": 8,
         "paged_kv": 96, "max_len": 96}],
    ids=["xla", "kernels"])
def test_engine_serves_the_references_tokens(weights, features):
    """Through ``LMEngine`` (admission, chunked prefill interleaved with
    decode, lanes re-admitted, the live-width ladder, the pipelined driver):
    every served token is the reference's choice; state slots and pages come
    home; the gauges count the latent layer's rows and the six layers' state;
    the step's counts reach counters and recorder."""
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
        # six KDA layers: 4 x 16 x 16 float32 and 3 rows of 3 x 64 channels
        assert g["state_bytes_per_lane"] == 6 * (4 * 1024 + 4 * 3 * 192)
        # ONE latent layer: a row of 128 lanes (32 + 8 numbers, padded)
        assert g["kv_bytes_per_token"] == 128 * 4
        assert g["kv_storage_in_place"] == 1
        assert c.get("kv_storage_rebuilds", 0) == 0
        assert c["state_resets"] == len(prompts)
        steps = c["decode_dispatches"]
        assert c["dispatches_sent_ahead"] + c["pipeline_drains"] == steps
        held = c["moe_assignments_held"]
        assert held + c["moe_assignments_elsewhere"] \
            == steps * eng.slots * 4 * 6
        assert 0 < held < c["moe_assignments_elsewhere"]
        turns = eng.recorder.turns()
        assert int(turns[:, tracing.COL_MOE_HIT].sum()) == c["moe_experts_hit"]
        assert int(turns[:, tracing.COL_MOE_HELD].sum()) == held
        if eng._kernel_active:
            # the latent decode walk is handed only what it walks (ISSUE
            # 41); the chunks count the table's width
            pages = c["attn_page_steps"], c["attn_page_steps_live"]
            assert 0 < pages[1] < pages[0]
            assert int(turns[:, tracing.COL_ATTN_STEPS].sum()) == pages[0]
            assert int(turns[:, tracing.COL_ATTN_LIVE].sum()) == pages[1]
    finally:
        eng.stop()


def test_a_lane_holds_state_for_six_layers_and_pages_of_one_pool(weights):
    """The storage by layer: (state, tail) for a KDA layer, ONE pool of
    latent rows for the MLA layer; every leaf that goes into a dispatch is
    consumed (ISSUE 27's rule)."""
    _, wf = weights
    eng = engine(wf, slots=2)
    storage = eng._storage()
    assert [len(layer) for layer in storage] == [2, 2, 2, 2, 1, 2, 2]
    assert storage[MLA_LAYER][0].shape == (33, 1, 16, 128)
    assert [a.shape for a in storage[0]] == [(2, 4, 16, 16), (2, 3, 192)]
    assert storage[0][0].dtype == jnp.float32
    made = [a for layer in storage for a in layer]
    assert eng.kv_bytes_resident() == sum(a.nbytes for a in made)
    assert eng._layers_of_kind == [(model_config.FULL, 1)]
    eng.start()
    try:
        assert all(a.is_deleted() for a in made)
        assert len(eng.submit(tokens(19, 5), 9).result(timeout=120)) == 9
        assert eng.metrics.counter("kv_storage_rebuilds") == 0
    finally:
        eng.stop()


@pytest.mark.parametrize("option, match", [
    ({"spec_k": 2}, "spec_k"), ({"prefix_cache": 4}, "prefix_cache"),
    ({"megastep": 2}, "megastep"), ({"tp": 2}, "tp >= 2")])
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


# -------------------------------------------------------------- the record
def test_record_from_the_published_keys():
    cfg = record()
    assert cfg.block == "pre_rms" and cfg.kinds == (model_config.FULL,)
    assert cfg.state_layers == (0, 1, 2, 3, 5, 6)
    assert cfg.latent.q_rank is None and cfg.latent.head_gate
    assert cfg.latent.row == 128
    lin = cfg.linear
    assert (lin.decay, lin.gate, lin.lower_bound) == ("channel", "sigmoid",
                                                     -5.0)
    assert lin.conv_width == 3 * 64
    assert (cfg.moe.n_group, cfg.moe.topk_group) == (8, 4)
    assert cfg.moe.held == (8, 8) and cfg.moe.router_width == 32
    assert cfg.ffn_kinds == ("dense",) + ("moe",) * 6
    assert not cfg.layer_rope(0) and cfg.layer_rope(MLA_LAYER)
    # the pattern derived from layer_group_size, as published
    derived = record(layer_types=None, num_hidden_layers=12)
    assert derived.state_layers == (0, 1, 2, 3, 4, 6, 7, 8, 9, 10)


@pytest.mark.parametrize("over, match", [
    ({"expert_swiglu_limit_list": [0, 0, 0, 0, 0, 0, 4]},
     "expert_swiglu_limit_list"),
    ({"share_expert_swiglu_limit_list": [0, 0, 0, 0, 0, 5, 7]},
     "share_expert_swiglu_limit_list"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"kda_safe_gate": False}, "kda_safe_gate"),
    ({"use_kda_lora": True}, "use_kda_lora"),
    ({"gated_attention_proj_granularity_type": "elementwise"},
     "granularity"),
    ({"kda_lower_bound": -8}, "lower_bound"),
    ({"topk_group": 9}, "topk_group")])
def test_the_record_refuses_what_it_cannot_compute(over, match):
    with pytest.raises(ValueError, match=match):
        record(**over)


def test_the_records_own_rules():
    """Latent attention and linear layers share a stack only by kinds; a
    decay per channel needs its bound; the groups divide the router."""
    lat = model_config.LatentConfig(None, 32, 16, 8, 16)
    with pytest.raises(ValueError, match="beside linear layers"):
        model_config.ModelConfig(4, block="pre_rms", latent=lat,
                                 attn_kinds=("full", "full"))
    with pytest.raises(ValueError, match="come together"):
        model_config.LinearConfig(4, 4, 16, 16, decay="channel")
    with pytest.raises(ValueError, match="n_group"):
        model_config.MoEConfig(router_width=30, top_k=2, n_group=4,
                               topk_group=2)
    with pytest.raises(ValueError, match="two best"):
        model_config.MoEConfig(router_width=8, top_k=2, n_group=8,
                               topk_group=2)


def test_the_configuration_file_carries_the_published_widths():
    """Every number of the catalog's row under its own key, but for the
    keys listed as reduced; the record reads the file as it stands."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash-vl-ep4.json")) as f:
        cfg = json.load(f)
    published = {
        "hidden_size": 2560, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_key_value_heads": 32, "rope_theta": 6000000, "head_dim": 128,
        "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
        "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
        "short_conv_kernel_size": 4, "kda_lower_bound": -5,
        "rotary_dim": 64, "partial_rotary_factor": 0.5}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "max_position_embeddings", "expert_swiglu_limit_list",
        "share_expert_swiglu_limit_list"}
    assert cfg["router_width"] == 512 and cfg["held_experts"] == [0, 128]
    rec = model_config.from_published(cfg)
    assert rec.state_layers == (0, 1, 2, 3, 5, 6)
    assert rec.ffn_kinds == ("dense",) + ("moe",) * 6
    assert rec.latent.row == 640 and rec.dtype == "bfloat16"
    small = model_config.from_published(dict(cfg, **cfg["rehearsal"]))
    assert small.dtype == "float32" and small.moe.n_group == 8
