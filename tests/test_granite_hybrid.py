"""ISSUE 46: a ``pre_rms`` stack of Mamba-2 state-space layers (the linear
kind's slots under another rule: ``ops/linear_attn.py`` with ``rule ==
"ssd"``) and plain grouped-query attention layers WITHOUT any position signal,
a dense gated feed forward after every mixer, Granite's four multipliers and
a tied head, against the benchmark's plain reference
``benchmark/reference/granite_hybrid.py`` (float32, the recurrence token by
token, imports nothing of veles_tpu); and the engine's lane: a slot of state
for three layers of four beside a page table over one layer's k and v pools.

Tolerances: the program in float32 and the reference compute the same sums in
another order (the chunked rule against the recurrent one, paged attention
against a whole softmax), so logits agree to float32 roundoff (1e-4 on logits
of magnitude 1; the greedy tokens are then the reference's own, gap 0), and a
part of the mathematics left out moves them by 2e-3 or more."""

import json
import os

import numpy
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import granite_hybrid as ref
from veles_tpu import model_config
from veles_tpu.ops import linear_attn
from veles_tpu.ops import pallas_kernels as PK

PAGE = 8
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: two Mamba layers, one attention layer, one more Mamba layer; two groups of
#: four heads (one B and C serving several heads), 4 query heads on 2
SMALL = {
    "model_type": "granitemoehybrid", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "shared_intermediate_size": 96,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_n_groups": 2,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "mamba_chunk_size": 256, "attention_bias": False,
    "embedding_multiplier": 12, "attention_multiplier": 0.03125,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "position_embedding_type": "nope", "tie_word_embeddings": True,
    "num_local_experts": 0, "num_experts_per_tok": 0, "rms_norm_eps": 1e-5,
    "normalization_function": "rmsnorm", "hidden_act": "silu",
    "rope_scaling": None, "rope_theta": 10000, "num_hidden_layers": 4,
    "layer_types": ["mamba"] * 2 + ["attention"] + ["mamba"],
    "vocab_size": 96, "max_position_embeddings": 128,
    "initializer_std": 0.1, "dtype": "float32",
}
ATTENTION_LAYER = 2
PARTS = ("embedding_multiplier", "attention_multiplier",
         "residual_multiplier", "logits_scaling", "conv_bias", "D",
         "gate_first")


def record(**over):
    return model_config.from_published(dict(SMALL, **over))


@pytest.fixture(scope="module")
def weights():
    """(the reference's bfloat16-valued tree, the same raised to float32)."""
    w = ref.make_weights(3, SMALL)
    return w, jax.tree.map(lambda a: a.astype(jnp.float32), w)


def tokens(n, seed=0):
    return numpy.random.default_rng(seed).integers(0, SMALL["vocab_size"], n)


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def empty_storage(cfg, lanes, pages):
    """What the engine keeps a layer: (state, tail) of ``lanes`` slots for a
    Mamba layer, (k pool, v pool) for the attention layer."""
    state, tail = cfg.linear.state_shapes(lanes)
    pool = (pages + 1, cfg.n_kv_heads, PAGE, cfg.head_dim)
    return [(jnp.zeros(state), jnp.zeros(tail))
            if cfg.kind(i) == model_config.LINEAR
            else (jnp.zeros(pool), jnp.zeros(pool))
            for i in range(len(cfg.attn_kinds))]


# ------------------------------------------------------------ the forward
@pytest.mark.parametrize("length", [9, 203])
def test_whole_forward_matches_the_reference(weights, length):
    """Less than one inner chunk, and two with the second padded: the
    chunked rule against the reference's token-by-token rule."""
    from veles_tpu.ops.transformer import transformer_forward
    w, wf = weights
    toks = tokens(length)
    want = ref.logits(w, toks, numpy.arange(length), SMALL)
    got = transformer_forward(wf, jnp.asarray(toks)[None], record())[0]
    numpy.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("part", PARTS)
def test_a_part_left_out_moves_the_logits(weights, part):
    """Each of the four multipliers, the convolution's bias, ``D`` and the
    gate-before-norm order is IN the program: the reference without it lies
    twenty tolerances away or more."""
    from veles_tpu.ops.transformer import transformer_forward
    w, wf = weights
    toks = tokens(40, 1)
    got = transformer_forward(wf, jnp.asarray(toks)[None], record())[0]
    without = ref.logits(w, toks, numpy.arange(40), SMALL, leave_out=(part,))
    assert float(jnp.abs(got - without).max()) > 2e-3


# ------------------------------------------------------- the rule's orders
@pytest.mark.parametrize("length", [5, 128, 300])
def test_recurrent_chunked_and_reference_agree_row_for_row(weights, length):
    """One Mamba layer three ways: the reference's mixer (token by token),
    the chunked order over the whole sequence, and the recurrent order a row
    at a time through state and tail; lengths under, at and between
    multiples of the inner chunk."""
    w, wf = weights
    cfg = record()
    z = ref.sizes(SMALL)
    u = normal(numpy.random.default_rng(length), length, 64)
    with jax.default_matmul_precision("highest"):
        want = ref.mamba_mixer(u, w["blocks"][0]["attn"], z, None)
    p = wf["blocks"][0]["attn"]
    chunked = linear_attn.linear_forward(p, u[None], cfg)[0]
    numpy.testing.assert_allclose(chunked, want, atol=2e-5)

    state, tail = (jnp.zeros(s) for s in cfg.linear.state_shapes(1))

    @jax.jit
    def row(x, state, tail):
        return linear_attn.linear_paged_chunk_step(
            p, x[None, None], state, tail, cfg, jnp.ones((1,), jnp.int32))

    rows = []
    for t in range(min(length, 24)):
        o, state, tail = row(u[t], state, tail)
        rows.append(o[0, 0])
    numpy.testing.assert_allclose(jnp.stack(rows), want[:len(rows)],
                                  atol=2e-5)


def rule_inputs(seed, b, length, h=8, groups=2, dk=16, dv=16):
    """C, B (one a group), x, dt and g of ``length`` rows of ``b``
    sequences: decays from forgetting within a token (g of -16 and under) to
    keeping thousands."""
    rng = numpy.random.default_rng(seed)
    dt = jax.nn.softplus(normal(rng, b, length, h) - 2.0)
    a = jnp.asarray(rng.uniform(0.02, 16.0, h), jnp.float32)
    return (normal(rng, b, length, groups, dk),
            normal(rng, b, length, groups, dk),
            normal(rng, b, length, h, dv), dt, -a * dt)


def of_heads(y, h=8):
    return jnp.repeat(y, h // y.shape[-2], axis=-2)


@pytest.mark.parametrize("length", [128, 200])
def test_the_chunked_rule_equals_the_recurrent_one(length):
    """From a state that is not zero, the last inner chunk padded with rows
    whose dt and g are 0: the two orders agree, and every term is finite
    though a chunk's cumulative decay passes -300 (``exp`` of its negation
    alone would overflow float32)."""
    q, k, v, dt, g = rule_inputs(1, 2, length)
    q, k = of_heads(q), of_heads(k)
    s0 = normal(numpy.random.default_rng(2), 2, 8, 16, 16)
    s, outs = s0, []
    for t in range(length):
        o, s = linear_attn.recurrent_step(s, q[:, t], k[:, t], v[:, t],
                                          dt[:, t], g[:, t], correct=False)
        outs.append(o)
    pad = [(0, 0), (0, -length % linear_attn.SSD_CHUNK)]
    padded = [jnp.pad(y, pad + [(0, 0)] * (y.ndim - 2))
              for y in (q, k, v, dt, g)]
    terms = linear_attn.chunk_terms(*padded, correct=False,
                                    chunk=linear_attn.SSD_CHUNK)
    assert terms[0] is None and float(jnp.cumsum(g, 1).min()) < -300
    assert all(bool(jnp.isfinite(t).all()) for t in terms[1:])
    o, s1 = linear_attn.chunk_pass(s0, terms)
    o = jnp.moveaxis(o, 1, 3).reshape(2, -1, 8, 16)[:, :length]
    # (sums of a hundred terms of magnitude 10: relative roundoff)
    numpy.testing.assert_allclose(o, jnp.stack(outs, 1), rtol=1e-4,
                                  atol=2e-5)
    numpy.testing.assert_allclose(s1, s, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("groups", [1, 2])
def test_the_kernels_equal_their_twins(groups):
    """In interpret mode, on the PACKED state (the heads of a group side by
    side in a row): ``gdn_decode`` without the correction against
    ``recurrent_step`` on the active lanes (the others' state bit for bit,
    their outputs 0); ``ssd_chunk`` against ``chunk_pass`` (a fresh lane from
    zeros, the slots not named untouched)."""
    lin = model_config.LinearConfig(groups, 8, 16, 16, rule="ssd")
    assert lin.pack == 8 // groups
    length = 2 * linear_attn.SSD_CHUNK
    q, k, v, dt, g = rule_inputs(3, 5, length, groups=groups)
    apart = normal(numpy.random.default_rng(4), 5, 8, 16, 16)
    state = linear_attn._packed(apart, lin)
    assert state.shape == lin.state_shapes(5)[0]
    assert bool((linear_attn._apart(state, lin) == apart).all())
    active = jnp.asarray([True, False, True, False, False])
    row = [y[:, 0] for y in (q, k, v, dt, g)]
    o, s = PK.gdn_decode(state, *row, active, correct=False, interpret=True)
    o2, s2 = linear_attn.recurrent_step(
        apart, of_heads(row[0]), of_heads(row[1]), *row[2:], correct=False)
    s = linear_attn._apart(s, lin)
    numpy.testing.assert_allclose(o[active], o2[active], atol=1e-5)
    numpy.testing.assert_allclose(s[active], s2[active], atol=1e-6)
    assert bool((s[~active] == apart[~active]).all())
    assert not bool(o[~active].any())

    two = [y[:2] for y in (q, k, v, dt, g)]
    slots, fresh = jnp.asarray([3, 1]), jnp.asarray([False, True])
    o, s = PK.ssd_chunk(state, slots, fresh, *two,
                        chunk=linear_attn.SSD_CHUNK, interpret=True)
    terms = linear_attn.chunk_terms(
        of_heads(two[0]), of_heads(two[1]), *two[2:], correct=False,
        chunk=linear_attn.SSD_CHUNK)
    o2, s2 = linear_attn.chunk_pass(
        jnp.where(fresh[:, None, None, None], 0.0, apart[slots]), terms)
    o2 = jnp.moveaxis(o2, 1, 3).reshape(2, length, 8, 16)
    s = linear_attn._apart(s, lin)
    numpy.testing.assert_allclose(o, o2, atol=2e-5)
    numpy.testing.assert_allclose(s[slots], s2, atol=2e-5)
    rest = jnp.asarray([0, 2, 4])
    assert bool((s[rest] == apart[rest]).all())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b, c", [(64, 1), (1, 300)],
                         ids=["step", "ragged chunk"])
def test_the_gated_norm_kernel_equals_its_twin(b, c, dtype):
    """ISSUE 47: ``_output`` handed the convolution's whole output (what the
    serving kernels' caller does) runs ``pallas_kernels.gated_rms_norm``, in
    interpret mode here, and equals the ``jax.numpy`` branch on the heads'
    inputs sliced out of it: a decode step's 64 rows in one block, and a
    chunk whose 300 rows leave a last block of 44.  ``W_o`` is the identity,
    so what is compared is the normalised row itself: float32 to 1e-6 of the
    largest output (a skip that cancels ``o`` is rounded once where the
    compiler fuses the multiply into the add), bfloat16 to one unit in the
    last place."""
    cfg = record(hidden_size=256, mamba_d_head=64, mamba_n_groups=1,
                 mamba_d_state=128, dtype=dtype)
    lin = cfg.linear
    width = lin.value_width
    assert (width, lin.conv_width) == (512, 768)
    rng = numpy.random.default_rng(47)
    p = {"D": normal(rng, lin.v_heads), "norm": 1 + 0.1 * normal(rng, width),
         "wo": jnp.eye(width, dtype=dtype)}
    act = normal(rng, b, c, lin.conv_width)
    o = normal(rng, b, c, lin.v_heads, lin.v_dim)
    z = normal(rng, b, c, width).astype(dtype)
    want = numpy.asarray(linear_attn._output(
        p, o, z, cfg, act[..., :width].reshape(o.shape)), numpy.float32)
    got = linear_attn._output(p, o, z, cfg, act)
    assert got.dtype == z.dtype and got.shape == want.shape
    off = numpy.abs(numpy.asarray(got, numpy.float32) - want)
    if dtype == "float32":
        assert off.max() <= 1e-6 * numpy.abs(want).max()
    else:
        ulp = 2.0 ** (numpy.floor(numpy.log2(numpy.abs(want) + 1e-30)) - 7)
        assert (off <= ulp).all()


def test_a_padded_row_moves_no_state(weights):
    """A chunk of 8 rows of which 5 are real leaves the state and tail that
    the 5 rows alone leave, whatever ids lie behind them; ``rows`` 0 hands
    both back bit for bit."""
    _, wf = weights
    cfg = record()
    p = wf["blocks"][0]["attn"]
    x = normal(numpy.random.default_rng(8), 1, 8, 64)
    state, tail = (1.0 + jnp.zeros(s) for s in cfg.linear.state_shapes(1))

    @jax.jit
    def run(x, rows):
        return linear_attn.linear_paged_chunk_step(
            p, x, state, tail, cfg, jnp.asarray([rows]))

    o5, s5, t5 = run(x[:, :5], 5)
    o8, s8, t8 = run(x, 5)
    numpy.testing.assert_allclose(o8[:, :5], o5, atol=1e-6)
    numpy.testing.assert_allclose(s8, s5, atol=1e-6)
    assert bool((t8 == t5).all())
    _, s0, t0 = run(x, 0)
    assert bool((s0 == state).all()) and bool((t0 == tail).all())


# ------------------------------------------------- pages and state slots
@pytest.mark.parametrize("kernel", [None, "kernel"])
def test_paged_prefill_then_decode_matches_the_reference(weights, kernel):
    """Prefill by chunks of a page (the chunked rule, state and tail carried
    from chunk to chunk; attention over the pools; the last chunk PADDED:
    the prompt is no multiple of the chunk), then single steps (the
    recurrent rule) through state and pages: the logits of every decoded
    position are the reference's over the whole sequence.  The lane is slot
    1 of two; slot 0 rides the steps without decoding and keeps its bits."""
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
    want = ref.logits(w, seq, numpy.arange(prompt_len - 1, 43), SMALL)
    numpy.testing.assert_allclose(jnp.stack(got), want, atol=1e-4)
    after = [layer for i, layer in enumerate(pools)
             if cfg.kind(i) == model_config.LINEAR]
    for (s0, t0), (s1, t1) in zip(idle, after):
        assert bool((s0[0] == s1[0]).all()) and bool((t0[0] == t1[0]).all())
        assert not bool((s0[1] == s1[1]).all())


# -------------------------------------------------------------- the engine
def engine(wf, **over):
    from veles_tpu.serving import LMEngine
    return LMEngine(wf, record(), **dict(
        {"max_len": 128, "slots": 4, "paged_kv": 32, "prefill_chunk": 16},
        **over))


def assert_served_the_references(w, prompts, outs):
    for p, o in zip(prompts, outs):
        seq = numpy.concatenate([p, o])
        want = ref.logits(w, seq, numpy.arange(len(p) - 1, len(seq) - 1),
                          SMALL)
        gap = want.max(-1) - want[numpy.arange(len(o)), o]
        assert float(gap.max()) <= 1e-4


@pytest.mark.parametrize("features", [
    {}, {"slots": 16, "attn_kernel": "force", "prefill_chunk": 8,
         "paged_kv": 96, "max_len": 96}],
    ids=["xla", "kernels"])
def test_engine_serves_the_references_tokens(weights, features):
    """Through ``LMEngine`` (admission, chunked prefill interleaved with
    decode, lanes re-admitted, the live-width ladder, the pipelined driver):
    every served token is the reference's choice; state slots and pages come
    home; the gauges count the attention layer's rows and the three layers'
    state and tail."""
    w, wf = weights
    eng = engine(wf, **features).start()
    try:
        prompts = [tokens(n, 10 + n) for n in (5, 21, 40, 16, 70)]
        outs = [f.result(timeout=600)
                for f in [eng.submit(p, 12) for p in prompts]]
        assert_served_the_references(w, prompts, outs)
        assert eng.verify_pool_invariants()["used_pages"] == 0
        snap = eng.metrics.snapshot()
        g, c = snap["gauges"], snap["counters"]
        assert g["kv_pages_free"] == g["kv_pages_total"]
        assert g["state_slots_free"] == g["state_slots_total"] == eng.slots
        # three Mamba layers: 8 x 16 x 16 float32 and 3 rows of 192 channels
        assert g["state_bytes_per_lane"] == 3 * (4 * 2048 + 4 * 3 * 192)
        # ONE attention layer: k and v of 2 heads of 16
        assert g["kv_bytes_per_token"] == 2 * 2 * 16 * 4
        assert g["kv_storage_in_place"] == 1
        assert c.get("kv_storage_rebuilds", 0) == 0
        assert c["state_resets"] == len(prompts)
        steps = c["decode_dispatches"]
        assert c["dispatches_sent_ahead"] + c["pipeline_drains"] == steps
        if eng._kernel_active:
            pages = c["attn_page_steps"], c["attn_page_steps_live"]
            assert 0 < pages[1] < pages[0]
    finally:
        eng.stop()


def test_a_lane_holds_state_for_three_layers_and_pages_of_one(weights):
    """The storage by layer: (state, tail) for a Mamba layer, the heads of a
    group packed side by side in a row, (k pool, v pool) for the attention
    layer; every leaf that goes into a dispatch is consumed."""
    _, wf = weights
    eng = engine(wf, slots=2)
    storage = eng._storage()
    assert [len(layer) for layer in storage] == [2] * 4
    assert [a.shape for a in storage[ATTENTION_LAYER]] \
        == [(33, 2, 16, 16)] * 2
    assert [a.shape for a in storage[0]] == [(2, 2, 16, 64), (2, 3, 192)]
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


# -------------------------------------------------------------- the record
def test_record_from_the_published_keys():
    cfg = record()
    assert cfg.block == "pre_rms" and cfg.kinds == (model_config.FULL,)
    assert cfg.state_layers == (0, 1, 3)
    lin = cfg.linear
    assert (lin.rule, lin.decay, lin.gate) == ("ssd", "head", "silu")
    assert (lin.k_heads, lin.v_heads, lin.k_dim, lin.v_dim) == (2, 8, 16, 16)
    assert lin.conv_width == 8 * 16 + 2 * 2 * 16 and lin.pack == 4
    assert cfg.ffn_kinds == ("dense",) * 4 and cfg.moe is None
    assert not any(cfg.layer_rope(i) for i in range(4))
    assert (cfg.embed_mult, cfg.residual_mult, cfg.attn_scale,
            cfg.logits_div) == (12.0, 0.22, 0.03125, 8.0)
    assert cfg.tied and cfg.plain_full and cfg.head_dim == 16
    # 1/32 for a head of 16 where the plain scale is 1/4 (the published
    # model: 1/64 for 1/8)
    assert cfg.query_scale(16) == 0.125


@pytest.mark.parametrize("over, match", [
    ({"num_local_experts": 8}, "num_local_experts"),
    ({"num_experts_per_tok": 2}, "num_experts_per_tok"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"layer_types": ["mamba"] * 3 + ["sliding"]}, "layer_types"),
    ({"mamba_n_groups": 3}, "k_heads")])
def test_the_record_refuses_what_it_cannot_compute(over, match):
    with pytest.raises(ValueError, match=match):
        record(**over)


def test_the_records_own_rules():
    """The state-space rule decays by head under a silu gate; the
    multipliers belong to a pre_rms stack of one stream."""
    with pytest.raises(ValueError, match="state-space rule"):
        model_config.LinearConfig(1, 4, 16, 16, rule="ssd", gate="sigmoid")
    with pytest.raises(ValueError, match="multipliers"):
        model_config.ModelConfig(4, residual_mult=0.5)
    # a pack never straddles two groups, and a wide head lies alone
    assert model_config.LinearConfig(4, 8, 16, 16, rule="ssd").pack == 2
    assert model_config.LinearConfig(1, 8, 16, 128, rule="ssd").pack == 1
    assert model_config.LinearConfig(1, 64, 128, 64, rule="ssd") \
        .state_shapes(3) == ((3, 32, 128, 128), (3, 3, 4352))


def test_the_configuration_file_carries_the_published_widths():
    """Every number of the catalog's row under its own key, but for the one
    key listed as reduced; the record reads the file as it stands."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["layer_types"] == [
        "attention" if i % 10 == 5 else "mamba" for i in range(40)]
    assert cfg["reduced"] == ["max_position_embeddings"]
    rec = model_config.from_published(cfg)
    assert len(rec.state_layers) == 36 and rec.dtype == "bfloat16"
    assert rec.linear.state_shapes(64)[0] == (64, 32, 128, 128)
    assert rec.query_scale(64) == 0.125
    small = model_config.from_published(dict(cfg, **cfg["rehearsal"]))
    assert small.dtype == "float32" and small.linear.k_heads == 1
