"""ISSUE 34: the ``pre_rms`` block (latent attention under YaRN, an n-stream
mHC residual, the routed layer of ``ops/moe.py``) against the benchmark's
plain reference ``benchmark/reference/xing4.py`` (float32, expanded attention
only, no cache, no kernel, imports nothing of veles_tpu), and the engine's
latent pool: ONE array a layer whose rows are ``(c_kv, k_rope)``, written
through the page table, read absorbed in decode and expanded in prefill.

Tolerances: the program in float32 and the reference compute the same sums in
another order, so logits agree to float32 roundoff (1e-4 on logits of
magnitude 3; the greedy tokens are then the reference's own, gap 0)."""

import numpy
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import xing4
from veles_tpu import model_config
from veles_tpu.ops import hyper, latent

PAGE = 8

SMALL = {
    "model_type": "xing4_0", "hidden_size": 64, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "vocab_size": 96, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rope_scaling": {"type": "yarn", "factor": 4, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16},
    "initializer_std": 0.1, "dtype": "float32",
}


def record(**over):
    return model_config.from_published(dict(SMALL, **over))


@pytest.fixture(scope="module")
def weights():
    """(the reference's bfloat16-valued tree, the same raised to float32)."""
    w = xing4.make_weights(3, SMALL)
    return w, jax.tree.map(lambda a: a.astype(jnp.float32), w)


def tokens(n, seed=0):
    return numpy.random.default_rng(seed).integers(0, SMALL["vocab_size"], n)


def test_whole_forward_matches_the_reference(weights):
    from veles_tpu.ops.transformer import transformer_forward
    w, wf = weights
    toks = tokens(40)
    ref = xing4.logits(w, toks, numpy.arange(40), SMALL)
    got = transformer_forward(wf, jnp.asarray(toks)[None], record())[0]
    numpy.testing.assert_allclose(got, ref, atol=1e-4)


def test_the_reference_in_blocks_is_the_reference_whole(weights, monkeypatch):
    """At the cell's size the reference runs its row-wise parts and its
    attention a block at a time (``ROWS``); the sums are the same."""
    w, _ = weights
    toks = tokens(40, 7)
    whole = xing4.logits(w, toks, numpy.arange(40), SMALL)
    monkeypatch.setattr(xing4, "ROWS", 8)
    jax.clear_caches()
    blocks = xing4.logits(w, toks, numpy.arange(40), SMALL)
    jax.clear_caches()
    numpy.testing.assert_allclose(blocks, whole, atol=2e-5)


@pytest.mark.parametrize("kernel", [None, "kernel"])
def test_paged_prefill_then_decode_matches_the_reference(weights, kernel):
    """Prefill by chunks (expanded), then single steps (absorbed), through
    the paged latent pool: the logits of every decoded position are the
    reference's over the whole sequence; contexts over five pages."""
    from veles_tpu.ops.transformer import head_logits, paged_chunk_apply
    w, wf = weights
    cfg = record()
    seq = tokens(44, 2)
    prompt_len, max_pages = 24, 6
    pools = [(jnp.zeros((max_pages + 1, 1, PAGE, cfg.latent.row)),)
             for _ in range(3)]
    table = jnp.arange(1, max_pages + 1, dtype=jnp.int32)[None]

    @jax.jit
    def apply(pools, chunk, pos):
        h, new = paged_chunk_apply(
            wf, chunk[None], pools, table, pos, cfg,
            attn_kernel=({1: "decode", PAGE: "prefill"}[chunk.shape[0]]
                         if kernel else None))
        return head_logits(wf, h, cfg)[0], new

    for pos in range(0, prompt_len, PAGE):
        logits, pools = apply(pools, jnp.asarray(seq[pos:pos + PAGE]),
                              jnp.asarray([pos]))
    got = [logits[-1]]
    for pos in range(prompt_len, 43):
        logits, pools = apply(pools, jnp.asarray(seq[pos:pos + 1]),
                              jnp.asarray([pos]))
        got.append(logits[0])
    assert all(len(layer) == 1 for layer in pools)   # ONE pool a layer
    ref = xing4.logits(w, seq, numpy.arange(prompt_len - 1, 43), SMALL)
    numpy.testing.assert_allclose(jnp.stack(got), ref, atol=1e-4)


def test_absorbed_equals_expanded(weights, dtype="float32", atol=2e-5):
    """The two orders of the same sums, over the same cached rows, agree to
    float32 roundoff.  (In bfloat16 they round in different places, the
    absorbed queries at ``kv_rank`` width and the expanded keys at ``nope``:
    the chip smoke's latent phase holds the kernels' engine to the XLA
    twin's there; the CPU has no bfloat16 batched dot.)"""
    cfg = record(dtype=dtype)
    p = weights[1]["blocks"][1]["attn"]
    rng = numpy.random.default_rng(5)
    x = jnp.asarray(rng.normal(0, 1, (2, 21, 64)), dtype)
    pos = jnp.arange(21)
    cos, sin = latent.rotary(cfg, pos)
    q_nope, q_rope = latent.queries(p, x, cfg, cos, sin)
    rows = latent.latent_rows(p, x, cfg, cos, sin)
    assert rows.shape == (2, 21, cfg.latent.row)
    assert not jnp.any(rows[..., cfg.latent.width:])
    from veles_tpu.ops.attention import chunk_live_mask
    live = chunk_live_mask(0, 21, 21)[None, None]
    a = latent.attend_absorbed(p, q_nope, q_rope, rows, live, cfg)
    e = latent.attend_expanded(p, q_nope, q_rope, rows, live, cfg)
    numpy.testing.assert_allclose(numpy.asarray(a, numpy.float32),
                                  numpy.asarray(e, numpy.float32), atol=atol)


def test_yarn_against_a_hand_computed_table():
    """rope 8, theta 10000, factor 4 over 16 original positions, beta 32 /
    1: the correction dimensions are floor(8 ln(16 / (32 x 2 pi)) / (2 ln
    1e4)) = -2 -> 0 and ceil(8 ln(16 / (2 pi)) / (2 ln 1e4)) = 1, so the
    ramp over i = 0..3 is 0, 1, 1, 1: the fastest frequency is kept and
    the three others are divided by 4."""
    yarn = model_config.YarnConfig(factor=4, original=16, mscale=1,
                                   mscale_all_dim=1)
    base = 10000.0 ** (-numpy.arange(4) / 4.0)
    numpy.testing.assert_allclose(
        latent.yarn_inv_freq(8, 10000.0, yarn),
        [base[0], base[1] / 4, base[2] / 4, base[3] / 4], rtol=1e-12)
    numpy.testing.assert_allclose(latent.yarn_inv_freq(8, 10000.0, None),
                                  base, rtol=1e-12)
    cfg = record()
    m = 0.1 * numpy.log(4.0) + 1.0
    assert latent.softmax_scale(cfg) == pytest.approx(24 ** -0.5 * m * m)
    cos, sin = latent.rotary(cfg, jnp.asarray([0, 3]))
    # mscale(4, 1) / mscale(4, 1) = 1: angles alone
    numpy.testing.assert_allclose(cos[1], numpy.cos(3 * numpy.asarray(
        [base[0], base[1] / 4, base[2] / 4, base[3] / 4])), rtol=1e-5)
    # the reference computes the same frequencies on its own
    numpy.testing.assert_allclose(
        xing4.yarn_inv_freq(xing4.sizes(SMALL)),
        latent.yarn_inv_freq(8, 10000.0, yarn), rtol=1e-6)


def test_h_res_is_doubly_stochastic_and_not_the_identity(weights):
    cfg = record()
    hc = weights[1]["blocks"][0]["hc_attn"]
    x = jnp.asarray(numpy.random.default_rng(6).normal(0, 1, (50, 4, 64)),
                    jnp.float32)
    pre, post, res = hyper.coefficients(hc, x, cfg)
    numpy.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-5)
    numpy.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)
    assert float(res.min()) > 0
    off = res - jnp.eye(4)
    assert float(jnp.abs(off).max(axis=(-1, -2)).min()) > 0.05
    assert float(res.std(0).max()) > 1e-3          # differs by token
    assert 0 < float(pre.min()) and float(pre.max()) < 1
    assert 0 < float(post.min()) and float(post.max()) < 2


def test_one_stream_is_the_plain_pre_norm_block(weights):
    """n = 1: H_res of one stream is 1 (but for hc_eps); with H_pre = 1
    (a large bias) and H_post = 1 (2 sigmoid(0)) the wiring is ``h + F(
    rms(h))``, the block of a record without ``hyper``."""
    from veles_tpu.ops.transformer import transformer_forward
    _, wf = weights
    one = {"proj": jnp.zeros((64, 3)), "a": jnp.zeros(3),
           "b": jnp.asarray([40.0, 0.0, 0.0])}
    tree = dict(wf, blocks=[dict(b, hc_attn=one, hc_mlp=one)
                            for b in wf["blocks"]])
    toks = jnp.asarray(tokens(17, 8))[None]
    streamed = transformer_forward(tree, toks, record(hc_mult=1))
    plain_cfg = record()
    plain_cfg = model_config.ModelConfig(**dict(
        {f: getattr(plain_cfg, f) for f in plain_cfg.__dataclass_fields__},
        hyper=None))
    plain = transformer_forward(wf, toks, plain_cfg)
    numpy.testing.assert_allclose(streamed, plain, atol=2e-4)


@pytest.mark.parametrize("features", [
    {"slots": 3, "prefill_chunk": 4},
    {"slots": 16, "attn_kernel": "force", "prefill_chunk": 8}],
    ids=["xla", "kernels_row_write"])
def test_engine_serves_the_references_tokens(weights, features,
                                             page_step_census):
    """Through ``LMEngine`` (admission, chunked prefill interleaved with
    decode, the live-width ladder, one latent pool a layer): every served
    token is the reference's choice, every page comes home, the storage is
    updated in place, and the step's counts reach counters and recorder."""
    from veles_tpu.serving import LMEngine, tracing
    w, wf = weights
    eng = LMEngine(wf, record(), max_len=48, paged_kv=96, **features).start()
    count = page_step_census(eng)
    try:
        prompts = [tokens(n, 10 + n) for n in (5, 17, 26, 9)]
        outs = [f.result(timeout=300)
                for f in [eng.submit(p, 22) for p in prompts]]
        for p, o in zip(prompts, outs):
            seq = numpy.concatenate([p, o])
            ref = xing4.logits(w, seq, numpy.arange(len(p) - 1, len(seq) - 1),
                               SMALL)
            gap = ref.max(-1) - ref[numpy.arange(len(o)), o]
            assert float(gap.max()) <= 1e-4
        assert eng.verify_pool_invariants()["used_pages"] == 0
        snap = eng.metrics.snapshot()
        g, c = snap["gauges"], snap["counters"]
        assert g["kv_pages_free"] == g["kv_pages_total"] == 96
        assert g["kv_storage_in_place"] == 1
        assert c.get("kv_storage_rebuilds", 0) == 0
        # a row of 40 numbers lies in 128 lanes: 512 bytes in float32, a
        # layer; three layers
        assert g["kv_bytes_per_token"] == 3 * 128 * 4
        steps = c["decode_dispatches"]
        assert c["moe_assignments_held"] == steps * eng.slots * 2 * 2
        assert c.get("moe_assignments_elsewhere", 0) == 0
        turns = eng.recorder.turns()
        assert int(turns[:, tracing.COL_MOE_HIT].sum()) == c["moe_experts_hit"]
        pages = (c.get("attn_page_steps"), c.get("attn_page_steps_live"))
        assert pages == (count() if eng._kernel_active else (None, None))
        assert int(turns[:, tracing.COL_ATTN_STEPS].sum()) == (pages[0] or 0)
        assert int(turns[:, tracing.COL_ATTN_LIVE].sum()) == (pages[1] or 0)
        if eng._kernel_active:
            assert 0 < pages[1] < pages[0]
    finally:
        eng.stop()


def test_dispatches_consume_the_latent_pools(weights):
    """ISSUE 27's rule for the latent kind: one pool a layer, every leaf
    that goes into a dispatch is consumed (none copied or held twice)."""
    from veles_tpu.serving import LMEngine
    _, wf = weights
    eng = LMEngine(wf, record(), max_len=64, slots=2, paged_kv=24,
                   prefill_chunk=PAGE)
    leaves = lambda: [a for layer in eng._storage() for a in layer]  # noqa
    made = leaves()
    assert len(made) == 3 and {a.shape for a in made} \
        == {(25, 1, PAGE, eng.cfg.latent.row)}
    eng.start()
    try:
        assert all(a.is_deleted() for a in made)
        warm, handed, real = leaves(), [], eng._step_jit

        def watched(p, storage, *args):
            handed.append([a for layer in storage for a in layer])
            return real(p, storage, *args)
        eng._step_jit = watched
        assert len(eng.submit(tokens(11, 5), 9).result(timeout=120)) == 9
        assert handed and all(a.is_deleted() for a in warm)
        assert all(a.is_deleted() for ls in handed for a in ls)
        assert not any(a.is_deleted() for a in leaves())
        assert eng.metrics.counter("kv_storage_rebuilds") == 0
    finally:
        eng.stop()


@pytest.mark.parametrize("option,match", [
    ({"prefix_cache": 8}, "prefix_cache"),
    # (spec_k is refused no longer: ISSUE 40, tests/test_joyai.py; a model
    # without a module takes the module's option for what it says)
    ({"spec_k": 2, "megastep": 2}, "megastep"),
    ({"megastep": 4}, "megastep"), ({"tp": 2}, "tp >= 2")])
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
    # ONE pool of latent rows a layer
    assert all(len(layer) == 1 for layer in eng._storage())


def test_the_contiguous_cached_path_refuses_latent(weights):
    from veles_tpu.ops.transformer import generate
    with pytest.raises(ValueError, match="no contiguous cache"):
        generate(weights[1], jnp.asarray(tokens(8))[None], 4, record(),
                 temperature=0.0, max_len=16)


def test_record_from_the_published_keys():
    cfg = record(dtype="bfloat16")
    assert cfg.block == "pre_rms" and cfg.kinds == (model_config.FULL,)
    assert not cfg.by_kind and cfg.wide and cfg.streams == 4
    lat = cfg.latent
    assert (lat.q_rank, lat.kv_rank, lat.nope, lat.rope, lat.v) \
        == (24, 32, 16, 8, 16)
    assert lat.width == 40 and lat.row == 128
    assert model_config.LatentConfig(768, 512, 128, 64, 128).row == 640
    assert cfg.yarn.factor == 4 and cfg.yarn.original == 16
    assert cfg.hyper == model_config.HyperConfig(4, 20, 1e-6, (-30.0, 30.0))
    assert cfg.ffn_kinds == ("dense", "moe", "moe")
    assert cfg.moe.held is None and cfg.moe.router_width == 8
    assert cfg.moe.shared and cfg.moe.route_scale == 2.0
    with pytest.raises(ValueError, match="model_type"):
        model_config.from_published(dict(SMALL, model_type="xing9"))
    with pytest.raises(ValueError, match="attn_kinds"):
        model_config.ModelConfig(n_heads=4, block="pre_rms")


def test_the_configuration_file_carries_the_published_widths():
    """``benchmark/configs/xing4.0-29b-a4b.json``: every published width as
    published, the four reduced keys, and a record can be made of it."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "xing4.0-29b-a4b.json")
    with open(path) as f:
        cfg = json.load(f)
    want = {"hidden_size": 3584, "num_attention_heads": 32,
            "q_lora_rank": 768, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "moe_intermediate_size": 1024,
            "intermediate_size": 9216, "n_routed_experts": 64,
            "num_experts_per_tok": 4, "vocab_size": 131072, "hc_mult": 4,
            "hc_sinkhorn_iters": 20}
    assert {k: cfg[k] for k in want} == want
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace",
         "max_position_embeddings", "num_nextn_predict_layers"])
    assert cfg["num_dense_layers"] == cfg["first_k_dense_replace"]
    rec = model_config.from_published(cfg)
    assert rec.latent.row == 640 and rec.streams == 4
    assert rec.ffn_kinds.count("moe") == 5
    dep = cfg["deployment"]
    assert cfg["max_position_embeddings"] % dep["prefill_chunk"] == 0
    assert dep["paged_kv"] == dep["slots"] * (
        cfg["max_position_embeddings"] // dep["prefill_chunk"])
