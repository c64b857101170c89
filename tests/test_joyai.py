"""ISSUE 40: ``joyai_llm_flash`` served with its multi-token-prediction module
LOADED.  The module drafts, the decode step verifies two positions a lane
through the latent pool, acceptance and the next draft are decided in the
graph, and a step yields one or two tokens a lane with two dispatches still
in flight (``LMEngine._settle_counts``, ``_advance_by_count``).  Held here,
at tiny float32 sizes on the CPU:

(a) the program's logits, prefill then decode through the pool, against the
    plain reference ``benchmark/reference/joyai.py``;
(b) LOSSLESSNESS: the same requests served with ``spec_k`` 1 and 0 give the
    same tokens, with drafts that are always right, (nearly) always wrong and
    mixed, across page boundaries, at ``n_new`` odd and even and 1, with
    lanes that end while two dispatches are in flight;
(c) the module's draft against the reference's module, step for step, and
    the program's acceptance against ``draft_hits``;
(d) the shares' routed parts with the shared expert once make the uncut layer;
(e) the pool's invariants after rejected drafts, at finish and at a cancel;
(f) the narrowed refusal.

No case asserts a duration: only counts, identities and tokens."""

import dataclasses
import json
import os

import numpy
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import joyai
from veles_tpu import model_config

PAGE = 8
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMALL = {
    "model_type": "joyai_llm_flash", "hidden_size": 64,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32, "vocab_size": 96,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 4, "router_width": 8, "held_experts": [4, 4],
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "rope_theta": 32000000, "rope_scaling": None,
    "rms_norm_eps": 1e-6, "num_nextn_predict_layers": 1,
    "initializer_std": 0.1, "dtype": "float32",
}

#: how the seeded weights are made for a case of (b) and (c): the module's
#: drafts (nearly) never right (everything i.i.d.: 1 in 96 by chance), mixed
#: (the configuration's ``mtp_init``, the module tied to the stack's last
#: layer, at two ``residual_std`` and ``h_mix``), always right (nothing
#: before the last layer writes into the residual stream, the last layer's
#: attention writes nothing either and every embedding has the size the
#: module's norm gives it: the next token is a function of the last one
#: alone, and the module computes the same function)
INITS = {"wrong": None,
         "mixed_0.002": {"residual_std": 0.002, "h_mix": 0.2},
         "mixed_0.0005": {"residual_std": 0.0005, "h_mix": 0.05},
         "right": {"residual_std": 0.0, "h_mix": 0.0}}

#: (prompt length, n_new) of a round: more requests than lanes, prompts of
#: one to four pages of 8, answers odd, even and of ONE token (its tail
#: chunk's first token is its last), contexts across page boundaries
ROUND = [(5, 9), (19, 6), (3, 1), (26, 13), (9, 2), (12, 7), (30, 22),
         (8, 3)]


def config(init="wrong"):
    return dict(SMALL, mtp_init=INITS[init])


def record(init="wrong"):
    return model_config.from_published(config(init))


_MADE = {}


def weights(init="wrong"):
    """(the reference's bfloat16-valued tree, the same raised to float32)."""
    if init not in _MADE:
        w = joyai.make_weights(3, config(init))
        if init == "right":
            def mute(blk):
                return dict(blk, attn=dict(
                    blk["attn"], wo=jnp.zeros_like(blk["attn"]["wo"])))
            e = w["embed"].astype(jnp.float32)
            e = e * SMALL["initializer_std"] / jnp.sqrt(
                (e * e).mean(-1, keepdims=True))
            w = dict(w, embed=e, blocks=w["blocks"][:-1]
                     + [mute(w["blocks"][-1])],
                     mtp=[dict(w["mtp"][0],
                               block=mute(w["mtp"][0]["block"]))])
        _MADE[init] = (w, jax.tree.map(lambda a: a.astype(jnp.float32), w))
    return _MADE[init]


def tokens(n, seed=0):
    return numpy.random.default_rng(seed).integers(0, SMALL["vocab_size"], n)


def serve(init, spec_k, requests, **over):
    """(the requests' outputs, the counters, the engine) after the engine
    served ``requests`` [(prompt, n_new)] and stopped."""
    from veles_tpu.serving import LMEngine, ServingMetrics
    name = "joyai_%s_%d" % (init, spec_k)
    kw = dict(max_len=64, slots=3, paged_kv=True, prefill_chunk=PAGE,
              spec_k=spec_k, metrics=ServingMetrics(name), name=name)
    kw.update(over)
    eng = LMEngine(weights(init)[1], record(init), **kw).start()
    try:
        outs = [f.result(timeout=300)
                for f in [eng.submit(p, n) for p, n in requests]]
        assert eng.verify_pool_invariants()["used_pages"] == 0
        assert eng._ahead is None or eng._ahead.step is None
        assert not eng._flights and not eng._undelivered
        assert not eng._unseen.any()
    finally:
        eng.stop()
    return outs, eng.metrics.snapshot()["counters"], eng


def a_round():
    return [(tokens(p, 30 + p), n) for p, n in ROUND]


# ------------------------------------------------ (a) the model, no engine
def test_record_from_the_published_keys():
    cfg = record()
    assert cfg.block == "pre_rms" and cfg.latent is not None
    assert cfg.yarn is None and cfg.hyper is None and cfg.streams == 1
    assert cfg.nextn == 1 and not cfg.by_kind
    # the module's layer stands behind the stack's in ``ffn_kinds``
    assert cfg.ffn_kinds == ("dense", "moe", "moe", "moe")
    assert cfg.moe.held == (4, 4) and cfg.moe.router_width == 8
    assert cfg.moe.score == "sigmoid" and cfg.moe.route_scale == 2.5
    with pytest.raises(ValueError, match="rope_scaling"):
        model_config.from_published(dict(SMALL, rope_scaling={"type": "yarn"}))
    with pytest.raises(ValueError, match="one multi-token-prediction"):
        model_config.from_published(dict(SMALL, num_nextn_predict_layers=2))


def test_whole_forward_matches_the_reference():
    from veles_tpu.ops.transformer import transformer_forward
    w, wf = weights()
    toks = tokens(40)
    ref = joyai.logits(w, toks, numpy.arange(40), config())
    got = transformer_forward(wf, jnp.asarray(toks)[None], record())[0]
    numpy.testing.assert_allclose(got, ref, atol=1e-4)


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    w, _ = weights("mixed_0.002")
    cfg = config("mixed_0.002")
    toks = tokens(40, 7)
    whole = joyai.draft_logits(w, toks, numpy.arange(39), cfg)
    hits = joyai.draft_hits(w, toks, 9, cfg)
    monkeypatch.setattr(joyai, "ROWS", 8)
    jax.clear_caches()
    joyai._HIDDEN.clear()
    blocks = joyai.draft_logits(w, toks, numpy.arange(39), cfg)
    assert joyai.draft_hits(w, toks, 9, cfg, pad_to=48, rows_to=40) == hits
    jax.clear_caches()
    joyai._HIDDEN.clear()
    numpy.testing.assert_allclose(blocks, whole, atol=2e-5)


def drive(init, kernel, prompt, steps):
    """One lane by hand through the chunk program's and the verify step's
    functions, its state carried from step to step as the engine carries it
    on the device: (the sequence it made, [(position, the draft held there,
    whether the step accepted it, the two tokens it picked)])."""
    from veles_tpu.ops.transformer import mtp_chunk_apply, mtp_verify_step
    _, wf = weights(init)
    cfg = record(init)
    max_pages = 7
    pools = [(jnp.zeros((max_pages + 1, 1, PAGE, cfg.latent.row)),)
             for _ in range(4)]
    table = jnp.arange(1, max_pages + 1, dtype=jnp.int32)[None]
    kern = ("prefill", "decode") if kernel else (None, None)

    @jax.jit
    def chunk(pools, toks, nxt, pos, last_idx, tail):
        return mtp_chunk_apply(wf, toks[None], nxt[None], pools, table,
                               pos, cfg, last_idx, tail,
                               attn_kernel=kern[0])

    @jax.jit
    def step(pools, state):
        return mtp_verify_step(wf, pools, table, *state,
                               jnp.ones(1, bool), cfg, attn_kernel=kern[1])

    n = len(prompt)
    padded = numpy.concatenate([prompt, numpy.zeros(PAGE + 1, prompt.dtype)])
    for pos in range(0, n, PAGE):
        pools, tok, draft = chunk(
            pools, jnp.asarray(padded[pos:pos + PAGE]),
            jnp.asarray(padded[pos + 1:pos + PAGE + 1]),
            jnp.asarray([pos]), jnp.asarray(min(n - 1 - pos, PAGE - 1)),
            jnp.asarray(pos + PAGE >= n))
    seq = list(prompt) + [int(tok)]
    state = (tok[None], draft[None], jnp.asarray([n], jnp.int32))
    out = []
    for _ in range(steps):
        held = (int(state[2][0]), int(state[1][0]))
        pools, state, picked, count, _ = step(pools, state)
        assert int(state[2][0]) == held[0] + int(count[0])
        assert int(state[0][0]) == int(picked[0, int(count[0]) - 1])
        seq.extend(int(t) for t in picked[0, :int(count[0])])
        out.append(held + (int(count[0]) == 2, [int(t) for t in picked[0]]))
    return numpy.asarray(seq), out


@pytest.mark.parametrize("kernel", [None, "kernel"])
def test_paged_prefill_then_decode_matches_the_reference(kernel):
    """(a) Prefill by chunks (expanded), then steps of ONE row (absorbed),
    through the paged latent pool: the logits of every decoded position are
    the reference's over the whole sequence; contexts over five pages."""
    from veles_tpu.ops.transformer import head_logits, paged_chunk_apply
    w, wf = weights()
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
    ref = joyai.logits(w, seq, numpy.arange(prompt_len - 1, 43), config())
    numpy.testing.assert_allclose(jnp.stack(got), ref, atol=1e-3)


@pytest.mark.parametrize("kernel", [None, "kernel"])
def test_verify_step_and_module_match_the_reference(kernel):
    """(a) and (c): the verify step's two rows a lane through the pool pick
    the reference's tokens, and the module's draft at every position is the
    reference's module's argmax there; whether a step accepts is whether the
    reference's module hit (``draft_hits`` counts the same positions)."""
    init = "mixed_0.002"
    w, _ = weights(init)
    cfg = config(init)
    prompt = tokens(19, 11)
    seq, steps = drive(init, kernel, prompt, 20)
    main = numpy.asarray(joyai.logits(
        w, seq, numpy.arange(len(seq)), cfg).argmax(-1))
    module = numpy.asarray(joyai.draft_logits(
        w, seq, numpy.arange(len(seq) - 1), cfg).argmax(-1))
    # the lane made the reference's own greedy sequence
    numpy.testing.assert_array_equal(seq[len(prompt):],
                                     main[len(prompt) - 1:-1])
    for pos, draft, accepted, picked in steps:
        # the draft held at ``pos`` scores the token at ``pos + 1``: the
        # module's row ``pos - 1``
        assert draft == module[pos - 1], (pos, draft)
        assert picked[0] == main[pos]
        assert accepted == (draft == seq[pos + 1])
        if accepted:
            assert picked[1] == main[pos + 1]
    hits = sum(accepted for _, _, accepted, _ in steps)
    assert 0 < hits < len(steps)
    hit = module[:len(seq) - 2] == seq[2:]
    assert joyai.draft_hits(w, seq, len(prompt), cfg) \
        == (int(hit[len(prompt) - 1:].sum()), len(seq) - 1 - len(prompt))


# ------------------------------------------------------- (b) losslessness
@pytest.mark.parametrize("init", list(INITS))
def test_served_tokens_are_plain_greedy_decodings(init):
    """The same round served with the module drafting and without it gives
    the same tokens, every one the reference's choice; every page comes
    home; what the counters say adds up."""
    w, _ = weights(init)
    plain, _, _ = serve(init, 0, a_round())
    spec, c, eng = serve(init, 1, a_round())
    assert eng._mtp and eng._late_fetch and eng._verify_jit is None
    for (p, n), a, b in zip(a_round(), plain, spec):
        assert len(b) == n
        numpy.testing.assert_array_equal(a, b)
        seq = numpy.concatenate([p, b])
        ref = numpy.asarray(joyai.logits(
            w, seq, numpy.arange(len(p) - 1, len(seq) - 1), config(init)))
        gap = ref.max(-1) - ref[numpy.arange(n), b]
        assert float(gap.max()) <= 1e-4
    total = sum(n for _, n in ROUND)
    assert c["tokens_out"] == total
    # every token but a request's first comes out of a verify step
    assert c["spec_tokens_kept"] == total - len(ROUND)
    assert c["spec_dispatches"] == c["decode_dispatches"]
    assert c["dispatches_sent_ahead"] + c["pipeline_drains"] \
        == c["decode_dispatches"]
    made = c["spec_tokens_kept"] + c["spec_tokens_discarded"]
    assert c["spec_lane_steps"] <= made <= 2 * c["spec_lane_steps"]
    accepted, drafts = c.get("draft_accepted", 0), c["draft_tokens"]
    if init == "right":
        assert accepted == drafts
        # two tokens a step: a request of odd ``n_new`` - 1 drops one
        assert c["spec_tokens_discarded"] > 0
        assert c["decode_dispatches"] < 0.75 * serve(
            init, 0, a_round())[1]["decode_dispatches"]
    elif init == "wrong":
        assert accepted <= 0.1 * drafts
    else:
        assert 0 < accepted < drafts


def test_dispatch_records_count_the_tokens_a_step_made():
    """The recorder's dispatch rows (``DCOL_TOKENS``) add up to what the
    steps made; a step makes one or two tokens a lane."""
    from veles_tpu.serving import tracing
    _, c, eng = serve("right", 1, a_round())
    rows = eng.recorder.dispatches()
    steps = rows[rows[:, tracing.DCOL_PHASE] == tracing.STEP_DISPATCH]
    assert len(steps) == c["decode_dispatches"]
    assert int(steps[:, tracing.DCOL_TOKENS].sum()) \
        == c["spec_tokens_kept"] + c["spec_tokens_discarded"]
    assert (steps[:, tracing.DCOL_TOKENS]
            <= 2 * steps[:, tracing.DCOL_LANES]).all()
    assert (steps[:, tracing.DCOL_TOKENS] > steps[:, tracing.DCOL_LANES]).any()
    chunks = rows[(rows[:, tracing.DCOL_PHASE] == tracing.PREFILL_DISPATCH)
                  & (rows[:, tracing.DCOL_FETCHED] > 0)]
    assert (chunks[:, tracing.DCOL_TOKENS] == 1).all()


def test_acceptance_is_the_references():
    """(c) the program's acceptance against ``draft_hits`` over the served
    sequences: the program drafts at the ends of its steps only (after an
    accepted draft it skips a position), the reference at every position, so
    the two shares agree closely, not exactly."""
    init = "mixed_0.0005"
    w, _ = weights(init)
    requests = [(tokens(p, 50 + p), n) for p, n in
                [(7, 40), (21, 36), (12, 44), (30, 26)]]
    outs, c, _ = serve(init, 1, requests)
    hits = positions = 0
    for (p, _), out in zip(requests, outs):
        got = joyai.draft_hits(w, numpy.concatenate([p, out]), len(p),
                               config(init))
        hits, positions = hits + got[0], positions + got[1]
    served = c["draft_accepted"] / c["draft_tokens"]
    assert abs(served - hits / positions) < 0.15, (served, hits, positions)


@pytest.mark.parametrize("init", ["wrong", "mixed_0.002", "mixed_0.0005"])
def test_every_draft_is_the_references_modules(init):
    """(c) the drafts a reply carries (``future.drafts``: ``(n, token)``,
    accepted or not) are the reference's module's choices at the same rows of
    the served sequence, for prompts of one to four chunks (the module's row
    at a chunk's end takes the NEXT chunk's first token, not a token picked
    there) and answers that end on a dropped second token."""
    w, _ = weights(init)
    requests = [(tokens(p, 70 + p), n) for p, n in
                [(7, 20), (8, 9), (21, 16), (30, 13), (16, 12)]]
    from veles_tpu.serving import LMEngine
    eng = LMEngine(weights(init)[1], record(init), max_len=64, slots=3,
                   paged_kv=True, prefill_chunk=PAGE, spec_k=1).start()
    try:
        futures = [eng.submit(p, n) for p, n in requests]
        for (p, n), f in zip(requests, futures):
            out = f.result(timeout=300)
            at = [i for i, _ in f.drafts]
            assert at == sorted(set(at)) and 1 <= at[0] and at[-1] < n
            assert len(at) >= (n - 1) // 2
            gaps, _ = joyai.draft_gaps(w, numpy.concatenate([p, out]),
                                       len(p), f.drafts, config(init))
            assert float(gaps.max()) <= 1e-4, (len(p), n, gaps)
    finally:
        eng.stop()


def test_a_reply_carries_the_drafts_where_they_are_asked_for():
    """``POST {"drafts": true}`` to a served model that drafts with its own
    module brings ``drafts`` beside ``tokens`` (what the benchmark's driver
    holds to the reference's module); without the key the reply is as it
    was."""
    import json
    import urllib.request
    from veles_tpu.ops.nn_units import NNWorkflow
    from veles_tpu.ops.transformer import TransformerTrainer
    from veles_tpu.restful_api import serve_lm
    init = "mixed_0.002"
    wf = NNWorkflow(None, name="joyai_http")
    wf.trainer = TransformerTrainer(
        wf, vocab=SMALL["vocab_size"], d_model=SMALL["hidden_size"],
        n_layers=SMALL["num_hidden_layers"], max_len=64, config=record(init))
    wf.trainer.params = weights(init)[1]
    api = serve_lm(wf, port=0, max_new=16, slots=2, paged_kv=True,
                   prefill_chunk=PAGE, spec_k=1)

    def post(body):
        req = urllib.request.Request(
            "http://127.0.0.1:%d/predict" % api.port,
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            return json.loads(resp.read())

    try:
        prompt = tokens(19, 4).tolist()
        plain = post({"input": [prompt], "n_new": 12})
        asked = post({"input": [prompt], "n_new": 12, "drafts": True})
    finally:
        api.stop()
    assert "drafts" not in plain and plain["tokens"] == asked["tokens"]
    (drafts,) = asked["drafts"]
    assert 5 <= len(drafts) <= 11
    gaps, _ = joyai.draft_gaps(weights(init)[0], asked["tokens"][0],
                               len(prompt), drafts, config(init))
    assert float(gaps.max()) <= 1e-4


# ------------------------------------------- (e) pages, cancels, the pool
def test_a_cancelled_lane_leaves_no_page_behind():
    import test_lm_ahead as ahead
    from veles_tpu.serving import LMEngine
    init = "mixed_0.002"
    eng = LMEngine(weights(init)[1], record(init), max_len=64, slots=2,
                   paged_kv=True, prefill_chunk=PAGE, spec_k=1)
    gate = ahead.gated(eng)
    eng.start()
    try:
        fa = eng.submit(tokens(6, 1), 30)
        fb = eng.submit(tokens(27, 2), 9)
        fc = eng.submit(tokens(11, 3), 8)
        ahead.after_stretch(eng, 2, lambda: eng._cancel(fa.request))
        gate.set()
        assert len(fb.result(timeout=120)) == 9
        assert len(fc.result(timeout=120)) == 8
        # withdrawn in its slot: it leaves with the tokens it had
        assert 1 <= len(fa.result(timeout=120)) < 30
        assert eng.verify_pool_invariants()["used_pages"] == 0
        assert eng.metrics.counter("ahead_discarded") >= 1
    finally:
        eng.stop()
    plain, _, _ = serve(init, 0, [(tokens(27, 2), 9), (tokens(11, 3), 8)])
    numpy.testing.assert_array_equal(plain[0], fb.result())
    numpy.testing.assert_array_equal(plain[1], fc.result())


def test_headroom_and_pools_of_an_engine_that_drafts():
    from veles_tpu.serving import LMEngine
    _, wf = weights()
    eng = LMEngine(wf, record(), max_len=64, slots=2, paged_kv=24,
                   prefill_chunk=PAGE, spec_k=1)
    # the stack's three pools and the module's own; two steps of two rows
    # may be in flight that the host has not seen
    assert len(eng._storage()) == 4 and eng.headroom == 4
    assert eng.metrics.snapshot()["gauges"]["kv_bytes_per_token"] \
        == 4 * 128 * 4
    with pytest.raises(ValueError, match="speculative headroom"):
        eng.start().submit(tokens(40), 21)
    eng.stop()
    plain = LMEngine(wf, record(), max_len=64, slots=2, paged_kv=24,
                     prefill_chunk=PAGE)
    assert len(plain._storage()) == 3 and plain.headroom == 0
    assert not plain._mtp
    with pytest.raises(ValueError, match="spec_k must be 1"):
        LMEngine(wf, record(), max_len=64, slots=2, paged_kv=24,
                 prefill_chunk=PAGE, spec_k=2)


# ------------------------------------------------------------ (d) shares
def test_the_shares_and_the_shared_expert_make_the_uncut_layer():
    """A layer that holds ALL 8 experts equals the shared expert once plus
    the routed parts of its 2 shares of 4, each computed by a tree that holds
    only its share, as the record of ``joyai_llm_flash`` states it."""
    from veles_tpu.ops import moe
    from veles_tpu.ops.attention import cfg_matmul
    cfg = record()
    whole_cfg = model_config.from_published(
        dict(SMALL, n_routed_experts=8, held_experts=None))
    assert whole_cfg.moe.held is None
    mm = lambda a, b: cfg_matmul(cfg, a, b)   # noqa: E731
    rng = numpy.random.default_rng(4)
    d, f = 64, 32
    mk = lambda *shape: jnp.asarray(rng.normal(0, 0.1, shape),  # noqa: E731
                                    jnp.float32)
    p = {"router": mk(d, 8), "bias": mk(8) * 0.1,
         "w_gate": mk(8, d, f), "w_up": mk(8, d, f), "w_down": mk(8, f, d),
         "shared": {"w_gate": mk(d, f), "w_up": mk(d, f),
                    "w_down": mk(f, d)}}
    x = mk(2, 9, d)
    whole, stats = moe.routed_ffn(p, x, whole_cfg.moe, mm)
    assert int(stats[0]) == 2 * 9 * 2 and int(stats[1]) == 0
    total = moe.gated_ffn(p["shared"], x.reshape(-1, d), mm).reshape(x.shape)
    for lo in (0, 4):
        share = dict(p, **{k: p[k][lo:lo + 4]
                           for k in ("w_gate", "w_up", "w_down")})
        part, _ = moe.routed_ffn(
            share, x, dataclasses.replace(cfg.moe, held=(lo, 4),
                                          shared=False), mm)
        total = total + part
    numpy.testing.assert_allclose(total, whole, atol=1e-5)


# ----------------------------------------------------------- (f) refusals
@pytest.mark.parametrize("kind,match", [
    ("window", "sliding layer's released pages"),
    ("linear", "linear layer's\\s+recurrent state")])
def test_speculation_is_refused_where_a_draft_cannot_be_undone(kind, match):
    import lm_cases
    with pytest.raises(ValueError, match=match):
        lm_cases.make_engine(kind, spec_k=1)


def test_a_latent_model_without_a_module_drafts_from_its_text():
    """The refusal went for the latent kind: without a module the engine
    drafts by prompt lookup (the synchronous driver) and serves the same
    tokens as without."""
    import lm_cases
    _, _, check = lm_cases._model("latent")
    prompt = numpy.tile(tokens(6, 5), 4)        # text that repeats
    outs = []
    for k in (0, 2):
        eng = lm_cases.make_engine("latent", name="latent_spec%d" % k,
                                   spec_k=k)
        assert not eng._mtp and (eng._verify_jit is not None) == bool(k)
        eng.start()
        try:
            outs.append(eng.submit(prompt, 20).result(timeout=300))
            assert eng.verify_pool_invariants()["used_pages"] == 0
        finally:
            eng.stop()
    numpy.testing.assert_array_equal(outs[0], outs[1])
    check(prompt, outs[1])


# ------------------------------------------------- the configuration file
def test_the_configuration_file_carries_the_published_widths():
    """``benchmark/configs/joyai-llm-flash-ep8.json``: every published key
    of the catalog's row at its published value but the three reduced ones;
    the module is LOADED (``num_nextn_predict_layers`` 1, not reduced); a
    record can be made of it and of its rehearsal."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai-llm-flash-ep8.json")) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    reduced = {"n_routed_experts": 32, "num_hidden_layers": 16,
               "max_position_embeddings": 9216}
    assert sorted(cfg["reduced"]) == sorted(reduced)
    assert sorted(cfg["reduced_note"]) == sorted(reduced)
    for key, value in published.items():
        assert cfg[key] == reduced.get(key, value), key
    assert cfg["router_width"] == 256 and cfg["held_experts"] == [0, 32]
    assert cfg["deployment"]["spec_k"] == 1
    assert "mtp_init" in cfg["assumed"] and "residual_std" in cfg["mtp_init"]
    rec = model_config.from_published(cfg)
    assert rec.nextn == 1 and rec.moe.held == (0, 32)
    assert rec.latent.row == 640 and len(rec.ffn_kinds) == 17
    from benchmark.lib.files import overlay
    small = model_config.from_published(overlay(cfg, cfg["rehearsal"]))
    assert small.nextn == 1 and small.dtype == "float32"
