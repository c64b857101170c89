"""What the engine's test files share (the files split from
``test_lm_fastpath.py``, ``test_kv_pool.py``, ``test_tracing.py``,
``test_serving.py``): the small ``pre_ln`` model and its greedy reference,
the small two-kinds ``sandwich`` model (``test_afmoe.py`` reads it from
here), an engine of each kind the benchmark's cells run with its tokens'
check (:func:`make_engine`, :func:`check_tokens`), the jit-cache
guard, and the feature-set tables of the parity matrices."""

import functools

import numpy
import pytest


def _params(max_len=96, vocab=16, n_heads=2, n_layers=2, d_model=32):
    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.ops.transformer import init_transformer_params
    host = init_transformer_params(prng.get("init"), vocab,
                                   d_model=d_model, n_heads=n_heads,
                                   n_layers=n_layers, max_len=max_len)
    return jax.tree.map(jnp.asarray, host)


def _greedy(params, prompt, n_new, max_len, n_heads=2):
    import jax.numpy as jnp
    from veles_tpu.ops.transformer import generate
    return numpy.asarray(generate(
        params, jnp.asarray([prompt], jnp.int32), n_new, n_heads,
        temperature=0.0, max_len=max_len))[0]


@pytest.fixture
def jit_guard():
    """Collects an engine's jitted programs and asserts the compile
    count stayed bounded: ONE program per (shape) family — chunk
    prefill, page copy, and per live-width ladder entry verify and
    step — regardless of how many prompt lengths and feature mixes the
    workload threw at it.  The acceptance criterion's guard: a fast
    path that silently forked a compile per prompt length would be a
    dispatch-latency regression dressed as a feature."""
    def check(engine):
        # the page-table indirection is traced DATA (ISSUE 6), so the
        # whole mixed-length workload owns exactly one chunk and one
        # page-copy program; step/verify own one program PER LIVE-WIDTH
        # LADDER ENTRY (ISSUE 7 satellite — the table is sliced to the
        # batch's live page span), still a static bound independent of
        # the workload's prompt-length mix
        widths = len(engine._width_ladder)
        progs = {
            "step": (engine._step_jit, widths),
            "chunk": (engine._chunk_jit, 1),
            "page_copy": (engine._page_copy_jit, 1),
        }
        if engine._verify_jit is not None:
            progs["verify"] = (engine._verify_jit, widths)
        if engine._megastep_jit is not None:
            # ISSUE 13: the fused program's asserted compile bound
            # — ONE megastep program per (live-width ladder entry
            # × K) family, K fixed per engine
            progs["megastep"] = (engine._megastep_jit, widths)
        for name, (fn, bound) in progs.items():
            size = fn._cache_size()
            assert size <= bound, (
                "%s program compiled %d variants (bound %d)"
                % (name, size, bound))
    return check


#: the feature-off engine's parity (incl. slot reuse) is already pinned
#: by tests/test_serving.py::TestLMEngine — these legs cover what's new
FEATURE_SETS = [
    # the default page (32 of 96: three pages a lane) under speculation
    {"spec_k": 3},
    # the page-table indirection (ISSUE 6) under every fast-path
    # combination at pages of 8; paged_kv=12 also exercises a pool SMALLER
    # than slots×max_pages (lanes contend for pages and still finish)
    {"paged_kv": True, "prefill_chunk": 8},
    {"paged_kv": 12, "prefill_chunk": 8},
    {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32},
    {"paged_kv": True, "prefill_chunk": 8, "spec_k": 3},
    {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32,
     "spec_k": 3},
    # Pallas serving kernels (ISSUE 7): 'force' runs the REAL kernels
    # in interpret mode on CPU — the end-to-end kernel parity leg (the
    # full fast-path combination, so chunked prefill, prefix installs
    # and speculative verify all route through the kernels); 'auto'
    # off-TPU exercises the automatic XLA fallback end to end (parity
    # via the fallback, counter asserted in TestAttnKernelRouting)
    {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32,
     "spec_k": 3, "attn_kernel": "force"},
    {"paged_kv": True, "prefill_chunk": 8, "attn_kernel": True},
    # what the benchmark's cells deploy (`slots`, `paged_kv`,
    # `prefill_chunk`, the kernels, nothing else), the pool smaller
    # than lanes x pages
    {"paged_kv": 12, "prefill_chunk": 8, "attn_kernel": "force"},
    # sharded serving (ISSUE 8): the SAME programs under a 2-device
    # tensor-parallel mesh — plain decode, chunked+speculative, the
    # full paged fast path, and kernels-requested (which must fall
    # back to the XLA path under the mesh, metered, parity intact).
    # Skips loudly via the cached conftest probe on 1-device jaxlibs.
    {"tp": 2},
    {"tp": 2, "prefill_chunk": 8, "spec_k": 3},
    {"tp": 2, "paged_kv": True, "prefill_chunk": 8,
     "prefix_cache": 32, "spec_k": 3},
    {"tp": 2, "paged_kv": True, "prefill_chunk": 8,
     "attn_kernel": True},
]


#: ISSUE 27: with and without speculation and the fused decode loop —
#: every family that returns the storage; a ``kind`` names one of
#: :func:`make_engine`'s models (a latent pool, slots of recurrent state,
#: the drafting module's pool beside the stack's)
IN_PLACE_SETS = [
    {"paged_kv": True, "prefill_chunk": 8},
    {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32,
     "spec_k": 3},
    {"paged_kv": True, "prefill_chunk": 8, "megastep": 4},
    {"paged_kv": True, "prefill_chunk": 8, "attn_kernel": "force"},
    {"tp": 2, "paged_kv": True, "prefill_chunk": 8},
    {"kind": "latent"},
    {"kind": "linear"},
    {"kind": "mtp", "spec_k": 1},
]


#: ISSUE 13 parity matrix: K ∈ {1, 4, 8} × the fast-path features.
#: Every leg is tier-1 but the K=1 no-op family (pinned by
#: test_validation_and_noop), which rides the slow suite.
MEGASTEP_SETS = [
    # K=1 parity rides the slow suite: test_validation_and_noop pins
    # K=1 == tick path (no fused program built), and the tick path's
    # paged+chunk+spec parity is FastPathParity's full-stack leg —
    # this entry re-proved both at 15s (watchdog-headroom discipline)
    pytest.param(1, {"paged_kv": True, "prefill_chunk": 8,
                     "spec_k": 3}, marks=pytest.mark.slow),
    (8, {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32,
         "spec_k": 3}),
    (4, {"tp": 2, "paged_kv": True, "prefill_chunk": 8, "spec_k": 3}),
    (4, {"paged_kv": True, "prefill_chunk": 8,
         "attn_kernel": "force"}),
    (4, {"paged_kv": True, "prefill_chunk": 8}),
    (8, {"paged_kv": True, "prefill_chunk": 8}),
    (4, {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32,
         "spec_k": 3}),
    (8, {"tp": 2, "paged_kv": True, "prefill_chunk": 8}),
]


#: the small ``sandwich`` model (``test_afmoe.py``'s and the two-kinds
#: engine's of every lifecycle test): a published-style ``afmoe`` record
SMALL = {
    "model_type": "afmoe", "hidden_size": 64, "num_attention_heads": 6,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 160,
    "moe_intermediate_size": 48, "vocab_size": 96, "num_hidden_layers": 4,
    "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention"],
    "num_experts": 4, "router_width": 16, "held_experts": [4, 4],
    "num_experts_per_tok": 3, "sliding_window": 8, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "route_scale": 2.448, "route_norm": True,
    "score_func": "sigmoid", "num_shared_experts": 1,
    "initializer_std": 0.1, "max_position_embeddings": 64,
}


def record(cfg=SMALL, dtype="float32"):
    from veles_tpu import model_config
    return model_config.from_published(dict(cfg, dtype=dtype))


@functools.lru_cache(maxsize=2)
def _kinds_weights(seed):
    # 4 s of the reference's generator a seed; nothing writes to a tree
    import jax
    import jax.numpy as jnp
    from benchmark.reference import afmoe
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        afmoe.make_weights(seed, SMALL))


def kinds_model(seed=3):
    """(record, float32 weights) of the small ``sandwich`` model
    :data:`SMALL`: sliding and full layers (two kinds of cache: a page
    table and an allocator each, ``kv_pool.WindowTables``), an expert
    layer, a window of 8 — the second cell's engine at test size.
    Positions end at 64; the engine takes it without prefix
    cache, speculation, megastep or ``tp``."""
    return record(), _kinds_weights(seed)


def served_model(kinds=False):
    """``(record, params, max_len)`` to construct an engine from: the
    ``pre_ln`` model of :func:`_params` at 96 positions (a head count
    of 2 is its record), or :func:`kinds_model` at its 64."""
    if kinds:
        return (*kinds_model(), 64)
    return 2, _params(), 96


def assert_greedy(engine, prompt, out, n_new, params=None):
    """``out`` is the greedy continuation of ``prompt`` on ``engine``'s
    model with ``params`` (the engine's own when None): exactly the
    ``n_new`` tokens that were asked for, no fewer.  The ``pre_ln``
    model is held to ``generate``, token for token;
    :func:`kinds_model`'s to the benchmark's plain reference over the
    served sequence (every token the reference's choice given what
    precedes it, as ``test_afmoe.py`` reads it: no program to compile for
    each prompt length)."""
    prompt = numpy.asarray(prompt)
    out = numpy.asarray(out)
    assert out.shape == (n_new,), (out.shape, n_new)
    params = engine.params if params is None else params
    if engine.cfg.block == "pre_ln":
        numpy.testing.assert_array_equal(
            numpy.concatenate([prompt, out]),
            _greedy(params, prompt.tolist(), n_new, engine.max_len,
                    engine.cfg))
        return
    from benchmark.reference import afmoe
    seq = numpy.concatenate([prompt, out])
    ref = afmoe.logits(params, seq,
                       numpy.arange(len(prompt) - 1, len(seq) - 1), SMALL)
    gap = ref.max(-1) - ref[numpy.arange(n_new), out]
    assert float(gap.max()) <= 1e-4, gap


#: the kinds of engine :func:`make_engine` builds, each at the size of its
#: own test file: the classic block, two kinds of paged cache under an
#: expert layer, a pool of latent rows, slots of recurrent state beside a
#: pool, and a model that drafts with its own module (``spec_k=1``)
KINDS = ("pre_ln", "window", "latent", "linear", "mtp")


@functools.lru_cache(maxsize=None)
def _model(kind):
    """(record, the engine's float32 weights, checker(prompt, out))."""
    import jax
    import jax.numpy as jnp
    if kind == "pre_ln":
        return 2, _params(max_len=64), None
    if kind == "window":
        return (*kinds_model(), None)
    if kind == "mtp":
        import test_joyai as small
        from benchmark.reference import joyai as reference
        init = "mixed_0.002"      # some drafts taken, some refused
        cfg, record = small.config(init), small.record(init)
        w = small.weights(init)[0]
    else:
        over = {}
        if kind == "latent":
            import test_xing4 as small
            from benchmark.reference import xing4 as reference
            # (its own file keeps the 20 iterations; every one is
            # unrolled twice a layer in each of an engine's programs,
            # 45 s a start() where 4 take 12)
            over = {"hc_sinkhorn_iters": 4}
        else:
            import test_qwen3_next as small
            from benchmark.reference import qwen3_next as reference
        cfg, record = dict(small.SMALL, **over), small.record(**over)
        w = reference.make_weights(3, cfg)

    def check(prompt, out):
        seq = numpy.concatenate([prompt, out])
        ref = numpy.asarray(reference.logits(
            w, seq, numpy.arange(len(prompt) - 1, len(seq) - 1), cfg))
        gap = ref.max(-1) - ref[numpy.arange(len(out)), out]
        assert float(gap.max()) <= 1e-4, gap
    return record, jax.tree.map(lambda a: a.astype(jnp.float32), w), check


def make_engine(kind="pre_ln", name="ahead", **over):
    """An engine of ``kind`` as the benchmark's cells deploy theirs (a
    pool, pages of 8, three lanes of 64 positions; the drafting kind with
    ``spec_k=1``), not started."""
    from veles_tpu.serving import LMEngine, ServingMetrics
    record, params, _ = _model(kind)
    kw = dict(max_len=64, slots=3, paged_kv=True, prefill_chunk=8,
              metrics=ServingMetrics(name), name=name)
    if kind == "mtp":
        kw["spec_k"] = 1
    kw.update(over)
    return LMEngine(params, record, **kw)


def check_tokens(kind, engine, prompt, out, n_new):
    """``out`` is ``n_new`` tokens, each the choice of ``kind``'s
    reference given what precedes it."""
    check = _model(kind)[2]
    out = numpy.asarray(out)
    assert out.shape == (n_new,)
    if check is None:
        assert_greedy(engine, prompt, out, n_new)
    else:
        check(numpy.asarray(prompt), out)


def vocab_of(engine):
    return int(engine.params["embed"].shape[0])


#: (prompt length, n_new) of a round: more requests than lanes, prompts of
#: one to four chunks of 8, answers that end while others prefill — and an
#: answer of ONE token (its tail chunk's first token is its last) and of two
#: (freed by count under its only step)
ROUND = [(5, 9), (19, 6), (3, 1), (26, 12), (9, 2), (12, 7), (30, 5)]


def tokens(n, seed, vocab):
    return numpy.random.default_rng(seed).integers(0, vocab, n)


def serve(engine, round_=ROUND, seed=40):
    """Start, serve one round (all submitted at once), stop: the prompts
    and the served continuations."""
    engine.start()
    try:
        prompts = [tokens(n, seed + i, vocab_of(engine))
                   for i, (n, _) in enumerate(round_)]
        futures = [engine.submit(p, n_new)
                   for p, (_, n_new) in zip(prompts, round_)]
        outs = [f.result(timeout=300) for f in futures]
    finally:
        engine.stop()
    return prompts, outs


def counters(engine):
    return engine.metrics.snapshot()["counters"]


def pipeline_balances(engine):
    """Idle, nothing is left in flight, and every decode dispatch was either
    followed by one sent ahead of its fetch or drained (ISSUE 39)."""
    c = counters(engine)
    assert not engine._flights and engine._older == 0
    assert c.get("dispatches_sent_ahead", 0) + c.get("pipeline_drains", 0) \
        == c["decode_dispatches"]
    return c


def stamps_of(turns):
    from veles_tpu.serving import tracing
    return turns[:, tracing.COL_STAMPS:tracing.COL_END + 1]


def follows_a_step(turns):
    """Bool per turn: the turn before dispatched a decode program (its step
    was in flight while this one was prepared)."""
    from veles_tpu.serving import tracing
    step = turns[:, tracing.COL_STEP_PROGRAM] > 0
    return numpy.concatenate([[False], step[:-1]])
