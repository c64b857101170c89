"""What the engine's test files share (the files split from
``test_lm_fastpath.py``, ``test_kv_pool.py``, ``test_tracing.py``,
``test_serving.py``): the small ``pre_ln`` model and its greedy reference,
the small two-kinds ``sandwich`` model (``test_afmoe.py`` reads it from
here), the jit-cache
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
    prefill, verify, install/extract, step — regardless of how many
    prompt lengths and feature mixes the workload threw at it.  The
    acceptance criterion's guard: a fast path that silently forked a
    compile per prompt length would be a dispatch-latency regression
    dressed as a feature."""
    def check(engine, prefill_buckets=1):
        if engine._paged:
            # paged mode (ISSUE 6): the page-table indirection is
            # traced DATA, so the whole mixed-length workload owns
            # exactly one chunk and one page-copy program; step/verify
            # own one program PER LIVE-WIDTH LADDER ENTRY (ISSUE 7
            # satellite — the table is sliced to the batch's live page
            # span, the paged analogue of the contiguous prompt
            # buckets), still a static bound independent of the
            # workload's prompt-length mix
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
            return
        progs = {
            "step": (engine._step_jit, 1),
            "install": (engine._install_jit, 1),
            "prefill": (engine._prefill_jit, prefill_buckets),
        }
        if engine._chunk_jit is not None:
            progs["chunk"] = (engine._chunk_jit, 1)
            progs["chunk_install"] = (engine._chunk_install_jit, 1)
            progs["chunk_extract"] = (engine._chunk_extract_jit, 1)
        if engine._verify_jit is not None:
            progs["verify"] = (engine._verify_jit, 1)
        if engine._megastep_jit is not None:
            progs["megastep"] = (engine._megastep_jit, 1)
        for name, (fn, bound) in progs.items():
            size = fn._cache_size()
            assert size <= bound, (
                "%s program compiled %d variants (bound %d)"
                % (name, size, bound))
    return check


#: the feature-off engine's parity (incl. slot reuse) is already pinned
#: by tests/test_serving.py::TestLMEngine — these legs cover what's new
FEATURE_SETS = [
    {"prefill_chunk": 8},
    {"spec_k": 3},
    {"prefix_cache": 32, "prefill_chunk": 8},
    {"prefix_cache": 32, "prefill_chunk": 8, "spec_k": 3},
    # paged KV (ISSUE 6) — the page-table indirection under every
    # fast-path combination; paged_kv=12 also exercises a pool SMALLER
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


#: ISSUE 27: both KV layouts, with and without speculation and the
#: fused decode loop — every family that returns the storage
IN_PLACE_SETS = [
    {},
    {"prefill_chunk": 8, "prefix_cache": 32},
    {"spec_k": 3},
    {"megastep": 4},
    {"paged_kv": True, "prefill_chunk": 8},
    {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32,
     "spec_k": 3},
    {"paged_kv": True, "prefill_chunk": 8, "megastep": 4},
    {"paged_kv": True, "prefill_chunk": 8, "attn_kernel": "force"},
    {"tp": 2, "paged_kv": True, "prefill_chunk": 8},
]


#: ISSUE 13 parity matrix: K ∈ {1, 4, 8} × the fast-path features.
#: Every paged leg is tier-1; the contiguous layout keeps one
#: representative (plain at K=4) and its other legs, and the K=1 no-op
#: family (pinned by test_validation_and_noop), ride the slow suite.
MEGASTEP_SETS = [
    # K=1 parity rides the slow suite: test_validation_and_noop pins
    # K=1 == tick path (no fused program built), and the tick path's
    # paged+chunk+spec parity is FastPathParity's full-stack leg —
    # this entry re-proved both at 15s (watchdog-headroom discipline)
    pytest.param(1, {"paged_kv": True, "prefill_chunk": 8,
                     "spec_k": 3}, marks=pytest.mark.slow),
    (4, {}),
    (8, {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32,
         "spec_k": 3}),
    (4, {"tp": 2, "paged_kv": True, "prefill_chunk": 8, "spec_k": 3}),
    (4, {"paged_kv": True, "prefill_chunk": 8,
         "attn_kernel": "force"}),
    pytest.param(4, {"prefill_chunk": 8}, marks=pytest.mark.slow),
    pytest.param(4, {"spec_k": 3}, marks=pytest.mark.slow),
    pytest.param(8, {}, marks=pytest.mark.slow),
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
    Positions end at 64; the engine takes it paged, without prefix
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
