"""chip_smoke.py's phases at tiny sizes on the CPU mesh — the first of the
three rehearsals before a chip call (README "Verify"): paths, arguments and
control flow.  Sizes are arguments of the phase functions; the CLI has no
option for them, and without a TPU it must refuse."""

import os
import subprocess
import sys

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY_LM = dict(d_model=64, n_heads=4, n_layers=2, vocab=64, max_len=128,
               seq_len=32, minibatch=8, n_train=16, n_valid=8)


@pytest.fixture(autouse=True)
def fresh_globals():
    """The phases drive process-global config/prng/precision, as the
    launcher does; leave them as found."""
    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.ops import functional as F
    yield
    prng.reset()
    for name in ("imagenet", "char_lm"):
        root.__dict__.pop(name, None)
    F.set_matmul_precision("float32")


def test_sync_phase_times_both_ways():
    rec = chip_smoke.phase_sync(n=256, chain=16, reps=5)
    assert rec["block_s"] > 0 and rec["fetch_s"] > 0


def test_sync_phase_refuses_an_early_return():
    """A block_until_ready that came back before the work was done shows
    as a rate above the chip's peak."""
    with pytest.raises(chip_smoke.SmokeFailure, match="above the chip"):
        chip_smoke.phase_sync(n=256, chain=4, reps=3, peak_flops=1.0)


def test_train_phase_graph_loop_equals_epoch_scan(tmp_path):
    from veles_tpu.samples.imagenet import tiny_layers
    out = chip_smoke.phase_train(
        3, str(tmp_path), minibatch=8, train_minibatches=3,
        valid_minibatches=1, image_hw=(32, 32), n_classes=10,
        layers=tiny_layers())
    assert sorted(out) == [
        (precision, form) for precision in ("bfloat16", "float32")
        for form in ("epoch-scan", "graph", "update")]
    # train programs of the two forms, from equal state with equal keys
    assert out[("float32", "update")] <= 2e-5
    assert out[("float32", "graph")]["valid"] == pytest.approx(
        out[("float32", "epoch-scan")]["valid"], rel=2e-5)
    assert not any(name.endswith(".records")
                   for name in os.listdir(str(tmp_path)))


def test_kernels_phase_matches_xla_twins():
    chip_smoke.phase_kernels(
        3, interpret=True, sgd_shapes=((784, 100), (300, 7)),
        lrn_shapes=((2, 6, 6, 96),), dropout_shape=(64, 128),
        attn=dict(b=2, heads=4, kv=2, dh=16, page=8, max_len=64),
        gmm_shapes=((256, 256, 112, 32, 8), (256, 32, 96, 96, 4)),
        steady_reps=1)


def test_kernels_phase_needs_the_tpu_for_compiled_kernels():
    with pytest.raises(chip_smoke.SmokeFailure, match="needs the TPU"):
        chip_smoke.phase_kernels(3, interpret=False)


def test_serve_phase_answers_like_generate():
    chip_smoke.phase_serve(
        3, lm=TINY_LM, slots=4, prefill_chunk=8, clients=2,
        requests_per_client=2, mean_len=24, n_new=6)


def test_kinds_phase_serves_the_references_tokens():
    """ISSUE 28: the sandwich block with two kinds of layer, kernels in
    interpret mode, 16 lanes (the one-call row write), float32 so that the
    served tokens are the reference's own."""
    tiny = dict(chip_smoke.KINDS_LM, hidden_size=64, num_attention_heads=6,
                num_key_value_heads=2, head_dim=16, intermediate_size=160,
                moe_intermediate_size=48, vocab_size=96, router_width=16,
                held_experts=[4, 4], sliding_window=16, initializer_std=0.1,
                max_position_embeddings=64, dtype="float32")
    chip_smoke.phase_kinds(3, lm=tiny, slots=16, page=8, prompt_len=14,
                           n_new=30, gap_limit=1e-4, kernel="force")


def test_latent_phase_serves_the_references_tokens():
    """ISSUE 34: latent attention under the 4-stream residual, kernels in
    interpret mode (expanded prefill over whole pages and a part of one,
    absorbed decode, the one-call row write of 16 lanes), float32 so that
    the served tokens are the reference's own."""
    tiny = dict(chip_smoke.LATENT_LM, hidden_size=64, num_attention_heads=4,
                q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
                moe_intermediate_size=32, vocab_size=96, n_routed_experts=8,
                num_experts_per_tok=2, initializer_std=0.1,
                max_position_embeddings=64, dtype="float32",
                rope_scaling=dict(chip_smoke.LATENT_LM["rope_scaling"],
                                  factor=4,
                                  original_max_position_embeddings=16))
    chip_smoke.phase_latent(3, lm=tiny, slots=16, page=8, prompt_len=21,
                            n_new=30, gap_limit=1e-4, kernel="force")


def test_linear_phase_serves_the_references_tokens():
    """ISSUE 36: the gated delta rule's two kernels in interpret mode (the
    chunked rule against the recurrent one, the step with half the lanes
    masked), then a request through state slots and pages, float32 so that
    the served tokens are the reference's own."""
    tiny = dict(chip_smoke.LINEAR_LM, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
                linear_num_value_heads=4, linear_key_head_dim=16,
                linear_value_head_dim=16, moe_intermediate_size=32,
                shared_expert_intermediate_size=32, num_experts=4,
                router_width=16, held_experts=[4, 4], num_experts_per_tok=3,
                vocab_size=96, initializer_std=0.1,
                max_position_embeddings=64, dtype="float32")
    chip_smoke.phase_linear(3, lm=tiny, slots=16, page=8, prompt_len=21,
                            n_new=30, rows=128, gap_limit=1e-4,
                            kernel="force", interpret=True)


def test_mtp_phase_serves_the_same_tokens_with_the_module_drafting():
    """ISSUE 40: a latent stack that drafts with its own module, kernels in
    interpret mode (the verify step's two rows a lane through the row
    kernel, a call a row, and the absorbed kernel at two query rows a head),
    float32 so that the tokens with the module and without are the
    reference's own and the same."""
    tiny = dict(chip_smoke.MTP_LM, hidden_size=64, num_attention_heads=4,
                q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
                moe_intermediate_size=32, vocab_size=96, n_routed_experts=4,
                router_width=8, held_experts=[4, 4], num_experts_per_tok=2,
                initializer_std=0.1, max_position_embeddings=64,
                mtp_init={"residual_std": 0.002, "h_mix": 0.2},
                dtype="float32")
    chip_smoke.phase_mtp(3, lm=tiny, slots=16, page=8, prompt_len=21,
                         n_new=31, gap_limit=1e-4, kernel="force")


def test_serve_phase_treats_a_fallback_as_failure(monkeypatch):
    """On the chip attn_kernel='auto' must select the kernels.  The
    engine here is on the CPU and falls back; tell the phase it is on
    the TPU and it must report that."""
    monkeypatch.setattr(chip_smoke, "on_tpu", lambda: True)
    with pytest.raises(chip_smoke.SmokeFailure, match="fell back"):
        chip_smoke.phase_serve(
            3, lm=TINY_LM, slots=4, prefill_chunk=8, clients=2,
            requests_per_client=1, mean_len=24, n_new=4)


def test_compare_tokens_accepts_only_roundoff_ties():
    import numpy
    logits = numpy.zeros(8)
    logits[3], logits[5], logits[6] = 4.0, 4.0 + 1e-6, 1.0
    prompt, want = [1, 2], [1, 2, 5, 7]
    fn = lambda tokens: logits  # noqa: E731
    assert chip_smoke.compare_tokens(
        "t", "same", [want], [want], [prompt], fn) == 0
    assert chip_smoke.compare_tokens(
        "t", "tie", [[1, 2, 3, 0]], [want], [prompt], fn) == 1
    with pytest.raises(chip_smoke.SmokeFailure, match="not a roundoff"):
        chip_smoke.compare_tokens(
            "t", "wrong", [[1, 2, 6, 7]], [want], [prompt], fn)


def test_four_chip_phase_on_four_cpu_devices():
    from veles_tpu.samples.imagenet import tiny_layers
    chip_smoke.phase_four_chips(
        3, devices=jax.devices()[:4], minibatch=8, steps=2,
        image_hw=(32, 32), n_classes=10, layers=tiny_layers(),
        tp_min_width=64, lm=TINY_LM, slots=4, prefill_chunk=8, n_prompts=4,
        mean_len=24, n_new=6)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_cli_refuses_without_a_tpu(argv):
    """Under JAX_PLATFORMS=cpu: non-zero exit and no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")] + argv,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr
