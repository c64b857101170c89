"""Genetics (Tune + GA) and ensemble tests (SURVEY §2.1, §3.5)."""

import numpy
import pytest

from veles_tpu.config import Config, Tune, root
from veles_tpu.genetics import find_tunes, optimize, set_leaf


class TestTuneDiscovery:
    def test_find_and_set(self):
        cfg = Config("root")
        cfg.model.lr = Tune(0.01, 0.001, 0.1)
        cfg.model.momentum = 0.9
        cfg.loader.size = Tune(100, 10, 1000)
        tunes = find_tunes(cfg, "root")
        assert [p for p, _ in tunes] == ["root.loader.size", "root.model.lr"]
        set_leaf("root.model.lr", 0.05, cfg)
        assert cfg.model.lr == 0.05


class TestGA:
    def test_converges_on_quadratic(self):
        from veles_tpu import prng
        prng.reset()
        prng.seed_all(7)
        genes = [("root.ga_test.x", Tune(5.0, -10.0, 10.0)),
                 ("root.ga_test.y", Tune(-5.0, -10.0, 10.0))]

        def evaluate(individual):
            x, y = individual
            return (x - 2.0) ** 2 + (y + 3.0) ** 2

        best_fit, best_genes, pop = optimize(evaluate, generations=12,
                                             population=12, genes=genes)
        assert best_fit < 0.5, (best_fit, best_genes)
        assert abs(best_genes["root.ga_test.x"] - 2.0) < 1.0
        assert abs(best_genes["root.ga_test.y"] + 3.0) < 1.0
        # fitness history is monotone non-increasing at the elite
        fits = [h[0] for h in pop.history]
        assert fits[-1] <= fits[0]

    def test_bounds_respected(self):
        from veles_tpu import prng
        prng.reset()
        prng.seed_all(3)
        genes = [("root.ga_b.x", Tune(0.5, 0.0, 1.0))]
        seen = []

        def evaluate(ind):
            seen.append(ind[0])
            return ind[0]

        optimize(evaluate, generations=4, population=6, genes=genes)
        assert all(0.0 <= v <= 1.0 for v in seen)


class TestWorkflowOptimize:
    def test_optimizes_mnist_lr(self):
        """Tiny end-to-end GA over the MNIST sample's learning rate."""
        from veles_tpu import prng
        from veles_tpu.genetics import optimize_workflow
        prng.reset()
        prng.seed_all(1)
        root.mnist.update({
            "loader": {"minibatch_size": 50, "n_train": 200, "n_valid": 100},
            "decision": {"max_epochs": 2, "fail_iterations": 5},
            "layers": [
                {"type": "all2all_tanh", "output_sample_shape": 16,
                 "learning_rate": Tune(0.001, 0.0005, 0.1), "momentum": 0.9},
                {"type": "softmax", "output_sample_shape": 10,
                 "learning_rate": 0.03, "momentum": 0.9},
            ],
        })
        from veles_tpu.samples import mnist
        best_fit, best_genes, _ = optimize_workflow(
            mnist, generations=2, population=3, seed=1)
        assert numpy.isfinite(best_fit)
        (path, value), = best_genes.items()
        assert "learning_rate" in path
        assert 0.0005 <= value <= 0.1


class TestPopulationParallel:
    def test_parallel_matches_sequential(self):
        """Individuals screened across worker subprocesses must give the
        IDENTICAL GA trajectory as the sequential in-process path (each
        evaluation is deterministic in (config, genes, seed) — ref:
        SURVEY §3.5 fork-per-individual population parallelism)."""
        from veles_tpu import prng
        from veles_tpu.genetics import optimize_workflow
        from veles_tpu.samples import mnist

        def configure():
            prng.reset()
            prng.seed_all(1)
            root.__dict__.pop("mnist", None)
            root.mnist.update({
                "loader": {"minibatch_size": 50, "n_train": 200,
                           "n_valid": 100},
                "decision": {"max_epochs": 2, "fail_iterations": 5},
                "layers": [
                    {"type": "all2all_tanh", "output_sample_shape": 16,
                     "learning_rate": Tune(0.001, 0.0005, 0.1),
                     "momentum": 0.9},
                    {"type": "softmax", "output_sample_shape": 10,
                     "learning_rate": 0.03, "momentum": 0.9},
                ],
            })

        configure()
        seq_fit, seq_genes, _ = optimize_workflow(
            mnist, generations=2, population=3, seed=1, workers=0)
        configure()
        par_fit, par_genes, _ = optimize_workflow(
            mnist, generations=2, population=3, seed=1, workers=3)
        assert par_fit == seq_fit
        assert par_genes == seq_genes


    def test_worker_failure_raises_with_stderr(self):
        """A crashing worker surfaces its stderr; siblings are cleaned up."""
        import pytest
        from veles_tpu.config import Tune
        from veles_tpu.genetics import evaluate_population
        genes = [("root.ga_fail.x", Tune(0.5, 0.0, 1.0))]
        with pytest.raises(RuntimeError, match="worker"):
            evaluate_population("veles_tpu.samples.no_such_module", genes,
                                [[0.5], [0.6]], seed=1, workers=2)


class TestOptimizeCLI:
    def test_cli_optimize_with_workers(self, tmp_path):
        """`--optimize g:p:w` end to end through the real CLI: config file
        with a Tune leaf, GA across worker subprocesses, winner printed."""
        import os
        import subprocess
        import sys
        cfg = tmp_path / "tunes.py"
        cfg.write_text(
            "root.mnist.update({\n"
            "    'loader': {'minibatch_size': 50, 'n_train': 150,\n"
            "               'n_valid': 50},\n"
            "    'decision': {'max_epochs': 1, 'fail_iterations': 5},\n"
            "    'layers': [\n"
            "        {'type': 'all2all_tanh', 'output_sample_shape': 8,\n"
            "         'learning_rate': Tune(0.001, 0.0005, 0.1),\n"
            "         'momentum': 0.9},\n"
            "        {'type': 'softmax', 'output_sample_shape': 10,\n"
            "         'learning_rate': 0.03, 'momentum': 0.9},\n"
            "    ],\n"
            "})\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "veles_tpu", "veles_tpu.samples.mnist",
             str(cfg), "-d", "cpu", "--random-seed", "1",
             "--optimize", "1:2:2"],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            timeout=420)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "best fitness:" in proc.stdout
        assert "learning_rate" in proc.stdout


class TestEnsemble:
    def test_members_and_combination(self):
        from veles_tpu import prng
        from veles_tpu.ensemble import train_ensemble
        prng.reset()
        prng.seed_all(1)
        root.mnist.update({
            "loader": {"minibatch_size": 50, "n_train": 300, "n_valid": 100},
            "decision": {"max_epochs": 2, "fail_iterations": 5},
            "layers": [
                {"type": "all2all_tanh", "output_sample_shape": 16,
                 "learning_rate": 0.03, "momentum": 0.9},
                {"type": "softmax", "output_sample_shape": 10,
                 "learning_rate": 0.03, "momentum": 0.9},
            ],
        })
        from veles_tpu.samples import mnist
        trainer, combined = train_ensemble(mnist, size=3, base_seed=5)
        assert len(trainer.members) == 3
        assert combined["count"] == 100
        assert len(combined["members"]) == 3
        # the ensemble should not be (much) worse than its best member
        assert combined["ensemble_n_err"] <= min(combined["members"]) + 5
        # different seeds really produced different members (weights differ)
        w0 = numpy.asarray(
            trainer.members[0][1].forwards[0].weights.mem)
        w1 = numpy.asarray(
            trainer.members[1][1].forwards[0].weights.mem)
        assert not numpy.allclose(w0, w1)
        # ...but every member trained on the SAME dataset (pinned data
        # streams): evaluating members 1..N on member 0's validation set is
        # only meaningful if the data matches
        d0 = numpy.asarray(trainer.members[0][1].loader.original_data.mem)
        for _, wf, _ in trainer.members[1:]:
            numpy.testing.assert_array_equal(
                d0, numpy.asarray(wf.loader.original_data.mem))
        # and no member predicts at chance on the shared validation set
        assert max(combined["members"]) < 50

    @pytest.mark.slow
    def test_parallel_members_match_sequential(self):
        """Members trained in worker subprocesses and restored from their
        snapshots must equal in-process members exactly (same platform) —
        the reference's members-across-slaves parallelism (SURVEY §3.5).
        Slow-marked for tier-1 runtime headroom: the in-process
        ensemble leg (test_members_and_combination) and the GA
        population-parallel parity leg stay tier-1."""
        from veles_tpu import prng
        from veles_tpu.ensemble import train_ensemble
        from veles_tpu.samples import mnist

        def configure():
            prng.reset()
            prng.seed_all(1)
            root.__dict__.pop("mnist", None)
            root.mnist.update({
                "loader": {"minibatch_size": 50, "n_train": 200,
                           "n_valid": 100},
                "decision": {"max_epochs": 2, "fail_iterations": 5},
                "layers": [
                    {"type": "all2all_tanh", "output_sample_shape": 16,
                     "learning_rate": 0.03, "momentum": 0.9},
                    {"type": "softmax", "output_sample_shape": 10,
                     "learning_rate": 0.03, "momentum": 0.9},
                ],
            })

        configure()
        seq_trainer, seq_combined = train_ensemble(mnist, size=2,
                                                   base_seed=5)
        configure()
        par_trainer, par_combined = train_ensemble(mnist, size=2,
                                                   base_seed=5, workers=2)
        assert par_combined == seq_combined
        for (_, seq_wf, seq_sum), (_, par_wf, par_sum) in zip(
                seq_trainer.members, par_trainer.members):
            assert par_sum == seq_sum
            numpy.testing.assert_array_equal(
                numpy.asarray(seq_wf.forwards[0].weights.mem),
                numpy.asarray(par_wf.forwards[0].weights.mem))


@pytest.mark.slow
def test_optimizes_char_lm_learning_rate():
    """The GA generalizes to the transformer family: Tune over the
    char-LM trainer's learning rate, fitness = validation loss from
    TransformerDecision.best_metric (lower is better).  Slow-marked
    (tier-1 runtime headroom, same discipline as the PR-3 trim):
    tier-1 keeps the GA parity (TestPopulationParallel) and CLI
    (TestOptimizeCLI) representatives; this full GA-over-a-trained-LM
    convergence leg rides the slow suite."""
    from veles_tpu import prng
    from veles_tpu.genetics import optimize_workflow
    prng.reset()
    prng.seed_all(1)
    root.__dict__.pop("char_lm", None)
    root.char_lm.update({
        "loader": {"minibatch_size": 32, "n_train": 128, "n_valid": 64,
                   "seq_len": 32, "vocab": 16},
        "trainer": {"vocab": 16, "d_model": 32, "n_heads": 2,
                    "n_layers": 1, "max_len": 32,
                    "learning_rate": Tune(1e-3, 1e-4, 1e-2),
                    "n_experts": 0, "pipeline_stages": 0,
                    "remat": False},
        "decision": {"max_epochs": 2, "fail_iterations": 10},
    })
    from veles_tpu.samples import char_lm
    best_fit, best_genes, _ = optimize_workflow(
        char_lm, generations=2, population=3, seed=1)
    assert numpy.isfinite(best_fit)
    (path, value), = best_genes.items()
    assert "learning_rate" in path
    assert 1e-4 <= value <= 1e-2
