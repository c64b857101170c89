"""Tier-4 distributed tests on the virtual 8-device CPU mesh (SURVEY §4).

The analogue of the reference's loopback master/slave tests
(test_client_server.py style): same-machine, real collective semantics.
Key assertion: SPMD data-parallel training is numerically equivalent to
single-device training — the all-reduce IS the reference's gradient
averaging.
"""

import numpy
import pytest

import jax

from veles_tpu import prng
from veles_tpu.config import root
from veles_tpu.parallel import make_mesh, ShardedTrainer


def _build(mb=64):
    root.mnist.update({
        "loader": {"minibatch_size": mb, "n_train": 256, "n_valid": 64},
        "decision": {"max_epochs": 1, "fail_iterations": 10},
        "layers": [
            {"type": "all2all_tanh", "output_sample_shape": 32,
             "learning_rate": 0.05, "momentum": 0.9},
            {"type": "softmax", "output_sample_shape": 10,
             "learning_rate": 0.05, "momentum": 0.9},
        ],
    })
    from veles_tpu.samples import mnist
    wf = mnist.build(fused=True)
    wf.initialize()
    return wf


def _batch(mb, seed=3):
    rng = numpy.random.RandomState(seed)
    x = rng.randn(mb, 784).astype(numpy.float32)
    labels = rng.randint(0, 10, mb).astype(numpy.int32)
    mask = numpy.ones(mb, numpy.float32)
    return x, labels, mask


def test_dp_matches_single_device():
    prng.reset(); prng.seed_all(11)
    wf = _build()
    runner = wf._fused_runner
    import jax.numpy as jnp
    x, labels, mask = _batch(64)
    # single-device reference trajectory
    ref_state = jax.tree.map(lambda a: a, runner.state)
    for step in range(3):
        ref_state, ref_metrics = jax.jit(runner._train_step)(
            ref_state, x, labels, mask, jnp.asarray(64, jnp.int32))
    # sharded trajectory from the same init
    prng.reset(); prng.seed_all(11)
    wf2 = _build()
    runner2 = wf2._fused_runner
    mesh = make_mesh(8)
    trainer = ShardedTrainer(runner2, mesh)
    for step in range(3):
        metrics = trainer.train_step(x, labels, mask, 64)
    for ref_entry, entry in zip(ref_state, trainer.state):
        for key in ref_entry:
            numpy.testing.assert_allclose(
                numpy.asarray(ref_entry[key]), numpy.asarray(entry[key]),
                rtol=2e-5, atol=2e-6)
    assert int(metrics["n_err"]) == int(ref_metrics["n_err"])


def test_tp_model_sharding_matches():
    """Tensor-parallel first layer must give the same numbers too."""
    prng.reset(); prng.seed_all(11)
    wf = _build()
    runner = wf._fused_runner
    import jax.numpy as jnp
    x, labels, mask = _batch(64)
    ref_state, _ = jax.jit(runner._train_step)(
        runner.state, x, labels, mask, jnp.asarray(64, jnp.int32))

    prng.reset(); prng.seed_all(11)
    wf2 = _build()
    runner2 = wf2._fused_runner
    mesh = make_mesh(8, model_parallel=2)
    trainer = ShardedTrainer(runner2, mesh, model_shard_layers=(0,))
    trainer.train_step(x, labels, mask, 64)
    for ref_entry, entry in zip(ref_state, trainer.state):
        for key in ref_entry:
            numpy.testing.assert_allclose(
                numpy.asarray(ref_entry[key]), numpy.asarray(entry[key]),
                rtol=2e-5, atol=2e-6)
    # the plan's sharding really is in force (weights split over 'model')
    w0 = trainer.state[0]["w"]
    assert not w0.sharding.is_fully_replicated


def test_tp_alexnet_fc_trunk_matches():
    """TP at the scale it exists for: the AlexNet 4096-wide FC trunk
    sharded over 'model', asserted numerically equivalent to the
    replicated run (VERDICT r3 Weak #6: no 16-unit toys)."""
    import jax.numpy as jnp
    from veles_tpu.parallel import model_shard_candidates
    from veles_tpu.samples.imagenet import ImagenetWorkflow, alexnet_layers
    from veles_tpu.loader.fullbatch import FullBatchLoader

    mb = 16

    class _SmallImages(FullBatchLoader):
        def load_data(self):
            rng = numpy.random.RandomState(7)
            self.original_data.reset(
                rng.uniform(-1, 1, (mb * 2, 64, 64, 3))
                .astype(numpy.float32))
            self.original_labels.reset(
                rng.randint(0, 16, mb * 2).astype(numpy.int32))
            self.class_lengths = [0, mb, mb]

    def build():
        prng.reset(); prng.seed_all(21)
        wf = ImagenetWorkflow(
            None, name="tp_alexnet", loader_factory=_SmallImages,
            loader_config={"minibatch_size": mb},
            layers=alexnet_layers(n_classes=16, crop=(56, 56)),
            decision_config={"max_epochs": 1, "fail_iterations": 5},
            loss_function="softmax", fused=True)
        wf.initialize()
        return wf

    x, labels, mask = (numpy.random.RandomState(9)
                       .uniform(-1, 1, (mb, 64, 64, 3))
                       .astype(numpy.float32),
                       numpy.arange(mb, dtype=numpy.int32) % 16,
                       numpy.ones(mb, numpy.float32))
    rng = jax.random.PRNGKey(4)

    # replicated reference trajectory (single device)
    wf = build()
    runner = wf._fused_runner
    ref_state, ref_metrics = jax.jit(runner._train_step)(
        runner.state, x, labels, mask, jnp.asarray(mb, jnp.int32), rng,
        jnp.asarray(0, jnp.int32))

    # TP trajectory: both 4096-wide FC layers sharded over 'model'
    wf2 = build()
    runner2 = wf2._fused_runner
    fc = model_shard_candidates(runner2, min_width=4096)
    assert len(fc) == 2, fc  # exactly the two 4096-wide trunk layers
    assert all(runner2.state[i]["w"].shape[-1] == 4096 for i in fc)
    mesh = make_mesh(8, model_parallel=2)
    trainer = ShardedTrainer(runner2, mesh, model_shard_layers=fc)
    metrics = trainer.train_step(x, labels, mask, mb, rng=rng, step=0)

    # the trunk really is split over 'model' (not replicated)
    for i in fc:
        assert not trainer.state[i]["w"].sharding.is_fully_replicated
        assert trainer.state[i]["w"].sharding.shard_shape(
            trainer.state[i]["w"].shape)[-1] == 2048
    numpy.testing.assert_allclose(
        float(trainer.fetch(metrics)["loss_sum"]),
        float(ref_metrics["loss_sum"]), rtol=1e-4)
    assert int(trainer.fetch(metrics)["n_err"]) == int(ref_metrics["n_err"])
    for i, (ref_entry, entry) in enumerate(zip(ref_state, trainer.state)):
        for key in ref_entry:
            numpy.testing.assert_allclose(
                numpy.asarray(ref_entry[key]), numpy.asarray(entry[key]),
                rtol=2e-4, atol=2e-5,
                err_msg="layer %d %s diverged under TP" % (i, key))


def test_epoch_scan_matches_per_step_loop():
    """The one-dispatch-per-epoch scan path equals the per-minibatch path."""
    prng.reset(); prng.seed_all(13)
    wf = _build(mb=64)
    runner = wf._fused_runner
    import jax.numpy as jnp
    loader = wf.loader
    data = loader.original_data.devmem
    labels = loader.original_labels.devmem
    from veles_tpu.loader.base import TRAIN
    loader._plan_epoch()
    idx = numpy.stack([c for cls, c, a in loader._order if cls == TRAIN])
    mask = numpy.stack([
        (numpy.arange(len(c)) < a).astype(numpy.float32)
        for cls, c, a in loader._order if cls == TRAIN])

    # per-step loop
    state_a = jax.tree.map(lambda a: a, runner.state)
    step = jax.jit(runner._train_step)
    for i in range(idx.shape[0]):
        x = numpy.asarray(jax.numpy.take(data, idx[i], axis=0))
        lab = numpy.asarray(jax.numpy.take(labels, idx[i], axis=0))
        state_a, _ = step(state_a, x, lab, mask[i],
                          jnp.asarray(int(mask[i].sum()), jnp.int32))
    # scan path
    train_epoch, _ = runner.epoch_fns()
    state_b, totals = train_epoch(runner.state, data, labels, idx, mask)
    for ea, eb in zip(state_a, state_b):
        for key in ea:
            numpy.testing.assert_allclose(
                numpy.asarray(ea[key]), numpy.asarray(eb[key]),
                rtol=2e-5, atol=2e-6)


def test_epoch_chunk_matches_sequential_epochs():
    """epoch_chunk_fn(k) — k epochs in ONE device program (the dispatch
    amortization the bench times) — must equal k
    sequential train_epoch calls, including the per-epoch key folding by
    global step offset."""
    prng.reset(); prng.seed_all(13)
    wf = _build(mb=64)
    runner = wf._fused_runner
    loader = wf.loader
    data = loader.original_data.devmem
    labels = loader.original_labels.devmem
    from veles_tpu.loader.base import TRAIN
    loader._plan_epoch()
    idx = numpy.stack([c for cls, c, a in loader._order if cls == TRAIN])
    mask = numpy.stack([
        (numpy.arange(len(c)) < a).astype(numpy.float32)
        for cls, c, a in loader._order if cls == TRAIN])
    steps = idx.shape[0]
    base = jax.random.PRNGKey(7)

    # sequential: two train_epoch calls, base key folded by global offset
    # (real copy: train_epoch donates, and the chunk leg needs the
    # original buffers afterwards)
    train_epoch, _ = runner.epoch_fns()
    state_a = jax.tree.map(jax.numpy.array, runner.state)
    for e in range(2):
        off = e * steps
        state_a, totals_a = train_epoch(
            state_a, data, labels, idx, mask,
            rng=jax.random.fold_in(base, off), step0=off)

    # chunked: one dispatch, k=2
    chunk = runner.epoch_chunk_fn(2)
    state_b, stacked = chunk(runner.state, data, labels, idx, mask,
                             rng=base, step0=0)
    for ea, eb in zip(state_a, state_b):
        for key in ea:
            numpy.testing.assert_allclose(
                numpy.asarray(ea[key]), numpy.asarray(eb[key]),
                rtol=2e-5, atol=2e-6)
    # stacked metrics: one row per epoch; row 1 equals the sequential
    # second epoch's totals
    for key in totals_a:
        assert numpy.asarray(stacked[key]).shape[0] == 2
        numpy.testing.assert_allclose(
            numpy.asarray(stacked[key][1]), numpy.asarray(totals_a[key]),
            rtol=2e-5, atol=2e-6)


def test_epoch_chunk_eval_matches_sequential_rounds():
    """epoch_chunk_eval_fn(k) — k (train epoch -> val eval) rounds in one
    program — returns exactly the per-epoch val totals the sequential
    train_epoch/eval_epoch loop fetches, and the same final state."""
    prng.reset(); prng.seed_all(29)
    wf = _build(mb=64)
    runner = wf._fused_runner
    loader = wf.loader
    data = loader.original_data.devmem
    labels = loader.original_labels.devmem
    from veles_tpu.loader.base import TRAIN, VALID
    loader._plan_epoch()

    def order(cls):
        idx = numpy.stack([c for k_, c, a in loader._order if k_ == cls])
        mask = numpy.stack([
            (numpy.arange(len(c)) < a).astype(numpy.float32)
            for k_, c, a in loader._order if k_ == cls])
        return idx, mask

    idx, mask = order(TRAIN)
    vidx, vmask = order(VALID)
    steps = idx.shape[0]
    base = jax.random.PRNGKey(11)

    # sequential reference (on a copy: the chunk leg donates)
    train_epoch, eval_epoch = runner.epoch_fns()
    state_a = jax.tree.map(jax.numpy.array, runner.state)
    seq_vals = []
    for e in range(2):
        off = e * steps
        state_a, _ = train_epoch(state_a, data, labels, idx, mask,
                                 rng=jax.random.fold_in(base, off),
                                 step0=off)
        seq_vals.append(eval_epoch(state_a, data, labels, vidx, vmask))

    chunk = runner.epoch_chunk_eval_fn(2)
    state_b, _, val_stack, test_stack = chunk(
        runner.state, data, labels, idx, mask, vidx, vmask, rng=base,
        step0=0)
    assert test_stack is None   # no test plan given
    for ea, eb in zip(state_a, state_b):
        for key in ea:
            numpy.testing.assert_allclose(
                numpy.asarray(ea[key]), numpy.asarray(eb[key]),
                rtol=2e-5, atol=2e-6)
    for e in range(2):
        for key in seq_vals[e]:
            numpy.testing.assert_allclose(
                numpy.asarray(val_stack[key][e]),
                numpy.asarray(seq_vals[e][key]), rtol=1e-5)


def test_loader_host_sharding_composes_with_mesh():
    """Multi-host story: each process takes a strided shard; union of shards
    covers the dataset exactly once (replaces index shipping)."""
    prng.reset(); prng.seed_all(5)
    root.mnist.update({
        "loader": {"minibatch_size": 32, "n_train": 128, "n_valid": 32},
        "decision": {"max_epochs": 1, "fail_iterations": 10},
        "layers": [{"type": "softmax", "output_sample_shape": 10,
                    "learning_rate": 0.05}],
    })
    from veles_tpu.samples import mnist
    seen = set()
    for proc in range(2):
        prng.reset(); prng.seed_all(5)
        wf = mnist.build(fused=True)
        wf.loader.shard(proc, 2)
        wf.initialize()
        for cls, chunk, actual in wf.loader._order:
            seen.update(chunk[:actual].tolist())
    assert seen == set(range(160))


def test_sharded_epoch_scan_matches_per_step_spmd():
    """ShardedTrainer.train_epoch (one dispatch per epoch, plan matrices
    sharded over the data axis) equals the per-minibatch SPMD path and
    works with a TP layer in the same plan."""
    from veles_tpu.loader.base import TRAIN

    def plan(loader):
        loader._plan_epoch()
        idx = numpy.stack([c for cls, c, a in loader._order
                           if cls == TRAIN])
        mask = numpy.stack([
            (numpy.arange(len(c)) < a).astype(numpy.float32)
            for cls, c, a in loader._order if cls == TRAIN])
        return idx, mask

    # per-minibatch SPMD trajectory
    prng.reset(); prng.seed_all(17)
    wf_a = _build(mb=64)
    runner_a = wf_a._fused_runner
    mesh = make_mesh(8, model_parallel=2)
    trainer_a = ShardedTrainer(runner_a, mesh, model_shard_layers=(0,))
    data = numpy.asarray(wf_a.loader.original_data.mem)
    labels = numpy.asarray(wf_a.loader.original_labels.mem)
    idx, mask = plan(wf_a.loader)
    for i in range(idx.shape[0]):
        trainer_a.train_step(data[idx[i]], labels[idx[i]], mask[i],
                             int(mask[i].sum()), step=i)

    # epoch-scan SPMD trajectory from the same init and plan
    prng.reset(); prng.seed_all(17)
    wf_b = _build(mb=64)
    runner_b = wf_b._fused_runner
    trainer_b = ShardedTrainer(runner_b, mesh, model_shard_layers=(0,))
    idx_b, mask_b = plan(wf_b.loader)
    numpy.testing.assert_array_equal(idx, idx_b)   # same PRNG -> same plan
    trainer_b.place_dataset(data, labels)
    totals = trainer_b.train_epoch(idx_b, mask_b, step0=0)
    assert trainer_b.step_count == idx.shape[0]

    for ea, eb in zip(trainer_a.state, trainer_b.state):
        for key in ea:
            numpy.testing.assert_allclose(
                numpy.asarray(ea[key]), numpy.asarray(eb[key]),
                rtol=2e-5, atol=2e-6)
    # TP layer stayed sharded through the scan (out_shardings pinned)
    assert not trainer_b.state[0]["w"].sharding.is_fully_replicated

    # eval_epoch totals match summing per-step eval metrics
    totals_eval = trainer_b.eval_epoch(idx_b, mask_b)
    per = None
    for i in range(idx.shape[0]):
        m = trainer_b.eval_step(data[idx[i]], labels[idx[i]], mask[i])
        host = ShardedTrainer.fetch(m)
        per = (host if per is None else
               {k: per[k] + host[k] for k in per})
    host_tot = ShardedTrainer.fetch(totals_eval)
    for k in host_tot:
        numpy.testing.assert_allclose(numpy.ravel(host_tot[k]),
                                      numpy.ravel(per[k]), rtol=1e-5)


def test_sharded_train_epochs_chunk_matches_sequential():
    """ShardedTrainer.train_epochs — k epochs with per-epoch shuffled
    plans in ONE dispatch under the mesh (incl. a TP layer) — equals k
    sequential train_epoch calls on the same plans."""
    from veles_tpu.loader.base import TRAIN

    def plan(loader):
        loader._plan_epoch()
        idx = numpy.stack([c for cls, c, a in loader._order
                           if cls == TRAIN])
        mask = numpy.stack([
            (numpy.arange(len(c)) < a).astype(numpy.float32)
            for cls, c, a in loader._order if cls == TRAIN])
        return idx, mask

    mesh = make_mesh(8, model_parallel=2)

    def two_plans(loader):
        i0, m0 = plan(loader)
        i1, m1 = plan(loader)   # re-plan => independently shuffled epoch
        assert not numpy.array_equal(i0, i1)
        return (numpy.stack([i0, i1]), numpy.stack([m0, m1]))

    # sequential: two train_epoch dispatches
    prng.reset(); prng.seed_all(23)
    wf_a = _build(mb=64)
    trainer_a = ShardedTrainer(wf_a._fused_runner, mesh,
                               model_shard_layers=(0,))
    data = numpy.asarray(wf_a.loader.original_data.mem)
    labels = numpy.asarray(wf_a.loader.original_labels.mem)
    idx3, mask3 = two_plans(wf_a.loader)
    steps = idx3.shape[1]
    trainer_a.place_dataset(data, labels)
    for e in range(2):
        totals_a = trainer_a.train_epoch(idx3[e], mask3[e],
                                         step0=e * steps)

    # chunked: one dispatch with the same two plans
    prng.reset(); prng.seed_all(23)
    wf_b = _build(mb=64)
    trainer_b = ShardedTrainer(wf_b._fused_runner, mesh,
                               model_shard_layers=(0,))
    idx3_b, mask3_b = two_plans(wf_b.loader)
    numpy.testing.assert_array_equal(idx3, idx3_b)
    trainer_b.place_dataset(data, labels)
    stacked = trainer_b.train_epochs(idx3_b, mask3_b, step0=0)
    assert trainer_b.step_count == 2 * steps

    for ea, eb in zip(trainer_a.state, trainer_b.state):
        for key in ea:
            numpy.testing.assert_allclose(
                numpy.asarray(ea[key]), numpy.asarray(eb[key]),
                rtol=2e-5, atol=2e-6)
    assert not trainer_b.state[0]["w"].sharding.is_fully_replicated
    # stacked row 1 == the sequential second epoch's totals
    host = ShardedTrainer.fetch(stacked)
    host_a = ShardedTrainer.fetch(totals_a)
    for k in host:
        assert numpy.asarray(host[k]).shape[0] == 2
        numpy.testing.assert_allclose(numpy.ravel(host[k][1]),
                                      numpy.ravel(host_a[k]), rtol=1e-5)


def test_sharded_train_epochs_eval_matches_sequential():
    """ShardedTrainer.train_epochs_eval == per-epoch train_epoch +
    eval_epoch under the same mesh (per-epoch val totals, final state)."""
    from veles_tpu.loader.base import TRAIN, VALID

    def order(loader, cls):
        return loader.plan_arrays(cls)

    mesh = make_mesh(8, model_parallel=2)

    prng.reset(); prng.seed_all(31)
    wf_a = _build(mb=64)
    trainer_a = ShardedTrainer(wf_a._fused_runner, mesh,
                               model_shard_layers=(0,))
    data = numpy.asarray(wf_a.loader.original_data.mem)
    labels = numpy.asarray(wf_a.loader.original_labels.mem)
    wf_a.loader._plan_epoch()
    i0, m0 = order(wf_a.loader, TRAIN)
    vidx, vmask = order(wf_a.loader, VALID)
    wf_a.loader._plan_epoch()
    i1, m1 = order(wf_a.loader, TRAIN)
    steps = i0.shape[0]
    trainer_a.place_dataset(data, labels)
    seq_vals = []
    for e, (ei, em) in enumerate([(i0, m0), (i1, m1)]):
        trainer_a.train_epoch(ei, em, step0=e * steps)
        seq_vals.append(ShardedTrainer.fetch(
            trainer_a.eval_epoch(vidx, vmask)))

    prng.reset(); prng.seed_all(31)
    wf_b = _build(mb=64)
    trainer_b = ShardedTrainer(wf_b._fused_runner, mesh,
                               model_shard_layers=(0,))
    wf_b.loader._plan_epoch()
    i0b, m0b = order(wf_b.loader, TRAIN)
    wf_b.loader._plan_epoch()
    i1b, m1b = order(wf_b.loader, TRAIN)
    numpy.testing.assert_array_equal(i0, i0b)
    numpy.testing.assert_array_equal(i1, i1b)
    trainer_b.place_dataset(data, labels)
    _, val_stack = trainer_b.train_epochs_eval(
        numpy.stack([i0b, i1b]), numpy.stack([m0b, m1b]), vidx, vmask,
        step0=0)
    host = ShardedTrainer.fetch(val_stack)
    for e in range(2):
        for key in seq_vals[e]:
            numpy.testing.assert_allclose(
                numpy.ravel(host[key][e]),
                numpy.ravel(seq_vals[e][key]), rtol=1e-5)
    for ea, eb in zip(trainer_a.state, trainer_b.state):
        for key in ea:
            numpy.testing.assert_allclose(
                numpy.asarray(ea[key]), numpy.asarray(eb[key]),
                rtol=2e-5, atol=2e-6)


def test_epoch_scan_requires_divisible_minibatch():
    prng.reset(); prng.seed_all(17)
    wf = _build(mb=64)
    trainer = ShardedTrainer(wf._fused_runner, make_mesh(8))
    trainer.place_dataset(numpy.asarray(wf.loader.original_data.mem),
                          numpy.asarray(wf.loader.original_labels.mem))
    bad_idx = numpy.zeros((2, 13), numpy.int32)   # 13 % 8 != 0
    bad_mask = numpy.ones((2, 13), numpy.float32)
    with pytest.raises(ValueError):
        trainer.train_epoch(bad_idx, bad_mask)
