"""Distribution — SPMD over a device mesh.

Replaces the reference's asynchronous master–slave parameter server
(ref: veles/server.py, veles/client.py, veles/distributable.py [H],
SURVEY §2.5) with the TPU-native equivalent BASELINE.json mandates: the
training step is jitted over a ``jax.sharding.Mesh``; gradient averaging is
the XLA all-reduce GSPMD inserts over ICI when the batch axis is sharded and
parameters are replicated.  Semantic change (documented, SURVEY §7): the
reference applied slave updates asynchronously; SPMD all-reduce is
synchronous — which converges at least as well, satisfying the val-acc
parity criterion.

Mesh axes:
- ``data`` — data parallelism (the reference's only strategy),
- ``model`` — optional tensor parallelism for wide layers (beyond-parity),
multi-host: ``jax.distributed.initialize`` + ``Loader.shard(process_index,
process_count)`` replaces master→slave minibatch index shipping.
"""

from __future__ import annotations

import numpy


def make_mesh(n_devices=None, model_parallel=1, devices=None):
    """Build a (data, model) mesh over the first ``n_devices`` devices."""
    import jax
    from jax.sharding import Mesh
    devices = list(devices if devices is not None else jax.devices())
    n = n_devices or len(devices)
    if n > len(devices):
        raise ValueError("requested %d devices, have %d" % (n, len(devices)))
    if n % model_parallel:
        raise ValueError("n_devices %d not divisible by model_parallel %d"
                         % (n, model_parallel))
    grid = numpy.array(devices[:n]).reshape(n // model_parallel,
                                            model_parallel)
    return Mesh(grid, ("data", "model"))


def make_tp_mesh(tp, devices=None):
    """One-axis ``('tp',)`` mesh over ``tp`` devices for TENSOR-PARALLEL
    SERVING (``serving/lm_engine.py::LMEngine(tp=)``) — the serving
    sibling of :func:`make_mesh`'s ``model`` axis, kept separate because
    an engine mesh is a DEVICE SLICE: data-parallel engine replicas each
    build their own disjoint tp mesh out of one host's devices
    (``serving/router.py``), whereas the training mesh owns them all.
    ``devices`` defaults to the first ``tp`` of ``jax.devices()``."""
    import jax
    from jax.sharding import Mesh
    if tp < 2:
        raise ValueError("a tp mesh needs >= 2 devices (got tp=%d); "
                         "tp<2 serving runs without a mesh" % tp)
    devices = list(devices if devices is not None else jax.devices())
    if tp > len(devices):
        raise ValueError("requested tp=%d devices, have %d"
                         % (tp, len(devices)))
    return Mesh(numpy.array(devices[:tp]), ("tp",))


def model_shard_candidates(runner, min_width=1024):
    """Layer indices whose output width makes model-axis sharding pay
    (e.g. AlexNet's 4096-wide FC trunk).  Narrow layers stay replicated —
    a sharded 10-wide softmax costs more in collectives than it saves."""
    return [i for i, entry in enumerate(runner.state)
            if entry and entry["w"].shape[-1] >= min_width]


class ShardedTrainer:
    """Runs a FusedRunner's steps SPMD over a mesh.

    Parameters live replicated (or model-axis sharded for listed layers);
    the batch is sharded over ``data``.  Gradients contract over the sharded
    batch axis, so GSPMD inserts the ICI all-reduce automatically — that
    all-reduce IS the reference's master-side gradient averaging, minus the
    ZeroMQ hop.
    """

    def __init__(self, runner, mesh, model_shard_layers=()):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.runner = runner
        self.mesh = mesh
        #: True when the mesh spans multiple processes (multi-host SPMD):
        #: arrays are then assembled from per-process local shards instead
        #: of device_put (which requires every device to be addressable)
        self.multiprocess = len({d.process_index
                                 for d in mesh.devices.flat}) > 1
        self._repl = NamedSharding(mesh, P())
        self._batch = NamedSharding(mesh, P("data"))
        #: epoch-scan placement: (B, mb) plan matrices sharded over the
        #: data axis along the minibatch dimension; dataset replicated
        self._mb_shard = NamedSharding(mesh, P(None, "data"))
        self._data = None
        self._labels = None
        shardings = []
        for i, entry in enumerate(runner.state):
            if not entry:      # weightless layer (pooling, dropout, crop…)
                shardings.append({})
                continue
            if i in model_shard_layers:
                # output-dimension (column/channel) sharding: dense weights
                # are (n_in, n_out), conv weights HWIO (kh, kw, cin, cout) —
                # the last axis is the output width either way, the split
                # the reference could not express at all (SURVEY §2.5
                # "beyond-parity" TP row)
                ndim = entry["w"].ndim
                w = NamedSharding(mesh, P(*([None] * (ndim - 1) + ["model"])))
                b = NamedSharding(mesh, P("model"))
            else:
                w = b = self._repl
            # optimizer state shards with the array it accompanies: keys
            # ending in "w" are weight-shaped (w, vw, aw), keys ending in
            # "b" bias-shaped (b, vb, ab) — GradientDescentBase.state_entry
            # guarantees the convention
            spec = {k: (w if k.endswith("w") else b) for k in entry}
            shardings.append(spec)
        self.state_shardings = shardings
        #: global train-step counter (lr policies); see train_step
        self.step_count = 0
        # Multi-process placement cuts every device's shard from the
        # process-LOCAL host copy (_put), which is only correct when all
        # processes built bit-identical initial state — divergent init
        # (version skew, nondeterministic op order) would silently
        # assemble a Frankenstein tensor on a cross-process model axis.
        # Cross-check a digest first, mirroring the place_dataset guard.
        if self.multiprocess:
            import zlib
            from jax.experimental import multihost_utils
            digest = [zlib.crc32(numpy.ascontiguousarray(leaf).tobytes())
                      for leaf in jax.tree.leaves(runner.state)]
            multihost_utils.assert_equal(
                numpy.asarray(digest, numpy.uint32),
                "initial runner state differs across processes — every "
                "process must build bit-identical params (same seed, "
                "pinned PRNG streams) before ShardedTrainer assembles "
                "shards from local copies")
        #: device state, placed according to the sharding plan (replicated
        #: state: every process holds the full value, so local data == the
        #: global array in the multi-process assembly)
        self.state = jax.tree.map(self._put, runner.state, shardings)
        # out_shardings pins the updated state to the plan — otherwise
        # GSPMD may re-shard it to whatever propagation preferred
        # _step_fn: the runner's configured per-minibatch step
        # (monolithic or gradient-accumulating) — grad_accum must hold
        # on the SPMD path exactly as it does single-chip
        self._train = jax.jit(runner._step_fn, donate_argnums=(0,),
                              out_shardings=(shardings, None))
        self._eval = jax.jit(runner._eval_step)

    def _put(self, arr, sharding):
        """Place PARAMETER/OPTIMIZER state.  Multi-process: every process
        builds the identical full host value (same seed, pinned streams),
        so each device's shard is cut from the local full copy by global
        index — which supports ANY sharding, including a model axis that
        spans processes (megatron-style TP across hosts rides the same
        path as within-host TP)."""
        import jax
        if arr is None:
            return None
        if self.multiprocess:
            host = numpy.asarray(arr)
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx])
        return jax.device_put(arr, sharding)

    def put_batch(self, x, labels, mask):
        """Shard one (padded, static-shape) minibatch over the data axis.

        Single-process: the arrays are GLOBAL and device_put splits them.
        Multi-process: each process passes its LOCAL rows — the slice of
        the global batch its data-coordinates cover, exactly what
        ``Loader.shard_spmd`` yields when driven by
        :func:`spmd_loader_shard` (processes that share data-coordinates,
        i.e. a cross-process model axis, pass identical rows) — and the
        global array is assembled with
        ``jax.make_array_from_process_local_data``.
        """
        import jax
        if self.multiprocess:
            put = (lambda a: jax.make_array_from_process_local_data(
                self._batch, numpy.asarray(a)))
        else:
            put = lambda a: jax.device_put(a, self._batch)
        return put(x), put(labels), put(mask)

    def train_step(self, x, labels, mask, batch_size, rng=None, step=None):
        """One SPMD train step; ``step`` defaults to an internal counter so
        lr policies decay in the distributed path exactly as they do under
        FusedStep (pass it explicitly to resume from a checkpointed step)."""
        import jax.numpy as jnp
        if rng is None and self.runner._has_stochastic:
            from veles_tpu import prng
            rng = prng.get("dropout").key()
        if step is None:
            step = self.step_count
        x, labels, mask = self.put_batch(x, labels, mask)
        self.state, metrics = self._train(
            self.state, x, labels, mask, jnp.asarray(batch_size, jnp.int32),
            rng, jnp.asarray(step, jnp.int32))
        self.step_count = int(step) + 1
        return metrics

    def train_step_pending(self, x, labels, mask, batch_size, rng=None,
                           step=0):
        """Graph-mode (FusedStep) variant of :meth:`train_step`: computes
        the updated state WITHOUT committing it, so FusedCommit can adopt
        or discard it after Decision gates — the same pending/commit
        dance the single-device path does.  Non-donating (the current
        state must survive a discarded update)."""
        import jax
        import jax.numpy as jnp
        if not hasattr(self, "_train_pending"):
            self._train_pending = jax.jit(
                self.runner._step_fn,
                out_shardings=(self.state_shardings, None))
        x, labels, mask = self.put_batch(x, labels, mask)
        return self._train_pending(
            self.state, x, labels, mask,
            jnp.asarray(batch_size, jnp.int32), rng,
            jnp.asarray(step, jnp.int32))

    def eval_step(self, x, labels, mask):
        x, labels, mask = self.put_batch(x, labels, mask)
        return self._eval(self.state, x, labels, mask)

    def reload_from_runner(self):
        """Re-place device state from the runner's host-side state —
        the restore-side inverse of :meth:`sync_to_runner` (snapshot
        restore rewrites the unit Vectors and refreshes runner.state;
        this pushes it back out over the mesh, digest-guarded in
        multi-process mode like __init__)."""
        import jax
        if self.multiprocess:
            import zlib
            from jax.experimental import multihost_utils
            digest = [zlib.crc32(numpy.ascontiguousarray(
                numpy.asarray(leaf)).tobytes())
                for leaf in jax.tree.leaves(self.runner.state)]
            multihost_utils.assert_equal(
                numpy.asarray(digest, numpy.uint32),
                "restored runner state differs across processes — every "
                "process must restore the same snapshot")
        self.state = jax.tree.map(self._put, self.runner.state,
                                  self.state_shardings)

    # ------------------------------------------------- epoch-scan (SPMD)
    # GLOBAL-plan API: every process passes the SAME full dataset and the
    # SAME (B, mb) epoch plan — unlike the per-minibatch path, which
    # consumes each process's shard_spmd-local rows.  Multi-process
    # callers therefore plan from an UNsharded loader (the plan is
    # deterministic from the shared PRNG seed); train_epoch cross-checks
    # the plan across processes to fail loudly instead of silently
    # training on mismatched batches.
    def place_dataset(self, data, labels=None):
        """Put the full GLOBAL dataset in HBM, replicated over the mesh,
        for the one-dispatch-per-epoch path (labels None for AE
        targets).  Every process must pass identical arrays —
        cross-checked by digest, so a process feeding different data
        fails here instead of silently diverging."""
        if self.multiprocess:
            import zlib
            from jax.experimental import multihost_utils
            digest = [zlib.crc32(numpy.ascontiguousarray(data).tobytes())]
            if labels is not None:
                digest.append(zlib.crc32(
                    numpy.ascontiguousarray(labels).tobytes()))
            multihost_utils.assert_equal(
                numpy.asarray(digest, numpy.uint32),
                "place_dataset arrays differ across processes — the "
                "epoch-scan path needs the identical GLOBAL dataset "
                "everywhere")
        self._data = self._put(data, self._repl)
        self._labels = (self._put(labels, self._repl)
                        if labels is not None else None)

    def _place_plan(self, idx, mask, rng=None):
        """Shared guard + placement for train_epoch/eval_epoch: validates
        the plan, cross-checks it (and the rng key, whose divergence
        would silently desynchronize dropout masks across hosts) in
        multi-process mode, and uploads the plan matrices data-sharded."""
        if self._data is None:
            raise ValueError("call place_dataset(data, labels) first")
        if idx.shape[-1] % self.mesh.shape["data"]:
            raise ValueError(
                "minibatch size %d not divisible by data-axis size %d"
                % (idx.shape[-1], self.mesh.shape["data"]))
        if self.multiprocess:
            from jax.experimental import multihost_utils
            tree = (numpy.asarray(idx), numpy.asarray(mask))
            if rng is not None:
                tree += (numpy.asarray(rng),)
            multihost_utils.assert_equal(
                tree,
                "epoch-scan plan/rng differs across processes — build "
                "the plan from an UNsharded loader (global plan, not "
                "shard_spmd) and derive the rng from the shared seed")
        self._ensure_epoch_jits()
        # plan matrices shard over the data axis along the (last)
        # minibatch dimension — (B, mb) per-epoch, (k, B, mb) chunked
        from jax.sharding import NamedSharding, PartitionSpec as P
        shard = (self._mb_shard if idx.ndim == 2 else
                 NamedSharding(self.mesh, P(None, None, "data")))
        return (self._put(numpy.asarray(idx, numpy.int32), shard),
                self._put(numpy.asarray(mask, numpy.float32), shard))

    def train_epoch(self, idx, mask, rng=None, step0=None):
        """One device dispatch per EPOCH, data-parallel inside the scan.

        The single-chip fast path (FusedRunner._epoch_train: lax.scan over
        the minibatch index matrix, SURVEY §3.1 rebuild) runs unchanged
        under the mesh — the ONLY distribution work is placement: the
        dataset is replicated, and ``idx``/``mask`` (B, mb) are sharded
        over the data axis along the minibatch dimension, so each scan
        step's gather yields a batch-sharded ``x`` and GSPMD propagates
        DP (and any model-axis sharding of the params) through the whole
        epoch, inserting one gradient all-reduce per step.  Zero host
        work between minibatches, N-chip parallel.
        """
        import jax.numpy as jnp
        self.runner.require_epoch_rng(rng)
        idx_g, mask_g = self._place_plan(idx, mask, rng)
        if step0 is None:
            step0 = self.step_count
        self.state, totals = self._epoch_train_jit(
            self.state, self._data, self._labels, idx_g, mask_g, rng,
            jnp.asarray(step0, jnp.int32))
        self.step_count = int(step0) + idx.shape[0]
        return totals

    def train_epochs(self, idx, mask, rng=None, step0=None):
        """``k`` epochs in ONE dispatch under the mesh
        (FusedRunner._epoch_chunk): ``idx``/``mask`` are (k, B, mb) —
        one independently shuffled plan per epoch, precomputed on the
        host — and the per-epoch metric totals come back stacked
        (k rows), so the host still sees every epoch's metrics, at
        k-epoch readback granularity instead of k execute round-trips.
        Trade-off: early-stopping decisions lag up to
        k-1 epochs."""
        import functools
        import jax
        import jax.numpy as jnp
        idx = numpy.asarray(idx)
        if idx.ndim != 3:
            raise ValueError("train_epochs wants (k, B, mb) per-epoch "
                             "plans; use train_epoch for a single epoch")
        k = idx.shape[0]
        self.runner.require_epoch_rng(rng)
        idx_g, mask_g = self._place_plan(idx, mask, rng)
        cache = getattr(self, "_chunk_jits", None)
        if cache is None:
            cache = self._chunk_jits = {}
        if k not in cache:
            cache[k] = jax.jit(
                functools.partial(self.runner._epoch_chunk, k),
                donate_argnums=(0,),
                out_shardings=(self.state_shardings, None))
        if step0 is None:
            step0 = self.step_count
        self.state, stacked = cache[k](
            self.state, self._data, self._labels, idx_g, mask_g, rng,
            jnp.asarray(step0, jnp.int32))
        self.step_count = int(step0) + k * idx.shape[-2]
        return stacked

    def train_epochs_eval(self, idx, mask, vidx, vmask, rng=None,
                          step0=None, eval_first=False):
        """``k`` (train epoch + validation eval) rounds in ONE dispatch
        under the mesh (FusedRunner._epoch_chunk_eval) — the convergence
        loop's body at 1 execute per k epochs, SPMD.  idx/mask are
        (k, B, mb) per-epoch plans; vidx/vmask the fixed validation
        plan.  Returns (train totals stacked, val totals stacked)."""
        import functools
        import jax
        import jax.numpy as jnp
        idx = numpy.asarray(idx)
        if idx.ndim != 3:
            raise ValueError("train_epochs_eval wants (k, B, mb) "
                             "per-epoch plans")
        k = idx.shape[0]
        self.runner.require_epoch_rng(rng)
        idx_g, mask_g = self._place_plan(idx, mask, rng)
        vidx_g, vmask_g = self._place_plan(vidx, vmask)
        cache = getattr(self, "_chunk_eval_jits", None)
        if cache is None:
            cache = self._chunk_eval_jits = {}
        if (k, eval_first) not in cache:
            cache[(k, eval_first)] = jax.jit(
                functools.partial(self.runner._epoch_chunk_eval, k,
                                  eval_first=eval_first),
                donate_argnums=(0,),
                out_shardings=(self.state_shardings, None, None, None))
        if step0 is None:
            step0 = self.step_count
        self.state, train_stack, val_stack, _ = cache[(k, eval_first)](
            self.state, self._data, self._labels, idx_g, mask_g, vidx_g,
            vmask_g, rng, jnp.asarray(step0, jnp.int32))
        self.step_count = int(step0) + k * idx.shape[-2]
        return train_stack, val_stack

    def chunk_eval_pending(self, idx, mask, vidx, vmask, rng=None,
                           step0=None, eval_first=False, tidx=None,
                           tmask=None):
        """Driver-facing variant of :meth:`train_epochs_eval`: k epochs
        with per-epoch (k, B, mb) plans plus per-epoch valid (and
        optional test) evals in one dispatch — NON-donating and
        NON-committing.  ``self.state`` stays at the chunk input so the
        epoch-scan driver can replay a mid-chunk completion exactly
        (see epoch_driver.py); commit with ``self.state = new_state``.
        Returns (new_state, train stacked, val stacked, test stacked or
        None)."""
        import functools
        import jax
        import jax.numpy as jnp
        idx = numpy.asarray(idx)
        if idx.ndim != 3:
            raise ValueError("chunk_eval_pending wants (k, B, mb) "
                             "per-epoch plans")
        k = idx.shape[0]
        self.runner.require_epoch_rng(rng)
        idx_g, mask_g = self._place_plan(idx, mask, rng)
        vidx_g, vmask_g = self._place_plan(vidx, vmask)
        tidx_g = tmask_g = None
        if tidx is not None:
            tidx_g, tmask_g = self._place_plan(tidx, tmask)
        cache = getattr(self, "_chunk_pending_jits", None)
        if cache is None:
            cache = self._chunk_pending_jits = {}
        if (k, eval_first) not in cache:
            cache[(k, eval_first)] = jax.jit(
                functools.partial(self.runner._epoch_chunk_eval, k,
                                  eval_first=eval_first),
                out_shardings=(self.state_shardings, None, None, None))
        if step0 is None:
            step0 = self.step_count
        return cache[(k, eval_first)](
            self.state, self._data, self._labels, idx_g, mask_g, vidx_g,
            vmask_g, rng, jnp.asarray(step0, jnp.int32), tidx=tidx_g,
            tmask=tmask_g)

    def _ensure_epoch_jits(self):
        import jax
        if not hasattr(self, "_epoch_train_jit"):
            self._epoch_train_jit = jax.jit(
                self.runner._epoch_train, donate_argnums=(0,),
                out_shardings=(self.state_shardings, None))
            self._epoch_eval_jit = jax.jit(self.runner._epoch_eval)

    def eval_epoch(self, idx, mask):
        """Whole-set evaluation in one dispatch (see train_epoch)."""
        idx_g, mask_g = self._place_plan(idx, mask)
        return self._epoch_eval_jit(self.state, self._data, self._labels,
                                    idx_g, mask_g)

    @staticmethod
    def fetch(tree):
        """Host values of replicated outputs (metrics), multi-process safe:
        reads the local replica instead of requiring full addressability."""
        import jax

        def leaf(a):
            if isinstance(a, jax.Array) and not a.is_fully_addressable:
                return numpy.asarray(a.addressable_data(0))
            return numpy.asarray(a)
        return jax.tree.map(leaf, tree)

    def sync_to_runner(self):
        """Gather sharded state back into the runner (for snapshots)."""
        import jax
        self.runner.state = jax.tree.map(jax.numpy.asarray,
                                         self.fetch(self.state))
        self.runner.sync_to_units()


def spmd_loader_shard(mesh):
    """(shard_index, shard_count) for ``Loader.shard_spmd`` derived from
    the mesh layout, generalizing "shard by process" to meshes whose
    ``model`` axis spans processes.

    The batch is sharded over the ``data`` axis only, so the rows a
    process must load are determined by which data-coordinates its
    devices cover: processes covering the same block of data-coordinates
    (they sit on different ``model`` columns of the same rows) must load
    IDENTICAL rows — the input replication a cross-host tensor-parallel
    layout requires.  Falls back to the familiar (process_index,
    process_count) on the standard blocked layout, where each process
    owns its own data block.
    """
    import jax
    if "data" not in mesh.axis_names:
        raise ValueError("mesh has no 'data' axis (axes: %r)"
                         % (mesh.axis_names,))
    # blocks are computed over the DATA axis wherever it sits in the
    # grid (put_batch shards by axis name, so position must not matter)
    grid = numpy.moveaxis(mesh.devices,
                          mesh.axis_names.index("data"), 0)
    grid = grid.reshape(grid.shape[0], -1)
    rows_of = {}
    for p in {d.process_index for d in grid.flat}:
        rows = tuple(sorted({r for r in range(grid.shape[0])
                             if any(d.process_index == p
                                    for d in grid[r].flat)}))
        rows_of[p] = rows
    blocks = sorted(set(rows_of.values()), key=lambda t: t[0])
    flat = [r for b in blocks for r in b]
    if flat != list(range(grid.shape[0])) or \
            len({len(b) for b in blocks}) != 1:
        raise ValueError(
            "mesh data-axis layout is not a contiguous equal partition "
            "across processes (blocks: %r) — deterministic loader "
            "sharding needs one; reorder the device grid" % (blocks,))
    return blocks.index(rows_of[jax.process_index()]), len(blocks)


def initialize_multihost(coordinator_address=None, num_processes=None,
                         process_id=None):
    """Multi-host entry: jax.distributed + per-host loader sharding.

    The reference's launcher started a master and N slave processes
    (SURVEY §3.2); the TPU equivalent is one process per host joining the
    same computation (DCN for control, ICI/DCN collectives for data).
    """
    import jax
    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(coordinator_address=coordinator_address,
                      num_processes=num_processes, process_id=process_id)
    jax.distributed.initialize(**kwargs)
    return jax.process_index(), jax.process_count()
