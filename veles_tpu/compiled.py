"""Fused execution of the accelerated segment of a StandardWorkflow.

SURVEY §7's central design move: the reference dispatched one OpenCL/CUDA
kernel per unit per minibatch; here the whole steady-state inner cycle
(forwards → evaluator → backwards → updates) is traced ONCE into a jitted
``train_step(state, batch) -> (state, metrics)`` (plus an ``eval_step``), so
XLA fuses across layer boundaries and the host does a single dispatch per
minibatch.  The unit graph is left intact — the accelerated units are
gate-skipped and a ``FusedStep`` node executes in their place — so Decision
gating, snapshotting and plotting keep working unchanged (they are host-side
outer-graph logic, exactly like the reference's event loop).

The pure functions composed here are the SAME ``forward_fn``/``backward_fn``/
``update_fn``/``loss_fn`` methods the units jit individually in unit mode, so
fused and unit mode are numerically identical by construction.
"""

from __future__ import annotations

from veles_tpu.mutable import Bool
from veles_tpu.units import Unit
from veles_tpu.loader.base import TRAIN


class FusedRunner:
    """Builds and owns the fused step functions + device parameter state."""

    def __init__(self, wf, grad_accum=1):
        import jax
        self.wf = wf
        self.forwards = list(wf.forwards)
        self.evaluator = wf.evaluator
        self.gds = list(wf.gds)
        #: microbatches per optimizer step (>1 = gradient accumulation:
        #: the minibatch is split, grads — batch SUMS by convention —
        #: add across microbatches, ONE update applies; peak activation
        #: memory shrinks by the factor, enabling effective batches that
        #: do not fit in HBM at once)
        self.grad_accum = int(grad_accum)
        if self.grad_accum < 1:
            raise ValueError("grad_accum must be >= 1")
        self.state = self._pull_state()
        # loss routing: softmax-style evaluators consume labels, MSE-style
        # consume a target (linked on the evaluator; for autoencoders it
        # aliases the loader's minibatch_data)
        from veles_tpu.ops.evaluator import EvaluatorMSE
        self._is_mse = isinstance(self.evaluator, EvaluatorMSE)
        self._has_stochastic = any(getattr(f, "STOCHASTIC", False)
                                   for f in self.forwards)
        # No donation in per-minibatch graph mode: the update is only
        # COMMITTED after Decision gates it (see FusedStep/FusedCommit), so
        # the previous state must stay alive.  The epoch-scan path donates.
        #: the configured per-minibatch train step (monolithic or
        #: gradient-accumulating) — the per-step jit AND the epoch scan
        #: both route through it, so grad_accum is never silently dropped
        self._step_fn = (self._train_step if self.grad_accum == 1
                         else self._train_step_accum)
        self._train = jax.jit(self._step_fn)
        self._eval = jax.jit(self._eval_step)

    # ----------------------------------------------------------------- state
    def _pull_state(self):
        """Collect per-layer optimizer state from the unit Vectors
        (weightless layers contribute an empty entry).  The GD unit owns
        the entry layout — params + velocity, plus solver accumulators for
        adagrad/adadelta (see GradientDescentBase.state_entry)."""
        return [gd.state_entry() if fwd.has_params else {}
                for fwd, gd in zip(self.forwards, self.gds)]

    def sync_to_units(self):
        """Write fused state back into the unit Vectors (for snapshots)."""
        for entry, fwd, gd in zip(self.state, self.forwards, self.gds):
            if fwd.has_params:
                gd.absorb_entry(entry)

    # ----------------------------------------------------------------- steps
    def _layer_rng(self, rng, i):
        import jax
        return None if rng is None else jax.random.fold_in(rng, i)

    def _forward_chain(self, state, x, rng=None, train=False):
        acts = [x]
        h = x
        for i, (fwd, entry) in enumerate(zip(self.forwards, state)):
            if getattr(fwd, "HAS_SKIP_EDGE", False):
                # skip-edge layers (residual / residual_proj) see the
                # whole activation list — the unit owns the math
                # (ops/residual.py chain_forward), the chain owns acts
                h = fwd.chain_forward(i, acts, entry,
                                      self._layer_rng(rng, i), train)
            else:
                h = fwd.apply_fused(h, entry, self._layer_rng(rng, i),
                                    train)
            acts.append(h)
        return acts

    def _loss(self, y, y_ref, mask):
        """y_ref: labels (classification) or the regression/AE target."""
        if self._is_mse:
            return self.evaluator.loss_fn(y, y_ref.reshape(y.shape), mask)
        return self.evaluator.loss_fn(y, y_ref, mask)

    def _eval_step(self, state, x, y_ref, mask):
        acts = self._forward_chain(state, x, rng=None, train=False)
        _, metrics = self._loss(acts[-1], y_ref, mask)
        return metrics

    def _grads_and_metrics(self, state, x, y_ref, mask, rng=None):
        """Forward + loss + backward WITHOUT updates: per-layer grad sums
        (None for weightless layers) and the metric sums.  The per-layer
        update in _train_step and the accumulate-then-update in
        _train_step_accum both consume this."""
        acts = self._forward_chain(state, x, rng=rng, train=True)
        err, metrics = self._loss(acts[-1], y_ref, mask)
        all_grads = [None] * len(self.forwards)
        # residual fan-out: a skip edge makes acts[src] TWO consumers'
        # input, so its error has two contributions — the main chain's
        # and the stashed skip error, merged when the walk reaches src
        pending = {}
        for i in range(len(self.forwards) - 1, -1, -1):
            if err is not None and (i + 1) in pending:
                err = err + pending.pop(i + 1)
            if err is None:
                # the first parameterized gd skipped err_input; everything
                # below it is weightless (see link_gds) — nothing to do
                break
            fwd = self.forwards[i]
            if getattr(fwd, "HAS_SKIP_EDGE", False):
                # the unit returns its main-path error, where to stash
                # the skip error, and its own grads (None if weightless)
                err, src, d_src, grads = fwd.chain_backward(
                    i, acts, state[i], err, self._layer_rng(rng, i))
                pending[src] = (pending[src] + d_src if src in pending
                                else d_src)
                all_grads[i] = grads
                continue
            gd, entry = self.gds[i], state[i]
            err_in, grads = gd.backward_fused(
                acts[i], acts[i + 1], err, entry, self._layer_rng(rng, i))
            all_grads[i] = grads
            err = err_in
        return all_grads, metrics

    def _apply_updates(self, state, all_grads, batch_size, step):
        new_state = list(state)
        for i, grads in enumerate(all_grads):
            if grads is not None:
                new_state[i] = self.gds[i].update_fused(
                    state[i], grads, batch_size, step)
        return new_state

    def _train_step(self, state, x, y_ref, mask, batch_size, rng=None,
                    step=0):
        all_grads, metrics = self._grads_and_metrics(state, x, y_ref, mask,
                                                     rng)
        return self._apply_updates(state, all_grads, batch_size,
                                   step), metrics

    def _train_step_accum(self, state, x, y_ref, mask, batch_size,
                          rng=None, step=0):
        """Gradient-accumulation step: the minibatch splits into
        ``grad_accum`` microbatches scanned on device; grad sums add
        (they are batch SUMS by convention, so accumulation is exact up
        to fp summation order), ``*_max`` metrics combine with maximum,
        the rest add, and ONE update applies with the full live batch
        size.  Stochastic layers draw a distinct key per microbatch
        (documented semantics — dropout granularity follows the
        microbatch).  The microbatch graph is traced ONCE (zeros-init
        carry via eval_shape) so the accum path does not double compile
        time."""
        import jax
        import jax.numpy as jnp
        k = self.grad_accum
        if x.shape[0] % k:
            raise ValueError("minibatch %d not divisible by grad_accum %d"
                             % (x.shape[0], k))

        def split(a):
            return (None if a is None
                    else a.reshape((k, a.shape[0] // k) + a.shape[1:]))

        xs, ys, ms = split(x), split(y_ref), split(mask)

        def micro(i):
            r = None if rng is None else jax.random.fold_in(rng, i)
            y_i = None if ys is None else ys[i]
            return self._grads_and_metrics(state, xs[i], y_i, ms[i], r)

        g_shapes, m_shapes = jax.eval_shape(micro, 0)
        g0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), g_shapes)
        m0 = {key: (jnp.full(s.shape, -jnp.inf, s.dtype)
                    if key.endswith("_max")
                    else jnp.zeros(s.shape, s.dtype))
              for key, s in m_shapes.items()}

        def body(carry, i):
            g_acc, m_acc = carry
            g_i, m_i = micro(i)
            g_acc = jax.tree.map(jnp.add, g_acc, g_i)
            m_acc = {key: (jnp.maximum(m_acc[key], m_i[key])
                           if key.endswith("_max")
                           else m_acc[key] + m_i[key]) for key in m_acc}
            return (g_acc, m_acc), None

        (all_grads, metrics), _ = jax.lax.scan(body, (g0, m0),
                                               jnp.arange(k))
        return self._apply_updates(state, all_grads, batch_size,
                                   step), metrics

    def measure_device_step_time(self, iters=10):
        """Steady-state device time of one fused train step, by re-running
        the last dispatched batch ``iters`` times and ending the window in
        ``block_until_ready``.  None until a train step has run.  Feeds the
        ``print_stats`` device-time line (SURVEY §5.1 profiling rebuild).

        The timing dispatches REAL train steps but their updated state is
        DISCARDED (``self._train`` does not donate and the result is never
        assigned) — printing stats can never move the final weights;
        pinned by tests/test_launcher.py::
        test_stats_measurement_never_moves_weights."""
        import time
        import jax
        args = getattr(self, "_last_train_args", None)
        if args is None:
            return None
        _, metrics = self._train(self.state, *args)
        # warm (already compiled; syncs pending work)
        jax.block_until_ready(metrics)
        begin = time.perf_counter()
        for _ in range(iters):
            _, metrics = self._train(self.state, *args)
        jax.block_until_ready(metrics)
        return (time.perf_counter() - begin) / iters

    def eval_forward(self):
        """Jitted eval-mode forward ``(state, x) -> last activation``,
        compiled once and shared (REST serving, ensemble combination)."""
        import jax
        if not hasattr(self, "_eval_forward_jit"):
            self._eval_forward_jit = jax.jit(
                lambda state, x: self._forward_chain(
                    state, x, rng=None, train=False)[-1])
        return self._eval_forward_jit

    # ----------------------------------------------------- epoch-scan (fast)
    # One device dispatch per EPOCH: lax.scan over the minibatch index
    # matrix with the dataset resident in HBM.  This is the pure TPU-native
    # steady state — zero host work between minibatches (the reference did
    # host scheduling + H2D upload per minibatch, SURVEY §3.1).
    def _epoch_train(self, state, data, labels, idx, mask, rng=None,
                     step0=0):
        import jax
        import jax.numpy as jnp

        def body(carry, mb):
            step, mb_idx, mb_mask = mb
            x = jnp.take(data, mb_idx, axis=0)
            # labels doubles as the target array for MSE/AE workflows
            y = (jnp.take(labels, mb_idx, axis=0)
                 if labels is not None else x)
            bs = mb_mask.sum().astype(jnp.int32)
            step_rng = (jax.random.fold_in(rng, step)
                        if rng is not None else None)
            carry, metrics = self._step_fn(carry, x, y, mb_mask, bs,
                                           step_rng, step0 + step)
            return carry, metrics

        steps = jnp.arange(idx.shape[0])
        state, stacked = jax.lax.scan(body, state, (steps, idx, mask))
        totals = jax.tree.map(lambda m: m.sum(axis=0), stacked)
        return state, totals

    def _epoch_eval(self, state, data, labels, idx, mask):
        import jax
        import jax.numpy as jnp

        def body(carry, mb):
            mb_idx, mb_mask = mb
            x = jnp.take(data, mb_idx, axis=0)
            y = (jnp.take(labels, mb_idx, axis=0)
                 if labels is not None else x)
            metrics = self._eval_step(carry, x, y, mb_mask)
            return carry, metrics

        _, stacked = jax.lax.scan(body, state, (idx, mask))
        return jax.tree.map(lambda m: m.sum(axis=0), stacked)

    def _epoch_chunk(self, k, state, data, labels, idx, mask, rng=None,
                     step0=0):
        """``k`` epochs in ONE device program: lax.scan over the epoch
        axis around ``_epoch_train``.  Matches ``k`` sequential
        ``train_epoch`` calls exactly (same per-epoch key folding by
        global step, pinned by tests) while paying the host->device
        dispatch round-trip once per chunk instead of once per epoch.

        ``idx``/``mask`` of shape (B, mb) reuse ONE minibatch plan for
        every epoch in the chunk; shape (k, B, mb) gives each epoch its
        own plan (true per-epoch reshuffling, precomputed on the host),
        so chunking does not have to trade away shuffle-per-epoch SGD
        semantics."""
        import jax
        import jax.numpy as jnp
        per_epoch_plan = idx.ndim == 3
        steps = idx.shape[-2]

        def body(carry, xs):
            if per_epoch_plan:
                e, eidx, emask = xs
            else:
                e, eidx, emask = xs, idx, mask
            off = step0 + e * steps
            erng = (jax.random.fold_in(rng, off)
                    if rng is not None else None)
            carry, totals = self._epoch_train(carry, data, labels, eidx,
                                              emask, erng, off)
            return carry, totals

        xs = ((jnp.arange(k), idx, mask) if per_epoch_plan
              else jnp.arange(k))
        state, stacked = jax.lax.scan(body, state, xs)
        return state, stacked

    def epoch_chunk_fn(self, k):
        """Jitted ``(state, data, labels, idx, mask[, rng, step0]) ->
        (state, per-epoch metric totals stacked over the k epochs)``;
        donates state.  Compiled once per distinct ``k``."""
        import functools
        import jax
        cache = getattr(self, "_epoch_chunk_jits", None)
        if cache is None:
            cache = self._epoch_chunk_jits = {}
        if k not in cache:
            inner = jax.jit(functools.partial(self._epoch_chunk, k),
                            donate_argnums=(0,))

            def chunk(state, data, labels, idx, mask, rng=None, step0=0):
                import jax.numpy as jnp
                self.require_epoch_rng(rng)
                if idx.ndim == 3 and idx.shape[0] != k:
                    raise ValueError(
                        "per-epoch plan has %d epochs, chunk is %d"
                        % (idx.shape[0], k))
                return inner(state, data, labels, idx, mask, rng,
                             jnp.asarray(step0, jnp.int32))

            cache[k] = chunk
        return cache[k]

    def _epoch_chunk_eval(self, k, state, data, labels, idx, mask,
                          vidx, vmask, rng=None, step0=0,
                          eval_first=False, tidx=None, tmask=None):
        """``k`` (train epoch + validation eval) rounds in ONE program:
        the convergence loop's body, chunked.  Returns the updated state
        plus per-epoch TRAIN and VALID metric totals (k rows each), so a
        host-side early-stopping loop sees exactly the per-epoch values
        it would have fetched individually — at one dispatch per k
        epochs instead of 2k.  idx/mask as in ``_epoch_chunk`` ((B, mb)
        shared or (k, B, mb) per-epoch plans); vidx/vmask are the fixed
        validation plan.  ``eval_first`` evaluates valid BEFORE the
        epoch's training — the unit-graph loop's set order (the loader
        plans test → validation → train), which the epoch-scan CLI
        driver mirrors; the convergence bench keeps eval-after.
        ``tidx``/``tmask`` add a per-epoch TEST-set eval (ordered before
        valid, like the loader plans it); its stacked totals come back
        as the fourth output (None when no test plan is given)."""
        import jax
        import jax.numpy as jnp
        per_epoch_plan = idx.ndim == 3
        steps = idx.shape[-2]
        has_test = tidx is not None

        def evals(carry):
            test_totals = (self._epoch_eval(carry, data, labels, tidx,
                                            tmask) if has_test else None)
            val_totals = self._epoch_eval(carry, data, labels, vidx,
                                          vmask)
            return test_totals, val_totals

        def body(carry, xs):
            if per_epoch_plan:
                e, eidx, emask = xs
            else:
                e, eidx, emask = xs, idx, mask
            off = step0 + e * steps
            erng = (jax.random.fold_in(rng, off)
                    if rng is not None else None)
            if eval_first:
                test_totals, val_totals = evals(carry)
            carry, train_totals = self._epoch_train(
                carry, data, labels, eidx, emask, erng, off)
            if not eval_first:
                test_totals, val_totals = evals(carry)
            return carry, (train_totals, val_totals, test_totals)

        xs = ((jnp.arange(k), idx, mask) if per_epoch_plan
              else jnp.arange(k))
        state, (train_stack, val_stack, test_stack) = jax.lax.scan(
            body, state, xs)
        return state, train_stack, val_stack, test_stack

    def epoch_chunk_eval_fn(self, k, eval_first=False, donate=True):
        """Jitted ``(state, data, labels, idx, mask, vidx, vmask[, rng,
        step0, tidx, tmask]) -> (state, train totals stacked, val totals
        stacked, test totals stacked or None)``.
        Donates state unless ``donate=False`` (the epoch-scan CLI driver
        keeps the chunk-input state alive so a completion inside the
        chunk can be replayed exactly — see epoch_driver.py — without
        paying per-leaf device copies).  Compiled once per distinct
        ``(k, eval_first, donate)`` (plus a retrace when a test plan
        appears)."""
        import functools
        import jax
        cache = getattr(self, "_epoch_chunk_eval_jits", None)
        if cache is None:
            cache = self._epoch_chunk_eval_jits = {}
        if (k, eval_first, donate) not in cache:
            inner = jax.jit(functools.partial(self._epoch_chunk_eval, k,
                                              eval_first=eval_first),
                            donate_argnums=(0,) if donate else ())

            def chunk(state, data, labels, idx, mask, vidx, vmask,
                      rng=None, step0=0, tidx=None, tmask=None):
                import jax.numpy as jnp
                self.require_epoch_rng(rng)
                if idx.ndim == 3 and idx.shape[0] != k:
                    raise ValueError(
                        "per-epoch plan has %d epochs, chunk is %d"
                        % (idx.shape[0], k))
                return inner(state, data, labels, idx, mask, vidx,
                             vmask, rng, jnp.asarray(step0, jnp.int32),
                             tidx=tidx, tmask=tmask)

            cache[(k, eval_first, donate)] = chunk
        return cache[(k, eval_first, donate)]

    def window_scan_fn(self):
        """Jitted ``(state, data, labels, idx, mask[, rng, step0]) ->
        (state, window metric totals)``: ALL of a WINDOW's minibatches as
        one ``lax.scan`` device program over window-resident data —
        ``_epoch_train`` (and therefore ``_step_fn``) reused verbatim
        with ``idx`` indexing INTO the window arrays, so fused/graph
        numerics parity is preserved by construction.  This is the
        streaming epoch-scan inner program (see epoch_driver.py): the
        dataset streams through HBM one window at a time while the host
        stages the next window concurrently.

        Non-donating: the streaming driver keeps the final window's
        input state alive so a Decision completion can be replayed with
        the last minibatch's update discarded (graph-loop parity, same
        artifact the chunk driver reproduces).  Compiled once per
        distinct window geometry — a uniform window size plus one tail
        window means at most two traces per run."""
        import jax
        if not hasattr(self, "_window_scan_jit"):
            inner = jax.jit(self._epoch_train)

            def window_scan(state, data, labels, idx, mask, rng=None,
                            step0=0):
                import jax.numpy as jnp
                self.require_epoch_rng(rng)
                return inner(state, data, labels, idx, mask, rng,
                             jnp.asarray(step0, jnp.int32))

            self._window_scan_jit = window_scan
        return self._window_scan_jit

    def require_epoch_rng(self, rng):
        """Stochastic layers (dropout) need an explicit epoch rng — shared
        guard for the single-chip and SPMD epoch-scan entry points."""
        if self._has_stochastic and rng is None:
            raise ValueError(
                "this network has stochastic layers (dropout): "
                "pass rng=jax.random.PRNGKey(...) to train_epoch")

    def epoch_fns(self):
        """Jitted (train_epoch, eval_epoch): args (state, data, labels,
        idx (B,mb) int32, mask (B,mb) f32[, rng]); train donates state.
        Networks with stochastic layers (dropout) MUST pass rng to
        train_epoch — enforced with a clear error at call time."""
        import jax
        if not hasattr(self, "_epoch_train_jit"):
            inner = jax.jit(self._epoch_train, donate_argnums=(0,))

            def train_epoch(state, data, labels, idx, mask, rng=None,
                            step0=0):
                import jax.numpy as jnp
                self.require_epoch_rng(rng)
                # int32 device scalar: a bare python int would retrace the
                # epoch program once per distinct value
                return inner(state, data, labels, idx, mask, rng,
                             jnp.asarray(step0, jnp.int32))

            self._epoch_train_jit = train_epoch
            self._epoch_eval_jit = jax.jit(self._epoch_eval)
        return self._epoch_train_jit, self._epoch_eval_jit

    # ------------------------------------------------------------ graph hook
    def install(self):
        """Rewire the graph: gate-skip the accelerated units; FusedStep runs
        the traced step right after the loader, FusedCommit adopts the
        pending update AFTER Decision has gated it — exactly the reference's
        ordering, where GD units fire after Decision and are skipped by
        gd_skip/complete (ref: veles/znicz/standard_workflow.py [H])."""
        wf = self.wf
        always = Bool(True)
        for unit in self.forwards + [self.evaluator] + self.gds:
            unit.gate_skip = always
        fused = FusedStep(wf, self, name="fused_step")
        first_fwd = self.forwards[0]
        first_fwd.unlink_from(wf.loader)
        fused.link_from(wf.loader)
        first_fwd.link_from(fused)
        commit = FusedCommit(wf, self, name="fused_commit")
        commit.link_from(wf.decision)
        commit.gate_skip = wf.decision.gd_skip | wf.decision.complete
        wf.fused_step = fused
        wf.fused_commit = commit
        return fused


class FusedStep(Unit):
    """Executes one fused train/eval step per minibatch.

    For train minibatches the updated state is held PENDING; FusedCommit
    adopts it only if Decision lets the backward pass run.  Note the unit
    Vectors (weights/bias) are only synced back at snapshot time and at run
    end — mid-run host reads must go through the runner's state.
    """

    snapshot_attrs = ("train_steps",)

    def __init__(self, workflow, runner, **kwargs):
        super().__init__(workflow, **kwargs)
        self.runner = runner
        self.pending_state = None
        #: global train-minibatch counter feeding the lr policies
        self.train_steps = 0
        self._initialized = True

    def initialize(self, **kwargs):
        super().initialize(**kwargs)

    def run(self):
        import jax.numpy as jnp
        runner = self.runner
        loader = runner.wf.loader
        #: attached by the launcher under --distributed: minibatches
        #: route through the mesh (local rows -> global batch, GSPMD
        #: all-reduce on the sharded batch axis), same pending/commit
        #: ordering (ref: SURVEY §5.8 — the reference's master-side
        #: averaging, collapsed into the compiled step)
        trainer = getattr(runner.wf, "_sharded_trainer", None)
        x = loader.minibatch_data.devmem
        labels = (loader.minibatch_labels.devmem
                  if not loader.minibatch_labels.is_empty else None)
        mask = loader.minibatch_mask.devmem
        if runner._is_mse:
            y_ref = runner.evaluator.target.devmem
        else:
            y_ref = labels
        if (loader.minibatch_class == TRAIN
                and not getattr(runner.wf, "eval_only", False)):
            if runner._has_stochastic:
                from veles_tpu import prng
                rng = prng.get("dropout").key()
            else:
                rng = None
            if trainer is not None:
                self.pending_state, metrics = trainer.train_step_pending(
                    x, y_ref, mask, loader.minibatch_size, rng,
                    self.train_steps)
            else:
                args = (x, y_ref, mask,
                        jnp.asarray(loader.minibatch_size, jnp.int32),
                        rng, jnp.asarray(self.train_steps, jnp.int32))
                self.pending_state, metrics = runner._train(runner.state,
                                                            *args)
                runner._last_train_args = args  # measure_device_step_time
            self.train_steps += 1
        else:
            self.pending_state = None
            if trainer is not None:
                metrics = trainer.eval_step(x, y_ref, mask)
            else:
                metrics = runner._eval(runner.state, x, y_ref, mask)
        # decision reads these through its link_attrs alias on the evaluator
        runner.evaluator.metrics = metrics

    def stop(self):
        trainer = getattr(self.runner.wf, "_sharded_trainer", None)
        if trainer is not None:
            trainer.sync_to_runner()
        else:
            self.runner.sync_to_units()


class FusedCommit(Unit):
    """Adopts the pending update; gated like the GD units."""

    def __init__(self, workflow, runner, **kwargs):
        super().__init__(workflow, **kwargs)
        self.runner = runner
        self._initialized = True

    def initialize(self, **kwargs):
        super().initialize(**kwargs)

    def run(self):
        fused = self.runner.wf.fused_step
        if fused.pending_state is not None:
            trainer = getattr(self.runner.wf, "_sharded_trainer", None)
            if trainer is not None:
                trainer.state = fused.pending_state
            else:
                self.runner.state = fused.pending_state
            fused.pending_state = None
