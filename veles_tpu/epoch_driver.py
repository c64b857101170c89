"""Epoch-scan CLI training driver — the TPU steady state as the MAIN loop.

The unit-graph event loop (SURVEY §3.1's rebuild) dispatches one fused
step per minibatch; this driver instead runs whole epochs — or k-epoch
chunks — as ONE device program (``FusedRunner.epoch_chunk_eval_fn``),
while keeping the workflow's host-side brains exactly as they are:

- **Decision** sees the same per-epoch summed metrics it accumulates in
  graph mode (validation evaluated BEFORE each epoch's training — the
  loader plans test → validation → train — then the training pass's own
  totals), via the same ``reduce_metrics``/``_on_epoch_end`` methods, so
  improvement tracking, early stopping and logging are identical code.
- **Snapshotter** fires at chunk boundaries through its normal
  ``run()``/``stop()`` gates (the state inside a chunk is not
  addressable — with ``chunk > 1`` snapshot granularity coarsens to the
  chunk, documented).
- **The completion gate artifact is reproduced exactly.**  In graph
  mode, Decision setting ``complete`` gate-skips FusedCommit, so the
  stopping epoch's LAST minibatch update is computed but DISCARDED
  (the reference's ordering — GD units fire after Decision).  The scan
  commits every update, so when completion lands at chunk row R the
  driver replays rows 0..R from the (kept, non-donated) chunk-input
  state with row R truncated to its first ``steps-1`` minibatches —
  one extra dispatch, once per training run.

With no stochastic layers the driver's epoch_metrics and final weights
EQUAL the graph loop's at any chunk size (pinned by
tests/test_launcher.py); dropout networks draw scan-path keys
(documented divergence, same as every epoch-scan path).

**Streaming windowed mode** (``--stream-window W``): out-of-core
datasets (RecordsLoader/LMDBLoader) cannot park the whole dataset in
HBM, and used to fall back to one dispatch per minibatch through the
graph loop.  Instead the epoch's minibatch plan is split into contiguous
windows of W minibatches; each window's samples are gathered host-side
(``Loader.gather_window``), uploaded once, and ALL of the window's
minibatches run as one ``lax.scan`` program
(``FusedRunner.window_scan_fn`` — the same ``_step_fn``, so numerics
match the full-batch scan and the graph loop).  While window *i* trains,
a staging thread gathers and uploads window *i+1*
(``--stage-ahead N`` windows in flight) — the RecordsLoader per-minibatch
prefetch generalized to whole windows.  Dispatches per epoch drop from
~minibatches to ~windows, and per-window staging/compute timing feeds
``print_stats`` and the ``/metrics`` gauges (samples/sec, staging-stall
fraction).  The completion-gate artifact is reproduced at window
granularity: the stopping epoch's final window is replayed from its
kept input state with the last minibatch dropped.

Ref: veles/launcher.py + veles/znicz/decision.py [H] — behavior parity
with the reference's epoch bookkeeping, substrate redesigned.
"""

from __future__ import annotations

import time

import numpy

from veles_tpu.logger import Logger
from veles_tpu.loader.base import TRAIN, VALID, TEST

#: minibatches per window when --stream-window is bare/unset on a
#: streaming loader: big enough to amortize the dispatch round-trip,
#: small enough that two windows of typical ImageNet minibatches fit
#: HBM alongside the model
DEFAULT_STREAM_WINDOW = 16


class _WindowStager:
    """Double-buffers training windows for the streaming epoch-scan.

    Pool threads gather up to ``stage_ahead`` windows from the loader's
    backing store (memmap/LMDB pages; the native gather releases the
    GIL) and ``jax.device_put`` them while the device trains the current
    window — the whole-window generalization of RecordsLoader's
    per-minibatch prefetch.  ``take`` blocks until the window is staged;
    the blocked time IS the staging stall the stats report.
    """

    def __init__(self, loader, want_labels, stage_ahead, name="stager"):
        import concurrent.futures
        self.loader = loader
        self.want_labels = want_labels
        self.ahead = max(int(stage_ahead), 1)
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.ahead, thread_name_prefix=name)
        self._pending = {}
        self.stall_seconds = 0.0

    def stage(self, gidx, mask):
        """Gather + upload one window NOW (also the pool thread body):
        (x, labels-or-None, window-local idx, mask) device arrays."""
        import jax
        import jax.numpy as jnp
        gidx = numpy.ascontiguousarray(gidx, numpy.int32)
        rows, mb = gidx.shape
        data, labels = self.loader.gather_window(gidx.ravel())
        x = jax.device_put(data)
        y = (jax.device_put(labels)
             if self.want_labels and labels is not None else None)
        lidx = jnp.arange(rows * mb, dtype=jnp.int32).reshape(rows, mb)
        m = jax.device_put(numpy.asarray(mask, numpy.float32))
        return x, y, lidx, m

    def submit(self, key, gidx, mask):
        self._pending[key] = self._pool.submit(self.stage, gidx, mask)

    def take(self, key):
        """The staged window for ``key``, blocking (and accounting the
        block as staging stall) if the gather/upload is still running."""
        fut = self._pending.pop(key)
        begin = time.perf_counter()
        out = fut.result()
        self.stall_seconds += time.perf_counter() - begin
        return out

    def shutdown(self):
        for fut in self._pending.values():
            fut.cancel()
        self._pending.clear()
        self._pool.shutdown(wait=True)


class EpochScanDriver(Logger):
    """Drives a fused StandardWorkflow through epoch-scan chunks
    (HBM-resident datasets) or streamed device-resident windows
    (out-of-core datasets; ``stream_window`` > 0 forces it)."""

    def __init__(self, wf, chunk=1, stream_window=0, stage_ahead=1):
        from veles_tpu.ops.decision import DecisionGD, DecisionMSE
        self.wf = wf
        self.chunk = max(int(chunk), 1)
        self.stream_window = int(stream_window or 0)
        self.stage_ahead = max(int(stage_ahead), 1)
        #: filled by the streaming path: windows, dispatches,
        #: staging-stall/compute seconds, samples/sec (print_stats and
        #: the /metrics gauges read it off the workflow)
        self.stream_stats = None
        runner = getattr(wf, "_fused_runner", None)
        if runner is None:
            raise ValueError("--epoch-scan needs a fused workflow "
                             "(drop --no-fused)")
        loader = wf.loader
        full_batch = (getattr(loader, "original_data", None) is not None
                      and not loader.original_data.is_empty)
        if self.stream_window > 0:
            if not loader.can_gather_windows:
                raise ValueError(
                    "--stream-window needs a loader with gather_window "
                    "(RecordsLoader, LMDBLoader, FullBatchLoader); %s "
                    "has no random-access backing store"
                    % type(loader).__name__)
            self.streaming = True
        elif full_batch:
            self.streaming = False
        elif loader.can_gather_windows:
            # out-of-core loader under bare --epoch-scan: stream with
            # the default window instead of refusing (the pre-streaming
            # behavior) — this is exactly the workload the windowed
            # path exists for
            self.streaming = True
            self.stream_window = DEFAULT_STREAM_WINDOW
        else:
            raise ValueError(
                "--epoch-scan needs a full-batch loader (dataset "
                "resident in device memory) or a window-gatherable "
                "streaming loader (RecordsLoader/LMDBLoader — see "
                "--stream-window); %s is neither" % type(loader).__name__)
        decision = getattr(wf, "decision", None)
        if not isinstance(decision, (DecisionGD, DecisionMSE)):
            raise ValueError(
                "--epoch-scan supports DecisionGD/DecisionMSE workflows; "
                "%r drives training some other way — use the graph loop"
                % type(decision).__name__)
        if not loader.class_lengths[VALID]:
            raise ValueError("--epoch-scan needs a validation set (the "
                             "stopping rule evaluates it per epoch)")
        self.runner = runner
        self.loader = loader
        self.decision = decision

    # ------------------------------------------------------------------ run
    def _feed_decision(self, train_row, val_row, test_row, counts):
        """Hand one epoch's summed metrics to the decision through its
        normal host-side path (reduce_metrics + _on_epoch_end)."""
        dec = self.decision
        n_train, n_valid, n_test = counts

        def host(row, count):
            out = {}
            for key, value in row.items():
                arr = numpy.asarray(value)
                out[key] = float(arr) if arr.ndim == 0 else arr
            out["count"] = count
            return out

        current = {}
        if test_row is not None:
            current["test"] = dec.reduce_metrics(host(test_row, n_test))
        current["validation"] = dec.reduce_metrics(host(val_row, n_valid))
        current["train"] = dec.reduce_metrics(host(train_row, n_train))
        dec._current = current
        dec._on_epoch_end()
        dec._reset_epoch()

    def _notify_reporters(self):
        """Drive any StatusReporter units at epoch/chunk granularity —
        the graph loop runs them off Decision's link; the drivers bypass
        the graph pump, so dashboard/metrics rows are pushed here."""
        from veles_tpu.web_status import StatusReporter
        for unit in getattr(self.wf, "_units", []):
            if isinstance(unit, StatusReporter):
                try:
                    unit.run()
                except Exception as e:   # noqa: BLE001 — never fatal
                    self.warning("status report failed: %s", e)

    def run(self):
        if self.streaming:
            return self._run_streaming()
        return self._run_chunked()

    # ------------------------------------------------- chunked (HBM-resident)
    def _run_chunked(self):
        import jax
        wf = self.wf
        runner, loader, dec = self.runner, self.loader, self.decision
        #: --distributed: the launcher attached a ShardedTrainer — chunks
        #: run under the global mesh (dataset replicated, plan matrices
        #: sharded over 'data', GSPMD all-reduce per step), with the same
        #: host-side flow; metric rows read the local replica
        trainer = getattr(wf, "_sharded_trainer", None)
        if trainer is not None:
            trainer.place_dataset(
                numpy.asarray(loader.original_data.mem),
                None if runner._is_mse
                else numpy.asarray(loader.original_labels.mem))
            data = labels = None        # live in trainer._data/_labels
            fetch = trainer.fetch
        else:
            data = loader.original_data.devmem
            labels = (None if runner._is_mse
                      else loader.original_labels.devmem)
            fetch = lambda tree: jax.tree.map(numpy.asarray, tree)  # noqa: E731
        # fixed validation plan (valid never shuffles); the loader's
        # CURRENT plan supplies epoch 1 IF it is still unconsumed
        # (_position 0: fresh initialize) — the same plan the graph loop
        # would consume — otherwise (snapshot resume: the restored plan
        # was already trained) a fresh shuffle is drawn, exactly as the
        # graph loop's next_minibatch would
        vidx, vmask = loader.plan_arrays(VALID)
        n_valid = int(vmask.sum())
        tidx, tmask = loader.plan_arrays(TEST)   # (None, None) if absent
        n_test = int(tmask.sum()) if tmask is not None else 0
        rng_stream = None
        if runner._has_stochastic:
            from veles_tpu import prng
            rng_stream = prng.get("dropout")
        # non-donating: the chunk-input state must survive the dispatch so
        # a completion inside the chunk can be replayed exactly (below)
        if trainer is not None:
            def chunk_fn(unused_state, unused_data, unused_labels, idx,
                         mask, vidx_, vmask_, rng, step0, tidx, tmask):
                return trainer.chunk_eval_pending(
                    idx, mask, vidx_, vmask_, rng=rng, step0=step0,
                    eval_first=True, tidx=tidx, tmask=tmask)
        else:
            inner_chunk = runner.epoch_chunk_eval_fn(
                self.chunk, eval_first=True, donate=False)

            def chunk_fn(state_, data_, labels_, idx, mask, vidx_,
                         vmask_, rng, step0, tidx_, tmask_):
                return inner_chunk(state_, data_, labels_, idx, mask,
                                   vidx_, vmask_, rng=rng, step0=step0,
                                   tidx=tidx_, tmask=tmask_)
        first_plan_fresh = loader._position == 0
        state = trainer.state if trainer is not None else runner.state
        snap = getattr(wf, "snapshotter", None)
        while not bool(dec.complete):
            plans = []
            for _ in range(self.chunk):
                if first_plan_fresh:
                    first_plan_fresh = False
                else:
                    loader._plan_epoch()
                plans.append(loader.plan_arrays(TRAIN))
            # the plan is consumed: snapshots must restore like the graph
            # loop's end-of-epoch state (next consumer replans)
            loader._position = len(loader._order)
            idx = numpy.stack([p[0] for p in plans])
            mask = numpy.stack([p[1] for p in plans])
            steps = idx.shape[-2]
            n_train = int(mask[0].sum())
            step0 = int(loader.epoch_number) * steps
            rng = rng_stream.key() if rng_stream is not None else None
            state_in = state
            state, train_stack, val_stack, test_stack = chunk_fn(
                state, data, labels, idx, mask, vidx, vmask, rng,
                step0, tidx, tmask)
            train_rows = fetch(train_stack)
            val_rows = fetch(val_stack)
            test_rows = (fetch(test_stack)
                         if test_stack is not None else None)
            done_row = None
            for row in range(self.chunk):
                loader.epoch_number = int(loader.epoch_number) + 1
                self._feed_decision(
                    {k: v[row] for k, v in train_rows.items()},
                    {k: v[row] for k, v in val_rows.items()},
                    ({k: v[row] for k, v in test_rows.items()}
                     if test_rows is not None else None),
                    (n_train, n_valid, n_test))
                fused = getattr(wf, "fused_step", None)
                if fused is not None:
                    fused.train_steps += steps
                if bool(dec.complete):
                    done_row = row
                    break
            if done_row is not None:
                # graph-mode parity: Decision.complete gate-skips the
                # commit of the stopping epoch's LAST minibatch — replay
                # rows 0..done_row from the kept input state with the
                # final epoch truncated to steps-1 minibatches
                if trainer is not None:
                    state = self._replay_spmd(trainer, idx, mask, rng,
                                              step0, done_row, steps)
                else:
                    state = self._replay_to_completion(
                        state_in, data, labels, idx, mask, rng, step0,
                        done_row, steps)
            # chunk boundary: state is addressable — commit, then the
            # snapshot gates fire (snapshot_state() syncs the runner
            # itself when it writes)
            if trainer is not None:
                trainer.state = state
                if done_row is None:
                    trainer.step_count = step0 + self.chunk * steps
                else:
                    # graph-mode parity for the COUNTER too: the graph
                    # loop dispatches (and counts in train_steps) the
                    # stopping epoch's last minibatch even though its
                    # commit is discarded; the replay trains steps-1, so
                    # set the counter to the full-epoch value — a
                    # resumed lr policy must start at the same step
                    trainer.step_count = step0 + (done_row + 1) * steps
            else:
                runner.state = state
            if snap is not None:
                loader.epoch_ended = True   # plain attr, like the loader
                snap.run()
            self._notify_reporters()
        if trainer is not None:
            trainer.state = state
            trainer.sync_to_runner()
        else:
            runner.state = state
            runner.sync_to_units()
        if snap is not None:
            snap.stop()
        wf._finished = True

    # ------------------------------------------------- streaming (windowed)
    def _run_streaming(self):
        """Windowed streaming epoch-scan: the dataset flows through HBM
        one device-resident window (``stream_window`` minibatches) at a
        time, each window one ``lax.scan`` dispatch, the next window
        staged concurrently by ``_WindowStager``.  Decision, snapshots
        and the completion-gate replay behave exactly like the chunked
        path at chunk=1; state commits at window granularity but is only
        made addressable (snapshots, unit sync) at epoch boundaries."""
        import jax
        wf = self.wf
        runner, loader, dec = self.runner, self.loader, self.decision
        if getattr(wf, "_sharded_trainer", None) is not None:
            raise ValueError(
                "--stream-window does not combine with --distributed "
                "yet: the windowed path is single-process (multi-host "
                "runs keep the HBM-resident chunk driver)")
        W = self.stream_window
        window_fn = runner.window_scan_fn()
        _, eval_fn = runner.epoch_fns()
        want_labels = not runner._is_mse

        def fetch(tree):
            return jax.tree.map(numpy.asarray, tree)

        stager = _WindowStager(loader, want_labels, self.stage_ahead,
                               name=loader.name + "_stager")
        stats = self.stream_stats = {
            "window_minibatches": W, "stage_ahead": self.stage_ahead,
            "epochs": 0, "windows": 0, "dispatches": 0,
            "train_samples": 0, "staging_stall_s": 0.0,
            "compute_s": 0.0, "samples_per_sec": 0.0,
            "staging_stall_fraction": 0.0,
        }
        wf._stream_stats = stats
        rng_stream = None
        if runner._has_stochastic:
            from veles_tpu import prng
            rng_stream = prng.get("dropout")
        try:
            # fixed validation (and optional test) windows: gathered and
            # uploaded ONCE, device-resident for the whole run — eval
            # sets are the small splits, and their plans never reshuffle
            vidx, vmask = loader.plan_arrays(VALID)
            n_valid = int(vmask.sum())
            vwin = stager.stage(vidx, vmask)
            tidx, tmask = loader.plan_arrays(TEST)
            twin = stager.stage(tidx, tmask) if tidx is not None else None
            n_test = int(tmask.sum()) if tmask is not None else 0

            def eval_row(win):
                x, y, lidx, m = win
                return fetch(eval_fn(runner_state, x, y, lidx, m))

            first_plan_fresh = loader._position == 0
            runner_state = runner.state
            snap = getattr(wf, "snapshotter", None)
            fused = getattr(wf, "fused_step", None)
            while not bool(dec.complete):
                if first_plan_fresh:
                    first_plan_fresh = False
                else:
                    loader._plan_epoch()
                idx, mask = loader.plan_arrays(TRAIN)
                loader._position = len(loader._order)   # plan consumed
                steps = idx.shape[0]
                n_train = int(mask.sum())
                step0 = int(loader.epoch_number) * steps
                epoch_rng = (rng_stream.key()
                             if rng_stream is not None else None)
                starts = list(range(0, steps, W))
                # set order parity with the graph loop and the chunked
                # driver (eval_first): test → validation BEFORE the
                # epoch's training, on the pre-epoch state
                test_row = eval_row(twin) if twin is not None else None
                val_row = eval_row(vwin)
                stats["dispatches"] += 1 + (twin is not None)
                for j in range(min(self.stage_ahead, len(starts))):
                    w0 = starts[j]
                    stager.submit(j, idx[w0:w0 + W], mask[w0:w0 + W])
                train_tot = None
                prev_state = last_win = last_rng = None
                for j, w0 in enumerate(starts):
                    win = stager.take(j)
                    nxt = j + self.stage_ahead
                    if nxt < len(starts):
                        n0 = starts[nxt]
                        stager.submit(nxt, idx[n0:n0 + W],
                                      mask[n0:n0 + W])
                    # per-window key: folding the epoch key by the
                    # window's global step offset keeps dropout draws
                    # distinct across windows (scan-path keys — the
                    # documented epoch-scan divergence)
                    wrng = (jax.random.fold_in(epoch_rng, step0 + w0)
                            if epoch_rng is not None else None)
                    if j == len(starts) - 1:
                        # kept alive for the completion-gate replay
                        prev_state, last_win, last_rng = \
                            runner_state, win, wrng
                    x, y, lidx, m = win
                    begin = time.perf_counter()
                    runner_state, totals = window_fn(
                        runner_state, x, y, lidx, m, wrng, step0 + w0)
                    totals = fetch(totals)   # host blocks; stager works
                    stats["compute_s"] += time.perf_counter() - begin
                    stats["windows"] += 1
                    stats["dispatches"] += 1
                    train_tot = (totals if train_tot is None else
                                 {k: train_tot[k] + v
                                  for k, v in totals.items()})
                loader.epoch_number = int(loader.epoch_number) + 1
                self._feed_decision(train_tot, val_row, test_row,
                                    (n_train, n_valid, n_test))
                if fused is not None:
                    # graph-mode parity for the counter: the discarded
                    # final-minibatch dispatch still counts
                    fused.train_steps += steps
                stats["epochs"] += 1
                stats["train_samples"] += n_train
                if bool(dec.complete):
                    # completion-gate artifact, window-sized: graph mode
                    # discards the stopping epoch's LAST minibatch
                    # commit, so replay the final window from its kept
                    # input state truncated to its first rows-1
                    # minibatches — one extra dispatch, once per run
                    x, y, lidx, m = last_win
                    rows = lidx.shape[0]
                    runner_state, _ = window_fn(
                        prev_state, x, y, lidx[:rows - 1], m[:rows - 1],
                        last_rng, step0 + starts[-1])
                    stats["dispatches"] += 1
                # epoch boundary: commit, then snapshot gates fire
                runner.state = runner_state
                busy = stats["compute_s"] + stager.stall_seconds
                stats["staging_stall_s"] = stager.stall_seconds
                stats["staging_stall_fraction"] = (
                    stager.stall_seconds / busy if busy > 0 else 0.0)
                stats["samples_per_sec"] = (
                    stats["train_samples"] / busy if busy > 0 else 0.0)
                if snap is not None:
                    loader.epoch_ended = True
                    snap.run()
                self._notify_reporters()
            runner.state = runner_state
            runner.sync_to_units()
            if snap is not None:
                snap.stop()
        finally:
            stager.shutdown()
        wf._finished = True

    def _replay_spmd(self, trainer, idx, mask, rng, step0, done_row,
                     steps):
        """SPMD form of :meth:`_replay_to_completion`: trainer.state is
        still the chunk input (chunk_eval_pending never commits), so the
        committing train_epochs/train_epoch calls replay rows 0..done_row
        with the final epoch truncated — same key folding as the chunk."""
        import jax
        if done_row > 0:
            trainer.train_epochs(idx[:done_row], mask[:done_row],
                                 rng=rng, step0=step0)
        off = step0 + done_row * steps
        erng = (jax.random.fold_in(rng, off) if rng is not None else None)
        trainer.train_epoch(idx[done_row][:steps - 1],
                            mask[done_row][:steps - 1],
                            rng=erng, step0=off)
        return trainer.state

    def _replay_to_completion(self, state, data, labels, idx, mask, rng,
                              step0, done_row, steps):
        """Exact final state: full epochs for chunk rows 0..done_row-1,
        then the stopping epoch WITHOUT its last minibatch (whose update
        graph mode discards).  One extra dispatch (plus one for the
        leading rows when done_row > 0), once per training run."""
        import jax
        runner = self.runner
        if done_row > 0:
            head = runner.epoch_chunk_fn(done_row)
            state, _ = head(state, data, labels, idx[:done_row],
                            mask[:done_row], rng=rng, step0=step0)
        off = step0 + done_row * steps
        erng = (jax.random.fold_in(rng, off) if rng is not None else None)
        train_epoch, _ = runner.epoch_fns()
        state, _ = train_epoch(state, data, labels,
                               idx[done_row][:steps - 1],
                               mask[done_row][:steps - 1],
                               rng=erng, step0=off)
        return state
