"""Resilient training runtime (ISSUE 5): shared fault injector with
training seams, step-granular ASYNC checkpoints with bit-exact resume
(plain fit + both ParallelWrapper compression modes, residuals
included), supervised step loop (transient retry, in-graph anomaly
skip, K-consecutive rollback), and step-granular SIGTERM preemption."""
import os
import signal
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.faults import (FaultInjector, PreemptionFault,
                                       TransientFault)
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import (GradientSharingAccumulator,
                                         ParallelWrapper)
from deeplearning4j_tpu.parallel.elastic import (FaultTolerantTrainer,
                                                 PreemptionHandler)
from deeplearning4j_tpu.parallel.resilience import TrainingAnomalyError


def _mlp(seed=0):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=2, loss="mcxent",
                               activation="softmax"))
            .input_type_feed_forward(4).build())
    return MultiLayerNetwork(conf).init()


def _arrays(n=48, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.rand(n, 4).astype(np.float32)
    return X, np.eye(2, dtype=np.float32)[rs.randint(0, 2, n)]


def _it(X, Y, batch=8):
    # shuffle=True on purpose: resume must replay the exact shuffle
    # order of the dead run (iterator state rides in the checkpoint)
    return ArrayDataSetIterator(X, Y, batch=batch, shuffle=True, seed=3)


def _leaves(m):
    return [np.array(a, copy=True)
            for a in jax.tree_util.tree_leaves(m._params)]


def _same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a, b))


class _Traj:
    """Listener recording (step, params) after every iteration."""

    def __init__(self):
        self.steps = []

    def iteration_done(self, m, step, epoch):
        self.steps.append((step, _leaves(m)))


class TestSharedInjector:
    def test_serving_reexport_is_the_same_class(self):
        # one class hierarchy for both runtimes: an `except
        # TransientFault` in serving code catches a training fire
        from deeplearning4j_tpu import faults as shared
        from deeplearning4j_tpu import serving as served
        assert served.FaultInjector is shared.FaultInjector
        assert served.TransientFault is shared.TransientFault
        assert served.CorruptedStateFault is shared.CorruptedStateFault
        assert served.PoisonRequestError is shared.PoisonRequestError

    def test_training_seams_exist_and_unknown_rejected(self):
        FaultInjector(rates={"train_step": 0.5, "data_batch": 0.1,
                             "checkpoint_io": 0.2},
                      plan={"preempt": [3]})
        with pytest.raises(ValueError, match="unknown fault seams"):
            FaultInjector(rates={"train_stepp": 0.5})

    def test_preempt_seam_raises_preemption_fault(self):
        inj = FaultInjector(plan={"preempt": [2]})
        assert inj.fire("preempt") is False
        with pytest.raises(PreemptionFault):
            inj.fire("preempt")

    def test_slow_ms_sleeps_instead_of_raising(self):
        inj = FaultInjector(rates={"checkpoint_io": 1.0},
                            slow_ms={"checkpoint_io": 40.0})
        t0 = time.perf_counter()
        assert inj.fire("checkpoint_io") is True  # slept, no raise
        assert time.perf_counter() - t0 >= 0.035
        assert inj.snapshot()["fired"]["checkpoint_io"] == 1


class TestStepGranularCheckpoints:
    def test_step_cadence_names_listing_and_order(self, tmp_path):
        m = _mlp()
        X, Y = _arrays()
        tr = FaultTolerantTrainer(m, str(tmp_path), save_every_n_steps=2,
                                  keep_last=10)
        tr.fit(_it(X, Y), epochs=1)          # 6 batches -> steps 2,4,6
        names = [os.path.basename(p) for p in
                 FaultTolerantTrainer.list_checkpoints(str(tmp_path))]
        assert names == ["checkpoint_epoch0_step2.zip",
                         "checkpoint_epoch0_step4.zip",
                         "checkpoint_epoch0_step6.zip",
                         "checkpoint_epoch1.zip"], names
        # the epoch-boundary file (1,0) sorts after every mid-epoch-0
        # (0,S) entry — chronological order, so resume() takes it
        resumed = FaultTolerantTrainer.resume(str(tmp_path))
        assert resumed._step == 6 and resumed._epoch == 1

    def test_bit_exact_resume_plain_fit(self, tmp_path):
        X, Y = _arrays()
        # run A: uninterrupted, full trajectory recorded
        mA = _mlp()
        tA = _Traj()
        mA.set_listeners(tA)
        FaultTolerantTrainer(mA, str(tmp_path / "a"),
                             save_every_n_steps=4).fit(_it(X, Y), epochs=3)
        # run B: killed by a scripted preemption at step 8 (mid-epoch:
        # 6 batches/epoch), which flushes a step-granular checkpoint
        mB = _mlp()
        tr = FaultTolerantTrainer(
            mB, str(tmp_path / "b"), save_every_n_steps=4,
            fault_injector=FaultInjector(plan={"preempt": [8]}))
        with pytest.raises(PreemptionFault):
            tr.fit(_it(X, Y), epochs=3)
        # "restarted process": resume + continue with a FRESH iterator
        mC = FaultTolerantTrainer.resume(str(tmp_path / "b"))
        assert mC._step == 8
        assert mC._resume_cursor["epoch"] == 1
        tC = _Traj()
        mC.set_listeners(tC)
        FaultTolerantTrainer(mC, str(tmp_path / "b"),
                             save_every_n_steps=4).fit(_it(X, Y), epochs=3)
        assert mC._step == mA._step == 18
        # the resumed trajectory IS the uninterrupted one, bit for bit
        tail = {s: p for s, p in tA.steps if s > 8}
        for s, p in tC.steps:
            assert s in tail
            assert _same(p, tail[s]), f"trajectory diverged at step {s}"
        assert _same(_leaves(mA), _leaves(mC))

    def test_bit_exact_resume_after_hard_crash(self, tmp_path):
        """Crash WITHOUT a flush (retries exhausted mid-step): resume
        falls back to the last CADENCE checkpoint and still replays the
        uninterrupted trajectory bit-exactly."""
        X, Y = _arrays()
        mA = _mlp()
        tA = _Traj()
        mA.set_listeners(tA)
        FaultTolerantTrainer(mA, str(tmp_path / "a"),
                             save_every_n_steps=3).fit(_it(X, Y), epochs=2)
        mB = _mlp()
        inj = FaultInjector(plan={"train_step": [8, 9]})
        tr = FaultTolerantTrainer(mB, str(tmp_path / "b"),
                                  save_every_n_steps=3,
                                  fault_injector=inj, max_step_retries=1,
                                  retry_backoff_ms=1.0)
        with pytest.raises(TransientFault):
            tr.fit(_it(X, Y), epochs=2)       # dies attempting step 8
        mC = FaultTolerantTrainer.resume(str(tmp_path / "b"))
        assert mC._step == 6                  # last cadence checkpoint
        tC = _Traj()
        mC.set_listeners(tC)
        FaultTolerantTrainer(mC, str(tmp_path / "b"),
                             save_every_n_steps=3).fit(_it(X, Y), epochs=2)
        tail = {s: p for s, p in tA.steps if s > 6}
        for s, p in tC.steps:
            assert _same(p, tail[s]), f"diverged at step {s}"
        assert _same(_leaves(mA), _leaves(mC))

    def test_async_checkpoint_stalls_less_than_sync_write(self, tmp_path):
        """The acceptance bar: with an injected slow checkpoint_io, the
        ASYNC step loop's measured stall is a small fraction of what
        the same cadence costs written synchronously."""
        X, Y = _arrays(n=48)
        slow = FaultInjector(rates={"checkpoint_io": 1.0},
                             slow_ms={"checkpoint_io": 300.0})
        # async: one mid-run checkpoint at step 2 of 6; steps 3..6
        # proceed while the 300ms write runs on the background thread
        mA = _mlp()
        trA = FaultTolerantTrainer(mA, str(tmp_path / "a"),
                                   save_every_n_steps=6, keep_last=2,
                                   fault_injector=slow, async_write=True)
        trA.fit(_it(X, Y), epochs=1)
        # sync reference: same cadence, writes inline in the step loop
        slow2 = FaultInjector(rates={"checkpoint_io": 1.0},
                              slow_ms={"checkpoint_io": 300.0})
        mB = _mlp()
        trB = FaultTolerantTrainer(mB, str(tmp_path / "b"),
                                   save_every_n_steps=6, keep_last=2,
                                   fault_injector=slow2, async_write=False)
        trB.fit(_it(X, Y), epochs=1)
        a = trA.supervisor.checkpoint_stall_s
        b = trB.supervisor.checkpoint_stall_s
        assert b >= 0.3, f"sync stall {b} should include the slow write"
        assert a < b / 2, (a, b)
        assert a < 0.15, f"async step-loop stall {a} should be snapshot-only"
        # and the async checkpoint is REAL: durable + loadable
        assert trA._writer.writes >= 1
        assert FaultTolerantTrainer.resume(str(tmp_path / "a"))._step > 0

    def test_checkpoint_io_transient_is_retried(self, tmp_path):
        X, Y = _arrays()
        m = _mlp()
        inj = FaultInjector(plan={"checkpoint_io": [1]})
        tr = FaultTolerantTrainer(m, str(tmp_path), save_every_n_steps=3,
                                  fault_injector=inj)
        tr.fit(_it(X, Y), epochs=1)
        assert FaultTolerantTrainer.list_checkpoints(str(tmp_path))
        assert tr.supervisor.retries.value() >= 1
        assert inj.snapshot()["fired"]["checkpoint_io"] == 1

    def test_zero_seam_traffic_without_injector(self, tmp_path):
        """No injector -> the supervised loop consults nothing and the
        stats stay zero (the zero-overhead contract's observable)."""
        X, Y = _arrays()
        m = _mlp()
        tr = FaultTolerantTrainer(m, str(tmp_path), save_every_n_steps=4)
        tr.fit(_it(X, Y), epochs=1)
        snap = tr.faults_snapshot()
        assert snap["retries"] == 0 and snap["anomalies_skipped"] == 0
        assert snap["rollbacks"] == 0 and snap["preemptions"] == 0
        assert "injector" not in snap


class TestSupervisedLoop:
    def test_transient_retry_is_bit_exact(self, tmp_path):
        X, Y = _arrays()
        mA = _mlp()
        FaultTolerantTrainer(mA, str(tmp_path / "a"),
                             save_every_n_steps=100).fit(_it(X, Y), epochs=2)
        mB = _mlp()
        # scripted fires (calls 2, 5, 9 of the seam) rather than a
        # rate: deterministic >=1 retry without relying on a seed's
        # draw sequence
        inj = FaultInjector(plan={"train_step": [2, 5, 9]})
        tr = FaultTolerantTrainer(mB, str(tmp_path / "b"),
                                  save_every_n_steps=100,
                                  fault_injector=inj, max_step_retries=8,
                                  retry_backoff_ms=1.0)
        tr.fit(_it(X, Y), epochs=2)
        # the fault fires BEFORE the device call, so the retried step
        # replays bit-exactly: identical final params
        assert tr.supervisor.retries.value() == 3
        assert _same(_leaves(mA), _leaves(mB))

    def test_retries_exhausted_raises(self, tmp_path):
        X, Y = _arrays()
        m = _mlp()
        inj = FaultInjector(plan={"train_step": [1, 2, 3]})
        tr = FaultTolerantTrainer(m, str(tmp_path), fault_injector=inj,
                                  max_step_retries=1, retry_backoff_ms=1.0)
        with pytest.raises(TransientFault):
            tr.fit(_it(X, Y), epochs=1)

    @staticmethod
    def _batches(seed=0, n=5, bad=()):
        rs = np.random.RandomState(seed)
        out = []
        for i in range(n):
            x = rs.rand(8, 4).astype(np.float32)
            y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 8)]
            if i in bad:
                x = x.copy()
                x[0, 0] = np.nan
            out.append((x, y))
        return out

    def test_anomalous_batch_skip_matches_run_without_it(self, tmp_path):
        """Acceptance: skipping the bad batch leaves the trajectory
        identical to a run that never saw it — the step counter is NOT
        advanced (Adam's bias correction stays aligned) and the PRNG
        key consumed for the skipped batch is RESTORED, so models with
        per-batch RNG (dropout) keep drawing the same masks as a run
        without the bad batch."""
        bad_stream = self._batches(n=4, bad=(1,))
        clean_stream = [b for i, b in enumerate(self._batches(n=4))
                        if i != 1]
        mA = _mlp()
        trA = FaultTolerantTrainer(mA, str(tmp_path / "a"),
                                   anomaly_guard=True)
        trA.fit(bad_stream, epochs=1)
        mB = _mlp()
        FaultTolerantTrainer(mB, str(tmp_path / "b"),
                             anomaly_guard=True).fit(clean_stream, epochs=1)
        assert trA.supervisor.anomalies_skipped.value() == 1
        assert mA._step == mB._step == 3
        assert _same(_leaves(mA), _leaves(mB))
        # the key stream too: a skipped batch consumes nothing
        assert np.array_equal(np.asarray(mA._rng), np.asarray(mB._rng))

    def test_rollback_after_k_consecutive_anomalies(self, tmp_path):
        m = _mlp()
        tr = FaultTolerantTrainer(m, str(tmp_path), anomaly_guard=True,
                                  rollback_after=2)
        good_then_bad = self._batches(n=6, bad=(2, 3))
        tr.fit(good_then_bad, epochs=1)
        sup = tr.supervisor
        assert sup.anomalies_skipped.value() == 2
        assert sup.rollbacks.value() == 1
        # rolled back to the snapshot state (params + step coherent),
        # then the remaining good batches kept training
        assert m._step == 4        # 4 good batches advanced the step
        assert all(np.isfinite(a).all() for a in _leaves(m))

    def test_rollback_restores_snapshot_bits_and_rng(self, tmp_path):
        m = _mlp()
        tr = FaultTolerantTrainer(m, str(tmp_path), anomaly_guard=True,
                                  rollback_after=1)
        # 2 good batches; snapshot cadence is every good step here
        tr.fit(self._batches(n=2), epochs=1)
        want_params = _leaves(m)
        want_rng = np.array(m._rng, copy=True)
        want_step = m._step
        # now an all-bad epoch: skip -> immediate rollback each time
        bad = self._batches(seed=9, n=1, bad=(0,))
        tr.fit(bad * 1, epochs=2)  # fit target epochs=2 -> 1 more epoch
        assert tr.supervisor.rollbacks.value() >= 1
        assert _same(_leaves(m), want_params)
        assert np.array_equal(np.array(m._rng), want_rng)
        assert m._step == want_step

    def test_anomaly_error_after_max_rollbacks(self, tmp_path):
        m = _mlp()
        tr = FaultTolerantTrainer(m, str(tmp_path), anomaly_guard=True,
                                  rollback_after=1)
        tr.supervisor.max_rollbacks = 2
        poisoned = self._batches(n=12, bad=tuple(range(12)))
        with pytest.raises(TrainingAnomalyError):
            tr.fit(poisoned, epochs=1)

    def test_guarded_step_zero_recompiles_post_warmup(self, tmp_path):
        X, Y = _arrays()
        m = _mlp()
        tr = FaultTolerantTrainer(m, str(tmp_path), anomaly_guard=True)
        tr.fit(_it(X, Y), epochs=1)
        step = tr._step_fns["guard"]
        assert step._cache_size() == 1
        tr.fit(_it(X, Y), epochs=3)           # more epochs, same program
        assert step._cache_size() == 1


@pytest.mark.parametrize("mode", ["update", "gradient"])
class TestParallelWrapperResilience:
    """Bit-exact resume through BOTH compression modes, residual state
    included in the checkpoint (the satellite's acceptance)."""

    def _fit_wrapped(self, tmp_dir, mode, injector=None, guard=False,
                     epochs=3, model=None):
        m = model if model is not None else _mlp()
        pw = ParallelWrapper(
            m, accumulator=GradientSharingAccumulator(mode=mode))
        tr = FaultTolerantTrainer(m, tmp_dir, save_every_n_steps=3,
                                  wrapper=pw, fault_injector=injector,
                                  anomaly_guard=guard)
        X, Y = _arrays(n=64)
        return m, pw, tr, _it(X, Y, batch=16), epochs

    def test_bit_exact_resume_with_residuals(self, tmp_path, mode):
        X, Y = _arrays(n=64)
        # uninterrupted reference
        mA, pwA, trA, itA, _ = self._fit_wrapped(str(tmp_path / "a"), mode)
        trA.fit(itA, epochs=3)
        # killed at step 7 (4 batches/epoch -> mid-epoch 1)
        mB, pwB, trB, itB, _ = self._fit_wrapped(
            str(tmp_path / "b"), mode,
            injector=FaultInjector(plan={"preempt": [7]}))
        with pytest.raises(PreemptionFault):
            trB.fit(itB, epochs=3)
        died_residuals = np.concatenate(
            [np.asarray(a).ravel() for a in
             jax.tree_util.tree_leaves(pwB.accumulator.residuals)])
        # the checkpoint carries the gradient-sharing state explicitly
        import zipfile
        last = FaultTolerantTrainer.list_checkpoints(str(tmp_path / "b"))[-1]
        with zipfile.ZipFile(last) as z:
            assert "extra.npz" in z.namelist()
        # restart: fresh model, fresh wrapper, fresh accumulator
        mC = FaultTolerantTrainer.resume(str(tmp_path / "b"))
        assert mC._step == 7
        assert mC._resume_extra is not None
        assert any(k.startswith("gradient_sharing/residuals/")
                   for k in mC._resume_extra)
        pwC = ParallelWrapper(
            mC, accumulator=GradientSharingAccumulator(mode=mode))
        # building the step consumes _resume_extra: the rebuilt
        # accumulator starts from the dead run's exact residual bits
        pwC.ensure_step()
        rebuilt = np.concatenate(
            [np.asarray(a).ravel() for a in
             jax.tree_util.tree_leaves(pwC.accumulator.residuals)])
        assert np.array_equal(rebuilt, died_residuals)
        trC = FaultTolerantTrainer(mC, str(tmp_path / "b"),
                                   save_every_n_steps=3, wrapper=pwC)
        trC.fit(_it(X, Y, batch=16), epochs=3)
        assert _same(_leaves(mA), _leaves(mC)), \
            f"{mode}: resumed compressed trajectory diverged"
        assert mA._step == mC._step == 12

    def test_guarded_compressed_skip_spares_residuals(self, tmp_path,
                                                      mode):
        """A NaN batch under the guard leaves params AND the error-
        feedback residual bit-identical to a run that never saw it —
        the 'gradient-sharing residual state' clause of the issue."""
        rs = np.random.RandomState(4)

        def mk(bad):
            out = []
            for i in range(3):
                x = rs.rand(16, 4).astype(np.float32)
                y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 16)]
                if i == 1 and bad:
                    x = x.copy()
                    x[3, 1] = np.nan
                out.append((x, y))
            return out

        rs = np.random.RandomState(4)
        with_bad = mk(bad=True)
        rs = np.random.RandomState(4)
        without = [b for i, b in enumerate(mk(bad=False)) if i != 1]
        mA, pwA, trA, _, _ = self._fit_wrapped(str(tmp_path / "a"), mode,
                                               guard=True)
        trA.fit(with_bad, epochs=1)
        mB, pwB, trB, _, _ = self._fit_wrapped(str(tmp_path / "b"), mode,
                                               guard=True)
        trB.fit(without, epochs=1)
        assert trA.supervisor.anomalies_skipped.value() == 1
        assert mA._step == mB._step == 2
        assert _same(_leaves(mA), _leaves(mB))
        for a, b in zip(jax.tree_util.tree_leaves(pwA.accumulator.residuals),
                        jax.tree_util.tree_leaves(pwB.accumulator.residuals)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_compressed_guarded_zero_recompiles(self, tmp_path, mode):
        mA, pwA, trA, it, _ = self._fit_wrapped(str(tmp_path), mode,
                                                guard=True)
        trA.fit(it, epochs=2)
        jit_step = pwA._sharded_step._jit
        assert jit_step._cache_size() == 1
        trA.fit(_it(*_arrays(n=64), batch=16), epochs=4)
        assert jit_step._cache_size() == 1


class TestStepGranularPreemption:
    def test_sigterm_mid_epoch_flushes_at_step_boundary(self, tmp_path):
        """SIGTERM lands mid-supervised-fit: the handler only sets a
        flag (serving-style treatment); the loop flushes a
        STEP-granular mid-epoch checkpoint at the next boundary, runs
        on_preempt + chaining on its own thread, and fit raises
        PreemptionFault. Resume continues bit-exactly."""
        X, Y = _arrays()
        # uninterrupted reference for the bit-exactness claim
        mA = _mlp()
        FaultTolerantTrainer(mA, str(tmp_path / "a"),
                             save_every_n_steps=100).fit(_it(X, Y),
                                                         epochs=2)

        mB = _mlp()
        tr = FaultTolerantTrainer(mB, str(tmp_path / "b"),
                                  save_every_n_steps=100)
        sent = []

        class KillAtStep3:
            # delivered from a listener: the handler runs on the main
            # thread between bytecodes INSIDE the step loop — the
            # exact frame a blocking in-handler save could deadlock
            def iteration_done(self, m, step, epoch):
                if step == 3 and not sent:
                    sent.append(True)
                    os.kill(os.getpid(), signal.SIGTERM)

        mB.set_listeners(KillAtStep3())
        fired = []
        with PreemptionHandler(tr, signals=(signal.SIGTERM,),
                               on_preempt=fired.append,
                               reraise=False) as h:
            with pytest.raises(PreemptionFault):
                tr.fit(_it(X, Y), epochs=2)
        assert h.preempted and fired == [signal.SIGTERM]
        assert tr.supervisor.preemptions.value() == 1
        names = [os.path.basename(p) for p in
                 FaultTolerantTrainer.list_checkpoints(str(tmp_path / "b"))]
        assert "checkpoint_epoch0_step3.zip" in names   # MID-epoch
        mC = FaultTolerantTrainer.resume(str(tmp_path / "b"))
        assert mC._step == 3
        assert mC._resume_cursor == {"epoch": 0, "batches_into_epoch": 3,
                                     "iterator": {"epoch": 0}}
        FaultTolerantTrainer(mC, str(tmp_path / "b"),
                             save_every_n_steps=100).fit(_it(X, Y),
                                                         epochs=2)
        assert _same(_leaves(mA), _leaves(mC))

    def test_sigterm_outside_loop_keeps_epoch_semantics(self, tmp_path):
        """No supervised loop running -> the original inline-save path
        (blocked main thread = consistent snapshot) still holds."""
        m = _mlp()
        X, Y = _arrays()
        tr = FaultTolerantTrainer(m, str(tmp_path),
                                  save_every_n_epochs=100)
        with PreemptionHandler(tr, signals=(signal.SIGTERM,),
                               reraise=False) as h:
            m.fit([(X[:8], Y[:8])], epochs=2)
            os.kill(os.getpid(), signal.SIGTERM)
            assert h.preempted
        ckpts = FaultTolerantTrainer.list_checkpoints(str(tmp_path))
        assert len(ckpts) == 1
        assert FaultTolerantTrainer.resume(str(tmp_path))._epoch == 2

    def test_preempt_seam_counts_and_stats(self, tmp_path):
        X, Y = _arrays()
        m = _mlp()
        inj = FaultInjector(plan={"preempt": [4]})
        tr = FaultTolerantTrainer(m, str(tmp_path), save_every_n_steps=2,
                                  fault_injector=inj)
        with pytest.raises(PreemptionFault):
            tr.fit(_it(X, Y), epochs=2)
        snap = tr.faults_snapshot()
        assert snap["preemptions"] == 1
        # preempt landed on a cadence step (4): the flush found the
        # async checkpoint already written and rightly wrote (and
        # counted) nothing synchronous — but the step checkpoint IS on
        # disk, which is the flush's actual contract
        names = [os.path.basename(p) for p in
                 FaultTolerantTrainer.list_checkpoints(str(tmp_path))]
        assert "checkpoint_epoch0_step4.zip" in names
        assert snap["async_checkpoints"] >= 1
        assert snap["sync_checkpoints"] == 0
        assert snap["injector"]["fired"]["preempt"] == 1
