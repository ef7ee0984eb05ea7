"""CLSession — the continuous-learning engine (paper Fig. 4 + Algorithm 1).

Methodology mirrors the paper's evaluation split (§VII-A): the *virtual
clock* advances by phase durations computed from the performance estimator on
the FULL model configs (Table III / Table IV hardware), while the *learning
dynamics* (inference, labeling, retraining, accuracy) execute over the
synthetic drift stream — "integrating hardware simulation and GPU kernel
execution" exactly as the paper's system simulator does, with JAX in the GPU
role. By default the executed models are reduced same-family twins
(``VisionConfig.reduced``: 24-px frames, 8 classes) so the loop runs on a
CPU; ``reduced=False`` executes the configs as given (published widths,
224-px frames, 1000-class heads) — the estimator reads the full configs
either way, so the virtual clock is the same.

Layering (see ROADMAP.md "Architecture"):

    CLSystemSpec ──build()──▶ CLSession ──executes──▶ Decision
                               │    ▲          (SpatialPlan × TemporalPlan)
                     kernels ◀─┘    └── PhaseFeedback ◀── AllocationPolicy
             (core/kernel.py)                        (core/allocation.py)

The engine is policy-free: it consumes the two-plane
:class:`~repro.core.decision.Decision` the bound
:class:`~repro.core.allocation.AllocationPolicy` emits (flat legacy
``AllocationDecision``s are lifted via their ``.split()`` facade) — the
spatial plane carries the T-SA/B-SA row split, per-kernel MX precisions and
mesh re-fission intent; the temporal plane carries sample budgets, pacing,
retraining depth and profiling cost — and reports ``PhaseFeedback`` (with
the engine-side ``drifted`` verdict) back. When constructed
with a multi-device ``mesh``, the engine calls
:func:`~repro.core.partition.partition_mesh` to fission the mesh into T-SA /
B-SA sub-meshes and binds each kernel to its sub-accelerator (re-partitioning
online if a decision changes the split); on a single device the partition
degenerates to time-sharing, the paper's own fallback.

Execution goes through the dispatch layer (core/dispatch.py): each phase is
a :class:`~repro.core.dispatch.PhasePlan` the loop builds as it goes — kernel
programs are *dispatched* (issued async, returning device arrays) and host
values are *collected* only at the phase-end barrier where ``PhaseFeedback``
needs them. ``dispatch="sequential"`` (default) preserves the seed's serial
virtual-clock accounting bit-for-bit; ``dispatch="concurrent"`` charges
``max(t_TSA, t_BSA)`` per phase — the paper's Fig. 4 overlap of B-SA serving
with T-SA labeling/retraining — and fuses score windows into batched
inference calls.

Frame access goes through the data plane (data/pipeline.py): ``run`` wraps
the stream in a :class:`~repro.data.pipeline.FramePipeline` (or consumes a
ready pipeline handle) and every window — scoring, labeling — is fetched
through the phase plan, never by indexing the stream directly. In
concurrent mode the pipeline speculates the next phase's windows from the
last phase's layout and prefetches them on a background thread, so host
frame synthesis overlaps device dispatch; reconcile hits/misses are
threaded into each :class:`PhaseRecord` (``spec_hits``/``spec_misses``).

Per-phase structured metrics flow to observers — callables receiving a
:class:`PhaseRecord` — instead of being scraped out of ad-hoc dicts.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.dacapo_pairs import VisionConfig
from repro.core import mx as mx_lib
from repro.core.allocation import (
    AllocationDecision,
    AllocationPolicy,
    CLHyperParams,
    PhaseFeedback,
    make_allocator,
)
from repro.core.decision import SpatialPlan, as_decision
from repro.core.dispatch import KernelDispatcher, PhasePlan
from repro.core.estimator import DaCapoEstimator
from repro.core.kernel import InferenceKernel, LabelingKernel, RetrainKernel
from repro.core.partition import (
    SpatialPartition,
    partition_mesh,
    single_device_partition,
)
from repro.core.sample_buffer import SampleBuffer
from repro.core.trace import TraceRecorder, span
from repro.data.pipeline import FramePipeline
from repro.data.stream import DriftStream
from repro.models.registry import make_vision_model


@dataclasses.dataclass
class CLResult:
    name: str
    accuracy_timeline: List[Tuple[float, float]]  # (t, acc on [t-dt, t))
    phase_log: List[dict]
    avg_accuracy: float
    retrain_time: float
    label_time: float
    drift_events: int
    records: List["PhaseRecord"] = dataclasses.field(default_factory=list)


@dataclasses.dataclass(frozen=True)
class PhaseRecord:
    """Structured per-phase metrics delivered to observers."""

    index: int
    t: float  # virtual clock at phase end
    acc_valid: float
    acc_label: float
    drift: bool  # drift detected at this phase boundary
    retrain_time: float  # cumulative
    label_time: float  # cumulative
    decision: AllocationDecision  # the decision this phase executed
    next_decision: AllocationDecision  # what the policy chose for the next
    phase_start: float = 0.0  # virtual clock at phase start
    t_tsa: float = 0.0  # T-SA kernel time this phase (retrain+valid+label)
    t_bsa: float = 0.0  # B-SA kernel time this phase (serving-side programs)
    spec_hits: int = 0  # frame windows served from speculative prefetch
    spec_misses: int = 0  # frame windows synthesized inline (reconcile miss)
    stream: int = 0  # fleet stream lane this record belongs to

    def as_log_entry(self) -> dict:
        """``phase_log`` dict layout — every PhaseRecord field the legacy
        consumers scrape, including the per-phase timing split."""
        return {"t": self.t, "acc_valid": self.acc_valid,
                "acc_label": self.acc_label, "drift": self.drift,
                "retrain_time": self.retrain_time,
                "label_time": self.label_time,
                "phase_start": self.phase_start,
                "t_tsa": self.t_tsa, "t_bsa": self.t_bsa,
                "spec_hits": self.spec_hits,
                "spec_misses": self.spec_misses,
                "stream": self.stream}


PhaseObserver = Callable[[PhaseRecord], None]


class _ScoreSink:
    """Deferred accuracy timeline: the B-SA serving-side scoring stream.

    ``add`` queues a score window; without fusion each window is dispatched
    immediately as its own async predict (the seed's one-jitted-call-per-
    window pattern, minus the per-call host sync). With ``fuse`` (concurrent
    dispatch), windows accumulate and ``flush`` issues ONE batched predict
    per phase via ``InferenceKernel.predict_batched``. ``timeline`` is the
    only point that materializes predictions to host numpy.
    """

    def __init__(self, kernel: InferenceKernel, fuse: bool):
        self.kernel = kernel
        self.fuse = fuse
        self._pending: List[tuple] = []  # (t_end, x, y, keep_frac)
        self._params = None  # serving params of the pending windows
        self._entries: List[tuple] = []  # (t_end, pred_dev, y, keep_frac)

    def add(self, t_end: float, x, y, keep_frac: float, params) -> None:
        if not self.fuse:
            with span("issue.score"):
                pred = self.kernel.predict_async(params, x)
            self._entries.append((t_end, pred, y, keep_frac))
            return
        if self._pending and self._params is not params:
            self.flush()  # serving params changed mid-queue
        self._params = params
        self._pending.append((t_end, x, y, keep_frac))

    def flush(self) -> None:
        """Dispatch queued windows (one fused jitted call) — still async."""
        if not self._pending:
            return
        with span("issue.score"):
            preds = self.kernel.predict_batched(
                self._params, [x for _, x, _, _ in self._pending])
        for (t_end, _x, y, kf), pred in zip(self._pending, preds):
            self._entries.append((t_end, pred, y, kf))
        self._pending.clear()

    def timeline(self) -> List[Tuple[float, float]]:
        """Collect: materialize every queued prediction into (t, acc)."""
        self.flush()
        with span("collect"):
            return [(t_end, float((np.asarray(pred) == y).mean()) * kf)
                    for t_end, pred, y, kf in self._entries]


def flush_sinks_batched(kernel: InferenceKernel,
                        sinks: Sequence[_ScoreSink]) -> None:
    """Flush several lanes' score sinks through ONE vmapped fleet program
    (:meth:`InferenceKernel.predict_fleet_async`) instead of one fused
    predict per lane — the fleet's B-SA serves every lane's queued score
    windows in a single program per phase. Each live sink's windows are
    concatenated into that lane's batch; predictions split back per window
    device-side. Empty sinks are skipped and a single pending lane takes
    its sink's own fused flush path (exactly ``_ScoreSink.flush``)."""
    live = [s for s in sinks if s._pending]
    if len(live) <= 1:
        for sink in live:
            sink.flush()
        return
    with span("issue.score"):
        lane_windows = [np.concatenate([x for _, x, _, _ in s._pending],
                                       axis=0)
                        for s in live]
        preds = kernel.predict_fleet_async([s._params for s in live],
                                           lane_windows)
    for sink, pred in zip(live, preds):
        off = 0
        for t_end, x, y, kf in sink._pending:
            sink._entries.append((t_end, pred[off: off + len(x)], y, kf))
            off += len(x)
        sink._pending.clear()


class CLSession:
    """Executes allocation decisions phase-by-phase against the kernels."""

    def __init__(
        self,
        student_cfg: VisionConfig,
        teacher_cfg: VisionConfig,
        hp: Optional[CLHyperParams] = None,
        estimator=None,
        allocator: Union[str, AllocationPolicy] = "dacapo-spatiotemporal",
        precision_policy: mx_lib.PrecisionPolicy = mx_lib.DEFAULT_POLICY,
        apply_mx_numerics: bool = True,
        seed: int = 0,
        eval_fps: float = 2.0,
        mesh=None,
        observers: Sequence[PhaseObserver] = (),
        dispatch: str = "sequential",
        label_microbatch: Optional[int] = None,
        speculative_frames: Optional[bool] = None,
        decision_aware_spec: bool = True,
        trace: Union[None, bool, TraceRecorder] = None,
        reduced: bool = True,
    ):
        self.hp = hp or CLHyperParams()
        self.estimator = estimator or DaCapoEstimator()
        self.policy = precision_policy
        self.apply_mx = apply_mx_numerics
        self.eval_fps = eval_fps  # accuracy-scoring subsample rate
        self.allocator = make_allocator(allocator, self.hp, precision_policy)
        # Trace spine (core/trace.py): ``trace=None`` keeps recording off
        # (bit-identical, zero overhead) — unless the bound policy declares
        # ``needs_trace`` (dacapo-replay), in which case a recorder is
        # auto-created. ``trace=True`` makes a fresh recorder; a ready
        # TraceRecorder instance is shared as-is (fleet/manager tiers).
        if trace is None and getattr(self.allocator, "needs_trace", False):
            trace = True
        if trace is True:
            trace = TraceRecorder()
        elif trace is False:
            trace = None
        # NB: ``trace`` is None or a recorder here; len()-based truthiness
        # would drop a fresh (empty) recorder, so test against None only.
        self.dispatcher = KernelDispatcher(
            dispatch, recorder=trace if trace is not None else None)
        if trace is not None:
            self.allocator.attach_trace(trace)
        # Speculative frame prefetch (data/pipeline.py): defaults to the
        # dispatch mode's appetite — concurrent dispatch overlaps host frame
        # synthesis with device programs; sequential keeps the transparent
        # inline path the goldens pin.
        if speculative_frames is None:
            speculative_frames = self.dispatcher.concurrent
        self.speculative_frames = speculative_frames
        # Decision-aware speculation: at each phase barrier the next
        # decision's labeling budget is handed to the pipeline so the
        # speculated labeling burst is pre-sized (drift phases stop missing
        # on the replayed small layout). Only meaningful when speculating.
        self.decision_aware_spec = decision_aware_spec
        # Microbatched labeling: seed call pattern (one jitted call) by
        # default; concurrent mode chunks big label bursts unless overridden
        # (0 explicitly disables microbatching in either mode).
        if label_microbatch is None:
            self._label_microbatch = (64 if self.dispatcher.concurrent
                                      else None)
        else:
            self._label_microbatch = label_microbatch or None
        self.full_student, self.full_teacher = student_cfg, teacher_cfg
        # What executes: the reduced twins (CPU-sized, the default) or the
        # configs as given. The estimator prices the full configs either way.
        if reduced:
            student_cfg, teacher_cfg = (student_cfg.reduced(),
                                        teacher_cfg.reduced())
        self.student_cfg, self.teacher_cfg = student_cfg, teacher_cfg
        self.student = make_vision_model(self.student_cfg)
        self.teacher = make_vision_model(self.teacher_cfg)
        self.seed = seed
        self.key = jax.random.PRNGKey(seed)
        self.rng = np.random.default_rng(seed)
        self._observers: List[PhaseObserver] = list(observers)

        # (allocator constructed above, before the dispatcher, so the trace
        # recorder could be attached when the policy needs one)
        # The session's precision policy is authoritative — also for ready
        # policy instances handed in via the spec — so decisions, kernel
        # costs and the spatial split all agree on one PrecisionPolicy.
        self.allocator.precision = precision_policy
        self.allocator.bind(self.estimator, self.full_student)

        # Offline spatial allocation (Alg. 1 lines 1-2) — single source of
        # truth: the split the bound policy computed.
        self.r_tsa, self.r_bsa = self.allocator.rows

        # The three kernels (Fig. 4), each owning its jitted apply and cost.
        self.inference = InferenceKernel(
            self.student, self.full_student, self.estimator, self.apply_mx)
        self.labeling = LabelingKernel(
            self.teacher, self.full_teacher, self.estimator, self.apply_mx)
        self.retrain = RetrainKernel(
            self.student, self.full_student, self.estimator, self.hp)
        self.kernels = (self.inference, self.labeling, self.retrain)
        # Retraining supersedes the student tree: drop its RESIDENT
        # quantized serving copy from the inference kernel's version-keyed
        # cache (the teacher's cache needs no wiring — its tree never
        # changes, so its resident copy is filled once and lives forever).
        self.retrain.invalidates = (self.inference.serving_cache,)

        # Spatial partition: fission the mesh if one is given.
        self.mesh = mesh
        self._mesh_rows_bsa: Optional[int] = None
        self.partition: SpatialPartition = single_device_partition()
        self._repartition(self.r_bsa)

    # --------------------------------------------------------------- mesh
    def _mesh_split(self, rows_bsa: int) -> int:
        """Map the estimator's row split onto the mesh's leading axis.
        A single-row mesh cannot be fissioned — return 0 so `_repartition`
        degenerates to time-sharing (the paper's R=0 fallback) instead of
        asking `partition_mesh` to split an unsplittable mesh."""
        n_rows = self.mesh.devices.shape[0]
        if n_rows < 2:
            return 0
        frac = rows_bsa / max(1, self.estimator.total_rows)
        return max(1, min(n_rows - 1, round(n_rows * frac)))

    def _repartition(self, rows_bsa: int) -> None:
        """(Re)fission the mesh for a row split; bind kernels to sub-meshes.
        Single-device sessions keep the degenerate time-shared partition;
        an unchanged split leaves the current partition untouched."""
        if self.mesh is None:
            for k in self.kernels:
                k.bind_partition(self.partition)
            return
        want = self._mesh_split(rows_bsa)
        if want == self._mesh_rows_bsa:
            return
        self._mesh_rows_bsa = want
        self.partition = (single_device_partition() if want == 0
                          else partition_mesh(self.mesh, want))
        for k in self.kernels:
            k.bind_partition(self.partition)

    # ---------------------------------------------------------- observers
    def add_observer(self, observer: PhaseObserver) -> None:
        self._observers.append(observer)

    # --------------------------------------------------------- pretraining
    def pretrain(self, stream: DriftStream, teacher_steps: int = 300,
                 student_steps: int = 80, batch: int = 64):
        """Teacher: pretrained across the whole attribute space (general).
        Student: narrow slice only (first segment's context) -> must adapt."""
        t_params = pretrain_model(self.teacher, stream, teacher_steps, batch,
                                  rng=self.rng)
        s_params = pretrain_model(self.student, stream, student_steps, batch,
                                  rng=self.rng, segments=stream.segments[:1],
                                  seed=8)
        self.set_pretrained(t_params, s_params)

    def set_pretrained(self, teacher_params, student_params):
        """Install (shared) pretrained weights; benches pretrain once per
        (pair, scenario) and clone into every allocator variant."""
        self.teacher_params = teacher_params
        self.student_params = jax.tree_util.tree_map(
            lambda x: x.copy(), student_params)
        self._opt = self.retrain.init_state(self.student_params)

    # ------------------------------------------------------------ main loop
    def _resolve_spatial(self, decision) -> SpatialPlan:
        """The decision's spatial plane with concrete rows: ``None`` rows
        fall back to the offline split, a 0-row side time-shares the whole
        array (the paper's R=0 fallback)."""
        return as_decision(decision).spatial.resolve(
            self.r_tsa, self.r_bsa, self.estimator.total_rows)

    def _effective_rows(self, decision) -> Tuple[int, int]:
        """Legacy view of :meth:`_resolve_spatial`: the concrete row pair."""
        spatial = self._resolve_spatial(decision)
        return spatial.rows_tsa, spatial.rows_bsa

    def run(self, stream: Union[DriftStream, FramePipeline],
            duration: Optional[float] = None,
            observers: Sequence[PhaseObserver] = ()) -> CLResult:
        """Execute the continuous-learning loop over ``stream`` — a raw
        :class:`DriftStream` (the session wraps it in its own
        :class:`FramePipeline` data plane) or a ready pipeline handle."""
        if isinstance(stream, FramePipeline):
            pipe, own_pipe = stream, False
        else:
            pipe = FramePipeline(stream, speculative=self.speculative_frames)
            own_pipe = True
        try:
            return self._run(pipe, duration, observers)
        finally:
            if own_pipe:
                pipe.close()

    def _run(self, pipe: FramePipeline, duration: Optional[float],
             observers: Sequence[PhaseObserver]) -> CLResult:
        hp = self.hp
        duration = duration or pipe.duration
        buffer = SampleBuffer(hp.c_b, seed=3)
        observers = self._observers + list(observers)
        # The policy's raw output (legacy facade or two-plane Decision) is
        # what records carry; the engine consumes the two-plane view.
        raw = self.allocator.initial_decision()
        dec = as_decision(raw)

        spatial = self._resolve_spatial(dec)
        keep_frac = self.inference.plan_keep_frac(spatial, hp.fps)
        serving = self.inference.serving_params(
            self.student_params, spatial.precisions.inference)
        clock = 0.0
        eval_cursor = 0.0
        sink = _ScoreSink(self.inference,
                          fuse=self.dispatcher.concurrent)
        records: List[PhaseRecord] = []
        retrain_time = label_time = 0.0
        drift_events = 0

        def score_until(t_end: float, serving_params,
                        plan: Optional[PhasePlan]):
            """Queue student-accuracy scoring on [eval_cursor, t_end): the
            B-SA serving-side program of the phase. Predictions are
            dispatched async (fused per phase in concurrent mode) and
            materialized only when the timeline is assembled."""
            nonlocal eval_cursor
            if t_end <= eval_cursor + 1e-9:
                return
            n_eval = max(1, int((t_end - eval_cursor) * self.eval_fps))
            x, y = (plan.fetch(eval_cursor, t_end, max_frames=n_eval)
                    if plan is not None
                    else pipe.frames(eval_cursor, t_end, max_frames=n_eval))
            if plan is not None:
                plan.charge("b_sa", len(x)
                            * self.inference.plan_time_per_sample(spatial),
                            label="score", units=len(x))
            sink.add(t_end, x, y, keep_frac, serving_params)
            eval_cursor = t_end

        while clock < duration:
            with span("phase"):
                phase_start = clock
                # ---- Plan: open the phase ledger on the dispatcher; the
                # plan consumes the Decision — rotating the pipeline's
                # speculation onto this phase start, pre-sized with the
                # temporal plane's labeling budget (the decision-aware
                # predictor — the budget is known at the barrier, so
                # drift-phase N_ldd bursts prefetch whole). ----
                with span("plan"):
                    spatial = self._resolve_spatial(dec)
                    temporal = dec.temporal
                    prec = spatial.precisions
                    if spatial.refission:  # the plane's re-fission intent
                        self._repartition(spatial.rows_bsa)
                    keep_frac = self.inference.plan_keep_frac(spatial, hp.fps)
                    plan = self.dispatcher.begin_phase(
                        clock, pipe, decisions=(dec,),
                        fps=hp.fps if self.decision_aware_spec else None)
                    spec_seen = (pipe.hits, pipe.misses)
                    valid_h = xv = yv = None
                    # Profiling overhead (e.g. Ekya's per-window
                    # microprofiling) rides on the temporal plane and is
                    # charged to the T-SA ledger before the window's own
                    # work — zero for idealized policies.
                    if temporal.profile_cost_s:
                        plan.charge("t_sa", temporal.profile_cost_s,
                                    label="profile")
                # --------------- Retraining (Alg. 1 lines 4-7) ---------------
                acc_v = 1.0
                with span("retrain"):
                    if (len(buffer) >= hp.sgd_batch
                            and temporal.retrain_samples > 0):
                        xt, yt, xv, yv = buffer.get_data(
                            temporal.retrain_samples, temporal.valid_samples)
                        fit_t0 = time.perf_counter() if plan.traced else 0.0
                        self.student_params, self._opt, n_batches = \
                            self.retrain.fit(self.student_params, self._opt,
                                             xt, yt, self.rng,
                                             epochs=temporal.retrain_epochs)
                        t_phase = n_batches * self.retrain.plan_time_per_batch(
                            spatial)
                        plan.charge(
                            "t_sa", t_phase, label="retrain", units=n_batches,
                            wall_s=(time.perf_counter() - fit_t0
                                    if plan.traced else 0.0))
                        retrain_time += t_phase
                        # UpdateWeight + Valid (lines 6-7) — dispatched
                        # async; the accuracy is collected at the phase-end
                        # feedback barrier. Sequential keeps the seed's
                        # time-shared serial accounting (validation charged
                        # on the T-SA chain); concurrent places it where
                        # the inference kernel actually lives — the B-SA —
                        # so it overlaps the T-SA moving on to labeling.
                        serving = self.inference.serving_params(
                            self.student_params, prec.inference)
                        v_role = ("b_sa" if self.dispatcher.concurrent
                                  else "t_sa")
                        valid_h = plan.dispatch(
                            v_role, "valid",
                            lambda s=serving, v=xv:
                            self.inference.predict_async(s, v),
                            cost_s=len(xv)
                            * self.inference.plan_time_per_sample(
                                spatial, role=v_role),
                            units=len(xv))
                with span("score"):
                    score_until(min(plan.now(), duration), serving, plan)
                if plan.now() >= duration:
                    clock = plan.finish()
                    break

                # --------------- Labeling (lines 8-10) -----------------------
                with span("label"):
                    n_label = temporal.total_label_samples
                    if temporal.reset_buffer:
                        buffer.reset()  # line 12
                        drift_events += 1
                    t_lab0 = plan.now()
                    x_l, _y_true = plan.fetch(
                        t_lab0, t_lab0 + n_label / hp.fps,
                        max_frames=n_label, tag="label")
                    label_h = plan.dispatch(
                        "t_sa", "label",
                        lambda: self.labeling.label_async(
                            self.teacher_params, x_l, prec.labeling,
                            microbatch=self._label_microbatch),
                        cost_s=n_label
                        * self.labeling.plan_time_per_sample(spatial),
                        units=n_label)
                    label_time += plan.now() - t_lab0
                    pred_l_h = plan.dispatch(
                        "b_sa", "acc_label",
                        lambda: self.inference.predict_async(serving, x_l),
                        cost_s=len(x_l)
                        * self.inference.plan_time_per_sample(spatial),
                        units=len(x_l))
                with span("score"):
                    score_until(min(plan.now(), duration), serving, plan)
                    # Fixed-window pacing, declared by the temporal plane
                    # (no baseline-specific branch: any policy may pace on
                    # a grid).
                    if temporal.pace_window_s:
                        w = temporal.pace_window_s
                        next_boundary = (int(phase_start / w) + 1) * w
                        if plan.now() < next_boundary:
                            score_until(min(next_boundary, duration),
                                        serving, plan)
                            plan.pad_to(next_boundary)

                # ---- Collect: the phase-end barrier — the only host sync.
                with span("barrier"):
                    clock = plan.finish()
                    # Concurrent mode: when the B-SA dominates, the phase
                    # end runs past the T-SA clock the score windows
                    # tracked — score that tail now, under THIS phase's
                    # serving params (uncharged: the phase end already
                    # reflects the B-SA busy period). Sequential mode is a
                    # no-op (clock == the last scored boundary).
                    score_until(min(clock, duration), serving, None)
                    if valid_h is not None:
                        acc_v = float((valid_h.collect() == yv).mean())
                    y_l = label_h.collect()
                    acc_l = float((pred_l_h.collect() == y_l).mean())
                    buffer.update(x_l, y_l)  # line 14
                    sink.flush()  # fused scoring before serving params change

                # --------------- Next decision (lines 11-13) -----------------
                # The engine-side drift verdict: computed once here, handed
                # to the policy on the feedback (the deduped source of
                # truth).
                with span("decide"):
                    drifted = self.allocator.observe_drift(acc_l, acc_v,
                                                           clock)
                    feedback = PhaseFeedback(
                        acc_valid=acc_v, acc_label=acc_l, t=clock,
                        phase_start=phase_start, retrain_time=retrain_time,
                        label_time=label_time, drifted=drifted)
                    next_raw = self.allocator.next_decision(feedback)
                    next_dec = as_decision(next_raw)
                    record = PhaseRecord(
                        index=len(records), t=clock, acc_valid=acc_v,
                        acc_label=acc_l, drift=next_dec.temporal.reset_buffer,
                        retrain_time=retrain_time, label_time=label_time,
                        decision=raw, next_decision=next_raw,
                        phase_start=phase_start, t_tsa=plan.t_tsa,
                        t_bsa=plan.t_bsa,
                        spec_hits=pipe.hits - spec_seen[0],
                        spec_misses=pipe.misses - spec_seen[1])
                    records.append(record)
                    for obs in observers:
                        obs(record)
                raw, dec = next_raw, next_dec

        score_until(duration, serving, None)
        acc_timeline = sink.timeline()
        accs = [a for _, a in acc_timeline]
        return CLResult(
            name=self.allocator.name,
            accuracy_timeline=acc_timeline,
            phase_log=[r.as_log_entry() for r in records],
            avg_accuracy=float(np.mean(accs)) if accs else 0.0,
            retrain_time=retrain_time,
            label_time=label_time,
            drift_events=drift_events,
            records=records,
        )


@dataclasses.dataclass
class CLSystemSpec:
    """Declarative front door: describe a CL system, then ``build()`` it.

    ``estimator`` accepts an instance or a zero-arg factory (class/lambda);
    ``allocator`` accepts a registry name, an ``AllocationPolicy`` class, or
    a ready instance. ``student``/``teacher`` are the FULL paper configs
    (Table III); with ``reduced=True`` (default) the session executes their
    reduced twins, with ``reduced=False`` the configs as given — then feed
    it a stream at the configs' ``img_size`` (``DriftStream(..., img=224)``).

        spec = CLSystemSpec(student=RESNET18, teacher=WIDERESNET50,
                            allocator="ekya", apply_mx=False)
        session = spec.build()
    """

    student: Optional[VisionConfig] = None
    teacher: Optional[VisionConfig] = None
    allocator: Union[str, AllocationPolicy] = "dacapo-spatiotemporal"
    estimator: object = None  # instance or zero-arg factory
    policy: mx_lib.PrecisionPolicy = mx_lib.DEFAULT_POLICY
    hp: Optional[CLHyperParams] = None
    apply_mx: bool = True
    seed: int = 0
    eval_fps: float = 2.0
    mesh: object = None
    dispatch: str = "sequential"  # see core/dispatch.py for the semantics
    label_microbatch: Optional[int] = None
    # Speculative frame prefetch (data/pipeline.py); None = follow dispatch
    # mode (on for concurrent, off for sequential).
    speculative_frames: Optional[bool] = None
    # Pre-size speculated labeling bursts with the next decision's budget.
    decision_aware_spec: bool = True
    # Trace spine: None = off (bit-identical), True = fresh TraceRecorder,
    # or a ready TraceRecorder instance to share. See core/trace.py.
    trace: Union[None, bool, TraceRecorder] = None
    # Execute the reduced twins (True) or the configs as given (False).
    reduced: bool = True

    def _session_kwargs(self) -> dict:
        """The resolved CLSession constructor kwargs this spec describes —
        shared with subclasses (FleetSpec) so new knobs are mirrored once."""
        if self.student is None or self.teacher is None:
            raise ValueError(
                f"{type(self).__name__} needs student and teacher configs")
        est = self.estimator
        if est is not None and (isinstance(est, type)
                                or not hasattr(est, "total_rows")):
            est = est()  # class or zero-arg factory -> instance
        return dict(
            student_cfg=self.student,
            teacher_cfg=self.teacher,
            hp=self.hp,
            estimator=est,
            allocator=self.allocator,
            precision_policy=self.policy,
            apply_mx_numerics=self.apply_mx,
            seed=self.seed,
            eval_fps=self.eval_fps,
            mesh=self.mesh,
            dispatch=self.dispatch,
            label_microbatch=self.label_microbatch,
            speculative_frames=self.speculative_frames,
            decision_aware_spec=self.decision_aware_spec,
            trace=self.trace,
            reduced=self.reduced,
        )

    def build(self) -> CLSession:
        return CLSession(**self._session_kwargs())


# ------------------------------------------------------------------ helpers
def _sgd_state(params):
    return jax.tree_util.tree_map(jnp.zeros_like, params)


def pretrain_model(model, stream: DriftStream, steps: int, batch: int,
                   rng: np.random.Generator, segments=None, seed: int = 7,
                   lr: float = 3e-3):
    """Jitted SGD-momentum pretraining over IID stream samples."""
    params = model.init(jax.random.PRNGKey(seed))
    opt = _sgd_state(params)

    @jax.jit
    def update(params, opt, x, y):
        def loss_fn(p):
            logp = jax.nn.log_softmax(model.apply(p, x))
            return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

        grads = jax.grad(loss_fn)(params)
        opt = jax.tree_util.tree_map(lambda m, g: 0.9 * m + g, opt, grads)
        params = jax.tree_util.tree_map(lambda p, m: p - lr * m, params, opt)
        return params, opt

    for _ in range(steps):
        x, y = stream.sample_dataset(batch, rng, segments=segments)
        params, opt = update(params, opt, x, y)
    return params
