"""Async kernel dispatch: the plan → dispatch → collect execution layer.

The paper's core system claim (Fig. 4) is that inference on the B-SA runs
*concurrently* with labeling/retraining on the T-SA once the array is
spatially partitioned. This module is the execution layer that realizes that
overlap for the engine (core/session.py): instead of calling kernels inline
and forcing a host sync (``np.asarray``) after every call, the session builds
a per-phase :class:`PhasePlan`, *dispatches* device programs through it — JAX
async dispatch returns device arrays immediately, so programs enqueued on the
disjoint T-SA / B-SA sub-meshes overlap on device — and *collects* host
values only at the phase-end barrier where :class:`~repro.core.allocation.\
PhaseFeedback` genuinely needs them.

Virtual-clock semantics (``dispatch=`` on ``CLSystemSpec`` / ``CLSession``):

``"sequential"`` (default)
    The seed accounting, preserved bit-for-bit: everything time-shares one
    serial chain, so the phase clock advances by the **sum** of the charged
    program costs in issue order — retraining batches, validation inference
    (charged at the T-SA rows, as the seed did), labeling. The B-SA-side
    measurement programs (accuracy scoring of the serving stream,
    labeled-frame predictions) are tracked in the phase ledger but never
    gate the serial chain — exactly the seed numbers the golden test in
    ``tests/test_session.py`` pins.

``"concurrent"``
    The paper's spatial-concurrency model: T-SA and B-SA programs execute in
    parallel on their disjoint sub-accelerators, so the phase advances by
    ``max(t_TSA, t_BSA)`` — the **max** of the per-role cost totals — instead
    of the sum. Programs follow their kernel's placement: the T-SA chain is
    retraining + teacher labeling; the inference kernel's programs
    (post-update validation, labeled-frame serving predictions, accuracy
    scoring) are B-SA work charged at the B-SA's own throughput
    (``rows_bsa`` rows, the decision's inference precision). Fixed-window
    pacing (``pace_window_s``) still floors the phase end on the window grid.

Host-side, both modes issue every program eagerly (``dispatch`` calls the
program's thunk immediately); the difference is purely in clock accounting.
Because JAX dispatch is asynchronous, eager issue + deferred ``collect()`` is
what lets XLA overlap the B-SA scoring stream with T-SA work — the session
never blocks between programs of one phase.

Fleet sessions (core/fleet.py) bind N pipelines to one plan — one data-plane
lane per camera stream — and attribute every charge to a lane ledger next to
the fleet ledger, so the shared T-SA is charged once for the fleet while
per-stream shares stay auditable (``lane_time``). ``dispatch_multi`` issues
one device program on behalf of several lanes (cross-stream batched labeling)
and fans its per-lane results out into individual handles.

Trace spine (core/trace.py): the plan's program/charge stream IS the
execution trace. With a :class:`~repro.core.trace.TraceRecorder` attached
to the dispatcher (``CLSystemSpec(trace=...)``), every ``dispatch`` /
``dispatch_multi`` issue is recorded as a ``"program"``
:class:`~repro.core.trace.TraceEvent` — role, label, lane, virtual cost,
measured host wall time of the issue, the kernel path that served it, and
the unit count (samples/batches) the cost scales with — and every bare
``charge`` as a ``"charge"`` event, all in issue order. Recording is
observational only (no numeric plan state is touched), so traced runs are
bit-identical to untraced ones; with no recorder (the default) the traced
overrides reduce to a single ``is None`` check and the original code path.
Independently of the recorder, each program's issue runs in a profiler span
``dacapo.issue.<label>`` and each materializing ``collect()`` in
``dacapo.collect`` (:func:`~repro.core.trace.span`).
The per-phase event order, the phase start/end/floor and the per-role
float-add sequence are exactly what
:class:`~repro.core.replay.TraceReplayer` replays to reconstruct — and
predict — phase times.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from repro.core.trace import TraceEvent, TraceRecorder, span

import numpy as np

SEQUENTIAL = "sequential"
CONCURRENT = "concurrent"
DISPATCH_MODES = (SEQUENTIAL, CONCURRENT)

ROLES = ("t_sa", "b_sa")


def _as_pipelines(pipeline) -> Tuple:
    """Normalize ``begin_phase``'s pipeline argument: None, a single
    FramePipeline, or a sequence of them (one lane per fleet stream)."""
    if pipeline is None:
        return ()
    if isinstance(pipeline, (list, tuple)):
        return tuple(pipeline)
    return (pipeline,)


class ProgramHandle:
    """Deferred result of an issued device program.

    Holds the device value returned by the program's thunk; ``collect()`` is
    the only point that blocks (materializes to host numpy). Collect is
    idempotent — repeated calls return the cached host value.
    """

    __slots__ = ("_value", "_host", "_collected")

    def __init__(self, value: Any):
        self._value = value
        self._host: Any = None
        self._collected = False

    @property
    def issued(self) -> Any:
        """The raw (device-side) value, without forcing a sync."""
        return self._value

    def collect(self) -> np.ndarray:
        if not self._collected:
            with span("collect"):
                self._host = np.asarray(self._value)
            self._value = None  # drop the device reference
            self._collected = True
        return self._host


@dataclasses.dataclass(frozen=True)
class DeviceProgram:
    """One dispatched unit of device work, with its virtual-clock cost."""

    role: str  # "t_sa" | "b_sa"
    label: str  # e.g. "valid", "label", "score", "acc_label"
    cost_s: float
    handle: Optional[ProgramHandle]
    lane: Optional[int] = None  # fleet stream lane this program serves


class PhasePlan:
    """Clock + program ledger for one phase, built as the session executes.

    The running T-SA clock (``now()``) reproduces the seed's float-add
    sequence exactly: each T-SA charge is a single ``+=`` on the same
    accumulator the seed used, so sequential-mode boundaries (score windows,
    pacing, loop exits) see bit-identical times.
    """

    def __init__(self, mode: str, start: float, pipeline=None):
        self.mode = mode
        self.start = start
        # Bound data plane(s): one FramePipeline per stream lane. A single
        # pipeline (the CLSession case) is lane 0 of a one-lane plan.
        self.pipelines: Tuple = _as_pipelines(pipeline)
        # The two-plane Decision(s) this phase executes (one per lane),
        # when the session hands them to begin_phase — the plan's view of
        # the phase's intent (label hints derive from the temporal plane).
        self.decisions: Tuple = ()
        self.programs: List[DeviceProgram] = []
        self.totals: Dict[str, float] = {role: 0.0 for role in ROLES}
        # Per-lane ledgers: plain sums from 0.0 (the same addends that feed
        # ``totals``), so a one-lane plan's lane ledger is bit-identical to
        # the fleet ledger — the fleet golden test relies on that.
        self.lane_totals: Dict[int, Dict[str, float]] = {}
        self._now = start  # T-SA running clock (seed accumulator)
        self._floor = start  # pacing floor on the phase end

    @property
    def pipeline(self):
        """Lane-0 pipeline (back-compat single-stream handle)."""
        return self.pipelines[0] if self.pipelines else None

    @property
    def traced(self) -> bool:
        """Is a TraceRecorder observing this plan? (Engines use this to
        gate wall-time measurement of host-side work like retraining SGD,
        keeping the untraced path free of even a ``perf_counter`` call.)"""
        return False

    # ----------------------------------------------------------- dispatch
    def dispatch(self, role: str, label: str, issue: Callable[[], Any],
                 cost_s: float = 0.0,
                 lane: Optional[int] = None,
                 units: float = 0.0) -> ProgramHandle:
        """Issue a device program *now* (async — the thunk must not block)
        and charge its cost; returns a handle to ``collect()`` later.
        ``units`` is the trace-facing quantity the cost was computed from
        (frames scored, samples labeled) — ignored untraced."""
        del units
        with span("issue." + label):
            handle = ProgramHandle(issue())
        self.programs.append(DeviceProgram(role, label, cost_s, handle, lane))
        self.charge(role, cost_s, lane=lane)
        return handle

    def dispatch_multi(self, role: str, label: str,
                       issue: Callable[[], Sequence[Any]],
                       costs: Sequence[float],
                       lanes: Sequence[int],
                       units: Optional[Sequence[float]] = None
                       ) -> List[ProgramHandle]:
        """Issue ONE device program serving several stream lanes (e.g. a
        labeling burst batched across the fleet on the shared T-SA) and
        split its per-lane results into individual handles. The thunk must
        return one device value per lane; each lane's cost is charged to
        both the fleet ledger and that lane's ledger, in lane order — for a
        one-lane plan this is exactly a single ``dispatch``."""
        del units
        with span("issue." + label):
            values = issue()
        if len(values) != len(lanes) or len(costs) != len(lanes):
            raise ValueError(
                f"dispatch_multi: {len(values)} values / {len(costs)} costs "
                f"for {len(lanes)} lanes")
        handles = []
        for value, cost_s, lane in zip(values, costs, lanes):
            handle = ProgramHandle(value)
            self.programs.append(
                DeviceProgram(role, label, cost_s, handle, lane))
            self.charge(role, cost_s, lane=lane)
            handles.append(handle)
        return handles

    def fetch(self, t0: float, t1: float, max_frames: int = 0,
              lane: int = 0, tag: Optional[str] = None):
        """Pipeline-aware plan step: pull a frame window for this phase's
        programs through the bound :class:`~repro.data.pipeline.\
FramePipeline` of ``lane``, so dispatch issues device programs against
        prefetched, host-ready windows (speculation hits) instead of
        stalling on inline frame synthesis. Reconciliation keeps results
        bit-identical either way. ``tag`` marks the window's role in the
        phase layout (e.g. ``"label"``) for decision-aware speculation."""
        if not self.pipelines:
            raise ValueError(
                "no FramePipeline bound to this plan; pass one to "
                "KernelDispatcher.begin_phase")
        return self.pipelines[lane].frames(t0, t1, max_frames=max_frames,
                                           tag=tag)

    def charge(self, role: str, seconds: float,
               lane: Optional[int] = None, label: Optional[str] = None,
               units: float = 0.0, wall_s: float = 0.0) -> None:
        """Charge virtual time without an attached program (e.g. retraining
        SGD, whose cost is known only after the batch count is). With a
        ``lane``, the charge is also attributed to that stream's ledger.
        ``label``/``units``/``wall_s`` annotate the charge for the trace
        spine (kernel name, quantity the cost scales with, measured host
        wall) — ignored untraced."""
        del label, units, wall_s
        self.totals[role] += seconds
        if lane is not None:
            lane_led = self.lane_totals.setdefault(
                lane, {r: 0.0 for r in ROLES})
            lane_led[role] += seconds
        if role == "t_sa":
            self._now += seconds

    def lane_time(self, role: str, lane: int) -> float:
        """This phase's virtual seconds charged to ``lane`` on ``role``."""
        return self.lane_totals.get(lane, {}).get(role, 0.0)

    def pad_to(self, t: float) -> None:
        """Floor the phase end on a pacing-grid boundary (pace_window_s)."""
        if t > self._floor:
            self._floor = t

    # -------------------------------------------------------------- clock
    def now(self) -> float:
        """Running clock while the phase is being built: the T-SA chain
        drives phase structure in both modes (the B-SA overlaps it)."""
        return self._now

    @property
    def t_tsa(self) -> float:
        # Reported from the role ledger (a plain sum from 0.0) rather than
        # as ``_now - start``: mathematically identical, but the ledger form
        # is bitwise-reproducible by per-lane accounting, which the fleet's
        # 1-stream degeneracy golden pins.
        return self.totals["t_sa"]

    @property
    def t_bsa(self) -> float:
        return self.totals["b_sa"]

    def finish(self) -> float:
        """Phase-end clock. Sequential: the T-SA sum (seed semantics);
        concurrent: start + max(t_TSA, t_BSA). Both respect the pacing
        floor, matching the seed's ``clock = next_boundary`` assignment."""
        end = self._now
        if self.mode == CONCURRENT:
            end = max(end, self.start + self.totals["b_sa"])
        return max(end, self._floor)

    # ------------------------------------------------------------ collect
    def collect_all(self) -> None:
        """Barrier: materialize every outstanding program of this phase."""
        for prog in self.programs:
            if prog.handle is not None:
                prog.handle.collect()


class KernelDispatcher:
    """Factory + bookkeeping for per-phase plans.

    One dispatcher lives on a :class:`~repro.core.session.CLSession`; its
    mode decides the clock semantics of every :class:`PhasePlan` it opens
    (see module docstring). ``phases_dispatched`` / ``programs_dispatched``
    are cumulative counters for benchmarks and tests.

    ``recorder`` (a :class:`~repro.core.trace.TraceRecorder`, default
    None) turns on the trace spine: each ``begin_phase`` opens a
    :class:`~repro.core.trace.PhaseTrace` and the plan's traced overrides
    record every program issue and ledger charge as
    :class:`~repro.core.trace.TraceEvent`s (see core/trace.py).
    """

    def __init__(self, mode: str = SEQUENTIAL,
                 recorder: Optional[TraceRecorder] = None):
        if mode not in DISPATCH_MODES:
            raise ValueError(
                f"unknown dispatch mode {mode!r}; known: {DISPATCH_MODES}")
        self.mode = mode
        self.recorder = recorder
        self.phases_dispatched = 0
        self.programs_dispatched = 0

    @property
    def concurrent(self) -> bool:
        return self.mode == CONCURRENT

    def begin_phase(self, start: float, pipeline=None,
                    label_hints: Optional[Sequence] = None,
                    decisions: Optional[Sequence] = None,
                    fps: Optional[float] = None) -> PhasePlan:
        """Open a phase plan. With a ``pipeline``
        (:class:`~repro.data.pipeline.FramePipeline`, or a sequence of them
        — one lane per fleet stream), the plan becomes the phase's
        data-plane handle too: opening the plan rotates each pipeline's
        speculation onto this phase start, and ``plan.fetch(lane=i)`` serves
        the phase's frame windows from that lane's speculative prefetcher.

        ``decisions`` (one two-plane
        :class:`~repro.core.decision.Decision` per lane) is how the plan
        consumes the phase's intent: with a stream ``fps``, each lane's
        label hint — the decision-aware speculation signal — derives from
        its temporal plane's labeling budget, so drift-phase bursts are
        pre-sized instead of replayed from the last layout (``fps=None``
        records the decisions without hinting). ``label_hints`` (one
        ``(n_samples, fps)`` per lane, or None entries) is the pre-plane
        spelling of the same signal, kept for direct callers."""
        pipelines = _as_pipelines(pipeline)
        if label_hints is None and decisions is not None:
            label_hints = [
                (None if d is None or fps is None
                 else (d.temporal.total_label_samples, fps))
                for d in decisions]
        for i, pipe in enumerate(pipelines):
            hint = (label_hints[i]
                    if label_hints is not None and i < len(label_hints)
                    else None)
            pipe.begin_phase(start, label_hint=hint)
        plan = _TrackedPlan(self, self.mode, start, pipelines)
        plan.decisions = tuple(decisions) if decisions is not None else ()
        if self.recorder is not None:
            plan._trace = self.recorder.begin_phase(
                start, self.mode, decisions=plan.decisions)
        self.phases_dispatched += 1
        return plan


class _TrackedPlan(PhasePlan):
    """PhasePlan that feeds the dispatcher's cumulative counters — and,
    when the dispatcher carries a :class:`~repro.core.trace.TraceRecorder`,
    records the phase's program/charge stream as
    :class:`~repro.core.trace.TraceEvent`s. Recording never touches the
    numeric plan state (ledgers, clock, floor), so traced runs stay
    bit-identical; with ``_trace is None`` every override falls straight
    through to the untraced code path."""

    def __init__(self, dispatcher: KernelDispatcher, mode: str, start: float,
                 pipeline=None):
        super().__init__(mode, start, pipeline)
        self._dispatcher = dispatcher
        self._trace = None  # open PhaseTrace when the dispatcher records
        self._in_program = False  # suppress charge events inside dispatch

    @property
    def traced(self) -> bool:
        return self._trace is not None

    def dispatch(self, role: str, label: str, issue: Callable[[], Any],
                 cost_s: float = 0.0,
                 lane: Optional[int] = None,
                 units: float = 0.0) -> ProgramHandle:
        self._dispatcher.programs_dispatched += 1
        tr = self._trace
        if tr is None:
            return super().dispatch(role, label, issue, cost_s, lane=lane)
        recorder = self._dispatcher.recorder
        before = recorder.paths_before()
        t0 = time.perf_counter()
        self._in_program = True
        try:
            handle = super().dispatch(role, label, issue, cost_s, lane=lane)
        finally:
            self._in_program = False
        wall = time.perf_counter() - t0
        tr.events.append(TraceEvent(
            kind="program", role=role, label=label, cost_s=cost_s,
            lane=lane, wall_s=wall, path=recorder.dominant_path(before),
            units=units))
        return handle

    def dispatch_multi(self, role: str, label: str,
                       issue: Callable[[], Sequence[Any]],
                       costs: Sequence[float],
                       lanes: Sequence[int],
                       units: Optional[Sequence[float]] = None
                       ) -> List[ProgramHandle]:
        self._dispatcher.programs_dispatched += 1
        tr = self._trace
        if tr is None:
            return super().dispatch_multi(role, label, issue, costs, lanes)
        recorder = self._dispatcher.recorder
        before = recorder.paths_before()
        t0 = time.perf_counter()
        self._in_program = True
        try:
            handles = super().dispatch_multi(role, label, issue, costs,
                                             lanes)
        finally:
            self._in_program = False
        # One device program fanned across the lanes: the measured wall is
        # split evenly over the per-lane events (``fan`` marks the group).
        wall = (time.perf_counter() - t0) / max(1, len(lanes))
        path = recorder.dominant_path(before)
        for i, (cost_s, lane) in enumerate(zip(costs, lanes)):
            tr.events.append(TraceEvent(
                kind="program", role=role, label=label, cost_s=cost_s,
                lane=lane, wall_s=wall, path=path,
                units=(units[i] if units is not None else 0.0),
                fan=len(lanes)))
        return handles

    def charge(self, role: str, seconds: float,
               lane: Optional[int] = None, label: Optional[str] = None,
               units: float = 0.0, wall_s: float = 0.0) -> None:
        super().charge(role, seconds, lane=lane)
        tr = self._trace
        if tr is not None and not self._in_program:
            tr.events.append(TraceEvent(
                kind="charge", role=role, label=label or "charge",
                cost_s=seconds, lane=lane, wall_s=wall_s, units=units))

    def finish(self) -> float:
        end = super().finish()
        tr = self._trace
        if tr is not None:
            tr.end = end
            tr.floor = self._floor
        return end
