"""Streaming video engine: continuous batching over per-stream edge state.

The LM engine (``serve.engine``) proved the slot/admission shape: a fixed
population of slots, a queue feeding them, per-slot carried state, one
batched device call per step. This module adapts it to video frames — the
lane-detection workload the paper's kernel exists for — where the carried
state is temporal edge state instead of a KV cache:

  * **Slots + admission.** ``max_streams`` slots; :class:`StreamRequest`\\ s
    queue and are admitted as slots free up (a stream leaves when its frame
    source is exhausted). Streams join and leave mid-run without disturbing
    their neighbors — every slot owns an isolated row of a
    :class:`~repro.api.StreamState`.
  * **Continuous frame batching.** Each step serves every *due* stream
    (fps-paced on a deterministic virtual clock), grouping same-resolution
    streams into one batched :func:`~repro.api.edge_detect_stream` call —
    ragged resolutions simply land in different groups. A group's state
    stays batched on the device between steps: each slot points at the
    batched state the last call returned and at its row in it. When a
    group's members are exactly that batch's rows, in order, the batch is
    passed whole to the next call — no device op. Only when membership
    changes (a stream joins or retires, a straggler leaves its group, a
    due subset of streams with different fps) are the members' rows
    sliced out and concatenated for the call
    (``StreamStats.state_gathers`` counts those serves). A retired
    stream's row stays in its batch until the group's next regather, so a
    batch holds at most one frame's state per stream that has left it.
    Batching is an execution detail, never a semantic one.
  * **Delta-skip dispatch.** A group serve is one jitted
    ``dispatch.edge_stream`` call. Inside it the per-tile change test
    (``dispatch.stream_delta``) picks, on the device, between the cached
    maps of a fully static group — no kernel launch at all, just the
    cheap epilogue — and the masked-grid kernel that recomputes only
    flagged tiles. The host learns which it was from the per-stream
    skipped-tile counts it reads back anyway (``StreamStats.cached_steps``).
  * **Split timing.** Each group serve records two host-clock times on its
    members' :class:`StreamStats`: ``transfer_ms`` covers the host
    ``np.stack`` of the members' frames and their transfer to the device
    (``block_until_ready`` on the device-put); ``compute_ms`` covers the
    choice of the group's state (passed whole, regathered or zero-filled)
    and the step call, but no queue wait. The step's phases
    are also profiler spans named ``repro.stream.*`` (``repro.guard.*``
    for the retry ladder), on the device trace's clock: ``step`` (metadata
    ``step``, ``frames``, ``groups``), ``intake``, ``stack``, ``h2d``,
    ``concat`` (the choice of state; metadata ``whole``: 1 when the batch
    was passed whole, else 0), ``compute``, ``split`` (pointing
    the members at the new batch), ``account`` and ``police``.

Batched streams share their group's step latency — a reported per-stream
percentile is the latency of the batch the frame rode in, which is the
number a deadline cares about.

**Fault tolerance.** Every group serve runs under the degradation ladder
(:mod:`repro.serve.guard`): bounded retry with backoff, then a permanent
bit-exact pallas→xla backend fallback. Every pulled frame is screened —
corrupted frames (NaN/Inf, changed dtype/shape mid-stream) are quarantined
per-stream instead of poisoning their batch group, and a stream that keeps
blowing its latency budget sheds its oldest pending frame (hysteresis via
:class:`~repro.serve.guard.Shedder`). A :class:`~repro.runtime.monitor
.StepMonitor` + :class:`~repro.runtime.stragglers.StragglerPolicy` watch
per-stream step times; a straggling stream is excluded into a solo batch
group after repeated strikes so it stops dragging its neighbors. The
engine's :class:`~repro.serve.guard.Health` ledger accounts every
submitted frame as exactly one of served / retried / degraded / shed /
quarantined, and a :class:`~repro.runtime.chaos.FaultPlan` injects all of
the above deterministically for tests and ``serve.py --chaos``.

A stream whose *source iterator raises* mid-run is retired with the error
recorded in ``health.errors`` — one broken camera never takes down the
engine (frames it already served stay served and accounted).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import EdgeConfig, StreamState, detect_layout
from repro.kernels import dispatch
from repro.kernels.edge import kernel_dtype
from repro.runtime.chaos import FaultPlan
from repro.runtime.monitor import StepMonitor
from repro.runtime.stragglers import StragglerPolicy
from repro.serve.guard import (
    GuardPolicy,
    Health,
    Outcome,
    Shedder,
    StepGuard,
    quarantine_reason,
)

__all__ = ["StreamRequest", "StreamStats", "StreamEngine"]

FrameSource = Union[Iterable[np.ndarray], Callable[[int], Optional[np.ndarray]]]


@dataclasses.dataclass
class StreamRequest:
    """One video stream: an id, a frame source, and an fps budget.

    ``frames`` is either an iterable of frames (``HW`` / ``HWC`` arrays,
    all the same shape and dtype) or a callable ``frame_index ->
    frame | None`` (``None`` ends the stream). ``fps`` paces the stream on
    the engine's virtual clock — streams with different rates interleave
    deterministically — and names the latency budget (one frame period)
    the stats report against.
    """

    sid: int
    frames: FrameSource
    fps: float = 30.0

    def __post_init__(self):
        if self.fps <= 0:
            raise ValueError(f"stream {self.sid}: fps={self.fps} must be > 0")

    def frame_iter(self) -> Iterator[np.ndarray]:
        if callable(self.frames):
            def gen():
                i = 0
                while True:
                    f = self.frames(i)
                    if f is None:
                        return
                    yield f
                    i += 1
            return gen()
        return iter(self.frames)


@dataclasses.dataclass
class StreamStats:
    """Per-stream serving record (returned by ``StreamEngine.run``).

    ``frames`` counts frames actually served (on any ladder rung);
    ``submitted`` counts every frame pulled from the source, so
    ``submitted == frames + shed + quarantined`` always holds — the
    per-stream slice of the engine's health invariant. ``state_gathers``
    counts the group serves at which this stream's state had to be sliced
    out of one batch and concatenated into another, instead of being
    passed whole (the cold first serve counts as neither).
    """

    sid: int
    fps: float
    shape: tuple = ()
    frames: int = 0
    submitted: int = 0
    shed: int = 0                    # dropped under latency pressure
    quarantined: int = 0             # dropped as corrupt (NaN/dtype/shape)
    tiles_per_frame: int = 0
    skipped_tiles: int = 0
    cached_steps: int = 0            # steps served with no kernel launch
    state_gathers: int = 0           # serves that regathered the state
    transfer_ms: List[float] = dataclasses.field(default_factory=list)
    compute_ms: List[float] = dataclasses.field(default_factory=list)
    outputs: List[dict] = dataclasses.field(default_factory=list)  # collect=True

    @property
    def skip_rate(self) -> float:
        """Fraction of tiles delta-skipped after the cold first frame."""
        total = self.tiles_per_frame * max(0, self.frames - 1)
        return self.skipped_tiles / total if total else 0.0

    @property
    def budget_ms(self) -> float:
        return 1e3 / self.fps


@dataclasses.dataclass
class _Slot:
    req: StreamRequest
    it: Iterator[np.ndarray]
    stats: StreamStats
    next_due: float
    shedder: Shedder
    pending: Optional[np.ndarray] = None   # next frame, pulled at admit
    pending_idx: int = -1                  # source index of ``pending``
    frame_idx: int = 0                     # source frames pulled so far
    dtype: Optional[np.dtype] = None       # pinned by the first good frame
    layout: str = "HW"
    solo: bool = False                     # excluded straggler: own group
    batch: Optional[StreamState] = None    # group state holding this stream
    row: int = 0                           # this stream's row in ``batch``

    def group_key(self) -> tuple:
        key = (self.pending.shape, str(self.pending.dtype),
               self.batch is None or not self.batch.initialized)
        # An excluded straggler is batched alone so its injected/organic
        # slowness drags only itself, not its former groupmates.
        return key + (("solo", self.req.sid),) if self.solo else key


class StreamEngine:
    """Slot-scheduled streaming edge detection over many concurrent streams.

    ``config`` is the per-frame :class:`~repro.api.EdgeConfig` (typically
    ``hysteresis=True, temporal=True, decay=...`` for detector traffic);
    it is resolved once and shared by every stream. ``collect=True`` keeps
    each stream's outputs (host copies of magnitude/edges + skip counts)
    on its stats record — for tests and small runs, not production.

    ``chaos`` threads a :class:`~repro.runtime.chaos.FaultPlan` through the
    serving loop (site ``"step"`` per group serve, ``"fallback"`` on the
    degraded backend, plus frame corruption, per-stream straggler delay,
    and device-loss events keyed on the engine step). ``guard`` tunes the
    degradation ladder; ``fallback=False`` disables the pallas→xla rung
    (it is automatically absent when the configured backend already
    resolves to xla). ``engine.health`` / ``engine.outcomes`` carry the
    run's accounting.

    Usage::

        eng = StreamEngine(EdgeConfig(temporal=True, decay=0.9))
        eng.submit(StreamRequest(sid=0, frames=camera0, fps=30))
        eng.submit(StreamRequest(sid=1, frames=camera1, fps=15))
        stats = eng.run()          # drive until every stream is exhausted
        print(eng.health.summary())
    """

    def __init__(
        self,
        config: Optional[EdgeConfig] = None,
        *,
        max_streams: int = 8,
        collect: bool = False,
        chaos: Optional[FaultPlan] = None,
        guard: Optional[GuardPolicy] = None,
        fallback: bool = True,
        monitor: Optional[StepMonitor] = None,
        stragglers: Optional[StragglerPolicy] = None,
    ):
        self.config = (config or EdgeConfig()).resolved()
        if max_streams < 1:
            raise ValueError(f"max_streams={max_streams} must be >= 1")
        self.max_streams = max_streams
        self.collect = collect
        self.chaos = chaos
        self.guard_policy = guard or GuardPolicy()
        self.slots: List[Optional[_Slot]] = [None] * max_streams
        self.queue: collections.deque = collections.deque()
        self.finished: List[StreamStats] = []
        self.clock = 0.0
        self.engine_step = 0
        backend = dispatch.resolve_backend(self.config.backend)
        self._fb_config = (
            self.config.replace(backend="xla")
            if fallback and backend != "xla" else None
        )
        self.health = Health(backend=backend)
        self.outcomes: List[Outcome] = []
        self.monitor = monitor or StepMonitor(window=8)
        self.straggler_policy = stragglers or StragglerPolicy()
        self._excluded: set = set()
        self._make_jits()
        self._guard = StepGuard(
            lambda *a: self._exec_group(self.config, *a),
            fallback=(
                (lambda *a: self._exec_group(self._fb_config, *a))
                if self._fb_config is not None else None
            ),
            policy=self.guard_policy,
            chaos=chaos,
            seed=chaos.seed if chaos is not None else 0,
        )

    def _make_jits(self) -> None:
        """(Re)build the jitted step function — fresh after device loss."""
        self._jit_step = jax.jit(
            dispatch.edge_stream, static_argnames=("layout",)
        )

    # -- public API ----------------------------------------------------------
    def submit(self, req: StreamRequest) -> None:
        self.queue.append(req)

    def run(self, max_steps: int = 100_000) -> Dict[int, StreamStats]:
        """Drive until queue + slots drain; returns stats keyed by sid."""
        for _ in range(max_steps):
            if not self.step():
                break
        return {s.sid: s for s in self.finished}

    def active(self) -> List[int]:
        return [s.req.sid for s in self.slots if s is not None]

    # -- frame intake: corruption screen + quarantine + shedding -------------
    def _pull(self, slot: _Slot) -> Optional[np.ndarray]:
        """Next *servable* frame for ``slot`` (None = stream over).

        Every frame pulled from the source counts as submitted; the ones
        that never reach a batch are terminally accounted right here —
        corrupted frames are quarantined against the stream's pinned
        shape/dtype contract (plus the intrinsic NaN/Inf and invalid-dtype
        checks), and while the stream's :class:`Shedder` says it is behind
        budget, the oldest pending frame is shed to let it catch up.
        """
        sid = slot.req.sid
        while True:
            try:
                frame = next(slot.it, None)
            except Exception as err:  # noqa: BLE001 — isolate broken sources
                self.health.errors.append(
                    f"stream {sid}: source raised {type(err).__name__}: {err}"
                )
                return None
            if frame is None:
                return None
            idx = slot.frame_idx
            slot.frame_idx += 1
            self.health.submitted += 1
            slot.stats.submitted += 1
            frame = np.asarray(frame)
            if self.chaos is not None:
                mode = self.chaos.corruption(sid, idx)
                if mode is not None:
                    frame = self.chaos.corrupt(frame, mode)
            reason = quarantine_reason(
                frame,
                shape=slot.stats.shape or None,
                dtype=slot.dtype,
            )
            if reason is not None:
                self._account("quarantined", slot, idx, detail=reason)
                slot.stats.quarantined += 1
                continue
            if slot.shedder.shedding:
                self._account("shed", slot, idx, detail="latency budget")
                slot.stats.shed += 1
                slot.shedder.shed_one()
                continue
            slot.pending_idx = idx
            return frame

    def _account(self, kind: str, slot: _Slot, idx: int, *,
                 detail: str = "", attempts: int = 0,
                 latency_ms: float = 0.0) -> None:
        self.health.record(kind)
        self.outcomes.append(Outcome(
            kind=kind, step=self.engine_step, stream=slot.req.sid,
            frame=idx, attempts=attempts, latency_ms=latency_ms,
            backend=self.health.backend if kind not in ("shed", "quarantined")
            else None,
            detail=detail,
        ))

    # -- internals -----------------------------------------------------------
    def _admit(self) -> None:
        for i in range(self.max_streams):
            if self.slots[i] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            slot = _Slot(
                req=req, it=req.frame_iter(),
                stats=StreamStats(sid=req.sid, fps=req.fps),
                next_due=self.clock,
                shedder=Shedder(shed_after=self.guard_policy.shed_after),
            )
            first = self._pull(slot)
            if first is None:          # empty / all-quarantined: trivially done
                self.finished.append(slot.stats)
                continue
            slot.pending = first
            slot.stats.shape = first.shape   # pins the stream's contract
            slot.dtype = first.dtype
            slot.layout = "N" + detect_layout(first.shape)
            self.slots[i] = slot

    def _retire(self, i: int) -> None:
        self.finished.append(self.slots[i].stats)
        self.slots[i] = None

    def step(self) -> bool:
        """Serve every due stream once; returns False when fully drained."""
        with jax.profiler.TraceAnnotation("repro.stream.step",
                                          step=self.engine_step) as span:
            with jax.profiler.TraceAnnotation("repro.stream.intake"):
                self._admit()
            active = [i for i, s in enumerate(self.slots) if s is not None]
            if not active:
                return bool(self.queue)
            if self.chaos is not None:
                loss = self.chaos.device_loss(self.engine_step)
                if loss is not None:
                    # Single-host streaming: recovery is a re-jit on the
                    # surviving population (the mesh replan analog lives in
                    # the sharded serve loop, launch/serve.py).
                    self._make_jits()
                    self.health.replans += 1
            self.clock = min(self.slots[i].next_due for i in active)
            due = [i for i in active
                   if self.slots[i].next_due <= self.clock + 1e-9]
            groups: Dict[tuple, List[int]] = collections.defaultdict(list)
            for i in due:
                groups[self.slots[i].group_key()].append(i)
            span.set_metadata(frames=len(due), groups=len(groups))
            for members in groups.values():
                self._serve_group(members)
            with jax.profiler.TraceAnnotation("repro.stream.police"):
                self._police_stragglers()
            self.engine_step += 1
            with jax.profiler.TraceAnnotation("repro.stream.intake"):
                for i in due:
                    slot = self.slots[i]
                    if slot is None:
                        continue                    # retired in this step
                    slot.next_due += 1.0 / slot.req.fps
                    slot.pending = self._pull(slot)
                    if slot.pending is None:
                        self._retire(i)
            return True

    def _police_stragglers(self) -> None:
        """Feed the monitor's verdicts to the mitigation policy.

        A stream flagged ``strikes_to_exclude`` steps in a row is moved to
        a solo batch group — the streaming analog of dropping a straggler
        host from the mesh: its neighbors stop paying its latency, it
        keeps being served (and shed, if it cannot keep up even alone).
        """
        flagged = self.monitor.stragglers()
        for h in flagged:
            if h not in self.health.stragglers:
                self.health.stragglers.append(h)
        decision = self.straggler_policy.step(self.monitor)
        for host in decision["exclude"]:
            if host in self._excluded:
                continue
            self._excluded.add(host)
            self.health.excluded.append(host)
            for s in self.slots:
                if s is not None and f"s{s.req.sid}" == host:
                    s.solo = True

    def _exec_group(self, cfg, frames, state, layout):
        """One guarded group serve: one jitted step call, no host read.

        The step runs the delta test and chooses between the cached maps
        and the masked compute on the device (``dispatch.edge_stream``).
        Runs under :class:`~repro.serve.guard.StepGuard` — ``cfg`` is the
        primary or fallback config depending on the rung. Blocks on the
        result so failures surface here, inside the retry ladder.
        """
        with jax.profiler.TraceAnnotation("repro.stream.compute"):
            result, new_state = self._jit_step(frames, cfg, state,
                                               layout=layout)
            jax.block_until_ready(result)
        return result, new_state

    def _serve_group(self, members: List[int]) -> None:
        slots = [self.slots[i] for i in members]
        layout = slots[0].layout

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("repro.stream.stack"):
            host = np.stack([s.pending for s in slots])
        with jax.profiler.TraceAnnotation("repro.stream.h2d"):
            frames = jax.device_put(kernel_dtype(jnp.asarray(host)))
            jax.block_until_ready(frames)
        transfer_ms = (time.perf_counter() - t0) * 1e3

        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("repro.stream.concat") as span:
            state, how = self._group_state(slots, frames)
            span.set_metadata(whole=int(how == "whole"))
        (result, new_state), kind, attempts = self._guard(
            frames, state, layout
        )
        compute_ms = (time.perf_counter() - t1) * 1e3
        self.health.retries += attempts
        self.health.degraded = self._guard.degraded
        if self._guard.degraded and self._fb_config is not None:
            self.health.backend = "xla"

        # Injected straggler drag: the slowest member delays the whole
        # batch (shared wall clock), but the monitor is fed each member's
        # own time — base plus its own injected delay — so detection
        # attributes the lag to the right stream, not the whole group.
        lag = 0.0
        if self.chaos is not None:
            delays = [self.chaos.delay_s(f"s{s.req.sid}", s.stats.frames)
                      for s in slots]
            lag = max(delays)
            if lag > 0:
                time.sleep(lag)
        else:
            delays = [0.0] * len(slots)
        group_ms = compute_ms + lag * 1e3

        with jax.profiler.TraceAnnotation("repro.stream.split"):
            for b, s in enumerate(slots):
                s.batch, s.row = new_state, b
        with jax.profiler.TraceAnnotation("repro.stream.account"):
            skipped = np.asarray(result.skipped)
            # A warm group with every tile of every member skipped was
            # served from its cached maps, with no kernel launch.
            cached = state.initialized and bool(
                np.all(skipped == new_state.tiles))
            for b, s in enumerate(slots):
                st = s.stats
                st.frames += 1
                st.tiles_per_frame = s.batch.tiles
                if cached:
                    st.cached_steps += 1
                if how == "gather":
                    st.state_gathers += 1
                if st.frames > 1:        # frame 0 is the cold cache fill
                    st.skipped_tiles += int(skipped[b])
                st.transfer_ms.append(transfer_ms)
                st.compute_ms.append(group_ms)
                self.monitor.record(
                    f"s{s.req.sid}", compute_ms / 1e3 + delays[b]
                )
                self._account(kind, s, s.pending_idx, attempts=attempts,
                              latency_ms=group_ms,
                              detail=self._guard.last_error or "" if attempts
                              else "")
                if st.frames > self.guard_policy.warm_frames:
                    budget = self.guard_policy.deadline_ms or st.budget_ms
                    if s.shedder.observe(group_ms, budget):
                        self.health.deadline_violations += 1
                if self.collect:
                    st.outputs.append(self._host_outputs(result, b))

    def _group_state(self, slots: List[_Slot], frames):
        """The members' state for one batched call, and how it was made.

        ``"init"``: cold members get a zero state. ``"whole"``: the members
        are exactly the rows of one batch, in order, so that batch — the
        state the previous call returned — is passed as it is, with no
        device op. ``"gather"``: anything else (a stream joined or retired,
        a straggler left its group, a due subset of one group key); each
        member's row is sliced out and the rows are concatenated.
        """
        batch = slots[0].batch
        if batch is None:
            h, w = (frames.shape[1:3])
            rgb = frames.ndim == 4
            return StreamState.init(
                len(slots), h, w, self.config, rgb=rgb, dtype=frames.dtype
            ), "init"
        if batch.bmax.shape[0] == len(slots) and all(
                s.batch is batch and s.row == b for b, s in enumerate(slots)):
            return batch, "whole"
        rows = [jax.tree.map(lambda a, r=s.row: a[r:r + 1], s.batch)
                for s in slots]
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                            *rows), "gather"

    @staticmethod
    def _host_outputs(result, b: int) -> dict:
        out = {
            "magnitude": np.asarray(result.magnitude[b]),
            "skipped": int(np.asarray(result.skipped)[b]),
        }
        if result.edges is not None:
            out["edges"] = np.asarray(result.edges[b])
        return out
