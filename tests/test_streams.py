"""Streaming engine battery: temporal state, delta-skip, slot isolation.

The contracts under test, in dependency order:

  1. ``decay=0`` temporal streaming is bit-identical to stateless per-frame
     ``edge_detect`` — the streaming path adds nothing until asked to.
  2. A static stream delta-skips >90% of tiles after frame 1 and still
     produces bit-identical outputs (skip is an optimization, never an
     approximation), on both the XLA splice path and the masked-grid
     Pallas kernel.
  3. Partial change recomputes exactly the dilated changed neighborhood and
     splices the rest — still bit-identical.
  4. Temporal seeding (decay>0) keeps a fading edge alive that stateless
     detection drops, and seeds expire once decay pushes them under the
     floor.
  5. The engine's slots are isolated: ragged resolutions, mid-run
     join/leave, and grouping never corrupt a neighbor stream's state —
     every engine output equals the same stream served solo, whether a
     group's batched state is passed whole to the next step or
     regathered because the group's members changed.

Wall-clock latency assertions are gated behind the fast-host convention
(``REPRO_SLOW_HOST=1`` skips them); structure and counter assertions always
run.
"""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import slow_host
from repro.api import (
    EdgeConfig,
    StreamState,
    edge_detect,
    edge_detect_stream,
)
from repro.core.filters import list_operators
from repro.kernels import dispatch
from repro.runtime.chaos import FaultPlan, Straggler
from repro.serve import GuardPolicy, StreamEngine, StreamRequest

RNG = np.random.default_rng(7)


def _frame(h=40, w=48, rgb=False, seed=None):
    rng = np.random.default_rng(seed) if seed is not None else RNG
    shape = (h, w, 3) if rgb else (h, w)
    return rng.integers(0, 256, shape, dtype=np.uint8)


def _assert_same(res, ref):
    np.testing.assert_array_equal(np.asarray(res.magnitude),
                                  np.asarray(ref.magnitude))
    if ref.edges is not None:
        np.testing.assert_array_equal(np.asarray(res.edges),
                                      np.asarray(ref.edges))


# ---------------------------------------------------------------- config --

class TestConfigValidation:
    def test_temporal_requires_stream_path(self):
        with pytest.raises(ValueError, match="temporal"):
            edge_detect(_frame(), EdgeConfig(temporal=True, backend="xla"))

    def test_decay_requires_temporal(self):
        with pytest.raises(ValueError, match="decay"):
            EdgeConfig(hysteresis=True, decay=0.5).resolved()

    def test_decay_range(self):
        for bad in (-0.1, 1.5):
            with pytest.raises(ValueError, match="decay"):
                EdgeConfig(temporal=True, decay=bad).resolved()

    def test_temporal_implies_hysteresis(self):
        assert EdgeConfig(temporal=True).resolved().hysteresis

    def test_stream_rejects_shard(self):
        from repro.api import ShardConfig
        cfg = EdgeConfig(shard=ShardConfig(rows=1, cols=1, data=1))
        with pytest.raises(ValueError, match="shard"):
            edge_detect_stream(_frame(), cfg)

    def test_stream_rejects_components(self):
        with pytest.raises(ValueError, match="components"):
            edge_detect_stream(_frame(), EdgeConfig(with_components=True))


# ----------------------------------------------------------- state pytree --

class TestStreamState:
    def test_init_shapes(self):
        cfg = EdgeConfig(temporal=True, backend="xla").resolved()
        st = StreamState.init(2, 40, 48, cfg)
        assert st.frame.shape == (2, 40, 48)
        assert st.primary.shape == (2, 40, 48)
        assert st.bmax.shape[0] == 2
        assert st.seed.shape == (2, 40, 48)
        assert not st.initialized
        assert st.tiles == st.bmax.shape[1] * st.bmax.shape[2]

    def test_jit_roundtrip(self):
        cfg = EdgeConfig(backend="xla").resolved()
        st = StreamState.init(1, 40, 48, cfg)
        out = jax.jit(lambda s: s)(st)
        assert out.block == st.block
        assert out.initialized == st.initialized
        assert out.frame.shape == st.frame.shape

    def test_flatten_roundtrip(self):
        cfg = EdgeConfig(temporal=True, backend="xla").resolved()
        st = StreamState.init(1, 32, 32, cfg)
        leaves, treedef = jax.tree_util.tree_flatten(st)
        st2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert st2.block == st.block and st2.tiles == st.tiles


# ------------------------------------------------- decay=0 <=> stateless --

class TestStatelessEquivalence:
    @pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
    @pytest.mark.parametrize("rgb", [False, True])
    def test_decay0_bit_identical(self, backend, rgb):
        cfg = EdgeConfig(nms=True, temporal=True, decay=0.0, backend=backend,
                         block_h=16, block_w=16)
        ref_cfg = cfg.replace(temporal=False, decay=0.0, hysteresis=True)
        state = None
        for t in range(4):
            f = _frame(rgb=rgb, seed=100 + t)
            res, state = edge_detect_stream(f, cfg, state)
            _assert_same(res, edge_detect(f, ref_cfg))

    @pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
    @pytest.mark.parametrize("operator", list_operators())
    @pytest.mark.parametrize("nms", [False, True])
    def test_plain_stream_matches_plain_detect(self, backend, operator, nms):
        """The stream kernel computes a tile through the same tile math as
        the image kernel, for every operator, with and without NMS."""
        cfg = EdgeConfig(backend=backend, operator=operator, nms=nms)
        f = _frame(seed=3)
        res, _ = edge_detect_stream(f, cfg)
        _assert_same(res, edge_detect(f, cfg))


# ------------------------------------------------------------ delta-skip --

class TestDeltaSkip:
    @pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
    def test_static_stream_skips_and_matches(self, backend):
        """Acceptance: static stream skips >90% of tiles after frame 1,
        bit-identical to full recompute."""
        cfg = EdgeConfig(nms=True, hysteresis=True, backend=backend,
                         block_h=8, block_w=8)
        f = _frame(seed=11)
        ref = edge_detect(f, cfg)
        state = None
        for t in range(4):
            res, state = edge_detect_stream(f, cfg, state)
            _assert_same(res, ref)
            skipped = int(np.asarray(res.skipped))
            if t == 0:
                assert skipped == 0  # cold state: everything recomputes
            else:
                assert skipped == state.tiles  # 100% > 90%
        assert state.tiles > 10  # the acceptance ratio is over real tiles

    @pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
    @pytest.mark.parametrize("rgb", [False, True])
    def test_partial_change_splices_exactly(self, backend, rgb):
        cfg = EdgeConfig(nms=True, hysteresis=True, backend=backend,
                         block_h=8, block_w=8)
        f0 = _frame(rgb=rgb, seed=21)
        _, state = edge_detect_stream(f0, cfg)
        f1 = f0.copy()
        f1[18, 25] = 255 - f1[18, 25]  # one pixel, interior tile
        res, state = edge_detect_stream(f1, cfg, state)
        _assert_same(res, edge_detect(f1, cfg))
        skipped = int(np.asarray(res.skipped))
        assert 0 < skipped < state.tiles  # partial: some skipped, some not

    def test_changed_mask_dilation_covers_reach(self):
        """A changed pixel at a tile edge must invalidate the neighbor tile
        whose window reads it — skipping it would splice stale output."""
        cfg = EdgeConfig(nms=True, backend="xla",
                         block_h=8, block_w=8).resolved()
        f0 = _frame(seed=31)
        _, state = edge_detect_stream(f0, cfg)
        f1 = f0.copy()
        f1[8, 8] = 255 - f1[8, 8]  # corner of tile (1,1): reaches (0,0)
        changed, _ = dispatch.stream_delta(
            jnp.asarray(f1)[None], state, cfg, rgb=False)
        ch = np.asarray(changed)[0]
        assert ch[1, 1] and ch[0, 0] and ch[0, 1] and ch[1, 0]

    def test_whole_frame_change_skips_nothing(self):
        cfg = EdgeConfig(backend="xla", block_h=8, block_w=8)
        f0 = _frame(seed=41)
        _, state = edge_detect_stream(f0, cfg)
        f1 = (255 - f0.astype(np.int32)).astype(np.uint8)
        res, _ = edge_detect_stream(f1, cfg, state)
        assert int(np.asarray(res.skipped)) == 0
        _assert_same(res, edge_detect(f1, cfg))

    def test_cached_path_equals_recompute(self):
        cfg = EdgeConfig(nms=True, hysteresis=True, backend="xla").resolved()
        f = _frame(seed=51)
        _, state = edge_detect_stream(f, cfg)
        res, state2 = dispatch.edge_stream_cached(cfg, state, layout="HW")
        _assert_same(res, edge_detect(f, cfg))
        assert int(np.asarray(res.skipped)) == state.tiles
        assert state2.initialized


# -------------------------------------------------------------- temporal --

class TestTemporalHysteresis:
    @staticmethod
    def _fading_frames(n=4):
        """A permanent strong edge at col 8 holds the per-image peak (so
        normalization cannot promote the weak edge); the col-24 edge is
        strong at t=0 and fades to between-thresholds after: stateless
        hysteresis drops it, temporal seeding keeps it."""
        frames = []
        for t in range(n):
            f = np.zeros((32, 48), np.uint8)
            f[:, 8:] = 215
            f[:, 24:] = 40 if t == 0 else 245
            frames.append(f)
        return frames

    def test_seed_persists_fading_edge(self):
        cfg = EdgeConfig(nms=True, temporal=True, decay=0.9, backend="xla")
        stateless = cfg.replace(temporal=False, decay=0.0, hysteresis=True)
        frames = self._fading_frames()
        state = None
        for f in frames[:3]:
            res, state = edge_detect_stream(f, cfg, state)
        band = np.asarray(res.edges)[2:-2, 22:26]
        assert band.any()  # temporal: the faded edge survives
        ref = np.asarray(edge_detect(frames[2], stateless).edges)[2:-2, 22:26]
        assert not ref.any()  # stateless: the faded edge is gone

    def test_seed_strength_decays_and_expires(self):
        from repro.core.nms import TEMPORAL_FLOOR, temporal_seeds
        strength = jnp.full((4, 4), 1.0, jnp.float32)
        decay = 0.6
        alive_steps = 0
        for _ in range(10):
            seeds, strength = temporal_seeds(strength, decay)
            if not bool(np.asarray(seeds).any()):
                break
            alive_steps += 1
        # 1.0 * 0.6^k > 0.5 only for k=1 (0.6); k=2 is 0.36 < floor.
        assert alive_steps == 1
        assert TEMPORAL_FLOOR == 0.5

    def test_temporal_state_updates_even_when_all_skipped(self):
        """The epilogue runs every frame: on a fully-static stream the seed
        strengths still decay, so a stale seed eventually expires."""
        cfg = EdgeConfig(nms=True, temporal=True, decay=0.8, backend="xla",
                         block_h=8, block_w=8)
        f = _frame(seed=61)
        state = None
        seeds = []
        for _ in range(3):
            _, state = edge_detect_stream(f, cfg, state)
            seeds.append(np.asarray(state.seed))
        # strengths at non-edge pixels strictly decay across static frames
        quiet = seeds[0] < 0.5
        assert quiet.any()
        assert (seeds[2][quiet] <= seeds[1][quiet]).all()


# ---------------------------------------------------------------- engine --

def _list_source(frames):
    return [np.asarray(f) for f in frames]


class TestStreamEngine:
    def test_static_engine_acceptance(self):
        """The ISSUE acceptance criterion, end to end: static N-frame
        stream, >90% of tiles skipped after frame 1, outputs bit-identical
        to full recompute."""
        cfg = EdgeConfig(nms=True, hysteresis=True, backend="xla",
                         block_h=8, block_w=8)
        f = _frame(seed=71)
        eng = StreamEngine(cfg, collect=True)
        eng.submit(StreamRequest(sid=0, frames=_list_source([f] * 6)))
        st = eng.run()[0]
        assert st.frames == 6
        assert st.skip_rate > 0.90
        assert st.tiles_per_frame > 10
        ref = edge_detect(f, cfg)
        for out in st.outputs:
            np.testing.assert_array_equal(out["magnitude"],
                                          np.asarray(ref.magnitude))
            np.testing.assert_array_equal(out["edges"], np.asarray(ref.edges))

    def test_engine_outputs_equal_solo_runs(self):
        """Batched neighbors never corrupt a slot: every stream's outputs
        equal the same stream served alone."""
        cfg = EdgeConfig(nms=True, hysteresis=True, backend="xla",
                         block_h=16, block_w=16)
        streams = {
            0: [_frame(seed=80 + t) for t in range(4)],          # moving
            1: [_frame(seed=90)] * 4,                            # static
            2: [_frame(h=56, w=40, seed=95 + t) for t in range(3)],  # ragged
        }
        eng = StreamEngine(cfg, collect=True)
        for sid, fs in streams.items():
            eng.submit(StreamRequest(sid=sid, frames=_list_source(fs)))
        stats = eng.run()
        for sid, fs in streams.items():
            solo = StreamEngine(cfg, collect=True)
            solo.submit(StreamRequest(sid=0, frames=_list_source(fs)))
            solo_st = solo.run()[0]
            assert stats[sid].frames == len(fs)
            for got, want in zip(stats[sid].outputs, solo_st.outputs):
                np.testing.assert_array_equal(got["magnitude"],
                                              want["magnitude"])
                np.testing.assert_array_equal(got["edges"], want["edges"])

    def test_mid_run_join_and_leave(self):
        """A stream admitted after others retire lands in a freed slot and
        is served from a clean state (no inherited neighbor cache)."""
        cfg = EdgeConfig(backend="xla", block_h=16, block_w=16)
        short = [_frame(seed=101)] * 2
        late = [_frame(seed=102 + t) for t in range(3)]
        eng = StreamEngine(cfg, max_streams=1, collect=True)
        eng.submit(StreamRequest(sid=0, frames=_list_source(short)))
        eng.submit(StreamRequest(sid=1, frames=_list_source(late)))
        stats = eng.run()
        assert stats[0].frames == 2 and stats[1].frames == 3
        # late stream frame 0 recomputes everything: nothing inherited
        assert stats[1].outputs[0]["skipped"] == 0
        for t, f in enumerate(late):
            ref = edge_detect(f, cfg)
            np.testing.assert_array_equal(stats[1].outputs[t]["magnitude"],
                                          np.asarray(ref.magnitude))

    def test_fps_interleaving_deterministic(self):
        cfg = EdgeConfig(backend="xla")
        eng = StreamEngine(cfg)
        eng.submit(StreamRequest(sid=0, frames=_list_source(
            [_frame(seed=111)] * 4), fps=30))
        eng.submit(StreamRequest(sid=1, frames=_list_source(
            [_frame(seed=112)] * 2), fps=15))
        stats = eng.run()
        assert stats[0].frames == 4 and stats[1].frames == 2

    def test_temporal_decay0_through_engine(self):
        cfg = EdgeConfig(nms=True, temporal=True, decay=0.0, backend="xla")
        fs = [_frame(seed=120 + t) for t in range(3)]
        eng = StreamEngine(cfg, collect=True)
        eng.submit(StreamRequest(sid=0, frames=_list_source(fs)))
        st = eng.run()[0]
        ref_cfg = cfg.replace(temporal=False, hysteresis=True)
        for t, f in enumerate(fs):
            ref = edge_detect(f, ref_cfg)
            np.testing.assert_array_equal(st.outputs[t]["edges"],
                                          np.asarray(ref.edges))

    def test_frame_shape_change_quarantined(self):
        """A mid-stream shape change is a corrupted frame, not a fatal
        error: the frame is quarantined against the stream's pinned
        contract and the stream keeps serving."""
        cfg = EdgeConfig(backend="xla")
        eng = StreamEngine(cfg, collect=True)
        fs = [_frame(seed=130), _frame(h=24, w=24, seed=131),
              _frame(seed=132)]
        eng.submit(StreamRequest(sid=0, frames=_list_source(fs)))
        st = eng.run()[0]
        assert st.frames == 2 and st.quarantined == 1 and st.submitted == 3
        assert eng.health.unaccounted == 0
        q = [o for o in eng.outcomes if o.kind == "quarantined"]
        assert len(q) == 1 and "shape changed" in q[0].detail
        for out, i in zip(st.outputs, (0, 2)):   # 1 was dropped
            ref = edge_detect(fs[i], cfg)
            np.testing.assert_array_equal(out["magnitude"],
                                          np.asarray(ref.magnitude))

    def test_bad_fps_rejected(self):
        with pytest.raises(ValueError, match="fps"):
            StreamRequest(sid=0, frames=[], fps=0)

    def test_timing_split_recorded(self):
        cfg = EdgeConfig(backend="xla")
        eng = StreamEngine(cfg)
        eng.submit(StreamRequest(sid=0, frames=_list_source(
            [_frame(seed=140)] * 3)))
        st = eng.run()[0]
        assert len(st.transfer_ms) == 3 and len(st.compute_ms) == 3
        assert all(x >= 0 for x in st.transfer_ms + st.compute_ms)

    def test_overload_submit_beyond_capacity_all_drain(self):
        """More streams than slots: the queue holds the overflow and every
        stream is admitted, served completely, and accounted as slots
        free up."""
        cfg = EdgeConfig(backend="xla", block_h=16, block_w=16)
        n_streams, n_frames = 6, 2
        eng = StreamEngine(cfg, max_streams=2)
        for sid in range(n_streams):
            eng.submit(StreamRequest(sid=sid, frames=_list_source(
                [_frame(seed=200 + sid)] * n_frames)))
        stats = eng.run()
        assert sorted(stats) == list(range(n_streams))
        assert all(st.frames == n_frames for st in stats.values())
        assert eng.health.submitted == n_streams * n_frames
        assert eng.health.unaccounted == 0
        assert eng.health.counts["served"] == n_streams * n_frames

    def test_broken_source_is_isolated(self):
        """A source iterator raising mid-run retires its own stream (error
        recorded on the health ledger) without disturbing neighbors or the
        accounting invariant."""
        cfg = EdgeConfig(backend="xla")

        def broken():
            yield _frame(seed=210)
            raise RuntimeError("camera unplugged")

        good = [_frame(seed=211 + t) for t in range(3)]
        eng = StreamEngine(cfg, collect=True)
        eng.submit(StreamRequest(sid=0, frames=broken()))
        eng.submit(StreamRequest(sid=1, frames=_list_source(good)))
        stats = eng.run()
        assert stats[0].frames == 1          # served what arrived
        assert stats[1].frames == 3          # neighbor unaffected
        assert eng.health.unaccounted == 0
        assert any("camera unplugged" in e for e in eng.health.errors)
        for t, f in enumerate(good):
            ref = edge_detect(f, cfg)
            np.testing.assert_array_equal(stats[1].outputs[t]["magnitude"],
                                          np.asarray(ref.magnitude))

    def test_deadline_shedding_accounts_on_stream_stats(self):
        """Sustained pressure (injected 50ms lag vs a 5ms deadline) sheds
        frames; the per-stream stats keep the submitted = frames + shed +
        quarantined invariant."""
        cfg = EdgeConfig(backend="xla")
        n = 10
        plan = FaultPlan([Straggler(host="s0", delay_ms=50.0)])
        eng = StreamEngine(
            cfg, chaos=plan,
            guard=GuardPolicy(deadline_ms=5.0, warm_frames=1),
        )
        eng.submit(StreamRequest(sid=0, frames=_list_source(
            [_frame(seed=220)] * n)))
        st = eng.run()[0]
        assert st.shed >= 1
        assert st.submitted == n
        assert st.submitted == st.frames + st.shed + st.quarantined
        assert eng.health.deadline_violations >= 3
        assert eng.health.unaccounted == 0

    @slow_host
    def test_cached_steps_are_cheaper(self):
        """Latency-sensitive: on a fast host, fully-cached steady-state
        steps must beat the cold full-recompute step. Counters above give
        the structural version of this on any host."""
        cfg = EdgeConfig(nms=True, hysteresis=True, backend="xla")
        f = _frame(h=128, w=128, seed=150)
        eng = StreamEngine(cfg)
        eng.submit(StreamRequest(sid=0, frames=_list_source([f] * 10)))
        st = eng.run()[0]
        assert st.cached_steps >= 8
        steady = st.compute_ms[3:]
        assert np.median(steady) < st.compute_ms[0]


# ------------------------------------------------------- batched state --

BATCH_CFG = EdgeConfig(nms=True, hysteresis=True, backend="xla",
                       block_h=16, block_w=16)


def _solo_outputs(fs, fps=30.0):
    solo = StreamEngine(BATCH_CFG, collect=True)
    solo.submit(StreamRequest(sid=0, frames=_list_source(fs), fps=fps))
    return solo.run()[0].outputs


def _assert_equal_solo(stats, streams, fps=None):
    for sid, fs in streams.items():
        want = _solo_outputs(fs, (fps or {}).get(sid, 30.0))
        got = stats[sid].outputs
        assert len(got) == len(want) == len(fs)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g["magnitude"], w["magnitude"])
            np.testing.assert_array_equal(g["edges"], w["edges"])


def _run_counting_gathers(eng):
    """Drive ``eng`` to the end. Returns, per serving step, the groups it
    served (tuples of sids in row order) and the sids whose
    ``state_gathers`` rose in it."""
    groups, gathers = [], []
    serve = eng._serve_group

    def recording(members):
        groups[-1].append(tuple(eng.slots[i].req.sid for i in members))
        serve(members)

    eng._serve_group = recording

    def counts():
        stats = [s.stats for s in eng.slots if s is not None] + eng.finished
        return {st.sid: st.state_gathers for st in stats}

    before = counts()
    while True:
        groups.append([])
        if not eng.step():
            groups.pop()
            break
        after = counts()
        gathers.append({sid for sid, n in after.items()
                        if n != before.get(sid, 0)})
        before = after
    return groups, gathers


def _moving(seed, n, h=40, w=48):
    return [_frame(h=h, w=w, seed=seed + t) for t in range(n)]


def _recording_step(eng):
    """Wrap ``eng._jit_step``; returns the list it appends ``(state
    passed, result, state returned)`` to at each call."""
    calls = []
    step = eng._jit_step

    def call(frames, cfg, state, **kw):
        result, new_state = step(frames, cfg, state, **kw)
        calls.append((state, result, new_state))
        return result, new_state

    eng._jit_step = call
    return calls


class TestBatchedState:
    def test_genlocked_group_passes_state_whole(self):
        """Four genlocked streams: from the second step on, the step call
        gets the very state object the previous call returned (no slice,
        no concat), across masked and cached serves alike."""
        # frames 0-2 move, 3-5 repeat frame 2: the last serves keep the
        # cached maps (no kernel launch)
        streams = {sid: [_frame(seed=400 + 10 * sid + min(t, 2))
                         for t in range(6)] for sid in range(4)}
        eng = StreamEngine(BATCH_CFG, collect=True)
        calls = _recording_step(eng)
        for sid, fs in streams.items():
            eng.submit(StreamRequest(sid=sid, frames=_list_source(fs)))
        stats = eng.run()
        assert len(calls) == 6
        for (_, _, returned), (passed, _, _) in zip(calls, calls[1:]):
            assert passed is returned
        assert all(st.cached_steps == 3 for st in stats.values())
        assert all(st.state_gathers == 0 for st in stats.values())
        assert all(st.frames == 6 for st in stats.values())
        _assert_equal_solo(stats, streams)

    @pytest.mark.parametrize("case", ["join", "retire", "fps", "straggler"])
    def test_membership_change_regathers(self, case):
        """Whenever a group's members differ from the batch that holds
        their states, the rows are regathered: on exactly those steps,
        with outputs still equal to each stream served alone and every
        frame accounted for."""
        kw, fps = {}, {}
        if case == "join":
            # sid 0 leaves after two frames; sid 2 waits in the queue,
            # joins alone (cold), then shares a group with sid 1
            streams = {0: _moving(500, 2), 1: _moving(510, 5),
                       2: _moving(520, 3)}
            kw = dict(max_streams=2)
            expected = [set(), set(), set(), {1, 2}, set(), {1}]
        elif case == "retire":
            streams = {0: _moving(530, 3), 1: _moving(540, 5),
                       2: _moving(550, 5)}
            expected = [set(), set(), set(), {1, 2}, set()]
        elif case == "fps":
            # sid 1 at 15 fps is due every other step of its 30 fps
            # group mates: every warm step serves another subset
            streams = {0: _moving(560, 6), 1: _moving(570, 3),
                       2: _moving(580, 6)}
            fps = {1: 15.0}
            expected = [set(), {0, 2}, {0, 1, 2}, {0, 2}, {0, 1, 2},
                        {0, 2}]
        else:
            # sid 1 lags 100 ms a frame until it is struck out into a
            # solo group; shedding is held off to keep every frame
            streams = {sid: _moving(590 + 20 * sid, 12) for sid in range(3)}
            kw = dict(chaos=FaultPlan.parse("slow@s1:100"),
                      guard=GuardPolicy(shed_after=100))
            expected = None
        eng = StreamEngine(BATCH_CFG, collect=True, **kw)
        for sid, fs in streams.items():
            eng.submit(StreamRequest(sid=sid, frames=_list_source(fs),
                                     fps=fps.get(sid, 30.0)))
        groups, gathers = _run_counting_gathers(eng)
        if expected is None:
            solo = [j for j, gs in enumerate(groups) if (1,) in gs]
            assert solo, "the straggler was never excluded"
            k = solo[0]
            assert groups[k] == [(0, 2), (1,)]
            expected = [set()] * len(groups)
            expected[k] = {0, 1, 2}
        assert gathers == expected, groups
        stats = {st.sid: st for st in eng.finished}
        for sid, st in stats.items():
            assert st.state_gathers == sum(sid in g for g in expected)
        c = eng.health.counts
        assert (c["served"] + c["retried"] + c["degraded"] + c["shed"]
                + c["quarantined"]) == eng.health.submitted
        assert eng.health.submitted == sum(map(len, streams.values()))
        _assert_equal_solo(stats, streams, fps)


# ------------------------------------------------ on-device delta choice --

class TestDeviceDeltaChoice:
    """A group serve is one jitted call that chooses, on the device,
    between the cached maps and the masked compute."""

    @pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
    def test_static_group_equals_cached_step(self, backend):
        """Two streams whose frames repeat: each warm serve gives the
        outputs and next state of ``edge_stream_cached`` on the state it
        was given, bit for bit, and counts as a cached step."""
        cfg = EdgeConfig(nms=True, hysteresis=True, temporal=True,
                         decay=0.9, backend=backend, block_h=16, block_w=16)
        eng = StreamEngine(cfg)
        calls = _recording_step(eng)
        for sid in range(2):
            eng.submit(StreamRequest(sid=sid, frames=_list_source(
                [_frame(seed=600 + sid)] * 3)))
        stats = eng.run()
        assert len(calls) == 3
        for passed, result, returned in calls[1:]:
            want, want_state = dispatch.edge_stream_cached(
                eng.config, passed, layout="NHW")
            for field in ("magnitude", "edges", "skipped"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(result, field)),
                    np.asarray(getattr(want, field)))
            got_leaves, got_aux = returned.tree_flatten()
            want_leaves, want_aux = want_state.tree_flatten()
            assert got_aux == want_aux
            for g, w in zip(got_leaves, want_leaves):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert all(st.cached_steps == 2 for st in stats.values())

    @pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
    def test_mixed_group_matches_recompute(self, backend):
        """One static and one moving stream in one group: the group runs
        the masked compute (not a cached step), the static member still
        skips all its tiles, and every frame equals stateless recompute."""
        cfg = EdgeConfig(nms=True, hysteresis=True, backend=backend,
                         block_h=16, block_w=16)
        streams = {0: [_frame(seed=610)] * 4, 1: _moving(620, 4)}
        eng = StreamEngine(cfg, collect=True)
        for sid, fs in streams.items():
            eng.submit(StreamRequest(sid=sid, frames=_list_source(fs)))
        stats = eng.run()
        for sid, fs in streams.items():
            st = stats[sid]
            assert st.frames == 4 and st.cached_steps == 0
            for t, (f, out) in enumerate(zip(fs, st.outputs)):
                ref = edge_detect(f, cfg)
                np.testing.assert_array_equal(out["magnitude"],
                                              np.asarray(ref.magnitude))
                np.testing.assert_array_equal(out["edges"],
                                              np.asarray(ref.edges))
                if t:
                    all_skipped = out["skipped"] == st.tiles_per_frame
                    assert all_skipped == (sid == 0)

    @pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
    def test_static_serve_launches_no_kernel(self, monkeypatch, backend):
        """The frame compute runs on the cold serve and on a serve whose
        frames changed, and not at all on a static serve: a callback traced
        into the compute counts its runs on the device."""
        runs, traced = [], []

        def counted(compute):
            def call(*args, **kw):
                traced.append(1)
                jax.debug.callback(lambda: runs.append(1))
                return compute(*args, **kw)
            return call

        if backend == "xla":
            make = dispatch._backend_compute
            monkeypatch.setattr(dispatch, "_backend_compute",
                                lambda *a, **kw: counted(make(*a, **kw)))
        else:
            monkeypatch.setattr(dispatch.ekern, "edge_stream_pallas",
                                counted(dispatch.ekern.edge_stream_pallas))
        cfg = EdgeConfig(nms=True, hysteresis=True, backend=backend,
                         block_h=16, block_w=16)
        # a frame size no other test traces, so the step is traced afresh
        # with the counter in it
        f0, f1 = (_frame(h=72, w=88, seed=750 + t) for t in range(2))
        eng = StreamEngine(cfg)
        eng.submit(StreamRequest(sid=0, frames=_list_source([f0, f0, f1])))
        seen = []
        for _ in range(3):
            assert eng.step()
            jax.effects_barrier()
            seen.append(len(runs))
        assert traced
        assert seen == [1, 1, 2]
        assert eng.run()[0].cached_steps == 1

    @pytest.mark.parametrize("moving", [False, True])
    def test_one_call_and_no_host_read_per_serve(self, monkeypatch,
                                                 moving):
        """A warm group serve makes exactly one jitted call and reads
        nothing back from the device before that call returns."""
        eng = StreamEngine(BATCH_CFG)
        for sid in range(2):
            fs = (_moving(700 + 10 * sid, 3) if moving
                  else [_frame(seed=700 + sid)] * 3)
            eng.submit(StreamRequest(sid=sid, frames=_list_source(fs)))
        assert eng.step()                       # cold serve: compiles
        events = []
        step, get = eng._jit_step, jax.device_get

        def call(*args, **kw):
            events.append("call")
            out = step(*args, **kw)
            events.append("returned")
            return out

        def device_get(x):
            events.append("device_get")
            return get(x)

        eng._jit_step = call
        monkeypatch.setattr(jax, "device_get", device_get)
        assert eng.step()
        assert events == ["call", "returned"]
        cached = [eng.slots[i].stats.cached_steps for i in range(2)]
        assert cached == ([0, 0] if moving else [1, 1])


# ----------------------------------------------------------------- spans --

def _program_spans(log_dir):
    """``(name, start_ns, end_ns, metadata)`` of the ``repro.*`` host
    events in the trace written under ``log_dir``."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    start = int(ev.start_ns)
                    out.append((ev.name, start, start + int(ev.duration_ns),
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced_engine(tmp_path_factory):
    """Two streams of four frames under the profiler, the second group
    serve failing once (so the guard retries it after a backoff), and the
    program's spans read back from the trace."""
    cfg = EdgeConfig(nms=True, hysteresis=True, backend="xla",
                     block_h=16, block_w=16)
    eng = StreamEngine(cfg, chaos=FaultPlan.parse("fail@step:1x1"))
    for sid in range(2):
        eng.submit(StreamRequest(sid=sid, frames=_list_source(
            [_frame(h=32, w=48, seed=300 + 4 * sid + t) for t in range(4)])))
    log_dir = str(tmp_path_factory.mktemp("stream-trace"))
    jax.profiler.start_trace(log_dir)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    return eng, _program_spans(log_dir)


class TestStreamSpans:
    PHASES = ("intake", "stack", "h2d", "concat", "compute", "split",
              "account", "police")

    def test_phase_spans_fall_inside_a_step(self, traced_engine):
        _, spans = traced_engine
        steps = [(s, e) for n, s, e, _ in spans
                 if n == "repro.stream.step"]
        phases = [(n, s, e) for n, s, e, _ in spans
                  if n.startswith("repro.") and n != "repro.stream.step"]
        names = {n for n, _, _ in phases}
        assert {f"repro.stream.{p}" for p in self.PHASES} <= names
        for n, s, e in phases:
            assert any(a <= s and e <= b for a, b in steps), n

    def test_step_carries_its_metadata(self, traced_engine):
        eng, spans = traced_engine
        meta = sorted((m for n, _, _, m in spans
                       if n == "repro.stream.step"), key=lambda m: m["step"])
        # four serving steps, then the one that finds the engine drained
        assert [m["step"] for m in meta] == list(range(eng.engine_step + 1))
        served = [m for m in meta if "frames" in m]
        assert len(served) == 4
        assert all(m["frames"] == 2 and m["groups"] == 1 for m in served)

    def test_backoff_only_where_a_retry_happened(self, traced_engine):
        eng, spans = traced_engine
        retried = {o.step for o in eng.outcomes if o.kind == "retried"}
        assert retried == {1}
        steps = {m["step"]: (s, e) for n, s, e, m in spans
                 if n == "repro.stream.step"}
        backoffs = [(s, e) for n, s, e, _ in spans
                    if n == "repro.guard.backoff"]
        assert len(backoffs) == 1
        a, b = steps[1]
        assert a <= backoffs[0][0] and backoffs[0][1] <= b
        attempts = [(s, e) for n, s, e, _ in spans
                    if n == "repro.guard.attempt" and a <= s and e <= b]
        assert len(attempts) == 2

    def test_concat_marks_whole_state(self, traced_engine):
        """The cold first serve builds a zero state; every later serve of
        the same two streams passes the batch whole."""
        eng, spans = traced_engine
        whole = [m["whole"] for _, _, _, m in sorted(
            (sp for sp in spans if sp[0] == "repro.stream.concat"),
            key=lambda sp: sp[1])]
        assert whole == [0, 1, 1, 1]
        assert all(st.state_gathers == 0 for st in eng.finished)
