"""Drift stream: determinism, scenario structure, drift effects."""
import numpy as np
import pytest

from repro.data.stream import DriftStream, SCENARIOS, Segment, scenario
from repro.data.tokens import TokenPipeline


def test_stream_deterministic():
    s1 = DriftStream(scenario("S1", 4), seed=3)
    s2 = DriftStream(scenario("S1", 4), seed=3)
    x1, y1 = s1.frames(10.0, 12.0)
    x2, y2 = s2.frames(10.0, 12.0)
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_array_equal(y1, y2)


def test_different_seeds_differ():
    x1, _ = DriftStream(scenario("S1", 2), seed=0).frames(0, 1)
    x2, _ = DriftStream(scenario("S1", 2), seed=1).frames(0, 1)
    assert not np.allclose(x1, x2)


def test_all_scenarios_build():
    for name in SCENARIOS:
        segs = scenario(name)
        assert len(segs) == 20
        stream = DriftStream(segs)
        assert stream.duration == pytest.approx(1200.0)  # 20 min (§VII-A)


def test_scenario_s1_flips_label_dist_only():
    segs = scenario("S1", 4)
    assert [s.label_dist for s in segs] == ["traffic", "all"] * 2
    assert len({s.time_of_day for s in segs}) == 1
    assert len({s.location for s in segs}) == 1


def test_extreme_scenario_flips_all_axes():
    segs = scenario("ES1", 16)
    assert len({s.label_dist for s in segs}) == 2
    assert len({s.time_of_day for s in segs}) == 2
    assert len({s.location for s in segs}) == 2
    assert len({s.weather for s in segs}) == 2


def test_traffic_segments_restrict_classes():
    stream = DriftStream([Segment(label_dist="traffic")], seed=0)
    _, y = stream.frames(0, 30)
    assert set(np.unique(y)) <= {0, 1, 2, 3, 4}
    stream2 = DriftStream([Segment(label_dist="all")], seed=0)
    _, y2 = stream2.frames(0, 30)
    assert len(np.unique(y2)) > 5


def test_night_darkens_frames():
    day = DriftStream([Segment(time_of_day="day")], seed=5)
    night = DriftStream([Segment(time_of_day="night")], seed=5)
    xd, _ = day.frames(0, 5)
    xn, _ = night.frames(0, 5)
    assert np.mean(np.abs(xn[..., :2])) < np.mean(np.abs(xd[..., :2]))


def test_max_frames_subsample():
    stream = DriftStream(scenario("S2", 2))
    x, y = stream.frames(0, 10, max_frames=7)
    assert len(x) == 7 and len(y) == 7


def test_token_pipeline_deterministic_and_learnable():
    pipe = TokenPipeline(vocab_size=64, seq_len=32, global_batch=4, seed=1)
    b1, b2 = pipe.batch(5), pipe.batch(5)
    np.testing.assert_array_equal(b1["inputs"], b2["inputs"])
    assert b1["inputs"].shape == (4, 32)
    # bigram structure: every (tok -> next) pair is one of 4 successors
    succ = pipe._succ
    ok = succ[b1["inputs"].reshape(-1)] == b1["labels"].reshape(-1)[:, None]
    assert ok.any(axis=-1).all()


def test_token_pipeline_host_sharding():
    full = TokenPipeline(64, 16, 8, seed=2)
    h0 = TokenPipeline(64, 16, 8, seed=2, num_hosts=2, host_index=0)
    h1 = TokenPipeline(64, 16, 8, seed=2, num_hosts=2, host_index=1)
    assert h0.local_batch == 4 and h1.local_batch == 4
    assert not np.array_equal(h0.batch(0)["inputs"], h1.batch(0)["inputs"])


# ----------------------------------------------------- pooled rendering --
# One segment per branch of ``_frame`` (label distribution, time of day,
# location, each weather), and a stream whose window crosses a boundary.
_BRANCHES = {
    "traffic-day-city-clear": [Segment()],
    "all-labels": [Segment(label_dist="all")],
    "night": [Segment(time_of_day="night")],
    "highway": [Segment(location="highway")],
    "overcast": [Segment(weather="overcast")],
    "rainy": [Segment(weather="rainy", time_of_day="night")],
    "snowy": [Segment(weather="snowy", location="highway",
                      label_dist="all")],
    "boundary": [Segment(duration_s=0.5),
                 Segment(duration_s=60.0, label_dist="all",
                         time_of_day="night", weather="rainy")],
}


@pytest.fixture
def render_pool_of_four(monkeypatch):
    """A fresh render pool of four threads, whatever the machine's cores,
    so that a window above the threshold fans out on every machine."""
    import repro.data.stream as stream_mod

    monkeypatch.setattr(stream_mod, "_render_threads", lambda: 4)
    monkeypatch.setattr(stream_mod, "_pool", None)
    yield stream_mod
    if stream_mod._pool is not None:
        stream_mod._pool.shutdown(wait=True)


def _serial_frames(stream, times):
    """The per-frame definition: ``_frame`` at each time, stacked."""
    frames = [stream._frame(float(t)) for t in times]
    return (np.stack([x for x, _ in frames]),
            np.asarray([y for _, y in frames], np.int32))


def _assert_same_bytes(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("max_frames", [0, 7])
@pytest.mark.parametrize("size", ["serial-32px", "pooled-224px"])
@pytest.mark.parametrize("branch", sorted(_BRANCHES))
def test_frames_equal_per_frame_definition(render_pool_of_four, branch, size,
                                           max_frames):
    """A window is byte for byte the stack of ``_frame`` over its times,
    rendered on the calling thread (32 px, under the fan-out threshold) or
    in chunks on the pool (224 px, 24 frames or 7 of them)."""
    img = 32 if size == "serial-32px" else 224
    stream = DriftStream(_BRANCHES[branch], seed=11, img=img)
    t0, t1 = 0.2, 1.0  # 24 frames at 30 fps; across the boundary case's edge
    got = stream.frames(t0, t1, max_frames=max_frames)
    _assert_same_bytes(got, _serial_frames(
        stream, stream.frame_times(t0, t1, max_frames)))
    n = len(got[0])
    pooled = stream.frames_pooled
    assert pooled == (n if size == "pooled-224px" else 0)
    assert pooled + stream.frames_serial == n


def test_single_frame_renders_on_the_calling_thread(render_pool_of_four):
    stream = DriftStream([Segment(weather="snowy")], seed=2, img=224)
    got = stream.frames(3.0, 3.0)  # one frame
    _assert_same_bytes(got, _serial_frames(stream, [3.0]))
    assert (stream.frames_pooled, stream.frames_serial) == (0, 1)


@pytest.mark.parametrize("img", [32, 224])
def test_sample_dataset_unchanged_for_a_fixed_rng(render_pool_of_four, img):
    """``sample_dataset`` draws the same times from the RNG and renders the
    same frames as the per-frame loop it replaced."""
    segs = [Segment(), Segment(label_dist="all", weather="overcast")]
    stream = DriftStream(segs, seed=4, img=img)
    got = stream.sample_dataset(16, np.random.default_rng(7),
                                segments=segs[1:])
    sub = DriftStream(segs[1:], seed=4, img=img)
    times = np.random.default_rng(7).uniform(0, sub.duration, size=16)
    _assert_same_bytes(got, _serial_frames(sub, times))


def test_streams_share_one_pool_under_concurrent_callers(
        render_pool_of_four):
    """Two speculative pipelines (their prefetch workers and their inline
    misses) and one inline caller render through the one pool at once:
    each gets exactly its serial frames, and the pool has no more threads
    than its size, however many streams use it."""
    import threading

    from repro.data.pipeline import FramePipeline

    stream_mod = render_pool_of_four
    layout = ((0.0, 0.4, 0), (0.4, 1.1, 9))  # 12 and 9 frames at 224 px
    starts = (10.0, 12.0, 14.0)
    before = {t.ident for t in threading.enumerate()}
    streams = [DriftStream(scenario("S4", 2), seed=s, img=224)
               for s in (1, 2, 3)]
    refs = [DriftStream(scenario("S4", 2), seed=s, img=224)
            for s in (1, 2, 3)]
    results, errors = {}, []

    def drive_pipeline(i):
        pipe = FramePipeline(streams[i], speculative=True)
        try:
            for s in starts:
                pipe.begin_phase(s)
                for dt0, dt1, mf in layout:
                    results[(i, s, dt0)] = pipe.frames(s + dt0, s + dt1,
                                                       max_frames=mf)
            assert pipe.hits > 0  # the prefetch worker rendered some
        except BaseException as exc:
            errors.append(exc)
        finally:
            pipe.close()

    def drive_inline():
        try:
            for s in starts:
                for dt0, dt1, mf in layout:
                    results[(2, s, dt0)] = streams[2].frames(
                        s + dt0, s + dt1, max_frames=mf)
        except BaseException as exc:
            errors.append(exc)

    threads = [threading.Thread(target=drive_pipeline, args=(0,)),
               threading.Thread(target=drive_pipeline, args=(1,)),
               threading.Thread(target=drive_inline)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors, errors
    for (i, s, dt0), got in results.items():
        dt1, mf = next((b, m) for a, b, m in layout if a == dt0)
        want = _serial_frames(refs[i], refs[i].frame_times(s + dt0, s + dt1,
                                                          mf))
        _assert_same_bytes(got, want)
    assert len(results) == 3 * len(starts) * len(layout)
    render = [t for t in threading.enumerate() if t.ident not in before
              and t.name.startswith("dacapo-render")]
    assert 0 < len(render) <= 4
    assert stream_mod._render_pool() is stream_mod._pool
    for st in streams:
        assert st.frames_serial == 0 and st.frames_pooled > 0


def test_frame_counters_add_up_under_many_threads(render_pool_of_four):
    """More callers than cores on one stream, with a short switch interval:
    no count is lost, pooled and serial together make every frame."""
    import sys
    import threading

    stream = DriftStream([Segment(location="highway")], seed=8, img=224)
    small = DriftStream([Segment()], seed=8, img=32)
    n_threads, calls = 12, 4

    def call(i):
        for c in range(calls):
            t0 = 0.5 * (i * calls + c)
            stream.frames(t0, t0 + 2 / stream.fps)  # 2 frames: pooled
            small.frames(t0, t0 + 3 / small.fps)  # 3 frames: serial

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert (stream.frames_pooled, stream.frames_serial) == (
        n_threads * calls * 2, 0)
    assert (small.frames_pooled, small.frames_serial) == (
        0, n_threads * calls * 3)
