"""The traffic generators: the same seed gives the same frames, and the
camera mixes change the share of tiles they promise."""
import numpy as np
import pytest

from bench import frames

BIG = 2**31 + 12345   # seeds are larger than 32 signed bits


def test_image_requests_are_deterministic():
    a = frames.image_requests(BIG, 2, 3, 32, 48, "float32")
    b = frames.image_requests(BIG, 2, 3, 32, 48, "float32")
    c = frames.image_requests(BIG + 1, 2, 3, 32, 48, "float32")
    assert len(a) == 2 and a[0].shape == (3, 32, 48) and a[0].dtype == np.float32
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[0], a[1])
    assert a[0].min() >= 0 and a[0].max() <= 255


def test_camera_frames_are_deterministic():
    a = frames.CameraStreams(2, 40, 64, seed=BIG, motion=2, sigma=2, pool=3)
    b = frames.CameraStreams(2, 40, 64, seed=BIG, motion=2, sigma=2, pool=3)
    assert np.array_equal(a.frame(1, 7), b.frame(1, 7))
    assert not np.array_equal(a.frame(0, 7), a.frame(1, 7))
    out = np.empty((40, 64), np.uint8)
    assert np.array_equal(a.fill(out, 1, 7), a.frame(1, 7))


def test_every_seed_deals_the_same_scenes():
    # The same work for every seed: one set of scenes, in another order.
    a = frames.CameraStreams(4, 40, 64, seed=BIG, motion=2, sigma=0, pool=1)
    b = frames.CameraStreams(4, 40, 64, seed=BIG + 1, motion=2, sigma=0, pool=1)
    assert a.order != b.order and sorted(a.order) == sorted(b.order)
    for i in (0, 7):
        assert (sorted(a.frame(s, i).tobytes() for s in range(4))
                == sorted(b.frame(s, i).tobytes() for s in range(4)))


def _changed_tile_share(cams, bh=64, bw=256, steps=4):
    h, w = cams.h, cams.w
    gh, gw = -(-h // bh), -(-w // bw)
    changed = total = 0
    for s in range(len(cams.lo)):
        prev = cams.frame(s, 10)
        for i in range(11, 11 + steps):
            cur = cams.frame(s, i)
            diff = np.zeros((gh * bh, gw * bw), bool)
            diff[:h, :w] = cur != prev
            changed += diff.reshape(gh, bh, gw, bw).any(axis=(1, 3)).sum()
            total += gh * gw
            prev = cur
    return changed / total


@pytest.mark.parametrize("sigma,pool,check", [
    (0.0, 1, lambda share: 0.0 < share < 0.3),
    (2.0, 4, lambda share: share == 1.0),
], ids=["moving", "noisy"])
def test_tile_change_share_at_1080p(sigma, pool, check):
    cams = frames.CameraStreams(2, 1080, 1920, seed=BIG, motion=2.0,
                                sigma=sigma, pool=pool)
    assert check(_changed_tile_share(cams))
