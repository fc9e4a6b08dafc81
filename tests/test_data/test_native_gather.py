"""Native C++ replay gather vs the numpy reference path (sheeprl_tpu/native)."""

import numpy as np
import pytest

from sheeprl_tpu import native
from sheeprl_tpu.data.buffers import ReplayBuffer, SequentialReplayBuffer


@pytest.fixture(scope="module")
def lib_available():
    if native.load() is None:
        pytest.skip("native gather library unavailable (no toolchain?)")


def test_gather_seq_matches_numpy(lib_available):
    rng = np.random.default_rng(0)
    size, n_envs, feat = 64, 3, (5, 4)
    src = rng.integers(0, 255, (size, n_envs) + feat, dtype=np.uint8)
    n_samples, T, B = 2, 7, 4
    starts = rng.integers(0, size, n_samples * B).astype(np.int64)
    envs = rng.integers(0, n_envs, n_samples * B).astype(np.int64)

    out = native.gather_seq(src, starts, envs, n_samples, T, B)
    assert out is not None
    assert out.shape == (n_samples, T, B) + feat
    for s in range(n_samples):
        for b in range(B):
            for t in range(T):
                row = (starts[s * B + b] + t) % size
                np.testing.assert_array_equal(out[s, t, b], src[row, envs[s * B + b]])

    # start_offset shifts the whole window (used for next-obs gathers)
    out1 = native.gather_seq(src, starts, envs, n_samples, T, B, start_offset=1)
    np.testing.assert_array_equal(out1[0, 0, 0], src[(starts[0] + 1) % size, envs[0]])


def test_gather_rows_matches_numpy(lib_available):
    rng = np.random.default_rng(1)
    src = rng.standard_normal((50, 2, 6)).astype(np.float32)
    rows = rng.integers(0, 50, 33).astype(np.int64)
    envs = rng.integers(0, 2, 33).astype(np.int64)
    out = native.gather_rows(src, rows, envs)
    assert out is not None
    np.testing.assert_array_equal(out, src[rows, envs])


def test_sequential_buffer_native_vs_fallback(lib_available, monkeypatch):
    """The full SequentialReplayBuffer.sample must produce identical results with the
    native gather and the numpy fallback (same rng stream → same indices)."""
    def fill(rb):
        rng = np.random.default_rng(2)
        for step in range(90):  # > buffer size: exercises wraparound starts
            rb.add({
                "obs": rng.integers(0, 255, (1, 2, 3, 8, 8), dtype=np.uint8).astype(np.float32),
                "rewards": rng.standard_normal((1, 2, 1)).astype(np.float32),
            })

    rb_native = SequentialReplayBuffer(64, 2)
    fill(rb_native)
    rb_native.seed(7)
    native_out = rb_native.sample(batch_size=5, n_samples=3, sequence_length=9)

    rb_np = SequentialReplayBuffer(64, 2)
    fill(rb_np)
    rb_np.seed(7)
    monkeypatch.setattr(native, "gather_seq", lambda *a, **k: None)
    np_out = rb_np.sample(batch_size=5, n_samples=3, sequence_length=9)

    assert set(native_out) == set(np_out)
    for k in np_out:
        np.testing.assert_array_equal(native_out[k], np_out[k], err_msg=k)


def test_replay_buffer_native_vs_fallback(lib_available, monkeypatch):
    def fill(rb):
        rng = np.random.default_rng(3)
        for _ in range(40):
            rb.add({
                "obs": rng.standard_normal((1, 2, 4)).astype(np.float32),
                "rewards": rng.standard_normal((1, 2, 1)).astype(np.float32),
            })

    rb_native = ReplayBuffer(32, 2, obs_keys=("obs",))
    fill(rb_native)
    rb_native.seed(11)
    a = rb_native.sample(batch_size=8, n_samples=2, sample_next_obs=True)

    rb_np = ReplayBuffer(32, 2, obs_keys=("obs",))
    fill(rb_np)
    rb_np.seed(11)
    monkeypatch.setattr(native, "gather_rows", lambda *a, **k: None)
    b = rb_np.sample(batch_size=8, n_samples=2, sample_next_obs=True)

    assert set(a) == set(b)
    for k in b:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_library_name_is_derived_from_the_committed_source(lib_available, tmp_path, monkeypatch):
    """A library left on disk by another checkout or an older gather.cpp cannot
    stand in for the committed source: the file name carries the source hash."""
    import hashlib

    digest = hashlib.sha256(native._SRC.read_bytes()).hexdigest()[:16]
    assert native.library_path().name == f"_gather_{digest}.so"
    assert native.status in ("built", "loaded") and native.library_path().is_file()

    changed = tmp_path / "gather.cpp"
    changed.write_bytes(native._SRC.read_bytes() + b"\n// edited\n")
    monkeypatch.setattr(native, "_SRC", changed)
    assert native.library_path().name != f"_gather_{digest}.so"  # no library yet: it would be built
