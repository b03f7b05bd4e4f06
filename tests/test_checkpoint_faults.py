"""Checkpoint-writer fault coverage (ISSUE 14 satellites 1+2).

io_save's atomic writer exposes two named crash points — 'pre_rename'
(payload still in the temp file) and 'pre_manifest' (payload renamed,
manifest sidecar missing) — and CheckpointManager.restore_latest must
fall back to the previous intact snapshot for BOTH torn states. The
AsyncCheckpointer's non-orbax fallback must honor orbax's contract:
save() returns immediately, wait_until_finished() blocks and re-raises
a writer error.
"""
import os
import threading

import numpy as np
import pytest

from paddle_tpu.distributed.checkpoint import (AsyncCheckpointer,
                                               CheckpointManager)
from paddle_tpu.framework import io_save
from paddle_tpu.testing import chaos


def _state(step):
    return {'step': step, 'w': np.full(8, step, np.float32)}


def _assert_restored(mgr, step):
    got_step, got = mgr.restore_latest()
    assert got_step == step
    np.testing.assert_array_equal(got['w'], np.full(8, step, np.float32))


@pytest.mark.parametrize('point,torn_file_present', [
    ('pre_rename', False),    # temp file only; target path untouched
    ('pre_manifest', True),   # data renamed in; manifest never written
])
def test_restore_falls_back_past_torn_save(tmp_path, point,
                                           torn_file_present):
    """A writer killed at either crash point must cost exactly one
    checkpoint interval: restore_latest lands on the previous snapshot,
    never on the torn one and never on (None, None)."""
    mgr = CheckpointManager(str(tmp_path), keep_last=3)
    mgr.save(1, _state(1))
    mgr.save(2, _state(2))
    with chaos.crash_io_save(point, path_substr='step_3') as fault:
        with pytest.raises(chaos.WriterKilled):
            mgr.save(3, _state(3))
    assert fault.fired == 1

    torn = os.path.join(str(tmp_path), 'step_3.ckpt')
    assert os.path.exists(torn) == torn_file_present
    assert not os.path.exists(io_save.manifest_path(torn))
    if torn_file_present:
        # manifest-less manager snapshot == writer died mid-commit: the
        # strict verify must refuse it even though the bytes are whole
        assert not io_save.verify_checkpoint(torn, require_manifest=True)
    _assert_restored(mgr, 2)

    # the torn state is not sticky: the next save commits normally and
    # becomes the restore target
    mgr.save(4, _state(4))
    _assert_restored(mgr, 4)


def test_keep_last_below_one_refused():
    """keep_last=0 used to prune NOTHING (steps()[:-0] == []); now it is
    a loud constructor error, as is any negative value."""
    for bad in (0, -1):
        with pytest.raises(ValueError, match='keep_last'):
            CheckpointManager('/tmp/never-created', keep_last=bad)


def test_keep_last_one_keeps_exactly_the_newest(tmp_path):
    """The smallest legal retention: after N saves only the newest
    snapshot (data + manifest sidecar, nothing else) remains."""
    mgr = CheckpointManager(str(tmp_path), keep_last=1)
    for step in range(1, 5):
        mgr.save(step, _state(step))
    assert sorted(os.listdir(str(tmp_path))) == \
        ['step_4.ckpt', 'step_4.ckpt.manifest']
    _assert_restored(mgr, 4)


def _fallback_checkpointer():
    ac = AsyncCheckpointer()
    # force the thread fallback even when orbax is importable — the
    # fallback path is what this file is proving
    ac._ocp = None
    ac._ckpt = None
    return ac


def test_async_fallback_save_returns_before_write_finishes(tmp_path,
                                                           monkeypatch):
    """Orbax contract: save() must NOT block on the write. Proven
    deterministically by gating the underlying io_save.save on an event
    the test holds closed until after save() has returned."""
    release = threading.Event()
    real_save = io_save.save

    def gated_save(obj, path, **kw):
        assert release.wait(10), 'writer never released'
        return real_save(obj, path, **kw)

    monkeypatch.setattr(io_save, 'save', gated_save)
    ac = _fallback_checkpointer()
    target = str(tmp_path / 'ckpt')
    ac.save(target, {'w': np.arange(4, dtype=np.float32)})
    # back in the caller while the writer is still parked on the event
    assert not os.path.exists(target + '.fallback.pdparams')
    release.set()
    ac.wait_until_finished()
    assert os.path.exists(target + '.fallback.pdparams')
    got = ac.restore(target)
    np.testing.assert_array_equal(got['w'],
                                  np.arange(4, dtype=np.float32))


def test_async_fallback_reraises_writer_error_on_wait(tmp_path):
    """A writer that dies in the background must surface at
    wait_until_finished(), exactly once — orbax raises there too, and a
    swallowed error would let the trainer believe the snapshot exists."""
    ac = _fallback_checkpointer()
    target = str(tmp_path / 'ckpt')
    ac.save(target, {'bad': lambda: None})      # unpicklable payload
    with pytest.raises(Exception) as exc_info:
        ac.wait_until_finished()
    # the writer's pickling failure, as Python 3.12 words it
    assert "can't get local object" in repr(exc_info.value).lower()
    # error is consumed: the checkpointer is reusable afterwards
    ac.wait_until_finished()
    ac.save(target, _state(7))
    ac.wait_until_finished()
    np.testing.assert_array_equal(ac.restore(target)['w'],
                                  np.full(8, 7, np.float32))


def test_async_fallback_restore_waits_for_inflight_save(tmp_path,
                                                        monkeypatch):
    """restore() right after save() must see the just-saved state, not
    ENOENT: it joins the in-flight writer first."""
    started = threading.Event()
    real_save = io_save.save

    def slow_save(obj, path, **kw):
        started.set()
        return real_save(obj, path, **kw)

    monkeypatch.setattr(io_save, 'save', slow_save)
    ac = _fallback_checkpointer()
    target = str(tmp_path / 'ckpt')
    ac.save(target, _state(5))
    assert started.wait(10)
    got = ac.restore(target)                    # no explicit wait
    np.testing.assert_array_equal(got['w'], np.full(8, 5, np.float32))


def test_no_leaked_io_save_faults():
    assert chaos.active_faults() == 0
