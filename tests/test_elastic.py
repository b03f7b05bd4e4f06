"""Elastic membership + fault-injection (SURVEY §5.3; reference
fleet/elastic.py ElasticManager + launcher relaunch-on-scale-event).

Drives the file-backed membership protocol directly: heartbeats define
the member set, stale beats drop out, membership changes trip the
relaunch trigger, and a crashing worker under the launch() supervision
loop gets relaunched and completes on its second life.
"""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                  ELASTIC_EXIT_CODE)


def test_membership_join_leave(tmp_path):
    srv = 'file://' + str(tmp_path)
    a = ElasticManager(srv, 'job1', np=2, host='hostA', ttl=0.5)
    b = ElasticManager(srv, 'job1', np=2, host='hostB', ttl=0.5)
    a.register()
    b.register()
    try:
        assert set(a.hosts()) == {'hostA', 'hostB'}
        a.membership_changed()          # prime the view
        assert not a.membership_changed()
        # B dies: stop its heartbeat, let the lease lapse
        b.unregister()
        deadline = time.time() + 5
        while time.time() < deadline and 'hostB' in a.hosts():
            time.sleep(0.1)
        assert set(a.hosts()) == {'hostA'}
        assert a.membership_changed()   # scale event visible
    finally:
        a.unregister()


def test_stale_heartbeat_expires(tmp_path):
    srv = 'file://' + str(tmp_path)
    a = ElasticManager(srv, 'job2', np=1, host='only', ttl=0.3)
    a.register()
    try:
        assert a.hosts() == ['only']
    finally:
        a.unregister()
    deadline = time.time() + 5
    while time.time() < deadline and a.hosts():
        time.sleep(0.1)
    assert a.hosts() == []


def test_reregister_after_unregister_keeps_lease_fresh(tmp_path):
    """A node that leaves and rejoins must get a LIVE heartbeat thread
    again — if register() saw the dead thread and declined to arm a new
    one, the lease would silently lapse after ttl."""
    srv = 'file://' + str(tmp_path)
    m = ElasticManager(srv, 'rejoin', np=1, host='only', ttl=0.6)
    m.register()
    first = m._hb_thread
    m.unregister()
    assert not first.is_alive()
    assert m.hosts() == []

    m.register()
    try:
        assert m._hb_thread is not first
        assert m._hb_thread.is_alive()
        # outlive the ttl: only a working heartbeat thread keeps the
        # lease fresh past this point
        time.sleep(m.ttl * 2)
        assert m.hosts() == ['only']
    finally:
        m.unregister()
    assert m.hosts() == []


class _StuckStop:
    """Stop-event stand-in for the retirement race: the flag reads as set
    but the loop thread has not exited yet (it is still inside its
    ttl/3 wait). set() releases the thread, as the real Event would."""

    def __init__(self):
        self._release = threading.Event()

    def is_set(self):
        return True

    def set(self):
        self._release.set()

    def wait(self, timeout=None):
        return self._release.wait(timeout)


def test_register_retires_stopping_heartbeat_thread(tmp_path):
    """register() must stop AND join a still-alive thread whose stop flag
    is set before arming a fresh one — otherwise the old loop's last
    heartbeat can land after the new thread's, or two loops beat at
    once."""
    srv = 'file://' + str(tmp_path)
    m = ElasticManager(srv, 'retire', np=1, host='only', ttl=0.5)
    m.register()
    # retire the real thread quietly, then install the stuck stand-in
    m._hb_stop.set()
    m._hb_thread.join()
    stuck = _StuckStop()
    blocker = threading.Thread(target=stuck.wait, daemon=True)
    blocker.start()
    m._hb_stop = stuck
    m._hb_thread = blocker

    m.register()
    try:
        blocker.join(timeout=5)
        assert not blocker.is_alive()       # retired: set + joined
        assert m._hb_thread is not blocker  # fresh thread armed...
        assert m._hb_thread.is_alive()
        assert not m._hb_stop.is_set()      # ...with a clear stop flag
        time.sleep(m.ttl * 1.5)
        assert m.hosts() == ['only']        # and the lease stays fresh
    finally:
        m.unregister()


def test_crash_once_worker_is_relaunched(tmp_path):
    """Fault injection through the real launcher supervision loop: the
    worker exits with ELASTIC_EXIT_CODE on its first life (simulated
    fault), the supervisor relaunches, and the second life succeeds."""
    marker = tmp_path / 'lives.txt'
    script = tmp_path / 'worker.py'
    script.write_text(
        "import os, sys\n"
        "m = %r\n"
        "lives = open(m).read().count('x') if os.path.exists(m) else 0\n"
        "open(m, 'a').write('x')\n"
        "if lives == 0:\n"
        "    sys.exit(%d)\n"           # first life: simulated fault
        "print('WORKER_OK rank', os.environ.get('PADDLE_TRAINER_ID'))\n"
        % (str(marker), ELASTIC_EXIT_CODE))

    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    proc = subprocess.run(
        [sys.executable, '-m', 'paddle_tpu.distributed.launch.main',
         '--nproc_per_node', '1',
         '--elastic_server', 'file://' + str(tmp_path / 'kv'),
         '--job_id', 'crashjob', str(script)],
        capture_output=True, text=True, env=env, timeout=180,
        cwd='/root/repo')
    lives = marker.read_text().count('x')
    assert lives == 2, (lives, proc.stdout[-500:], proc.stderr[-500:])
    assert proc.returncode == 0, proc.stderr[-500:]
