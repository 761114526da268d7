"""The controller's health sweep on fakes (no cluster): a booted replica that
is silent for one check is taken out of routing and kept; one silent for
three checks in a row, one whose actor died and one whose check raises are
replaced."""

import threading
import time
from types import SimpleNamespace as NS

import pytest

from ray_tpu.exceptions import ActorDiedError, GetTimeoutError
from ray_tpu.serve import _controller as ctl


class _Fake:
    """A replica whose next health checks end as `script` says: a dict is
    the answer, an exception is raised by `ray_tpu.get`."""

    def __init__(self, script):
        self.script = list(script)
        self.check_health = NS(remote=lambda: self)


def _controller(monkeypatch, replicas):
    c = ctl.ServeController.__new__(ctl.ServeController)
    c._lock = threading.RLock()
    c._replicas = {"app": replicas}
    c._autoscalers = {}
    c._boot_backoff = {}
    c._version = 0
    c._ckpt_dirty = False
    c.drained = []
    c._begin_drain = lambda name, infos, grace: c.drained.extend(infos)

    def get(ref, timeout=None):
        result = ref.script.pop(0)
        if isinstance(result, BaseException):
            raise result
        return result

    monkeypatch.setattr(ctl.ray_tpu, "get", get)
    return c


def _info(script, age_s=1000.0):
    return NS(replica_id="r", actor=_Fake(script), healthy=True, booted=True,
              created_at=time.monotonic() - age_s)


ITEMS = [("app", {"config": NS(graceful_shutdown_timeout_s=1.0)})]
OK = {"healthy": True, "ongoing": 0, "shed_delta": 0}


def test_one_silent_check_unroutes_a_replica_and_keeps_it(monkeypatch):
    info = _info([GetTimeoutError("t"), OK, GetTimeoutError("t"),
                  GetTimeoutError("t"), OK])
    c = _controller(monkeypatch, [info])
    assert c._check_health_all(ITEMS) is True      # routing hears of it
    assert info.healthy is False and c.drained == []
    assert c._check_health_all(ITEMS) is True      # and of its return
    assert info.healthy is True and info.health_timeouts == 0
    for _ in range(2):      # two in a row: still one short
        c._check_health_all(ITEMS)
    assert c.drained == [] and info.health_timeouts == 2
    c._check_health_all(ITEMS)
    assert info.healthy is True and c._replicas["app"] == [info]


def test_silence_for_three_checks_in_a_row_replaces(monkeypatch):
    info = _info([GetTimeoutError("t")] * ctl.HEALTH_TIMEOUTS_TO_REPLACE)
    c = _controller(monkeypatch, [info])
    for _ in range(ctl.HEALTH_TIMEOUTS_TO_REPLACE - 1):
        c._check_health_all(ITEMS)
        assert c.drained == []
    c._check_health_all(ITEMS)
    assert c.drained == [info] and c._replicas["app"] == []


@pytest.mark.parametrize("failure", [ActorDiedError("gone"),
                                     RuntimeError("the user's check")])
def test_a_dead_actor_or_a_raising_check_is_replaced_at_once(monkeypatch,
                                                             failure):
    info = _info([failure])
    c = _controller(monkeypatch, [info])
    assert c._check_health_all(ITEMS) is True
    assert c.drained == [info] and c._replicas["app"] == []


def test_a_booting_replica_keeps_its_grace(monkeypatch):
    info = _info([GetTimeoutError("t")] * 5, age_s=5.0)
    info.booted = False
    c = _controller(monkeypatch, [info])
    for _ in range(5):
        c._check_health_all(ITEMS)
    assert c.drained == [] and info.healthy is False
