"""Event engine tests: timers, mailboxes (priority), queues, leases —
all deterministic via the virtual clock."""

import sys
import threading
import time

import pytest

from aiko_services_tpu.runtime.event import EventEngine, VirtualClock
from aiko_services_tpu.runtime.lease import Lease


def test_timer_fires_on_schedule(engine):
    fired = []
    engine.add_timer_handler(lambda: fired.append(1), period=1.0)
    engine.advance(0.9)
    assert fired == []
    engine.advance(0.2)
    assert fired == [1]
    engine.advance(2.0)
    assert fired == [1, 1, 1]


def test_timer_once_and_remove(engine):
    fired = []
    handler = lambda: fired.append("x")
    engine.add_timer_handler(handler, 1.0, once=True)
    engine.advance(3.0)
    assert fired == ["x"]

    engine.add_timer_handler(handler, 1.0)
    engine.remove_timer_handler(handler)
    engine.advance(3.0)
    assert fired == ["x"]


def test_mailbox_priority_order(engine):
    log = []
    handler = lambda name, item: log.append((name, item))
    engine.add_mailbox_handler(handler, "in")
    engine.add_mailbox_handler(handler, "control", priority=True)
    engine.mailbox_put("in", 1)
    engine.mailbox_put("control", 2)
    engine.drain()
    assert log == [("control", 2), ("in", 1)]  # control preempts in


def test_mailbox_delay(engine):
    log = []
    engine.add_mailbox_handler(lambda n, i: log.append(i), "m")
    engine.mailbox_put("m", "later", delay=5.0)
    engine.mailbox_put("m", "now")
    engine.drain()
    assert log == ["now"]
    engine.advance(5.1)
    assert log == ["now", "later"]


def test_queue_handler(engine):
    got = []
    engine.add_queue_handler(got.append, "q")
    engine.queue_put("a", "q")
    engine.queue_put("b", "q")
    engine.drain()
    assert got == ["a", "b"]


def test_high_water_mark(engine):
    engine.add_mailbox_handler(lambda n, i: None, "m")
    for i in range(5):
        engine.mailbox_put("m", i)
    assert engine.mailbox_high_water("m") == 5
    engine.drain()
    assert engine.mailbox_size("m") == 0
    assert engine.mailbox_high_water("m") == 5


def test_real_loop_wakes_on_post():
    """The threaded loop processes a post promptly (no 10ms tick)."""
    engine = EventEngine()
    done = threading.Event()
    engine.add_mailbox_handler(lambda n, i: done.set(), "m")
    thread = engine.run_in_thread()
    engine.mailbox_put("m", "ping")
    assert done.wait(timeout=2.0)
    engine.terminate()
    thread.join(timeout=2.0)
    assert not thread.is_alive()


@pytest.mark.skipif(not sys.platform.startswith("linux"),
                    reason="thread names at the OS level: Linux")
def test_threaded_loop_carries_its_name_at_the_os_level():
    """What a profiler lists the engine loop's thread under (its
    ``engine:<phase>`` annotations get a line of their own)."""
    engine = EventEngine()
    names = []

    def handler(_name, _item):
        tid = threading.get_native_id()
        with open(f"/proc/self/task/{tid}/comm") as comm:
            names.append(comm.read().strip())

    engine.add_mailbox_handler(handler, "m")
    thread = engine.run_in_thread()
    engine.mailbox_put("m", "who")
    for _ in range(200):
        if names:
            break
        time.sleep(0.01)
    engine.terminate()
    thread.join(timeout=2.0)
    assert names == ["aiko-event-loop"]
    with open("/proc/self/comm") as comm:      # the process keeps its own
        assert comm.read().strip() != "aiko-event-loop"


def test_lease_expiry(engine):
    expired = []
    Lease(10.0, "u1", lease_expired_handler=expired.append, engine=engine)
    engine.advance(9.0)
    assert expired == []
    engine.advance(1.1)
    assert expired == ["u1"]


def test_lease_extend(engine):
    expired = []
    lease = Lease(10.0, "u2", lease_expired_handler=expired.append,
                  engine=engine)
    engine.advance(8.0)
    lease.extend()
    engine.advance(8.0)
    assert expired == []     # extended at t=8 -> expires t=18
    engine.advance(2.1)
    assert expired == ["u2"]


def test_lease_auto_extend_never_expires(engine):
    expired = []
    lease = Lease(10.0, "u3", lease_expired_handler=expired.append,
                  automatic_extend=True, engine=engine)
    engine.advance(100.0)
    assert expired == []
    lease.terminate()
    engine.advance(100.0)
    assert expired == []


def test_lease_terminate_cancels(engine):
    expired = []
    lease = Lease(5.0, "u4", lease_expired_handler=expired.append,
                  engine=engine)
    lease.terminate()
    engine.advance(10.0)
    assert expired == []
