"""The compile-event listener: every ``backend_compile_duration`` event
counts, a persistent-cache load (after a ``cache_hits`` event) included,
and only those inside the asked interval."""
import time

import jax

from bench import guards


def _fire(hit: bool, name: str) -> None:
    if hit:
        jax.monitoring.record_event(guards.CACHE_HIT_EVENT)
    jax.monitoring.record_event_duration_secs(guards.COMPILE_EVENT, 0.01,
                                              fun_name=name)


def test_compile_log_counts_cache_loads_as_compile_events():
    log = guards.CompileLog()
    try:
        t0 = time.perf_counter()
        _fire(True, "jit(scan)")
        _fire(False, "jit(branch_segment)")
        got = log.between(t0, time.perf_counter())
    finally:
        log.close()
    assert [(e[1], e[3]) for e in got] == [("jit(scan)", True),
                                           ("jit(branch_segment)", False)]


def test_compile_log_keeps_to_the_window():
    log = guards.CompileLog()
    try:
        _fire(False, "before")
        t0 = time.perf_counter()
        _fire(True, "inside")
        t1 = time.perf_counter()
        _fire(False, "after")
        got = log.between(t0, t1)
    finally:
        log.close()
    assert [e[1] for e in got] == ["inside"]
    assert len(log.events) == 3
