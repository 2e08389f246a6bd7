"""Run-time guards and the compile-event listener.

Copied from ``chip_smoke.py`` (PR 12): the run refuses to measure on
anything but the chip, or with Pallas in interpret mode; compiles are
counted from JAX's own ``backend_compile_duration`` event.  The
persistent compilation cache lives in ``JAX_COMPILATION_CACHE_DIR`` when
that is set, otherwise at ``<checkout>/.jax_cache`` (a fixed path: the
path is part of the cache key).
"""
from __future__ import annotations

import os
import pathlib
import time
from typing import List, Tuple

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Refused(RuntimeError):
    """The run cannot measure here; no result line may be printed."""


def require_chip(chips: int) -> None:
    """Raise :class:`Refused` unless JAX sees at least ``chips`` TPUs and
    Pallas kernels would compile (not interpret)."""
    if os.environ.get("REPRO_KERNEL_INTERPRET", "").strip():
        raise Refused("REPRO_KERNEL_INTERPRET is set; the benchmark runs "
                      "compiled kernels only")
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise Refused(f"no TPU: JAX backend is {backend!r}")
    n = len(jax.devices())
    if n < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {n}")


def enable_compile_cache() -> str:
    """Persistent compilation cache on, at the fixed path; every program
    is cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Backend-compile events as ``(perf_counter at the event, fun_name,
    seconds, from_cache)``.  JAX fires the event for a persistent-cache
    load too, just after a ``cache_hits`` event; ``from_cache`` marks
    those (for the log: either way a program was lowered and handed to
    the backend).  Registered once per instance."""

    def __init__(self):
        import jax
        self.events: List[Tuple[float, str, float, bool]] = []
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self._hit = True

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(),
                                str(kw.get("fun_name")), float(secs),
                                self._hit))
            self._hit = False

    def between(self, t0: float, t1: float
                ) -> List[Tuple[float, str, float, bool]]:
        """Every backend-compile event (compiles and cache loads alike)
        that fired in [t0, t1)."""
        return [e for e in self.events if t0 <= e[0] < t1]
