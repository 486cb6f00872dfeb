"""Compile accounting from ``jax.monitoring`` (copied from the repo's
``chip_smoke.py``): persistent-cache hits and misses, backend compiles and
their seconds.  The programs that missed the persistent cache, and those
that JAX would not write to it, are named from JAX's own debug log."""
from __future__ import annotations

import logging

import jax.monitoring as mon

HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
COMPILER_LOG = "jax._src.compiler"
MISS_MSG = "PERSISTENT COMPILATION CACHE MISS for '%s'"
NOT_WRITTEN_MSG = "Not writing persistent cache entry for '%s'"


class CompileMeter(logging.Filter):
    def __init__(self):
        super().__init__()
        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        self.missed, self.not_written = [], []

    def on_event(self, event, **_):
        if event == HIT:
            self.hits += 1
        elif event == MISS:
            self.misses += 1

    def on_duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def filter(self, record: logging.LogRecord) -> bool:
        """Notes the module a miss or an unwritten entry names; drops the
        debug records it raised the logger's level for."""
        msg = str(record.msg)
        if msg.startswith(MISS_MSG):
            self.missed.append(str(record.args[0]))
        elif msg.startswith(NOT_WRITTEN_MSG):
            self.not_written.append(str(record.args[0]))
        return record.levelno > logging.DEBUG

    def __enter__(self):
        mon.register_event_listener(self.on_event)
        mon.register_event_duration_secs_listener(self.on_duration)
        log = logging.getLogger(COMPILER_LOG)
        self._level = log.level
        log.setLevel(logging.DEBUG)
        log.addFilter(self)
        return self

    def __exit__(self, *exc):
        mon.unregister_event_listener(self.on_event)
        mon.unregister_event_duration_listener(self.on_duration)
        log = logging.getLogger(COMPILER_LOG)
        log.removeFilter(self)
        log.setLevel(self._level)

    def line(self) -> str:
        return (f"persistent_cache_hits={self.hits} "
                f"persistent_cache_misses={self.misses} "
                f"backend_compiles={self.compiles} "
                f"backend_compile_s={self.compile_s!r} "
                f"missed={','.join(self.missed) or '-'} "
                f"not_written={','.join(self.not_written) or '-'}")
