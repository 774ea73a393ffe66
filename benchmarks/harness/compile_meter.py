"""Copy of ``chip_smoke.py::CompileMeter``: seconds jax spent in backend
compiles and the persistent cache's hits and misses since the last reset.
``events()`` is what the window counts: a backend compile or a cache load
between window open and close means a shape was not warmed."""

import threading


class CompileMeter:
    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def reset(self):
        with self._lock:
            self.compile_s = 0.0
            self.compiles = self.hits = self.misses = 0

    def _duration(self, event, seconds, **_):
        if event.endswith("backend_compile_duration"):
            with self._lock:  # server and worker threads compile concurrently
                self.compile_s += seconds
                self.compiles += 1

    def _event(self, event, **_):
        with self._lock:
            if event.endswith("/cache_hits"):
                self.hits += 1
            elif event.endswith("/cache_misses"):
                self.misses += 1

    def events(self):
        """Backend compiles (a persistent-cache load runs inside one and is
        counted with it) seen since the last reset."""
        with self._lock:
            return self.compiles

    def snapshot(self):
        with self._lock:
            return {
                "compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.hits, "cache_misses": self.misses,
            }
