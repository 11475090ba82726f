"""What the benchmark asks of the machine: the device it runs on, the
published peaks of that device, and JAX's persistent-cache counters."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def require_device(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it. Exits non-zero when it is not a TPU or
    there are fewer chips than the cell asks for; a rehearsal (tests, CPU,
    tiny sizes) is let through and can never be read as a result because its
    line names the platform it ran on."""
    import jax
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        sys.exit(f"benchmark: JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        sys.exit(f"benchmark: the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used; 0 where the
    backend reports none (a CPU)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def load_peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in benchmark/peaks.json")
    return table[device_kind]


class CompileCounter:
    """Compilation as JAX itself reports it, for every runner alike: hits
    and misses of the persistent cache (after
    ``chip_smoke.PersistentCacheCounter``) and the number of and seconds in
    backend compiles, a load from the persistent cache included."""

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = self.programs = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **kw):
        if name.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            self.misses += 1

    def _on_duration(self, name, seconds, **kw):
        if name.endswith("/backend_compile_duration"):
            self.programs += 1
            self.compile_s += seconds

    def snapshot(self) -> dict:
        return {"cache_hits": self.hits, "cache_misses": self.misses,
                "programs": self.programs, "compile_s": self.compile_s}
