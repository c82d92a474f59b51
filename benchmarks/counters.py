"""The program's own counters, read from its Prometheus text (copied from
``chip_smoke.py``, which later PRs may change), and JAX's compile events."""

from __future__ import annotations

import threading


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {(name, ((label, value), ...)): float}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = tuple(sorted(
            (kv.partition("=")[0], kv.partition("=")[2].strip('"'))
            for kv in rest.rstrip("}").split(",") if kv))
        try:
            out[(name, labels)] = float(val)
        except ValueError:
            pass
    return out


def metric(m: dict, name: str, **labels) -> float:
    """Sum of the series of ``name`` whose labels include ``labels``."""
    want = set(labels.items())
    return sum(v for (n, ls), v in m.items() if n == name and want <= set(ls))


def device_health() -> dict:
    """The counters that say whether anything was verified off the device.
    ``route="device"`` counts a lane once at the seam (``verify_dense``);
    the kernels' own ``device_rlc`` route is another label value."""
    from cometbft_tpu.libs import metrics

    m = parse_metrics(metrics.DEFAULT.collect())
    lanes = "crypto_batch_lanes_total"
    return {
        "device_lanes": metric(m, lanes, route="device"),
        "host_lanes": sum(v for (n, ls), v in m.items() if n == lanes
                          and not dict(ls).get("route", "").startswith("device")),
        "abandoned": metric(m, "crypto_device_abandoned_total"),
        "degraded": metric(m, "crypto_device_degraded"),
        "first_dispatch_s": {
            f"{dict(ls)['kind']}:{dict(ls)['lanes']}": v
            for (n, ls), v in sorted(m.items())
            if n == "crypto_kernel_first_dispatch_seconds"},
    }


class JitLedger:
    """What JAX itself says start-up cost and the persistent compile cache
    saved (``jax.monitoring`` events, summed over threads).  ``programs``
    counts every program built OR loaded from the cache: one inside the
    measured window is an error of the run."""

    DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
        "/jax/compilation_cache/compile_time_saved_sec": "cache_saved_s",
    }
    COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax.monitoring

        self.sums = dict.fromkeys(
            [*self.DURATIONS.values(), *self.COUNTS.values(), "programs"], 0.0)
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _secs(self, event: str, secs: float, **kw) -> None:
        key = self.DURATIONS.get(event)
        if key is not None:
            with self._lock:
                self.sums[key] += secs
                if key == "backend_compile_s":
                    self.sums["programs"] += 1

    def _event(self, event: str, **kw) -> None:
        key = self.COUNTS.get(event)
        if key is not None:
            with self._lock:
                self.sums[key] += 1

    def report(self) -> dict:
        with self._lock:
            return dict(self.sums)
