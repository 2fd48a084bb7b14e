"""The chip: which one ran, its published peaks, its memory, and the
seconds spent compiling."""
from __future__ import annotations

# Published peaks per chip, keyed by ``device_kind``. Source: Google Cloud
# documentation, "TPU v5e" (system architecture: 197 TFLOP/s bf16,
# 394 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip).
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "source": "Google Cloud, TPU v5e"},
}


class NoAcceleratorError(RuntimeError):
    """The run found no TPU, or fewer chips than its cell asks for."""


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``. A kind missing from the
    table is an error: a roofline share against a guessed peak means
    nothing."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add a row with its source to bench/lib/device.py"
                       ) from None


def require_tpu(chips: int):
    """The first ``chips`` devices, all TPUs, or
    :class:`NoAcceleratorError`."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAcceleratorError(
            f"no TPU: the first device is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoAcceleratorError(
            f"the cell asks for {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    import jax
    first = devices[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices), "visible": len(jax.devices())}


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest of ``devices``, where the backend
    reports it (None where it does not, as on the CPU)."""
    peaks_seen = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                  for d in devices]
    peaks_seen = [p for p in peaks_seen if p is not None]
    return max(peaks_seen) if peaks_seen else None


class CompileClock:
    """Seconds spent in backend compiles, persistent-cache hits included
    (a run served from the cache still spends a little here), and a count
    of each of JAX's compile events: traces, lowerings, backend compiles,
    persistent-cache hits and misses."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    PREFIXES = ("/jax/core/compile/", "/jax/compilation_cache/")

    def __init__(self):
        import jax
        self.total = 0.0
        self.events = {}

        def count(name):
            if name.startswith(self.PREFIXES):
                self.events[name] = self.events.get(name, 0) + 1

        def listen_duration(name, secs, **_):
            if name == self.EVENT:
                self.total += secs
            count(name)

        def listen(name, **_):
            count(name)

        jax.monitoring.register_event_duration_secs_listener(listen_duration)
        jax.monitoring.register_event_listener(listen)
