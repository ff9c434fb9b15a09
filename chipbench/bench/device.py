"""The chip under test: which device it is, its published peaks, and a clock
of JAX's compile events."""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


class DeviceError(SystemExit):
    """The run cannot be measured here (no TPU, too few chips, a device the
    peak table does not know).  Exits nonzero before any result."""


def load_peaks(kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    """The published peaks of ``kind`` (a JAX ``device_kind``)."""
    table = json.loads(path.read_text())["devices"]
    if kind not in table:
        raise DeviceError(f"chipbench: no peaks for device kind {kind!r} in "
                          f"{path.name} (known: {sorted(table)})")
    return table[kind]


def check_device(jax, chips: int) -> dict:
    """The device record of this run; raises :class:`DeviceError` unless JAX
    sees at least ``chips`` TPU devices whose kind has published peaks and
    Pallas kernels take the Mosaic path."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise DeviceError(f"chipbench: needs a TPU; JAX found platform "
                          f"{dev.platform!r} ({dev.device_kind})")
    if len(devices) < chips:
        raise DeviceError(f"chipbench: the cell asks for {chips} chips, JAX "
                          f"found {len(devices)}")
    from repro.kernels.common import use_interpret
    if use_interpret():
        raise DeviceError("chipbench: Pallas kernels would run in interpret "
                          "mode on this TPU")
    load_peaks(dev.device_kind)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


class CompileClock:
    """Seconds and count of JAX backend compiles (a persistent-cache hit
    counts its retrieval), read from ``jax.monitoring`` events."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
