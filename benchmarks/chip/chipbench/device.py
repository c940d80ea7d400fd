"""Which device a run is on, the peaks table, and the compile counter."""
from __future__ import annotations

import collections
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    """Raised before any work when the run cannot have its chips."""


def describe(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def require_devices(chips: int, devices=None) -> dict:
    """The devices JAX reports, printed; raises ``NoChip`` unless the first
    is a TPU and there are at least ``chips`` of them."""
    import sys
    if devices is None:
        import jax
        devices = jax.devices()
    dev = describe(devices)
    print(f"devices: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", file=sys.stderr, flush=True)
    if dev["platform"] != "tpu":
        raise NoChip(f"needs a TPU; JAX found {dev['platform']}")
    if dev["count"] < chips:
        raise NoChip(f"needs {chips} chips; JAX found {dev['count']}")
    return dev


def peaks(kind: str, path: Path = PEAKS) -> dict:
    """Published peaks of one chip of ``kind``; a kind missing from the
    table is an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[kind]


class CompileMeter:
    """Backend compiles (count and seconds) and persistent-cache events,
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.cache = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name.startswith("/jax/compilation_cache/cache_"):
            self.cache[name.rsplit("/", 1)[1]] += 1
