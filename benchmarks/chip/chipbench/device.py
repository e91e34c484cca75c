"""The chip: presence, peaks, memory, compile events, keys from seeds."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

import jax

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chips(n: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def peaks(kind: str) -> Dict:
    """Published peaks of one chip of ``device_kind`` ``kind``; an unknown
    kind is an error, never a default."""
    table = json.loads(PEAKS.read_text())["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return table[kind]


def describe(devs) -> Dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


class CompileCounter:
    """Counts XLA backend compiles and their seconds while it is open."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def key_for(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size: JAX keeps only the low 32 bits
    of an integer seed, so the high bits are folded in."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
