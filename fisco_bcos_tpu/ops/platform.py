"""Where JAX runs — asked once, by everything that dispatches on it.

The Pallas dispatch in `ops.*` and the `CryptoSuite` seam both branch on
the platform. They read it here so there is one answer per process, and so
a backend that fails to initialise (no chip, chip held by another process)
raises at the first question instead of being read as "no Pallas".
"""

from __future__ import annotations

import dataclasses
import functools


@dataclasses.dataclass(frozen=True)
class Platform:
    platform: str      # jax.devices()[0].platform: "tpu" | "cpu" | ...
    device_kind: str   # jax.devices()[0].device_kind
    count: int         # len(jax.devices())


@functools.lru_cache(maxsize=1)
def resolve() -> Platform:
    """First call initialises the JAX backend; whatever that raises
    propagates (and is not cached — the next call asks again)."""
    import jax

    devs = jax.devices()
    return Platform(devs[0].platform, devs[0].device_kind, len(devs))


def on_tpu() -> bool:
    return resolve().platform == "tpu"
