"""Published per-chip peaks of the TPUs this repository knows, keyed by
what the device itself reports (``jax.Device.device_kind``).

This is the one table of peaks: the live utilization gauges
(:mod:`raytpu.util.stepprof`), ``bench.py`` and the slice model
(:mod:`raytpu.core.topology`) all read it. A kind that is not here is an
error, never a default: a utilization against a guessed peak is not a
measurement.

Numbers are per chip, from the Google Cloud TPU documentation ("TPU v4",
"TPU v5e", "TPU v5p", "TPU v6e" system-architecture pages). The
``device_kind`` strings are the ones JAX itself matches on
(``jax/_src/pallas/mosaic/tpu_info.py``); only ``"TPU v5 lite"`` has
been seen on a device by this repository.
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    generation: str          # slice-name prefix: "v5e" in "v5e-16"
    bf16_flops: float        # dense bf16 FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    cores_per_chip: int      # slice names of 2-core chips count cores
    chips_per_host: int


CHIP_SPECS: Dict[str, ChipSpec] = {
    "TPU v4": ChipSpec("v4", 275e12, 1200e9, 32 * 2**30, 2, 4),
    "TPU v5 lite": ChipSpec("v5e", 197e12, 819e9, 16e9, 1, 4),
    "TPU v5p": ChipSpec("v5p", 459e12, 2765e9, 95e9, 2, 4),
    "TPU v6 lite": ChipSpec("v6e", 918e12, 1640e9, 32e9, 1, 4),
}

# Accelerator-type spellings of a generation ("v5litepod-8").
_GENERATION_ALIASES = {"v5litepod": "v5e"}


def chip_spec(device_kind: str) -> ChipSpec:
    """The spec of the chip that reports ``device_kind``; raises
    ``ValueError`` for a kind the table does not hold."""
    try:
        return CHIP_SPECS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(CHIP_SPECS)}") from None


def generation_spec(generation: str) -> ChipSpec:
    """The spec of a generation as slice and accelerator-type names
    spell it (``"v5e"``, ``"v5litepod"``); raises ``ValueError``
    otherwise."""
    generation = _GENERATION_ALIASES.get(generation, generation)
    for spec in CHIP_SPECS.values():
        if spec.generation == generation:
            return spec
    raise ValueError(
        f"unknown TPU generation {generation!r}; known: "
        f"{sorted(s.generation for s in CHIP_SPECS.values())}")
