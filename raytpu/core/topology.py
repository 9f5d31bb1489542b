"""TPU slice topology model — ICI as a first-class scheduling dimension.

The reference bolts TPUs on via env vars and string-typed pod resources
(``python/ray/_private/accelerators/tpu.py:75`` — detects chips per host,
pod type from GCE metadata, sets ``TPU_VISIBLE_CHIPS``). Here the topology
is a native scheduler concept: a slice is an axis-aligned box in the ICI
torus, hosts own fixed sub-boxes of chips, and strict-pack placement groups
are allocated *contiguous sub-cubes* so collectives ride ICI with no DCN
hops (reference bundle policies: ``bundle_scheduling_policy.h:31`` know
nothing of physical adjacency — NCCL never needed it; ICI does).
"""

from __future__ import annotations

import itertools
import math
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from raytpu.core.chip_specs import generation_spec


@dataclass(frozen=True)
class SliceType:
    """E.g. ``v4-32``: generation v4, 32 TensorCores = 16 chips, 4 hosts."""

    name: str
    generation: str
    chips: int
    hosts: int
    mesh_shape: Tuple[int, ...]  # physical ICI box, e.g. (2, 2, 4) chips

    @classmethod
    def parse(cls, name: str) -> "SliceType":
        # "v4-32" → generation v4, 32 cores. v4/v5p count 2 cores per chip;
        # v5e/v6e pod names count chips directly (e.g. v5e-16).
        gen, _, n = name.partition("-")
        spec = generation_spec(gen)  # an unknown generation raises
        chips = max(1, int(n) // spec.cores_per_chip)
        hosts = max(1, chips // spec.chips_per_host)
        return cls(name, spec.generation, chips, hosts,
                   _default_box(chips, spec.generation))

    @property
    def tflops_bf16(self) -> float:
        return generation_spec(self.generation).bf16_flops / 1e12


def _default_box(chips: int, gen: str) -> Tuple[int, ...]:
    """Near-cubic axis-aligned box holding `chips` chips (3D for v4/v5p torus,
    2D otherwise)."""
    ndim = 3 if gen in ("v4", "v5p") else 2
    dims = [1] * ndim
    # Greedily double the smallest axis: yields 2x2x2, 2x2x4, ... like real pods.
    remaining = chips
    while remaining > 1:
        i = dims.index(min(dims))
        dims[i] *= 2
        remaining //= 2
    return tuple(sorted(dims))


Box = Tuple[Tuple[int, int], ...]  # ((lo, hi_exclusive), ...) per axis


@dataclass
class TpuTopology:
    """Occupancy-tracked ICI box; allocates contiguous sub-boxes.

    Used by the placement-group bundle policy: STRICT_PACK bundles carrying
    ``{"TPU": k}`` get a contiguous sub-box of k chips (so the k chips form
    an ICI-connected mesh), PACK prefers contiguity but degrades, SPREAD
    maximizes pairwise distance.
    """

    shape: Tuple[int, ...]
    _occupied: set = field(default_factory=set)
    _native: object = field(default=None, repr=False)

    def __post_init__(self):
        # Native C++ allocator (src/sched/sched_core.cc) when built: the
        # contiguous-box search is the scheduler's hot combinatorial loop
        # at pod scale. Pure-Python fallback keeps identical semantics.
        try:
            from raytpu.core.sched_native import NativeTopology, available

            if available():
                object.__setattr__(self, "_native",
                                   NativeTopology(self.shape))
        except Exception:
            pass

    @property
    def num_chips(self) -> int:
        return math.prod(self.shape)

    @property
    def num_free(self) -> int:
        if self._native is not None:
            return self._native.num_free
        return self.num_chips - len(self._occupied)

    def _coords(self):
        return itertools.product(*(range(d) for d in self.shape))

    def chip_ids(self, coords: Sequence[Tuple[int, ...]]) -> Tuple[int, ...]:
        """Flatten coords to host-local chip indices (row-major), the ids
        used for per-worker ``TPU_VISIBLE_CHIPS`` isolation (reference:
        ``python/ray/_private/accelerators/tpu.py:30-49``)."""
        out = []
        for c in coords:
            idx = 0
            for dim, x in zip(self.shape, c):
                idx = idx * dim + x
            out.append(idx)
        return tuple(sorted(out))

    def allocate_subcube(self, chips: int) -> Optional[List[Tuple[int, ...]]]:
        """Find and claim a free axis-aligned box of exactly `chips` chips.

        Returns the claimed coordinates, or None if no contiguous box fits.
        Tries the most compact factorization first (minimal surface area →
        best bisection bandwidth for collectives).
        """
        if chips <= 0 or chips > self.num_free:
            return None
        if self._native is not None:
            return self._native.allocate_subcube(chips)
        for dims in self._box_shapes(chips):
            claimed = self._find_free_box(dims)
            if claimed is not None:
                self._occupied.update(claimed)
                return claimed
        return None

    def allocate_any(self, chips: int) -> Optional[List[Tuple[int, ...]]]:
        """Claim `chips` free coordinates, contiguous if possible."""
        if self._native is not None:
            if chips <= 0 or chips > self.num_free:
                return None
            return self._native.allocate_any(chips)
        got = self.allocate_subcube(chips)
        if got is not None:
            return got
        free = [c for c in self._coords() if c not in self._occupied]
        if len(free) < chips:
            return None
        chosen = free[:chips]
        self._occupied.update(chosen)
        return chosen

    def release(self, coords: Sequence[Tuple[int, ...]]) -> None:
        if self._native is not None:
            self._native.release(coords)
            return
        for c in coords:
            self._occupied.discard(c)

    def _box_shapes(self, chips: int):
        """All axis-aligned box shapes with volume `chips` that fit in self.shape,
        most compact (min max-dim) first."""
        ndim = len(self.shape)
        shapes = set()

        def rec(remaining, dims):
            if len(dims) == ndim - 1:
                last = remaining
                if last <= self.shape[ndim - 1]:
                    shapes.add(tuple(dims + [last]))
                return
            axis = len(dims)
            d = 1
            while d <= min(remaining, self.shape[axis]):
                if remaining % d == 0:
                    rec(remaining // d, dims + [d])
                d += 1

        rec(chips, [])
        # Full deterministic order (max-dim, sum, lexicographic) — matches
        # the native core so both paths claim identical boxes.
        return sorted(shapes, key=lambda s: (max(s), sum(s), s))

    def _find_free_box(self, dims: Tuple[int, ...]) -> Optional[List[Tuple[int, ...]]]:
        for origin in itertools.product(
            *(range(self.shape[i] - dims[i] + 1) for i in range(len(self.shape)))
        ):
            coords = [
                tuple(origin[i] + off[i] for i in range(len(dims)))
                for off in itertools.product(*(range(d) for d in dims))
            ]
            if all(c not in self._occupied for c in coords):
                return coords
        return None


def detect_local_tpu() -> Dict[str, object]:
    """Best-effort local TPU detection (no GCE metadata egress here).

    Reference: ``python/ray/_private/accelerators/tpu.py:37`` counts chips
    from /dev entries and env vars. Deliberately NEVER initializes the JAX
    backend: creating the TPU client is slow, grabs the chip lock, and
    would make ``init()`` block (we only consult JAX if some other code in
    this process already initialized it).
    """
    chips, kind = 0, ""

    env_chips = os.environ.get("RAYTPU_NUM_TPUS")
    if env_chips:
        chips = int(env_chips)
    else:
        # /dev/accel* on TPU VMs (reference tpu.py:37 counts these).
        import glob as _glob

        accel = _glob.glob("/dev/accel*") or _glob.glob("/dev/vfio/[0-9]*")
        if accel:
            chips = len(accel)
        else:
            try:  # only if a backend already exists in-process (no init!)
                from jax._src import xla_bridge as _xb

                if _xb._backends:
                    import jax

                    devs = [d for d in jax.devices() if d.platform != "cpu"]
                    chips = len(devs)
                    kind = devs[0].device_kind if devs else ""
            except Exception:
                pass
    return {"chips": chips, "device_kind": kind}
