"""Pallas TPU kernels for the hot ops (guide: /opt/skills/guides/pallas_guide.md).

The reference has no kernel layer (torch/CUDA own it); here the compute
plane is ours, so the ops that dominate the profile get hand-tiled MXU/VMEM
kernels with jnp fallbacks everywhere else.
"""

from raytpu.ops.flash_attention import flash_attention, resolve_flash_impl
from raytpu.ops.paged_attention import paged_attention, resolve_paged_impl

__all__ = ["flash_attention", "paged_attention", "resolve_flash_impl",
           "resolve_paged_impl"]
