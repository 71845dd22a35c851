"""Public entry points: the flash attention kernel (Mosaic on a TPU,
interpret mode elsewhere) and its pure-jnp reference."""
from __future__ import annotations

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref

__all__ = ["flash_attention", "flash_attention_ref"]
