"""Jit'd public op for flash attention (interpret mode on CPU)."""

from __future__ import annotations

from ..platform import interpret_mode
from .kernel import flash_attention as _flash

__all__ = ["flash_attention"]


def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0):
    interpret = interpret_mode()
    return _flash(q, k, v, causal=causal, window=window,
                  q_offset_static=q_offset, interpret=interpret)
