"""Public jit'd wrappers for the Pallas kernels.

The kernels compile to Mosaic for the TPU.  ``interpret=True`` runs the
kernel body in Python per grid step instead (how the CPU tests validate
the exact TPU program); it happens only when the caller asks for it, so
a kernel can never drop into the interpreter unnoticed on a chip.
"""
from __future__ import annotations

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.pid_update import pid_update as _pid
from repro.kernels.ssd_scan import ssd_scan as _ssd


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    return _flash(q, k, v, causal=causal, window=window,
                  block_q=block_q, block_k=block_k, interpret=interpret)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 256, block_heads: int = 4,
             interpret: bool = False):
    return _ssd(x, dt, A, B, C, chunk=chunk, block_heads=block_heads,
                interpret=interpret)


def pid_update(target, power, temp, integ, prev_err, *, dt_s: float = 0.005,
               interpret: bool = False):
    return _pid(target, power, temp, integ, prev_err, dt_s=dt_s,
                interpret=interpret)
