"""Page storage formats for the paged KV pool — the pool-side 'CSR'.

The source paper's premise is mixed-precision storage under a hard memory
budget: Shaheen's cluster keeps operands in int8/int4/int2 and widens them
inside the datapath, because a nano-UAV SoC cannot afford fp memories.  The
serving-scale analog is the paged KV pool — pool bytes, not compute, cap
resident concurrency — so this module gives every pool page a pluggable
STORAGE FORMAT, selected once by ``ServeConfig.kv_format``:

  * ``"fp"``   — pages stored at model dtype.  The bit-exact reference path;
                 nothing about the existing layout or math changes.
  * ``"int8"`` — pages stored as int8 with one f32 absmax scale PER ROW
                 (per (page, slot-in-page)), living in a pool-shaped scale
                 leaf beside the page table.  4x smaller than f32 pages.
  * ``"int4"`` — as int8, but rows additionally packed 2 lanes/byte with
                 :mod:`repro.core.packing`'s strided layout.  8x smaller.

Quantized rows are produced ONCE at the write boundary (``paged_scatter``
time) and dequantized INSIDE the flash partial — lax ``_page_partials`` and
the Pallas ``paged_flash_decode`` kernel both — so no fp window is ever
materialized in HBM.  Scales are ordinary pool-shaped cache leaves
(``(num_pages, page_size)`` f32, logical axes ``("pages", None)``), which is
what makes the whole serving stack format-oblivious: COW privatize, swap
out/in, per-shard striping, and byte accounting all index pool leaves on the
page axis and therefore move scales WITH their pages for free.

Within a fixed quantized format every serving transform is still pure
addressing — COW/swap/resume/prefix-sharing copy quantized bytes and scales
verbatim — so int8 runs are bitwise invariant across shard counts and
preemption schedules; only the fp->int round-trip itself is lossy, and that
error is budgeted in tests/test_quant_pool.py (the chip benchmark,
``benchmarks/chip/run.py``, serves fp pages only).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.quant import dequantize_page_rows, quantize_page_rows

#: the ``ServeConfig.kv_format`` vocabulary, in capacity order.
KV_FORMATS = ("fp", "int8", "int4")


@dataclasses.dataclass(frozen=True)
class PageFormat:
    """How one pool page's rows are stored in HBM.

    ``bits is None`` means full-precision (model dtype) storage; otherwise
    rows are symmetric-quantized to ``bits`` with one f32 absmax scale per
    row and packed ``8 // bits`` lanes per byte along the last feature axis.
    """
    name: str
    bits: Optional[int] = None

    @property
    def quantized(self) -> bool:
        return self.bits is not None

    @property
    def pack(self) -> int:
        """Feature-axis shrink factor of the stored page (1 for fp/int8)."""
        return 1 if self.bits is None else packing.pack_factor(self.bits)

    def packed_feat(self, feat: int) -> int:
        """Stored last-dim length for a full feature length ``feat``."""
        if feat % self.pack:
            raise ValueError(
                f"kv_format={self.name!r} packs {self.pack} lanes/byte but "
                f"the page feature dim {feat} is not divisible by {self.pack}")
        return feat // self.pack

    def quantize_rows(self, rows: jax.Array):
        """(B, S, *feat) fp rows -> (packed int8 rows, (B, S) f32 scales).

        One absmax scale per ROW (reduced over every trailing feature
        axis), so a row re-quantized from identical fp input is bit-
        identical regardless of which physical page it lands on.
        """
        assert self.quantized, "fp pages are stored verbatim"
        q, scales = quantize_page_rows(rows, self.bits)
        return packing.pack(q, self.bits, axis=-1), scales

    def dequantize(self, q: jax.Array, scales: jax.Array,
                   dtype=jnp.float32) -> jax.Array:
        """Packed int8 rows + per-row scales -> fp rows of ``dtype``.

        Pure shift/mask/concat + one multiply — the identical op sequence
        runs on a gathered lax window and on a VMEM tile inside the Pallas
        kernel, so both read paths produce bitwise-equal fp rows.
        """
        assert self.quantized, "fp pages are stored verbatim"
        return dequantize_page_rows(
            packing.unpack(q, self.bits, axis=-1), scales, dtype)


FP = PageFormat("fp")
INT8 = PageFormat("int8", bits=8)
INT4 = PageFormat("int4", bits=4)

_FORMATS = {f.name: f for f in (FP, INT8, INT4)}


def get_format(name: str) -> PageFormat:
    if name not in _FORMATS:
        raise ValueError(f"unknown kv_format {name!r}; one of {KV_FORMATS}")
    return _FORMATS[name]


def format_for_packed(full_feat: int, stored_feat: int) -> PageFormat:
    """Recover the quantized format from pool geometry.

    The read path infers the format STRUCTURALLY — a scale leaf beside the
    pool marks it quantized, and the ratio of the full feature length to the
    stored (packed) last dim names the bit width — so no format context has
    to thread through jitted forward functions.
    """
    for fmt in (INT8, INT4):
        if stored_feat * fmt.pack == full_feat:
            return fmt
    raise ValueError(
        f"no page format stores a {full_feat}-wide feature in {stored_feat} "
        f"bytes/row (known ratios: 1x int8, 2x int4)")
