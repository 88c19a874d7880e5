"""Pallas TPU kernels for the paper's compute hot-spots, with jit
wrappers (ops) and pure-jnp oracles (ref):

  * mixed-precision quantized matmul (``quantized_matmul`` over
    ``PackedWeight`` — the paper's sub-byte compute story),
  * causal flash attention for train/prefill (``flash_attention``),
  * the FUSED paged flash-decoding kernel for serving
    (``paged_flash_decode``): page-table translation, pool-page gather,
    and per-logical-page flash partials in one kernel — one grid
    program per logical page, the table scalar-prefetched into the
    BlockSpec index maps, non-resident/future pages skipped.  Wired
    behind ``ServeConfig.use_pallas_decode``; partials are
    bit-identical to the lax ``_page_partials`` path for f32 pools.

``interpret=False`` compiles a kernel with Mosaic; ``interpret=True``
runs the same grid in the Pallas interpreter, which is how the CPU test
suite checks kernel logic.  tests/test_tpu_compile.py compiles the
paged-decode and quantized-matmul kernels for a described v5e topology;
``flash_attention`` is not yet accepted by Mosaic (its blocks are not
(8, 128)-tiled) and no model path calls it.
"""
from repro.kernels.ops import (  # noqa: F401
    PackedWeight, prepare_weight, quantized_matmul,
)
from repro.kernels.paged_flash_decode import (  # noqa: F401
    decode_kernel_config, mla_paged_decode_partials,
    paged_flash_decode_partials, use_pallas_decode,
)
