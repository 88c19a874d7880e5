#!/usr/bin/env python3
"""Check that a cell's engine fits one chip, without the chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/memcheck.py qwen3-8b-18L.reasoning

Compiles the cell's prefill (fresh and resumed) and decode programs at
their serving shapes for a described TPU v5e (``v5e:2x2``, first device)
and prints each program's ``memory_analysis()``: the weights and the
paged pool are its arguments, so argument + temporary bytes is what the
chip must hold while that program runs.  Nothing runs; this is no chip
measurement.
"""
from __future__ import annotations

import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent.parent / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HBM = 16 * 2**30


def main(cell_name: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import registry
    from run import build
    from repro.models import abstract_params, abstract_paged_cache
    from repro.train.step import (make_paged_chunked_prefill_step,
                                  make_paged_decode_step)

    jax.config.update("jax_enable_compilation_cache", False)
    bench = registry.Bench()
    cell = bench.cell(cell_name)
    arch, sc = build(bench.code(cell["config"]), bench.config(cell["config"]),
                     bench.traffic(cell["traffic"]))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), t)
    ps = sc.page_size
    pages = sc.max_batch * -(-sc.slot_rows // ps)
    params = place(abstract_params(arch))
    cache = place(abstract_paged_cache(arch, sc.max_batch, pages, ps))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa
    b, sp, p = sc.max_batch, sc.max_prompt, -(-sc.slot_rows // ps)
    progs = {
        "prefill fresh": (make_paged_chunked_prefill_step(arch),
                          (params, cache, i32(b, sp), i32(b), i32(b, p), None)),
        "prefill resumed": (make_paged_chunked_prefill_step(arch),
                            (params, cache, i32(b, sp), i32(b), i32(b, p),
                             i32(b))),
        "decode": (make_paged_decode_step(arch),
                   (params, cache, i32(b, 1), i32(b), i32(b, p))),
    }
    print(f"{cell_name}: max_batch {b}, max_prompt {sp}, {pages} pages of "
          f"{ps} rows")
    worst = 0
    for name, (fn, args) in progs.items():
        try:
            c = jax.jit(fn, donate_argnums=1).lower(*args).compile()
        except jax.errors.JaxRuntimeError as e:
            print(f"  {name}: refused: " + " ".join(str(e).split("\n")[:7]))
            worst = HBM
            continue
        ma = c.memory_analysis()
        need = ma.argument_size_in_bytes + ma.temp_size_in_bytes + \
            ma.output_size_in_bytes - ma.alias_size_in_bytes
        worst = max(worst, need)
        print(f"  {name}: arguments {ma.argument_size_in_bytes / 2**30:.2f} "
              f"GiB, outputs {ma.output_size_in_bytes / 2**30:.2f} GiB "
              f"(aliased {ma.alias_size_in_bytes / 2**30:.2f}), temporaries "
              f"{ma.temp_size_in_bytes / 2**30:.2f} GiB; needs "
              f"{need / 2**30:.2f} GiB", flush=True)
    print(f"largest: {worst / 2**30:.2f} GiB of {HBM / 2**30:.0f} GiB")
    return 0 if worst < HBM else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
