#!/usr/bin/env python3
"""Compile a cell's step programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <name> [--slots N]
        [--num-pages P] [--layers N] [--lengths 256,16]
    python3 bench/rehearse.py --chip --workload <name> [...]

Builds the programs the cell's scheduler runs (the decode chunk-scan and
the mixed prefill+decode step at each given chunk length) at the cell's
slots and page pool, compiles each for one described v5e chip and prints
`memory_analysis()`: arguments (weights + pool), temporaries and outputs.
`--slots`, `--num-pages` and `--layers` try other sizes than the files
state.  Nothing runs; this sizes a cell before any chip time.

With `--chip` the programs compile for the chip this process holds, and
then the cell's scheduler is built at those sizes and runs one request per
chunk length (every step program): the device's memory statistics after
that say whether the sizes fit and what the runtime counts as its peak.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--num-pages", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--lengths", default="256")
    ap.add_argument("--chip", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.lib import spec as S
    from repro.models.model_zoo import build_model
    from repro.runtime import serve_lib

    jax.config.update("jax_enable_compilation_cache", False)
    cell = S.load_cell(ROOT, args.workload)
    layout = S.layout_module(ROOT, cell.config)
    server = dict(cell.traffic["server"])
    if args.slots:
        server["max_batch_slots"] = args.slots
    if args.num_pages:
        server["num_pages"] = args.num_pages
    over = {"num_layers": args.layers} if args.layers else {}
    cfg = layout.program_config(cell.config, **over)
    model = build_model(cfg)
    B, max_len = server["max_batch_slots"], server["max_len"]
    ps, P = server["page_size"], server["num_pages"]
    max_pages = -(-max_len // ps)

    if args.chip:
        from bench.lib import harness
        harness.device_check(1)
        one = SingleDeviceSharding(jax.devices()[0])
    else:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    params = sds(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = sds(jax.eval_shape(lambda: model.init_cache(
        B, max_len, ragged=True, page_size=ps, num_pages=P)))

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (B,), dtype, sharding=one)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    pages = vec(jnp.int32, B, max_pages)
    i32, b8 = jnp.int32, jnp.bool_
    print(f"{args.workload}: layers {cfg.num_layers}, slots {B}, max_len "
          f"{max_len}, pool {P} pages x {ps} tokens, "
          f"{serve_lib.kv_bytes_per_token(cfg)} KV bytes per token",
          flush=True)
    dec = serve_lib.make_ragged_decode_fn(model, 8, 0.0, 0, None, max_len)
    progs = [("decode chunk-scan x8", dec,
              (params, vec(i32), cache, vec(i32), vec(b8), vec(i32),
               vec(i32), vec(i32), key, vec(b8), pages))]
    for L in (int(x) for x in args.lengths.split(",")):
        fn = serve_lib.make_mixed_step_fn(model, B, L, 0.0, 0, 1.0)
        progs.append((f"mixed step L={L}", fn,
                      (params, vec(i32, B, L), cache, vec(i32), vec(i32),
                       vec(b8), vec(i32), vec(i32), key, vec(b8), pages)))
    for name, fn, a in progs:
        m = fn.lower(*a).compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.temp_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes)
        print(f"{name}: arguments {m.argument_size_in_bytes} B, "
              f"temporaries {m.temp_size_in_bytes} B, outputs "
              f"{m.output_size_in_bytes} B, aliased "
              f"{m.alias_size_in_bytes} B, total {total} B "
              f"({total / 2**30:.2f} GiB)", flush=True)
    if args.chip:
        cell.traffic["server"] = server
        harness.build_server(cell, layout, 1, over)
        stats = jax.devices()[0].memory_stats() or {}
        print("after every step program ran: " + ", ".join(
            f"{k} {v}" for k, v in sorted(stats.items())), flush=True)


if __name__ == "__main__":
    main()
