"""The expert layer's grouped product on the chip, both implementations, at
the serve cell's widths: where `ops/moe.py`'s crossover and
`ops/grouped_product.py`'s row tile come from.

    python tools/grouped_product_bench.py [--rows 16,64,...] [--runs 5]

One layer of `dropless_moe` (hidden 3072, 128 of 256 experts of width 1024
held, top-10, layer 1 of a three-layer stack, the router's logits of
deviation 4 as the cell's seeded weights have) over 16 rows (a decode step
of 16 slots) and each prefill bucket, with the grouped product forced to
the compiler's kernel and to the tiled one; then the tiled kernel alone
over a 512 and a 2048 bucket's pairs at other row tiles. Device times from a
profiler capture (the benchmark's reader), a run's whole program and the
product kernels inside it; the kernels' `op_name`, and whether the accepted
readers count them under `moe_experts`. Needs the chip; one JSON line, and
`chiprun_out/grouped_product_bench.d<hidden>f<width>k<top-k>.json`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

LAYERS = 3
ROW_TILES = (128, 256, 64)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="16,64,128,256,512,1024,2048")
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--top", type=int, default=12,
                    help="operations listed beside a program's products")
    # the expert layer's shape: Laguna-S-2.1's share by default; SDAR's is
    # --hidden 2048 --width 768 --experts 128 --held 128 --top-k 8
    # --scale 1 --rows 128 (a pass of 32 slots: 1,024 pairs, 8 an expert)
    ap.add_argument("--hidden", type=int, default=3072)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--experts", type=int, default=256)
    ap.add_argument("--held", type=int, default=128)
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--scale", type=float, default=2.5)
    ap.add_argument("--tiles", type=int, default=1,
                    help="0: leave the kernel-alone row-tile runs out")
    args = ap.parse_args(argv)
    D, F, EXPERTS, HELD, TOP_K = (args.hidden, args.width, args.experts,
                                  args.held, args.top_k)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("grouped_product_bench: a device time needs the "
                         "chip")
    import pb_core
    import pb_spans
    import pb_trace
    from pipe_tpu.obs.events import FFN, MOE_EXPERTS, device_scope
    from pipe_tpu.ops import moe
    from pipe_tpu.ops.grouped_product import grouped_gated_mlp, tile_groups

    moe_scope = pb_core.load_by_path("layers/moe.decode_share.py").moe_scope
    ks = jax.random.split(jax.random.key(33), 5)
    bf = jnp.bfloat16

    def stack(key, shape, fan_in):
        one = (jax.random.normal(key, shape, jnp.float32)
               / fan_in ** 0.5).astype(bf)
        return jnp.broadcast_to(one, (LAYERS,) + shape) + jnp.zeros((), bf)

    p = {"router": jax.random.normal(ks[0], (D, EXPERTS)) * 4 / D ** 0.5,
         "w_gate": stack(ks[1], (HELD, D, F), D),
         "w_up": stack(ks[2], (HELD, D, F), D),
         "w_down": stack(ks[3], (HELD, F, D), F)}
    jax.block_until_ready(p)
    programs = {}                   # name -> (function, arguments)
    for rows in (int(r) for r in args.rows.split(",")):
        x = jax.random.normal(jax.random.fold_in(ks[4], rows), (rows, D),
                              jnp.float32).astype(bf)
        for impl in ("compiler", "tiled"):
            def layer(p, x, impl=impl):
                with device_scope(FFN):
                    return moe._dropless_moe(
                        p, x, top_k=TOP_K, first=0, scale=args.scale,
                        live=None,
                        layer=jnp.int32(1), impl=impl)
            layer.__name__ = f"layer_{impl}_r{rows}"
            programs[layer.__name__] = (jax.jit(layer), (p, x))
    # the kernel alone, over sizes as a bucket's routing gives them
    for rows in (512, 2048) if args.tiles else ():
        m = rows * TOP_K
        sizes = jnp.bincount(jax.random.randint(
            jax.random.key(rows), (m // 2,), 0, HELD), length=HELD
        ).astype(jnp.int32)
        xs = jax.random.normal(ks[4], (m, D), jnp.float32).astype(bf)
        for tm in ROW_TILES:
            def kernel(xs, p, sizes, tm=tm):
                with device_scope(FFN), device_scope(MOE_EXPERTS):
                    return grouped_gated_mlp(
                        xs, *(p[n].reshape((LAYERS * HELD,) + p[n].shape[2:])
                              for n in ("w_gate", "w_up", "w_down")),
                        tile_groups(sizes, m, tm=tm), first_group=HELD)
            kernel.__name__ = f"kernel_r{rows}_tm{tm}"
            programs[kernel.__name__] = (jax.jit(kernel), (xs, p, sizes))
    first = {name: jax.block_until_ready(fn(*a))        # compile, warm
             for name, (fn, a) in programs.items()}
    apart = {}                  # the two implementations' layers, by rows
    for name, (y, counts) in ((n, v) for n, v in first.items()
                              if n.startswith("layer_tiled_")):
        want, counts_c = first[name.replace("tiled", "compiler")]
        apart[name.rsplit("_r", 1)[1]] = {
            "max_abs_diff": float(jnp.max(jnp.abs(
                y.astype(jnp.float32) - want.astype(jnp.float32)))),
            "max_abs": float(jnp.max(jnp.abs(want.astype(jnp.float32)))),
            "finite": bool(jnp.isfinite(y.astype(jnp.float32)).all()),
            "counts_equal": bool((counts == counts_c).all()),
            "counts": [int(c) for c in counts]}
    del first
    logdir = os.path.join(ROOT, "benchmark_out", "grouped_product_trace")
    with pb_trace.capture(logdir):
        for fn, a in programs.values():
            for _ in range(args.runs):
                out = fn(*a)
            jax.block_until_ready(out)
    cap = pb_spans.read({"trace_dir": logdir})
    report = {"device_kind": jax.devices()[0].device_kind, "runs": args.runs,
              "shape": {"hidden": D, "width": F, "experts": EXPERTS,
                        "held": HELD, "top_k": TOP_K},
              "tiled_against_compiler": apart, "programs": {}}
    for name in programs:
        pids, runs, ns = cap.programs(rf"^jit_{name}\(")
        kernels = [
            {"name": op.name, "op_name": op.op_name,
             "moe_scope": moe_scope(op),
             "ms_a_run": round(op.self_ns / 1e6 / max(runs, 1), 4)}
            for op in cap.ops if op.program in pids and (
                "grouped_product" in op.op_name
                or op.name.startswith("ragged-dot"))]
        rest = sorted((op for op in cap.ops if op.program in pids
                       and op.name not in {k["name"] for k in kernels}),
                      key=lambda op: -op.self_ns)[:args.top]
        report["programs"][name] = {
            "runs": runs, "ms_a_run": round(ns / 1e6 / max(runs, 1), 4),
            "kernels_ms_a_run": round(sum(k["ms_a_run"] for k in kernels), 4),
            "kernels": kernels,
            # the layer beside its products: sort, gathers, the gate
            "rest": [{"op": op.hlo, "op_name": op.op_name[-60:],
                      "ms_a_run": round(op.self_ns / 1e6 / max(runs, 1), 4)}
                     for op in rest]}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(
        ROOT, "chiprun_out", f"grouped_product_bench.d{D}f{F}k{TOP_K}.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    for name, r in report["programs"].items():
        print(f"{name:44s} {r['ms_a_run']:9.3f} ms a run, kernels "
              f"{r['kernels_ms_a_run']:9.3f}  "
              + " ".join(f"{k['name']}={k['ms_a_run']}[{k['moe_scope']}]"
                         for k in r["kernels"]))
    print(json.dumps({"ok": True, "out": out_path,
                      "tiled_against_compiler": apart}))


if __name__ == "__main__":
    main()
