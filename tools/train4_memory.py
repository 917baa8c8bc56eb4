"""Compile-only memory check of the four-stage train step for a DESCRIBED
``v5e:2x2`` (no device, no weights, no chip time): does the step that the
cell ``lm520m-train-4chip`` would run fit a chip's HBM?

``python tools/train4_memory.py [--batch=256] [--chunks=8] [--stages=4]
[--topology=v5e:2x2] [--cell=lm520m-train-1chip] [--report=PATH]``

Builds the benchmark's own ``Trainer`` for the one-chip train cell's
configuration (schedule, checkpoint mode, lr and clip from its traffic
file) over the described topology's devices with ``n_stages`` stages, gives
``Trainer._step_fn`` the state's shapes and placements (stage-stacked
parameters and their Adam moments on the stage axis, the rest replicated)
and compiles it. Prints ONE JSON line: the versions of jax, jaxlib and libtpu
(the verdict is the compiler's, so it is only as old as they are), the sizes
asked, ``fits`` and either ``memory_analysis()``'s numbers or the compiler's
refusal (its summary lines; the whole report goes to ``--report``).

PR 34 read with it (jax 0.9.0, jaxlib 0.9.0, libtpu 0.0.34, CPU host):
batch 256 in 8 micro-batches and batch 512 in 16 do not fit (``Used 15.86G
of 15.75G hbm``, 58.8% of it fragmentation); batch 128 in 8 compiles. A
compile takes 8 to 10 minutes of one core here and a few GiB of host memory.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def versions():
    import jax
    import jaxlib
    out = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        import libtpu
        out["libtpu"] = libtpu.__version__
    except ImportError:
        out["libtpu"] = None
    return out


def lower_step(cell_name, topology, stages, batch, chunks):
    """``Trainer._step_fn`` lowered over ``topology``'s described devices at
    the named sizes, from shapes alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import pb_core
    from pipe_tpu.parallel.mesh import STAGE_AXIS
    from pipe_tpu.parallel.spmd import stack_stage_params
    from pipe_tpu.train.loop import Trainer, TrainerConfig, TrainState
    from pipe_tpu.utils.rng import make_key

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    cell = pb_core.Cell(cell_name)
    t = cell.traffic["trainer"]
    seq = cell.traffic["seq"]
    tcfg = TrainerConfig(n_stages=stages, n_data=1, schedule=t["schedule"],
                         checkpoint=t["checkpoint"], batch_size=batch,
                         bptt=seq, chunks=chunks, lr=t["lr"],
                         grad_clip=t["grad_clip"])
    tr = Trainer(cell.family.model_config(cell.cfg), tcfg,
                 devices=list(topo.devices))
    staged = NamedSharding(tr.mesh, P(STAGE_AXIS))
    repl = NamedSharding(tr.mesh, P())

    def sds(a, sharding):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    def init():
        sp, prep, postp = tr.model.init(make_key(0))
        return stack_stage_params(sp), prep, postp

    tree_map = jax.tree_util.tree_map
    sp, prep, postp = jax.eval_shape(init)
    params = (tree_map(lambda a: sds(a, staged), sp),
              tree_map(lambda a: sds(a, repl), prep),
              tree_map(lambda a: sds(a, repl), postp))
    leaves = jax.tree_util.tree_leaves(params)

    def like_its_parameter(a):
        # a moment has its parameter's shape and placement; the count is
        # replicated
        for p in leaves:
            if a.ndim and p.shape == a.shape:
                return sds(a, p.sharding)
        return sds(a, repl)

    opt = tree_map(like_its_parameter, jax.eval_shape(tr.tx.init, params))
    state = TrainState(params=params, opt_state=opt,
                       step=sds(jax.ShapeDtypeStruct((), jnp.int32), repl))
    rows = np.zeros((batch, seq), np.int32)
    x, w = tree_map(lambda a: sds(a, repl),
                    jax.eval_shape(lambda: tr._make_x(rows, rows)))
    key = sds(jax.eval_shape(lambda: make_key(0)), repl)
    lr = sds(jax.ShapeDtypeStruct((), jnp.float32), repl)
    return tr._step_fn.lower(state, x, w, key, lr)


def check(cell="lm520m-train-1chip", topology="v5e:2x2", stages=4,
          batch=256, chunks=8, report=None):
    import jax
    out = dict(versions(), cell=cell, topology=topology, stages=stages,
               batch=batch, chunks=chunks)
    lowered = lower_step(cell, topology, stages, batch, chunks)
    t0 = time.time()
    try:
        compiled = lowered.compile()
    except jax.errors.JaxRuntimeError as e:
        text = str(e)
        if "RESOURCE_EXHAUSTED" not in text:
            raise
        if report:
            with open(report, "w") as f:
                f.write(text)
        out["fits"] = False
        out["refusal"] = [ln.strip() for ln in text.splitlines() if re.search(
            r"RESOURCE_EXHAUSTED|Total hbm usage|^\s+(reserved|program|"
            r"arguments|global|HLO temp)\s", ln)]
        out["largest"] = re.findall(r"Shape: (\S+?)\{", text)[:6]
    else:
        ma = compiled.memory_analysis()
        out["fits"] = True
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "alias_size_in_bytes", "temp_size_in_bytes",
                  "generated_code_size_in_bytes"):
            out[f] = getattr(ma, f)
        text = compiled.as_text()
        out["collectives"] = {
            k: len(re.findall(r"\b%s\(" % k, text))
            for k in ("collective-permute-start", "all-reduce",
                      "all-gather")}
    out["compile_s"] = round(time.time() - t0, 1)
    return out


def main(argv):
    kw = {}
    for a in argv:
        k, _, v = a.lstrip("-").partition("=")
        kw[k] = int(v) if v.isdigit() else v
    print(json.dumps(check(**kw)))


if __name__ == "__main__":
    main(sys.argv[1:])
