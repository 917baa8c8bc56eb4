"""Two-process CPU dryrun of the multi-host runtime (VERDICT r2 #8).

Launched as ``python -m pipe_tpu.runtime._multiproc_check <pid> <nprocs>
<port> <out_file>`` once per process. Each process:

* boots a 2-local-device CPU platform (so 2 processes give a 4-device
  global topology);
* wires the runtime with :func:`pipe_tpu.runtime.distributed.initialize`
  (explicit local coordinator);
* builds :func:`global_pipeline_mesh` (2 stages x 2 data) in BOTH
  layouts — default (stage within a process / ICI analogue, data across
  / DCN analogue) and ``stage_across=True`` (1 stage per process, so
  every inter-stage ppermute crosses the process boundary) — assembles
  the host-local batch via :func:`host_local_batch`, and runs ONE 1F1B
  pipeline train step (ScheduledPipeline.loss_and_grad) across both
  processes per layout;
* process 0 writes the losses to ``out_file``.

The launchers (``tests/test_multiprocess.py`` under ``PIPE_TPU_MULTIPROC=1``
and ``__graft_entry__.dryrun_multichip``, both via
:func:`launch_two_process_check`) compare the loss against the same step
computed single-process on a local 4-device mesh — the multi-host data
plane must be a pure layout choice.
"""

from __future__ import annotations

import functools
import sys


# Deterministic tiny workload shared by the 2-process run and the
# single-process reference (keys fixed; pure function of nothing).
WIDTH = 16
ROWS_PER_CHUNK = 4
CHUNKS = 2
N_STAGES = 2
N_DATA = 2


def _build(mesh):
    """Pipeline + params + FULL global batch (deterministic)."""
    import jax
    import jax.numpy as jnp

    from ..core import microbatch as mb
    from ..parallel.scheduled import ScheduledPipeline
    from ..parallel.spmd import stack_stage_params

    def stage_fn(p, h, ctx):
        return jnp.tanh(h @ p["w"] + p["b"])

    def pre_fn(p, x, ctx):
        return x

    def post_fn(p, h, x, ctx):
        return jnp.sum((h - 1.0) ** 2, axis=-1)

    ks = jax.random.split(jax.random.key(0), N_STAGES)
    params = [{"w": jax.random.normal(k, (WIDTH, WIDTH)) * 0.3,
               "b": jnp.zeros((WIDTH,))} for k in ks]
    stacked = stack_stage_params(params)
    pipe = ScheduledPipeline(mesh, stage_fn, pre_fn=pre_fn, post_fn=post_fn,
                             checkpoint="except_last", schedule="1f1b")
    rows = ROWS_PER_CHUNK * CHUNKS * N_DATA
    x_full = jax.random.normal(jax.random.key(1), (rows, WIDTH))
    xs, n_rows = mb.stack_scatter(x_full, CHUNKS)   # [m, rows_g, W]
    w = mb.valid_row_mask(xs, n_rows)
    return pipe, stacked, xs, w


def _zero_step(mesh, pipe, stacked, xs, w):
    """One train step with ZeRO-1 moments sharded over the DATA axis of
    ``mesh`` — on the 2-process topology that axis SPANS the processes,
    so the partitioned Adam update and the param re-gather cross the DCN
    analogue. Returns ``(loss, checksum-of-updated-params)`` (both
    replicated scalars; layout must never change the math)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ..train import zero as zero_mod

    from jax.sharding import NamedSharding, PartitionSpec as P

    tx = optax.adam(1e-2)
    shardings = zero_mod.moment_shardings(
        mesh, stacked, jax.eval_shape(tx.init, stacked))
    repl = NamedSharding(mesh, P())

    # outputs must be FULLY REPLICATED so float() works on the multihost
    # topology (a process can only fetch addressable values)
    # xs/w/params must enter as jit ARGUMENTS: on the 2-process topology
    # they span both processes, and closed-over constants cannot
    @functools.partial(jax.jit, out_shardings=(repl, repl))
    def step(params, xs, w):
        opt_state = zero_mod.constrain_moments(tx.init(params), shardings)
        loss, grads = pipe.loss_and_grad(params, {}, {}, xs, w)
        updates, opt_state = tx.update(grads[0], opt_state, params)
        new = optax.apply_updates(params, updates)
        # Fold the constrained post-update moments into the checksum:
        # an unused constrain_moments result would be dead-code-eliminated
        # by XLA and the "partitioned update rides the DCN" claim this
        # check documents would not actually be enforced.
        opt_state = zero_mod.constrain_moments(opt_state, shardings)
        checksum = sum(jnp.sum(jnp.abs(a.astype(jnp.float32)))
                       for a in jax.tree_util.tree_leaves(new))
        checksum = checksum + sum(
            jnp.sum(jnp.abs(a.astype(jnp.float32)))
            for a in jax.tree_util.tree_leaves(opt_state))
        return loss, checksum

    loss, checksum = step(stacked, xs, w)
    return float(loss), float(checksum)


def single_process_loss(devices=None):
    """Reference: the same step on a single-process 4-device mesh.
    Returns ``(loss, zero_checksum)``."""
    import jax

    from ..parallel.mesh import make_mesh

    devices = devices if devices is not None else jax.devices()[:4]
    mesh = make_mesh(N_STAGES, N_DATA, devices=devices)
    pipe, stacked, xs, w = _build(mesh)
    loss, _ = jax.jit(pipe.loss_and_grad)(stacked, {}, {}, xs, w)
    _, checksum = _zero_step(mesh, pipe, stacked, xs, w)
    return float(loss), checksum


def worker(process_id: int, num_processes: int, port: int,
           out_file: str) -> None:
    from ..utils.platform import force_cpu_platform
    force_cpu_platform(2)  # 2 local devices per process

    import jax
    import numpy as np

    from . import distributed as dist

    dist.initialize(coordinator_address=f"127.0.0.1:{port}",
                    num_processes=num_processes, process_id=process_id)
    assert jax.process_count() == num_processes, dist.process_summary()
    assert jax.device_count() == 2 * num_processes

    mesh = dist.global_pipeline_mesh(N_STAGES, N_DATA)
    pipe, stacked, xs_global, w_global = _build(mesh)

    # Re-create xs as if each host loaded ONLY its data shard: slice this
    # process's rows out of the deterministic global batch, then assemble
    # the global array from per-host shards (the multi-host data-loading
    # contract).
    rows_g = xs_global.shape[1]
    lo = process_id * (rows_g // num_processes)
    hi = lo + rows_g // num_processes
    xs_local = np.asarray(xs_global)[:, lo:hi]
    xs = dist.host_local_batch(mesh, xs_local, batch_axis=1)
    w = dist.host_local_batch(mesh, np.asarray(w_global)[:, lo:hi],
                              batch_axis=1)

    loss, grads = jax.jit(pipe.loss_and_grad)(stacked, {}, {}, xs, w)
    jax.block_until_ready(grads)
    # ZeRO-1 across the process-spanning data axis: the sharded update's
    # collectives ride the DCN analogue
    _, checksum = _zero_step(mesh, pipe, stacked, xs, w)

    # STAGE axis across the process boundary (1 stage per process): every
    # inter-stage ppermute hop crosses the DCN analogue — the regime the
    # reference's vestigial RPC layer declared future work
    # (``pipe.py:295-302``). The data axis is intra-process here, so every
    # process addresses the full batch.
    mesh_sx = dist.global_pipeline_mesh(N_STAGES, N_DATA, stage_across=True)
    pipe_sx, stacked_sx, xs_g_sx, w_g_sx = _build(mesh_sx)
    xs_sx = dist.host_local_batch(mesh_sx, np.asarray(xs_g_sx),
                                  batch_axis=1)
    w_sx = dist.host_local_batch(mesh_sx, np.asarray(w_g_sx), batch_axis=1)
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh_sx, P())
    loss_sx, grads_sx = jax.jit(
        pipe_sx.loss_and_grad,
        out_shardings=(repl, None))(stacked_sx, {}, {}, xs_sx, w_sx)
    jax.block_until_ready(grads_sx)

    if process_id == 0:
        with open(out_file, "w") as f:
            f.write(f"{float(loss)!r} {checksum!r} {float(loss_sx)!r}")


def launch_two_process_check(out_file: str, *, timeout: float = 600.0,
                             repo_root: str = None):
    """Spawn the two workers as REAL processes; returns process 0's
    ``(loss, zero_checksum)``.

    Shared by the gated test and the dryrun. Raises
    ``subprocess.TimeoutExpired``/``OSError`` when the environment cannot
    launch or connect the processes (callers may classify those as
    sandbox restrictions), and ``RuntimeError`` when a worker genuinely
    fails or breaks the output contract — never leaves orphans.
    """
    import os
    import socket
    import subprocess

    if repo_root is None:
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    # CPU children only, so this is safe beside a parent that holds a
    # chip (a chip belongs to one process). The workers must not inherit
    # a forced device count: they set their own 2-device CPU platform.
    # The checkout goes in front of the caller's PYTHONPATH, which stays.
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo_root, env.get("PYTHONPATH")) if p)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    procs = []
    try:
        procs = [subprocess.Popen(
            [sys.executable, "-m", "pipe_tpu.runtime._multiproc_check",
             str(i), "2", str(port), str(out_file)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            for i in range(2)]
        texts = [p.communicate(timeout=timeout)[0] for p in procs]
    finally:
        for p in procs:               # never leave orphaned JAX processes
            if p.poll() is None:
                p.kill()
                p.wait()
    if any(p.returncode != 0 for p in procs):
        raise RuntimeError(
            "multiproc worker failed:\n" +
            "\n".join(t.decode(errors="replace")[-3000:] for t in texts))
    try:
        with open(out_file) as f:
            loss_s, ck_s, loss_sx_s = f.read().split()
            return float(loss_s), float(ck_s), float(loss_sx_s)
    except (OSError, ValueError) as e:
        raise RuntimeError(
            f"workers exited 0 but the loss file contract broke: {e}")


if __name__ == "__main__":
    from ..utils.platform import configure_compile_cache
    configure_compile_cache()
    worker(int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]),
           sys.argv[4])
