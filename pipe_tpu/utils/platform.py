"""Platform helpers: the virtual multi-device CPU platform, and where the
compile cache lives.

The TPU-build analogue of the reference's CPU-sentinel-stream trick
(``AbstractStream`` admits a CPU fallback so every layer unit-tests without
GPUs — reference pipe.py:22, pipeline.py:22): here the whole framework —
scheduler, SPMD shard_map pipeline, ppermute rings, remat — runs on N virtual
CPU devices, so multi-"chip" tests need no TPU pod.

On a machine with a TPU, JAX picks it by default and one process owns it:
everything that needs the chip runs in that process, and child processes are
started on the CPU (``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import os
from typing import Mapping, Optional

__all__ = ["force_cpu_platform", "sync_if_forced_cpu", "compile_cache_dir",
           "configure_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir(environ: Mapping[str, str] = os.environ
                      ) -> Optional[str]:
    """The directory this checkout sets for JAX's persistent compile cache:
    ``<checkout>/.jax_cache``, or None when ``JAX_COMPILATION_CACHE_DIR`` is
    set — JAX reads that variable itself and the code sets no other.

    The path is fixed (no pid, time or temp dir): it is part of the cache
    key's surroundings, and a directory that moves never hits.
    """
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> None:
    """Point JAX's persistent compile cache at :func:`compile_cache_dir`,
    and key it with the operations' metadata. Entry points call this first,
    before anything compiles.

    JAX leaves metadata (``op_name``, source lines) out of the key by
    default. The named scopes a profiler reads (``obs.events.DEVICE_SCOPES``)
    are metadata: a cache filled before a scope existed, or by a checkout
    without it, would go on serving executables whose captures show none,
    and nothing would say so. Keyed with the metadata, a capture shows what
    the code says; the price is that an edit which moves lines in a traced
    function compiles again."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = compile_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)


def force_cpu_platform(num_devices: int = 8) -> None:
    """Make jax see ``num_devices`` CPU devices.

    Must run before the first jax *computation* (backend init), but is safe
    after ``import jax``; raises ``RuntimeError`` once a backend is up.
    """
    os.environ.setdefault("PIPE_TPU_FORCED_CPU", "1")
    import jax

    # N virtual devices time-share the host cores (often ONE core in CI).
    # XLA:CPU's collective rendezvous hard-terminates the process when a
    # participant is >45s late — which a device legitimately is whenever its
    # pre-collective compute runs serialized behind 7 siblings. Give the
    # rendezvous real headroom; these flags must be set before backend init.
    flags = os.environ.get("XLA_FLAGS", "")
    for flag in ("xla_cpu_collective_timeout_seconds",
                 "xla_cpu_collective_call_terminate_timeout_seconds"):
        if flag not in flags:   # never override an operator's setting
            flags = f"{flags} --{flag}=600".strip()
    os.environ["XLA_FLAGS"] = flags

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", num_devices)


def sync_if_forced_cpu(x):
    """Block on ``x`` when running on the forced-CPU virtual platform.

    On N virtual devices time-sharing few host cores, jax's async dispatch
    lets successive compiled runs interleave; blocked collective-rendezvous
    waiters from run k+1 can then starve the worker threads run k still
    needs — a livelock (observed: 7 devices parked in run k+1's first
    ppermute while run k never finishes on the one remaining thread).
    Serializing steps with a host sync removes the hazard. On real TPU this
    is a no-op: async dispatch is exactly what overlaps host and device
    there, and the rendezvous mechanism does not exist.
    """
    if os.environ.get("PIPE_TPU_FORCED_CPU"):
        import jax

        jax.block_until_ready(x)
    return x
