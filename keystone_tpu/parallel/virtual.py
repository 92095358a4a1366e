"""Virtual-device provisioning: an N-device CPU platform standing in for a
TPU slice, the way Spark ``local[n]`` stands in for a cluster in the
reference's tests (src/test/scala/keystoneml/workflow/PipelineContext.scala:9-25).

Used by tests/conftest.py (fixed 8-device mesh for the suite) and by
``__graft_entry__.dryrun_multichip`` (driver-chosen device count).
"""

from __future__ import annotations

import os
from typing import Optional

_COUNT_FLAG = "xla_force_host_platform_device_count"


def provision_virtual_devices(n_devices: int) -> None:
    """Force an ``n_devices``-device virtual CPU platform, process-wide.

    Importing this module already pulls in jax (via the package __init__),
    so this always works through the live config: tear down any initialized
    backend, then point the config at an N-device CPU platform. The env vars are also set so child
    processes inherit the same view. The switch is one-way: after this
    call, everything in the process runs on virtual CPU devices — callers
    that still need the real accelerator must use a separate process.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split() if _COUNT_FLAG not in f)
    # The XLA:CPU thunk runtime (default since jaxlib 0.4.32) can
    # deadlock inside sharded executables whose collectives rendezvous
    # across MANY virtual devices oversubscribed onto FEW cores — seen
    # here as the tier-1 suite hanging forever inside the BCD block
    # update's psum on the 8-device mesh (ordering-sensitive: which
    # programs compiled beforehand changes whether it fires; the same
    # fragility bcd.py's donation note records as intermittent aborts).
    # The virtual mesh is exactly the oversubscribed configuration, so
    # provisioning opts back into the legacy runtime; real-accelerator
    # paths never pass through here. An explicit user-set value wins.
    if "xla_cpu_use_thunk_runtime" not in flags:
        flags = f"{flags} --xla_cpu_use_thunk_runtime=false"
    # Parallel LLVM codegen (default split 32) segfaults this jaxlib on
    # hosts with a single schedulable core — reproducibly, deep in a
    # sharded weighted-solver lowering mid-suite, and on the untouched
    # seed too; any perturbation of the run (buffering, filters) moves
    # or hides it, the signature of a native race. Single-threaded
    # codegen trades a few seconds of compile time for a crash-free
    # suite; an explicit user-set value wins.
    if "xla_cpu_parallel_codegen_split_count" not in flags:
        flags = f"{flags} --xla_cpu_parallel_codegen_split_count=1"
    os.environ["XLA_FLAGS"] = (
        flags + f" --{_COUNT_FLAG}={n_devices}"
    ).strip()
    # The PJRT CPU client sizes its execution pool from host parallelism
    # (PJRT_NPROC overrides it). A cross-module collective needs every
    # partition RUNNING concurrently to reach the rendezvous; on a host
    # with fewer cores than virtual devices the queued partitions sit
    # behind pool-mates already blocked in the rendezvous and the
    # dispatch deadlocks at 0% CPU (seen: 7/8 AllReduce participants
    # arrive, the 8th never scheduled — a 1-core box hangs the BCD
    # sweep). Guarantee one runnable thread per partition plus headroom
    # for continuation work. An explicit user-set value wins.
    if "PJRT_NPROC" not in os.environ:
        os.environ["PJRT_NPROC"] = str(
            max(2 * n_devices, os.cpu_count() or 1)
        )

    import jax
    import jax.extend.backend
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        # Drop the live backend so the next jax.devices() re-reads the
        # config. Must happen before the config updates below
        # (num_cpu_devices rejects changes post-init). The public API also
        # flushes the get_backend memo and jit caches.
        jax.extend.backend.clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"could not provision {n_devices} virtual CPU devices "
            f"(have {len(jax.devices())})"
        )


def provision_from_env(default: Optional[int] = None) -> int:
    """Provision ``KEYSTONE_VIRTUAL_DEVICES`` virtual CPU devices (or
    ``default`` when the env var is unset) when more than one is asked for
    — lets a 2-vCPU container exercise an 8-lane mesh scan from any entry
    point (bench subprocesses, ad-hoc repros) without editing code.
    Returns the provisioned count; 1 means no-op (real backend kept)."""
    from ..utils import env_int

    n = env_int("KEYSTONE_VIRTUAL_DEVICES", int(default or 1))
    if n is not None and n > 1:
        provision_virtual_devices(n)
        return n
    return 1
