"""XLA runtime tuning applied *before* the JAX backend initializes.

The device-resident search (:mod:`repro.core.search_jax`) fans its island
population out over a 1-D device mesh; how many devices exist — and how
well XLA overlaps their collectives — is decided by process-wide XLA
flags that must be in the environment before the first backend use:

* ``--xla_force_host_platform_device_count=N`` splits the host CPU
  backend into N emulated devices.  This is how CI (and any CPU-only
  host) exercises the real ``shard_map``/``ppermute`` lowering of the
  multi-device search: the N shards are genuine XLA partitions, they just
  time-share the host cores.
  It is written only for a run on the CPU backend (:func:`runs_on_cpu`):
  on an accelerator the mesh is the first N real devices.
* the GPU latency-hiding / async-collective flags (:data:`GPU_FLAGS`)
  let the per-device annealing loop overlap its elite-migration
  collectives with compute on real multi-GPU hosts.

:func:`enable_compile_cache` turns on JAX's persistent compilation cache
for the entry points (launchers, benchmarks, ``chip_smoke.py``), so
processes that compile the same programs share the work.

``import jax`` alone does *not* initialize the backend — flags applied
from ``main()`` before the first ``jax.devices()``/array op still take
effect — but a flag applied after initialization is silently inert, so
:func:`apply` warns loudly in that case instead of pretending.

Idempotent by construction: re-applying replaces a stale setting of the
same flag instead of appending a duplicate, and unrelated user-set
``XLA_FLAGS`` entries are preserved.
"""
from __future__ import annotations

import os
import pathlib
import sys
from typing import Iterable, MutableMapping

from ..obs import get_logger
log = get_logger(__name__)

HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"

#: where the persistent compilation cache lives when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed directory of the
#: checkout, derived from this package's path (a cache that moves between
#: runs is never found again).
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

#: GPU runtime-tuning flags (SNIPPETS.md exemplar set): overlap the
#: mesh-search collectives with compute and keep triton fusions on.
GPU_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def _flag_name(token: str) -> str:
    return token.split("=", 1)[0]


def backend_initialized() -> bool:
    """Best-effort probe: has this process already created XLA backends?

    Reads jax's private backend table without *triggering* initialization
    (``jax.devices()`` would).  Unknown jax internals degrade to False —
    the caller then proceeds and XLA itself decides.
    """
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge
        return bool(getattr(xla_bridge, "_backends", None))
    except Exception:  # pragma: no cover - jax internals moved
        return False


def runs_on_cpu(env: MutableMapping[str, str] = os.environ) -> bool:
    """Does jax in this process (or one started with ``env``) compute on
    its CPU backend?

    An initialized backend answers directly.  Before that the first entry
    of ``JAX_PLATFORMS`` decides; with it unset, jax would pick an
    attached accelerator, which cannot be known without initializing one,
    so the answer is True — the host-device flag only shapes the CPU
    backend and is inert beside an accelerator.
    """
    if env is os.environ and backend_initialized():
        import jax
        return jax.default_backend() == "cpu"
    first = env.get("JAX_PLATFORMS", "").split(",")[0].strip()
    return first in ("", "cpu")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    used as is: no other directory is set in code.  Otherwise the cache
    goes to :data:`DEFAULT_CACHE_DIR`.  Called by the entry points only —
    never by tests, whose compiles must not leave files behind.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def merge_flags(existing: str, new: Iterable[str]) -> str:
    """Merge flag tokens into an ``XLA_FLAGS`` string; new settings win.

    Tokens are whitespace-separated ``--flag=value`` entries; a new token
    replaces any existing token with the same flag name, everything else
    is preserved in order.
    """
    new = list(new)
    names = {_flag_name(t) for t in new}
    kept = [t for t in existing.split() if _flag_name(t) not in names]
    return " ".join(kept + new)


def apply(devices: int | None = None, gpu: bool = False,
          extra: Iterable[str] = (),
          env: MutableMapping[str, str] = os.environ) -> str:
    """Install the requested XLA flags into ``env["XLA_FLAGS"]``.

    ``devices=N`` emulates N host-platform devices when the run is on
    the CPU backend (:func:`runs_on_cpu`; on an accelerator nothing is
    written and the mesh uses N real devices); ``gpu=True`` adds
    :data:`GPU_FLAGS`; ``extra`` appends verbatim tokens.  Returns the resulting ``XLA_FLAGS`` value.  When mutating
    this process's own ``os.environ``, warns (but still writes — a later
    subprocess inherits the env) if the backend is already initialized
    and cannot pick the flags up; copies built for subprocesses
    (:func:`subprocess_env`) stay silent.
    """
    flags: list[str] = []
    if devices is not None:
        devices = int(devices)
        if devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        if runs_on_cpu(env):
            flags.append(f"{HOST_DEVICE_FLAG}={devices}")
    if gpu:
        flags.extend(GPU_FLAGS)
    flags.extend(extra)
    merged = merge_flags(env.get("XLA_FLAGS", ""), flags)
    env["XLA_FLAGS"] = merged
    if flags and env is os.environ and backend_initialized():
        log.warning(
            "XLA backend already initialized in this process; XLA_FLAGS "
            "%s will only affect subprocesses (apply before the first "
            "jax.devices()/array operation)", " ".join(flags))
    return merged


def device_count() -> int:
    """Visible jax devices (initializes the backend — call after apply)."""
    import jax
    return jax.device_count()


def subprocess_env(devices: int, gpu: bool = False,
                   base: MutableMapping[str, str] | None = None) -> dict:
    """A copy of ``base`` (default ``os.environ``) with the flags merged —
    for launching workers that must see an N-device host backend."""
    env = dict(os.environ if base is None else base)
    apply(devices=devices, gpu=gpu, env=env)
    return env
