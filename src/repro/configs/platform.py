"""Computation-platform setup + detection for the kernel hot path.

One place does platform work for the whole tree (the way bayespec's
``elisa/util/config.py`` centralizes it): ``set_platform`` pins the JAX
backend and — for GPU — installs the ``xla_gpu_*`` flag block that the
Triton lowering of the program kernel family wants (async collectives,
latency-hiding scheduler, triton fusions), and ``detect_platform`` /
``detect_device_kind`` are THE detection seam every dispatch layer reads:

  * kernels/ops.py routes blocked/auto/sparse dispatch off
    ``detect_platform()`` ("tpu" → Mosaic lowering, "gpu" → Triton
    lowering, anything else → the jitted jnp scan);
  * roofline/analysis.py maps ``detect_device_kind()`` onto its
    per-platform hardware registry (an unrecognized kind is ``unknown``
    and the roofline REFUSES to predict — no silent v5e numbers);
  * benchmarks/common.py stamps both into every BENCH_*.json so perf
    trajectories are comparable across heterogeneous runners.

``set_platform`` only takes effect before the first JAX device init, like
every XLA_FLAGS knob — call it at entry-point top, not mid-run.
"""
from __future__ import annotations

import os
import warnings
from multiprocessing import cpu_count
from typing import Optional

# Installed for platform == "gpu": the standard jax GPU performance block
# (https://jax.readthedocs.io/en/latest/gpu_performance_tips.html). The
# kernel family is bandwidth-bound, so the latency-hiding scheduler and
# async collectives are the flags that matter for multi-GPU fleets.
GPU_XLA_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true "
    "--xla_gpu_triton_gemm_any=True "
    "--xla_gpu_enable_async_collectives=true "
    "--xla_gpu_enable_latency_hiding_scheduler=true "
    "--xla_gpu_enable_highest_priority_async_stream=true "
)

_PLATFORMS = ("cpu", "gpu", "tpu")
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def set_platform(platform: str = "cpu") -> None:
    """Pin the JAX backend to 'cpu', 'gpu', or 'tpu' and install the
    platform's XLA flag block. Only takes effect at program start (before
    the first jax device init)."""
    if platform not in _PLATFORMS:
        raise ValueError(f"platform must be one of {_PLATFORMS}, "
                         f"got {platform!r}")
    import jax

    jax.config.update("jax_platform_name", platform)
    if platform == "gpu":
        existing = os.environ.get("XLA_FLAGS", "")
        missing = [f for f in GPU_XLA_FLAGS.split() if f not in existing]
        if missing:
            os.environ["XLA_FLAGS"] = (existing + " " +
                                       " ".join(missing)).strip()


def set_cpu_devices(n: int) -> None:
    """Force `n` XLA host devices (shard_map testing). Before first init."""
    n = int(n)
    total = cpu_count()
    if n > total:
        warnings.warn(f"only {total} CPUs available; forcing {n} XLA host "
                      "devices anyway (oversubscribed shard_map mesh)",
                      stacklevel=2)
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip())


def detect_platform(device=None) -> str:
    """The local device's platform string: 'tpu' | 'gpu' | 'cpu'.

    A device-init failure raises: reading it as 'cpu' would quietly run
    the jnp scan on a machine that was meant to use its accelerator."""
    if device is None:
        import jax

        device = jax.devices()[0]
    return str(device.platform)


def detect_device_kind(device=None) -> str:
    """The local device's hardware kind string (e.g. 'TPU v5 lite',
    'NVIDIA H100 80GB HBM3', 'cpu') — what roofline/analysis.py matches
    against its per-platform registry. Raises on device-init failure."""
    if device is None:
        import jax

        device = jax.devices()[0]
    return str(getattr(device, "device_kind", device.platform))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``. The
    path is fixed because it is part of the cache key: a directory that
    moves between runs never hits. Call at entry-point top."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def compiled_kernel_platforms() -> tuple:
    """Platforms the program kernel family lowers for COMPILED (Mosaic on
    TPU, Triton on GPU). kernels/ops.py refuses an explicit
    ``interpret=False`` anywhere else."""
    return ("tpu", "gpu")


def supports_compiled_kernels(platform: Optional[str] = None) -> bool:
    return (detect_platform() if platform is None
            else platform) in compiled_kernel_platforms()
