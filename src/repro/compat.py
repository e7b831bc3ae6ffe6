"""Small platform helpers shared across the repo.

  - ``keystr_slash``: bare-name, slash-separated key paths
    (``params/0/moe/w_gate``).  The output is load-bearing: checkpoint
    manifests (ckpt/manager.py) and the sharding-rule substring patterns
    (parallel/sharding.py, e.g. ``"moe/w_gate"``) both key on this exact
    spelling.
  - ``overlap_supported`` / ``enable_overlap_xla_flags``: whether the
    active backend can actually hide collectives behind compute, and the
    XLA flags that make it do so.  The overlap schedule only pays off with
    async collectives + the latency-hiding scheduler (gpu/tpu); the host
    CPU backend runs collectives inline, which is why overlap *measures*
    slower than sync there (BENCH_tiled.json overhead 1.06-1.12) despite
    modeling faster - ``schedule="auto"`` gates on this.
  - ``enable_compile_cache``: JAX's persistent compilation cache, in the
    directory ``JAX_COMPILATION_CACHE_DIR`` names or else in one fixed
    git-ignored directory of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

from jax.tree_util import keystr

#: XLA flags that let the GPU runtime run boundary collectives concurrently
#: with interior compute (the latency-hiding levers the overlap schedule
#: was designed for): async collectives, the latency-hiding scheduler, and
#: a high-priority stream for the async ops.
XLA_GPU_OVERLAP_FLAGS: tuple[str, ...] = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def overlap_supported(backend: str | None = None) -> bool:
    """True when the active (or named) jax backend can hide collectives
    behind compute - gpu/tpu, where async collectives and the latency-
    hiding scheduler exist.  ``schedule="auto"`` resolves to "sync" when
    this is False, so overlap is never the selected schedule on the host
    CPU mesh where it measures >1.0 overhead."""
    if backend is None:
        import jax

        backend = jax.default_backend()
    return backend in ("gpu", "tpu")


def enable_overlap_xla_flags(env=None) -> list[str]:
    """Append ``XLA_GPU_OVERLAP_FLAGS`` to ``XLA_FLAGS`` (skipping flags
    whose key is already set, so explicit user choices win).  Must run
    before jax initialises its backend to take effect.  Returns the flags
    newly added - empty when everything was already present."""
    env = os.environ if env is None else env
    cur = env.get("XLA_FLAGS", "")
    added = [f for f in XLA_GPU_OVERLAP_FLAGS if f.split("=")[0] not in cur]
    if added:
        env["XLA_FLAGS"] = " ".join(([cur] if cur else []) + added)
    return added


#: The compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: one
#: fixed path in the checkout (listed in .gitignore), so every run of this
#: checkout finds what earlier runs compiled.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache goes to
    ``COMPILE_CACHE_DIR``.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def keystr_slash(path) -> str:
    return keystr(path, simple=True, separator="/")
