"""Kernel block autotuning: shape-bucketed registry + persistent compile cache.

The Pallas kernels (matmul / flash-attention / mamba-scan) each expose block
sizes that trade VMEM residency against grid overhead.  One hardcoded tile is
never right across shapes, so the public ops consult a small checked-in
registry instead: winners from the sweep harness
(``benchmarks/bench_kernels.py --update-registry``), keyed by

    op | backend | shape bucket

where every shape dimension is bucketed to its next power of two — the MaxText
decode-microbench convention: close shapes share tiles, the registry stays
tiny, and an unswept shape cleanly falls back to the op's built-in defaults.
Callers that pass explicit block sizes bypass the registry entirely.

The second half of the recipe is the persistent JAX compilation cache
(:func:`enable_compilation_cache`): repeat benches and relaunches skip XLA
recompiles entirely.  It lives where ``JAX_COMPILATION_CACHE_DIR`` says, and
otherwise at ``<checkout>/.jax_cache`` (gitignored).
"""

from __future__ import annotations

import functools
import json
import os

__all__ = [
    "shape_bucket", "registry_key", "lookup", "load_registry",
    "save_registry", "REGISTRY_PATH", "CACHE_DIR", "enable_compilation_cache",
]

#: The checked-in winners (regenerate with
#: ``python -m benchmarks.bench_kernels --update-registry``).
REGISTRY_PATH = os.path.join(os.path.dirname(__file__),
                             "autotune_registry.json")

#: Default persistent-cache directory.  Fixed to the checkout, not the
#: working directory: the path is part of the cache's key, so a directory
#: that moves with the cwd never hits.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def shape_bucket(dims: dict[str, int]) -> str:
    """Bucket each dimension to its next power of two: ``m=1000, k=512`` ->
    ``"k512_m1024"`` (sorted for key stability)."""
    parts = []
    for name in sorted(dims):
        v = int(dims[name])
        if v < 1:
            raise ValueError(f"shape dim {name}={v} must be >= 1")
        parts.append(f"{name}{1 << (v - 1).bit_length()}")
    return "_".join(parts)


def registry_key(op: str, dims: dict[str, int],
                 backend: str | None = None) -> str:
    if backend is None:
        import jax
        backend = jax.default_backend()
    return f"{op}|{backend}|{shape_bucket(dims)}"


@functools.lru_cache(maxsize=1)
def load_registry(path: str = REGISTRY_PATH) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def save_registry(registry: dict, path: str = REGISTRY_PATH) -> None:
    with open(path, "w") as f:
        json.dump(registry, f, indent=2, sort_keys=True)
        f.write("\n")
    load_registry.cache_clear()


def lookup(op: str, dims: dict[str, int],
           backend: str | None = None) -> dict:
    """Tuned block params for this op/backend/shape bucket, or ``{}`` when the
    bucket was never swept (callers then keep their built-in defaults)."""
    entry = load_registry().get(registry_key(op, dims, backend))
    if not isinstance(entry, dict):
        return {}
    return entry.get("blocks", {})


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and no
    directory is set here; otherwise the cache goes to :data:`CACHE_DIR`.
    Thresholds drop to zero so even the small test-shape kernels are cached."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir
