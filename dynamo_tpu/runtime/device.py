"""The accelerator as one process sees it: identity, published peaks, and
where compiled programs are cached.

Importing this module does NOT import JAX (chip_smoke.py's parent and the
bench drivers use `compile_cache_dir()` while a child holds the chip);
the functions that need a backend import it when called.

A chip belongs to one process at a time: call `device_identity()` /
`require_tpu()` only in the process that is meant to hold it.
"""

from __future__ import annotations

import os
from typing import Dict

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Published per-chip peaks, keyed by `jax.devices()[0].device_kind`.
# Source: Google Cloud documentation, "TPU v5e" system architecture
# (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s).  A kind
# that is not in the table is an error, never a default.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gbps": 819.0, "hbm_gb": 16.0},
}


def compile_cache_dir() -> str:
    """Where this checkout's processes keep JAX's persistent compile
    cache: `$JAX_COMPILATION_CACHE_DIR` when set from outside, else one
    fixed directory inside the checkout (the path is part of the cache
    key's surroundings — a directory that moves never hits)."""
    return os.environ.get(CACHE_ENV) or os.path.join(_REPO_ROOT,
                                                     ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on before the first compile.
    With `$JAX_COMPILATION_CACHE_DIR` set JAX reads it itself and nothing
    is set in code; unset, the fixed in-checkout directory is used."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_identity() -> Dict[str, object]:
    """`{"platform", "kind", "count"}` as JAX reports the default
    backend.  Initializes the backend: the calling process takes the
    chip."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> Dict[str, object]:
    """device_identity(), or RuntimeError when the default backend is
    not a TPU — measurement paths fail without a chip, they do not fall
    back to the CPU or to interpret-mode kernels."""
    ident = device_identity()
    if ident["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: JAX's default backend is {ident['platform']!r} "
            f"({ident['kind']!r} x{ident['count']}); this path measures "
            "a chip and has no CPU fallback")
    return ident


def device_peaks(kind: str) -> Dict[str, float]:
    """Published peaks for `device_kind`; an unknown kind is an error."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise RuntimeError(
            f"no published peaks for device kind {kind!r}; add it (with "
            "its source) to dynamo_tpu/runtime/device.py DEVICE_PEAKS"
        ) from None
