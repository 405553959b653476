"""Runtime feature detection (reference ``python/mxnet/runtime.py:75-89`` +
``src/libinfo.cc:39-52``).

The reference reports compiled-in features (CUDA, CUDNN, MKLDNN, …); here
features reflect the JAX/XLA runtime actually loaded.
"""
from __future__ import annotations

from typing import Dict, List

__all__ = ["Feature", "feature_list", "Features"]


class Feature:
    def __init__(self, name: str, enabled: bool):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect() -> Dict[str, bool]:
    import jax

    feats = {
        "TPU": False,
        "GPU": False,
        "CPU": True,
        "XLA": True,
        "BF16": True,
        "INT64_TENSOR_SIZE": jax.config.jax_enable_x64,
        "PALLAS": True,
        "DIST_KVSTORE": True,
        "OPENCV": False,
        "BLAS_OPEN": True,
        "SIGNAL_HANDLER": False,
        "PROFILER": True,
    }
    try:
        platforms = {d.platform for d in jax.devices()}
        feats["TPU"] = "tpu" in platforms
        feats["GPU"] = "gpu" in platforms or "cuda" in platforms
    except Exception:
        pass
    try:
        import cv2  # noqa: F401

        feats["OPENCV"] = True
    except ImportError:
        pass
    return feats


class Features(dict):
    """Mapping of feature name -> Feature (reference runtime.Features)."""

    instance = None

    def __init__(self):
        super().__init__(
            (k, Feature(k, v)) for k, v in _detect().items())

    def __repr__(self):
        return str(list(self.values()))

    def is_enabled(self, feature_name: str) -> bool:
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError(f"feature '{feature_name}' does not exist")
        return self[feature_name].enabled


def feature_list() -> List[Feature]:
    """List of runtime features (reference runtime.feature_list)."""
    if Features.instance is None:
        Features.instance = Features()
    return list(Features.instance.values())
