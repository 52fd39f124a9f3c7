"""mxnet_tpu.models — flagship SPMD model definitions.

Gluon-style model zoo lives in mxnet_tpu.gluon.model_zoo (reference parity:
python/mxnet/gluon/model_zoo/vision/); this package holds the pure-functional
mesh-aware flagships used for scale benchmarks (transformer LM with
dp/tp/sp sharding; the pattern-built hybrid decoder of Mamba-2, latent
mixture-of-experts and grouped-query attention blocks, served with K/V
pages and per-slot recurrent state).
"""
from .hybrid import HybridLM, HybridLMConfig
from .transformer import TransformerLM, TransformerLMConfig

__all__ = ["TransformerLM", "TransformerLMConfig", "HybridLM",
           "HybridLMConfig"]
