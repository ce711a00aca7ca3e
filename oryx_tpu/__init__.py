"""oryx_tpu: a TPU-native multimodal-LLM framework.

From-scratch JAX/XLA/Pallas rebuild of the capabilities of the Oryx reference
stack (gallenvara/oryx): arbitrary-resolution vision (OryxViT-equivalent),
on-demand visual-token compression (Dynamic Compressor), Qwen2/Yi-class LLM
backbone, SFT + inference, shard_map/pjit FSDP over ICI/DCN.

See SURVEY.md at the repo root for the reference structural analysis.
"""

__version__ = "0.1.0"

import jax as _jax

# Prefix-stable jax.random.split is a documented invariant of the decode
# paths (models/generate.py: streaming == non-streaming sample streams;
# chunked decode slicing a pre-split key array). The partitionable
# threefry guarantees it; pin it explicitly.
_jax.config.update("jax_threefry_partitionable", True)

from oryx_tpu.config import (  # noqa: F401
    OryxConfig,
    LLMConfig,
    VisionConfig,
    CompressorConfig,
    MeshConfig,
    TrainConfig,
    GenerationConfig,
    LoraConfig,
    oryx_7b,
    oryx_34b,
    oryx_1_5_7b,
    oryx_1_5_32b,
    oryx_tiny,
)
