"""Qwen2 (``model_type`` "qwen2"): the grouped-query decoder with a bias
on the q, k and v projections and a dense SiLU-gated FFN."""
from bench.families._gqa import (  # noqa: F401
    decode_step, dims, hidden, kernels, leaf_shapes, prefill_chunk,
    program_config)
