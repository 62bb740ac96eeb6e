"""Mixtral (``model_type`` "mixtral"): the grouped-query decoder whose FFN
is ``num_local_experts`` SiLU-gated experts, ``num_experts_per_tok`` of
them weighted by a renormalised top-k softmax router."""
from bench.families._gqa import (  # noqa: F401
    decode_step, dims, hidden, kernels, leaf_shapes, prefill_chunk,
    program_config)
