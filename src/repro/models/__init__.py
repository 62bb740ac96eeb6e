from .model import (decode_step, decode_step_layerwise, decode_step_paged,
                    forward, forward_blocked, forward_layerwise,
                    init_cache, init_params,
                    prefill, prefill_layerwise, rollback_cache,
                    whisper_encode)

__all__ = ["decode_step", "decode_step_layerwise", "decode_step_paged",
           "forward", "forward_blocked", "forward_layerwise", "init_cache",
           "init_params", "prefill", "prefill_layerwise", "rollback_cache",
           "whisper_encode"]
