"""KV manager: prompt tokens served from shared prefix pages over all
prompt tokens of the run's requests (%). ``PagedKVCache.prefix_hits``
counts pages."""


def read(obs):
    if not obs.prompt_tokens:
        return None
    return 100.0 * obs.prefix_hit_tokens / obs.prompt_tokens
